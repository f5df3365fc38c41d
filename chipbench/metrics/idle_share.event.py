"""Share of the traced window, in %, in which no operation ran on the
device: 1 − (union of the device's op intervals) / window."""


def read(rec):
    if rec.unit != "event" or rec.trace is None or not rec.trace.ops:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
