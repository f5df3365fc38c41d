"""Solve time per event, in ms: the program's ``solve`` spans, closed by
``block_until_ready`` (on ledger ticks the span also holds the ledger's
snapshot of the exact accumulator)."""


def read(rec):
    if rec.unit != "event":
        return None
    return rec.span_ms("solve")
