"""Python's cyclic collector per round, in ms: the program's ``gc`` spans,
0 where none was recorded. A program whose spans carry no parent links
records no collector passes, and reads None."""
from chipbench import spans


def read(rec):
    if rec.unit != "round" or not rec.steps or not spans.linked(rec):
        return None
    return 1e3 * sum(s.dur_s for s in spans.named(rec, "gc")) / rec.steps
