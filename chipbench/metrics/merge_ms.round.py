"""Coordinator fold per round, in ms: the program's ``merge`` spans,
which end on the aggregate where the spans are linked (a program whose
spans carry no parent links times only the enqueue, and reads None)."""
from chipbench import spans


def read(rec):
    if not spans.linked(rec):
        return None
    return spans.per_step_ms(rec, "round", "merge")
