"""Ledger snapshot per event, in ms: the program's ``ledger.snapshot``
spans (the exact accumulator's integers rounded to floats and placed on
the device), inside the ``solve`` span that ``solve_ms.event`` reads."""
from chipbench import spans


def read(rec):
    return spans.per_step_ms(rec, "event", "ledger.snapshot")
