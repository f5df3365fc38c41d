"""Ledger time per event, in ms: the program's ``ledger.apply`` spans
(signed merges into the exact accumulator)."""


def read(rec):
    if rec.unit != "event":
        return None
    return rec.span_ms("ledger.apply")
