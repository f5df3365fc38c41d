"""Preamble per event, in ms: the program's ``round.prep`` spans (each
``run_events`` call's shard table, roles and schedule before its tick)."""
from chipbench import spans


def read(rec):
    return spans.per_step_ms(rec, "event", "round.prep")
