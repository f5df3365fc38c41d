"""Per-shard preamble per round, in ms: the program's ``round.prep`` spans
(validation and target reshaping before the round's work)."""
from chipbench import spans


def read(rec):
    return spans.per_step_ms(rec, "round", "round.prep")
