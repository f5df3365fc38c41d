"""Host stacking per round, in ms: the program's ``bucket.stack`` spans
(shards read back to the host and copied into one padded stack per
bucket)."""
from chipbench import spans


def read(rec):
    return spans.per_step_ms(rec, "round", "bucket.stack")
