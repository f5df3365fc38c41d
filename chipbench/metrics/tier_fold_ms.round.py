"""Tier fold per round, in ms: the program's ``tier.fold`` spans (an
aggregator adding a completed child's statistics into its open
aggregate, at the edge and up the tiers), which end on the aggregate
while traced. A program whose tier fold times only the enqueue of its
add reads that enqueue: no attribute tells the two apart."""
from chipbench import spans


def read(rec):
    return spans.per_step_ms(rec, "round", "tier.fold")
