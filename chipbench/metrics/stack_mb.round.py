"""Bytes the host stacking moves per round, in MB: the sum of the
``bucket.stack`` spans' ``bytes`` (the shards pulled from the device
plus the stacks built, which the next dispatch uploads)."""
from chipbench import spans


def read(rec):
    if rec.unit != "round" or not rec.steps:
        return None
    counted = [s.attrs["bytes"] for s in spans.named(rec, "bucket.stack")
               if "bytes" in s.attrs]
    if not counted:
        return None
    return sum(counted) / 1e6 / rec.steps
