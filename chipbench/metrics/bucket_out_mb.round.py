"""Statistics the bucket programs write per round, in MB: the sum of the
``bucket.dispatch`` spans' ``bytes_out`` (the fleet pass's output, one
block set per client, or one for the bucket where the kernel folds its
clients in place). A program whose spans carry no such count reads
None."""
from chipbench import spans


def read(rec):
    if rec.unit != "round" or not rec.steps:
        return None
    counted = [s.attrs["bytes_out"]
               for s in spans.named(rec, "bucket.dispatch")
               if "bytes_out" in getattr(s, "attrs", {})]
    if not counted:
        return None
    return sum(counted) / 1e6 / rec.steps
