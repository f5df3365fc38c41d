"""Client-statistics time per event, in ms: the program's ``client.stats``
and ``bucket.dispatch`` spans, each closed by ``block_until_ready``."""


def read(rec):
    if rec.unit != "event":
        return None
    return rec.span_ms("client.stats", "bucket.dispatch")
