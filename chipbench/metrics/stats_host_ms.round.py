"""Host time of the client phase per round, in ms: the self time of the
``client.stats`` and ``bucket.dispatch`` spans, their durations less
their ``client.wait`` children (the host blocked on the device)."""
from chipbench import spans


def read(rec):
    return spans.self_ms(rec, "round", ("client.stats", "bucket.dispatch"),
                         "client.wait")
