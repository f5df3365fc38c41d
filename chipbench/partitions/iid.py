"""``iid``: ``P`` shards of equal size, each drawn on the device from its
own key, so that no shard passes through the host."""


def split(src, n_total: int, P: int, wl: dict):
    n = n_total // P
    parts = [src.rows(i, n)[:2] for i in range(P)]
    return [p[0] for p in parts], [p[1] for p in parts]
