"""Exchange, cluster path: device time of the all-to-all ops, averaged over
the cell's chips, per traced mine (ms).  A program whose exchange runs no
all-to-all (the miners vmapped on one chip) has nothing to read."""
from cost import exchange


def read(r):
    mines = r.layer_data.get("mines", 0)
    ns = exchange.a2a_ns_per_chip(r.device)
    return ns / 1e6 / mines if ns > 0 and mines else None
