"""Frontier loop, cluster path: time in the program's host spans
``cluster/mine`` (a round's Phase-4 mine and the rebalancer's decision
after it) per traced mine (ms).  The modeled per-miner lanes of the same
name are not host spans and are left out."""


def read(r):
    mines = r.layer_data.get("mines", 0)
    got = [ev["dur"] for ev in r.spans if ev["name"] == "cluster/mine"
           and ev.get("cat", "host") == "host"]
    return sum(got) / 1e3 / mines if got and mines else None
