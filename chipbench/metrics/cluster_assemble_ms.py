"""Store to device, cluster path: time in the program's span
``cluster/assemble`` (shards read block by block, placed one per chip of
the miner mesh) per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("cluster/assemble")
