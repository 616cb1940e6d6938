"""Frontier loop, Phase 4: time in the program's span ``fimi/phase4_mine`` per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("fimi/phase4_mine")
