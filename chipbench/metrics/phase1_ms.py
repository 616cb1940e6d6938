"""Sample planner, Phase 1: time in the program's span ``fimi/phase1_sample`` per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("fimi/phase1_sample")
