"""Sample planner, Phase 2: time in the program's span ``fimi/phase2_partition`` per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("fimi/phase2_partition")
