"""Exchange, Phase 3: time in the program's span ``fimi/phase3_exchange`` per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("fimi/phase3_exchange")
