"""Exchange, cluster path: time in the program's spans ``cluster/exchange``
(one Phase-3 all-to-all per round) per traced mine (ms)."""


def read(r):
    return r.per_mine_ms("cluster/exchange")
