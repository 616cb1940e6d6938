"""Sample planner, cluster path: time in the program's span ``cluster/plan``
(the plan drawn off disk: sample, FI sample, partition, schedule) per traced
mine (ms)."""


def read(r):
    return r.per_mine_ms("cluster/plan")
