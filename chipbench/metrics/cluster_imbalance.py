"""Load balance, cluster path: the rounds' barrier cost, the sum over
rounds of the busiest miner's trips over the sum over rounds of the mean
miner's trips (1 is perfect), from the arg ``trips`` (one count per miner)
of the program's host spans ``cluster/mine`` in the traced mines."""


def read(r):
    rounds = [ev["args"]["trips"] for ev in r.spans
              if ev["name"] == "cluster/mine"
              and ev.get("cat", "host") == "host"
              and "trips" in ev.get("args", {})]
    mean = sum(sum(t) / len(t) for t in rounds if t)
    return sum(max(t) for t in rounds if t) / mean if mean > 0 else None
