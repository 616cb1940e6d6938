"""Exchange, cluster path: rows the miners hold after a round's exchange
over the rows of the database (sum of |D'_i| / |D|), the arg
``replication`` of the program's spans ``cluster/exchange``, averaged over
the rounds of each traced mine and then over the mines."""


def read(r):
    by_mine: dict = {}
    for ev in r.spans:
        args = ev.get("args", {})
        if ev["name"] == "cluster/exchange" and "replication" in args:
            by_mine.setdefault(args.get("mine"), []).append(
                args["replication"])
    if not by_mine:
        return None
    return sum(sum(v) / len(v) for v in by_mine.values()) / len(by_mine)
