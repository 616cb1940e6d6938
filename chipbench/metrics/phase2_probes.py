"""Sample planner, Phase 2: device round trips of the partitioner per traced
mine, the arg ``probes`` of the program's span ``fimi/phase2_partition``."""


def read(r):
    got = [ev["args"]["probes"] for ev in r.spans
           if ev["name"] == "fimi/phase2_partition"
           and "probes" in ev.get("args", {})]
    mines = r.layer_data.get("mines", 0)
    return sum(got) / mines if got and mines else None
