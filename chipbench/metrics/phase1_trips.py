"""Sample planner, Phase 1: trips of the sample-mine loop per traced mine,
the arg ``trips`` (max over miners) of the program's span
``fimi/phase1_sample``."""


def read(r):
    got = [ev["args"]["trips"] for ev in r.spans
           if ev["name"] == "fimi/phase1_sample"
           and "trips" in ev.get("args", {})]
    mines = r.layer_data.get("mines", 0)
    return sum(got) / mines if got and mines else None
