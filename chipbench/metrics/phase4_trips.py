"""Frontier loop, Phase 4: trips of the Eclat loop per traced mine, the arg
``trips`` (max over miners) of the program's span ``fimi/phase4_mine``."""


def read(r):
    got = [ev["args"]["trips"] for ev in r.spans
           if ev["name"] == "fimi/phase4_mine"
           and "trips" in ev.get("args", {})]
    mines = r.layer_data.get("mines", 0)
    return sum(got) / mines if got and mines else None
