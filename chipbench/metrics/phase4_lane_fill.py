"""Frontier loop, Phase 4: percent of the Eclat loop's frontier lanes that
mined a node: args ``popped`` over ``P * trips * K`` of the program's span
``fimi/phase4_mine``, summed over the traced mines."""


def read(r):
    args = [ev["args"] for ev in r.spans
            if ev["name"] == "fimi/phase4_mine"
            and "popped" in ev.get("args", {})]
    lanes = sum(a["P"] * a["trips"] * a["K"] for a in args)
    return 100.0 * sum(a["popped"] for a in args) / lanes if lanes else None
