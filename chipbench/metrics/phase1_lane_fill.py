"""Sample planner, Phase 1: percent of the sample-mine loop's frontier lanes
that mined a node: args ``popped`` over ``P * trips * K`` of the program's
span ``fimi/phase1_sample``, summed over the traced mines."""


def read(r):
    args = [ev["args"] for ev in r.spans
            if ev["name"] == "fimi/phase1_sample"
            and "popped" in ev.get("args", {})]
    lanes = sum(a["P"] * a["trips"] * a["K"] for a in args)
    return 100.0 * sum(a["popped"] for a in args) / lanes if lanes else None
