"""Sample planner, Phase 1: percent of the reservoir loop's steps that offer
an itemset.  The loop takes one step per itemset slot of a trip, ``K * I``,
on each of ``P`` miners; args ``offers`` over ``P * trips * K * I`` of the
program's span ``fimi/phase1_sample``, summed over the traced mines."""


def read(r):
    args = [ev["args"] for ev in r.spans
            if ev["name"] == "fimi/phase1_sample"
            and "offers" in ev.get("args", {})]
    steps = sum(a["P"] * a["trips"] * a["K"] * a["I"] for a in args)
    return 100.0 * sum(a["offers"] for a in args) / steps if steps else None
