"""Sample planner, Phase 1: time of one trip of the sample-mine loop, the
program's span ``fimi/phase1_sample`` over its arg ``trips``, summed over
the traced mines (us)."""


def read(r):
    evs = [ev for ev in r.spans if ev["name"] == "fimi/phase1_sample"
           and "trips" in ev.get("args", {})]
    trips = sum(ev["args"]["trips"] for ev in evs)
    return sum(ev["dur"] for ev in evs) / trips if trips else None
