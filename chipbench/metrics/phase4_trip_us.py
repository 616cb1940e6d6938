"""Frontier loop, Phase 4: time of one trip of the Eclat loop, the program's
span ``fimi/phase4_mine`` over its arg ``trips``, summed over the traced
mines (us)."""


def read(r):
    evs = [ev for ev in r.spans if ev["name"] == "fimi/phase4_mine"
           and "trips" in ev.get("args", {})]
    trips = sum(ev["args"]["trips"] for ev in evs)
    return sum(ev["dur"] for ev in evs) / trips if trips else None
