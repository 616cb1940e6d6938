"""Sample planner, Phase 2: time in the partitioner's device round trips,
the program's spans ``fimi/phase2_probe``, per traced mine (ms).  The rest
of ``phase2_ms`` is host planning.  A program whose partition span carries
no ``probes`` arg does not time its probes: nothing to read."""


def read(r):
    counted = any("probes" in ev.get("args", {}) for ev in r.spans
                  if ev["name"] == "fimi/phase2_partition")
    mines = r.layer_data.get("mines", 0)
    if not counted or not mines:
        return None
    return sum(ev["dur"] for ev in r.spans
               if ev["name"] == "fimi/phase2_probe") / 1e3 / mines
