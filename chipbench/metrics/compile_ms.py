"""Set-up: time in XLA compiles (and compile-cache loads) inside the traced
window, the program's spans ``jax/compile``, per traced mine (ms).  A
program without span ``fimi/run`` does not record its compiles: nothing to
read."""


def read(r):
    recorded = any(ev["name"] == "fimi/run" for ev in r.spans)
    mines = r.layer_data.get("mines", 0)
    if not recorded or not mines:
        return None
    return sum(ev["dur"] for ev in r.spans
               if ev["name"] == "jax/compile") / 1e3 / mines
