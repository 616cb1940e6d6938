"""Exchange, cluster path: percent of its interconnect roofline the Phase-3
all-to-all reached: the least bytes a chip had to send (the arg
``rows_moved`` of the program's spans ``cluster/exchange`` times a row's
bytes, ``cost/exchange.py``, over the chips) at the chip's peak ICI rate,
over the all-to-all ops' device time per chip.

Only the exchanges whose span lies inside a recorded window count: each
segment maps the program's host spans onto its own clock, in the order of
the spans, so the n-th ``cluster/exchange`` label of a segment is the n-th
such span.  Where the device tracer dropped buffers, the exchanges past the
drop add neither bytes nor time."""
from cost import exchange

NAME = "cluster/exchange"


def read(r):
    spans = [ev for ev in r.spans if ev["name"] == NAME
             and ev.get("cat", "host") == "host"]
    rows, chips = 0, 1
    for part in r.device.parts:
        chips = max(chips, len(part.chips))
        w0, w1 = part.window
        labels = [(s, e) for s, e, name in part.labels if name == NAME]
        for (s, e), ev in zip(labels, spans):
            if s >= w0 and e <= w1:
                rows += ev.get("args", {}).get("rows_moved", 0)
    ns = exchange.a2a_ns_per_chip(r.device)
    if rows <= 0 or ns <= 0:
        return None
    bits = 8 * exchange.least_bytes(rows, r.config) / chips
    return 100.0 * bits / r.peaks["ici_bits_per_s"] / (ns / 1e9)
