"""Counters on the program's spans: loop trips, lane fill, reservoir offers,
Phase-2 probes, the mine id and compiles, on a small database; with tracing
off nothing is recorded and the result is the same; under a profiler
session the spans appear on the profiler's clock."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import eclat, fimi
from repro.obs import trace as obs_trace

P = 4
K = 4
PARAMS = fimi.FimiParams(
    variant="reservoir", min_support_rel=0.08, n_db_sample=256,
    n_fi_sample=128, alpha=0.7,
    eclat=eclat.EclatConfig(max_out=4096, max_stack=1024, frontier_size=K),
)
PHASES = ("fimi/phase1_sample", "fimi/phase2_partition",
          "fimi/phase3_exchange", "fimi/phase4_mine")


def _spans(events, name=None):
    return [e for e in events if e.get("ph") == "X"
            and (name is None or e["name"] == name)]


def _traced_run(shards):
    tr = obs_trace.TRACER
    tr.clear()
    tr.enable()
    try:
        res = fimi.run(shards, 24, PARAMS, jax.random.PRNGKey(1),
                       materialize=True)
    finally:
        tr.disable()
    return res, tr.export()["traceEvents"]


@pytest.fixture(scope="module")
def runs(small_db):
    """Two traced mines and one untraced mine of the same database."""
    dense, _, _, oracle = small_db
    shards = fimi.shard_db(dense, P)
    first = _traced_run(shards)
    second = _traced_run(shards)
    obs_trace.TRACER.clear()
    off = fimi.run(shards, 24, PARAMS, jax.random.PRNGKey(1),
                   materialize=True)
    n_events_off = obs_trace.TRACER.n_events
    return oracle, first, second, off, n_events_off


def test_phase1_and_phase4_record_loop_counts(runs):
    oracle, (res, events), _, _, _ = runs
    assert res.fi_dict == oracle
    (p1,) = _spans(events, "fimi/phase1_sample")
    a = p1["args"]
    assert a["P"] == P and a["K"] == K and a["I"] == 24
    assert 0 < a["popped"] <= P * a["trips"] * K
    # the reservoir took one step per offer, at most one per F·I slot
    assert 0 < a["offers"] <= P * a["trips"] * K * 24
    (p4,) = _spans(events, "fimi/phase4_mine")
    b = p4["args"]
    assert b["trips"] == int(np.max(res.work_iters))
    assert b["popped"] == int(np.sum(res.nodes_popped))
    assert 0 < b["popped"] <= P * b["trips"] * K
    # every pushed child is popped, after the seeds
    assert b["pushed"] == int(np.sum(np.asarray(res.phase4.nodes_pushed)))
    assert 0 < b["pushed"] < b["popped"]
    assert b["P"] == P and b["K"] == K
    for ev in (p1, p4):
        assert all(type(v) in (int, float, str) for v in ev["args"].values())


def test_phase2_counts_its_probes(runs):
    _, (_, events), _, _, _ = runs
    (p2,) = _spans(events, "fimi/phase2_partition")
    probes = _spans(events, "fimi/phase2_probe")
    assert p2["args"]["probes"] == len(probes) > 0
    for ev in probes:        # each probe lies inside the partition span
        assert p2["ts"] <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= p2["ts"] + p2["dur"]


def test_one_mine_id_per_run(runs):
    _, (_, first), (_, second), _, _ = runs
    ids = []
    for events in (first, second):
        fimi_spans = [e for e in _spans(events)
                      if e["name"].startswith("fimi/")]
        assert {e["name"] for e in fimi_spans} >= {"fimi/run", *PHASES}
        mine = {e["args"]["mine"] for e in fimi_spans}
        assert len(mine) == 1
        ids.append(mine.pop())
        (root,) = _spans(events, "fimi/run")
        for e in fimi_spans:
            assert root["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"]
    assert ids[0] != ids[1]


def test_tracing_off_records_nothing_and_mines_the_same(runs):
    _, (res, _), _, off, n_events_off = runs
    assert n_events_off == 0
    assert off.fi_dict == res.fi_dict
    for a, b in zip(jax.device_get(off.phase4), jax.device_get(res.phase4)):
        np.testing.assert_array_equal(a, b)


def test_spans_are_on_the_profilers_clock(small_db, tmp_path):
    from jax.profiler import ProfileData

    dense = small_db[0]
    shards = fimi.shard_db(dense, P)
    fimi.run(shards, 24, PARAMS, jax.random.PRNGKey(1))   # warm
    with jax.profiler.trace(str(tmp_path)):
        _, events = _traced_run(shards)
    ours = [(e["name"], e["dur"] * 1e3) for e in _spans(events)
            if e["name"] in PHASES]
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = sorted(
        (e.start_ns, e.name, e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name in PHASES)
    assert [n for _, n, _ in host] == [n for n, _ in ours] == list(PHASES)
    for (_, _, theirs), (_, mine) in zip(host, ours):
        assert abs(theirs - mine) <= max(1e6, 0.05 * mine)


def test_a_compile_span_for_a_fresh_jit_only():
    tr = obs_trace.TRACER
    tr.clear()
    x = jnp.arange(8.0)
    (x + x).block_until_ready()

    @jax.jit
    def fresh(v):
        return jnp.sin(v) * 3.0

    @jax.jit
    def unseen(v):
        return jnp.cos(v) - 1.0

    tr.enable()
    try:
        fresh(x).block_until_ready()
        fresh(x).block_until_ready()        # cached: no compile
        (x + x).block_until_ready()         # a primitive compiled before
    finally:
        tr.disable()
    unseen(x).block_until_ready()           # tracing off: not recorded
    compiles = _spans(tr.export()["traceEvents"], "jax/compile")
    assert [e["args"]["fun"] for e in compiles] == ["jit(fresh)"]
    assert compiles[0]["dur"] > 0
    tr.clear()


def test_span_args_set_after_opening():
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("a", P=2) as sp:
        sp.set(trips=7)
        sp.set(popped=np.int64(5).item())
    (ev,) = _spans(tr.export()["traceEvents"])
    assert ev["args"] == {"P": 2, "trips": 7, "popped": 5}
    off = obs_trace.Tracer(enabled=False)
    with off.span("b") as sp:
        assert sp.set(trips=1) is None
    assert off.n_events == 0
