"""Every ``kernels/ops`` family compiles for a TPU v5e, with no chip attached.

Each test lowers one dispatch with ``force="pallas"`` and compiles it for a
described ``v5e:2x2`` topology at the widths ``chip_smoke.py`` runs: 1,000
items, the 100,000-transaction Phase-4 slab (3,125 tid words) and K=16
frontier nodes; serving at Q=256 queries over 32-word masks; streaming at
S=2 blocks of 4,096 rows.  The four-chip cluster mine's Phase-3 exchange
and Phase-4 mine compile under ``shard_map`` over the 2x2's four chips at
the widths of a 1,000,000-row store (one 250,000-row shard per chip).  The
compiler refuses unaligned tiles and VMEM overuse here exactly as on the
chip.  The topology is described inside a fixture, never at import: only
one process may load the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch import compile_cache

I, W, K, P = 1000, 3125, 16, 4          # items, slab tid words, frontier, miners
W_SAMPLE = 64                           # Thm 6.1 sample: 2,048 tx
Q, F, IW = 256, 4096, 32                # serving batch, index rows, mask words
T = 4096                                # rows per arrive/expire block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # AOT compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


FAMILIES = {
    "bitmap": (partial(ops.extension_supports, force="pallas"),
               [(I, W_SAMPLE), (W_SAMPLE,)]),
    "multi_vpu": (partial(ops.multi_extension_supports, force="pallas"),
                  [(I, W), (K, W)]),
    "multi_mxu": (partial(ops.multi_extension_supports, force="pallas",
                          use_mxu=True),
                  [(I, W), (K, W)]),
    "pair_vpu": (partial(ops.pair_supports, force="pallas", use_mxu=False),
                 [(I, W_SAMPLE), (W_SAMPLE,)]),
    "pair_mxu": (partial(ops.pair_supports, force="pallas"),
                 [(I, W_SAMPLE), (W_SAMPLE,)]),
    "subset": (partial(ops.subset_superset_counts, force="pallas"),
               [(Q, IW), (F, IW)]),
    "delta": (partial(ops.delta_supports, force="pallas"),
              [(T, IW), (T, IW), (F, IW)]),
    # P miners on one chip: the kernel under vmap, as Phase 4 runs it
    "multi_vpu_vmap": (
        jax.vmap(partial(ops.multi_extension_supports, force="pallas")),
        [(P, I, W), (P, K, W)]),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_compiles_for_v5e(one_chip, family):
    fn, shapes = FAMILIES[family]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_compile_cache_dir_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cache"))
    assert compile_cache.cache_dir() == str(tmp_path / "cache")
    monkeypatch.delenv(compile_cache.ENV)
    fixed = compile_cache.cache_dir()
    assert fixed == str(compile_cache.CHECKOUT_CACHE)
    assert fixed.endswith("/.jax_cache")
    assert (compile_cache.CHECKOUT_CACHE.parent / "chip_smoke.py").exists()
    assert fixed == compile_cache.cache_dir()   # fixed: no pid, temp or time


T4, CHUNK4, A4 = 250_000, 8, 64        # rows per chip, classes per round


def _cluster_phase(phase: str):
    """``(fn, [(shape, dtype)])`` of one executor phase over P miners."""
    from repro.core import eclat, fimi, phases

    if phase == "exchange":
        C = P * CHUNK4
        fn = partial(phases.phase3_exchange, axis_name=fimi.AXIS,
                     capacity=T4)
        return fn, [((P, T4, IW), jnp.uint32), ((P, T4), jnp.bool_),
                    ((P, C, IW), jnp.uint32), ((P, C), jnp.bool_),
                    ((P, C), jnp.int32)]
    cfg = eclat.EclatConfig(max_out=1 << 15, max_stack=8192, frontier_size=K)
    fn = partial(phases.phase4_mine, axis_name=fimi.AXIS, n_items=I,
                 eclat_cfg=cfg,
                 multi_support_fn=ops.support_fns("pallas", False)[1])
    return fn, [((P, P * T4, IW), jnp.uint32), ((P, P * T4), jnp.bool_),
                ((P, T4, IW), jnp.uint32), ((P, T4), jnp.bool_),
                ((P, CHUNK4, I), jnp.bool_), ((P, CHUNK4, I), jnp.bool_),
                ((P, CHUNK4), jnp.bool_), ((P, A4, I), jnp.bool_),
                ((P,), jnp.int32), ((P, 2), jnp.uint32)]


@pytest.mark.parametrize("phase", ["exchange", "mine"])
def test_cluster_phase_compiles_for_four_v5e_chips(topo, phase):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import fimi

    mesh = Mesh(topo.devices[:P], (fimi.AXIS,))
    miners = NamedSharding(mesh, PartitionSpec(fimi.AXIS))
    fn, shapes = _cluster_phase(phase)
    args = [jax.ShapeDtypeStruct(s, t, sharding=miners) for s, t in shapes]
    compiled = fimi.shard_map_spmd(fn, P, mesh).lower(*args).compile()
    text = compiled.as_text()
    assert ("all-to-all" if phase == "exchange" else "tpu_custom_call") in text
    mem = compiled.memory_analysis()      # bytes on each chip
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held < 16e9
    if phase == "mine":
        # The frontier loop pushes no [K·I, W] child block and writes its
        # tidlists as one block, not row by row; the slab's transpose holds
        # nothing 32x wider than the slab.  Unpacking the slab to transpose
        # it, with a stack that kept every child's tidlist, compiled to
        # 8,098,377,728 temp bytes here.
        W4 = P * T4 // 32
        assert f"u32[{K * I},{W4}]" not in text
        assert f"u32[1,{W4}]" not in text
        assert mem.temp_size_in_bytes <= 8_098_377_728 - 2.0e9
