"""Roofline analysis: three terms per (arch × shape × mesh) cell.

Hardware model (TPU v5e, ``repro.obs.machine.TPU_V5E``, Google Cloud "TPU
v5e" page): peak = 197 TFLOP/s bf16/chip, HBM = 819 GB/s/chip, ICI =
1,600 Gbit/s (200 GB/s).

Term sources:
  * compute  = executed_FLOPs_per_chip / peak
  * memory   = HBM_bytes_per_chip / bw
  * collective = wire_bytes_per_chip / link_bw

FLOPs/bytes come from an **analytic cost model** (this file) parameterized by
the exact ModelConfig + the schedule the dry-run lowered (accum, remat,
sharding policy).  Reason: XLA's ``cost_analysis()`` counts while-loop bodies
ONCE (verified in tests/test_roofline_model.py), so raw HLO numbers
undercount scanned programs by the trip counts; the dry-run JSON still
supplies the *measured* per-device memory image (``memory_analysis``) and the
full collective inventory (op types/bytes/groups) against which the analytic
model is cross-checked.  The analytic model itself is validated against an
*unrolled* compile of a small config (same test).

MODEL_FLOPS convention: 6·N·D dense / 6·N_active·D MoE (N excl. embeddings).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, Optional

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, shapes_for  # noqa: E402
from repro.configs.registry import all_archs, get_config  # noqa: E402
from repro.obs.machine import TPU_V5E  # noqa: E402

# Machine constants live in repro.obs.machine (shared with the kernel
# profiler); the module-level names are kept for existing consumers/tests.
PEAK = TPU_V5E.peak_flops
HBM = TPU_V5E.hbm_bw
LINK = TPU_V5E.link_bw
RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"

WHISPER_DEC = 448


# ---------------------------------------------------------------------------
# Analytic FLOPs (forward), per GLOBAL step
# ---------------------------------------------------------------------------


def _attn_proj_flops(cfg: ModelConfig, T: float) -> float:
    d, hd = cfg.d_model, cfg.hd
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        per_tok = (
            d * m.q_lora_rank + m.q_lora_rank * cfg.n_heads * qk
            + d * (m.kv_lora_rank + m.qk_rope_dim)
            + m.kv_lora_rank * cfg.n_heads * (m.qk_nope_dim + m.v_head_dim)
            + cfg.n_heads * m.v_head_dim * d
        )
    else:
        per_tok = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
    return 2.0 * T * per_tok


def _attn_score_flops(cfg: ModelConfig, T: float, ctx: float, causal=True) -> float:
    hd_qk = cfg.hd
    hd_v = cfg.hd
    if cfg.mla is not None:
        hd_qk = cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim
        hd_v = cfg.mla.v_head_dim
    f = 2.0 * T * ctx * cfg.n_heads * (hd_qk + hd_v)
    return f / 2 if causal and T == ctx else f


def _mlp_flops(cfg: ModelConfig, T: float, layer: int) -> float:
    d = cfg.d_model
    if cfg.moe and cfg.moe.n_experts and layer % cfg.moe.every == 0:
        m = cfg.moe
        routed = 2.0 * T * m.top_k * 3 * d * m.expert_d_ff
        shared = 2.0 * T * 3 * d * (m.n_shared * m.expert_d_ff)
        router = 2.0 * T * d * m.n_experts
        return routed + shared + router
    k = 2 if cfg.mlp_type == "gelu" else 3
    return 2.0 * T * k * d * cfg.d_ff


def _ssm_flops(cfg: ModelConfig, T: float, decode: bool = False) -> float:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    H = di // s.head_dim
    gN = s.n_groups * s.d_state
    proj = 2.0 * T * (2 * d * di + d * 2 * gN + d * H) + 2.0 * T * di * d
    if decode:
        ssd = 2.0 * T * H * s.head_dim * s.d_state * 2  # state update + readout
    else:
        Q = s.chunk
        ssd = 2.0 * T * (Q * gN + Q * di) + 4.0 * T * di * s.d_state
    return proj + ssd


def fwd_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Forward FLOPs for one global step of this cell."""
    B, S = shape.global_batch, shape.seq_len
    decode = shape.kind == "decode"
    if cfg.family == "encdec":
        enc_T, dec_T = B * S, B * (1 if decode else WHISPER_DEC)
        ctx_self = WHISPER_DEC if decode else WHISPER_DEC
        total = 0.0
        for _ in range(cfg.n_enc_layers):
            if decode:
                continue  # encoder output cached during decode
            total += _attn_proj_flops(cfg, enc_T)
            total += _attn_score_flops(cfg, enc_T, S, causal=False)
            total += _mlp_flops(cfg, enc_T, 1)
        for _ in range(cfg.n_layers):
            total += _attn_proj_flops(cfg, dec_T) * 2  # self + cross proj≈q,o only
            total += _attn_score_flops(cfg, dec_T, ctx_self)
            total += _attn_score_flops(cfg, dec_T, S, causal=False)  # cross
            total += _mlp_flops(cfg, dec_T, 1)
        total += 2.0 * dec_T * cfg.d_model * cfg.vocab_padded
        return total

    T = B * (1 if decode else S)
    ctx = S
    total = 0.0
    for l in range(cfg.n_layers):
        if cfg.family == "ssm":
            total += _ssm_flops(cfg, T, decode)
        elif cfg.family == "hybrid":
            if l % cfg.attn_every == 0:
                total += _attn_proj_flops(cfg, T) + _attn_score_flops(
                    cfg, T, ctx, causal=not decode
                )
            else:
                total += _ssm_flops(cfg, T, decode)
            total += _mlp_flops(cfg, T, l)
        else:
            total += _attn_proj_flops(cfg, T) + _attn_score_flops(
                cfg, T, ctx, causal=not decode
            )
            total += _mlp_flops(cfg, T, l)
    total += 2.0 * T * cfg.d_model * cfg.vocab_padded  # logits
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """The brief's MODEL_FLOPS: 6·N(active, excl. embed)·D tokens."""
    from repro.models import model as M

    n = M.n_params(cfg)
    emb = cfg.vocab_padded * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n_active = n - emb
    if cfg.moe and cfg.moe.n_experts:
        m = cfg.moe
        n_moe_layers = sum(
            1 for l in range(cfg.n_layers) if l % m.every == 0
        )
        routed_total = n_moe_layers * m.n_experts * 3 * cfg.d_model * m.expert_d_ff
        routed_active = routed_total * m.top_k / m.n_experts
        n_active = n_active - routed_total + routed_active
    B, S = shape.global_batch, shape.seq_len
    D = B * (1 if shape.kind == "decode" else S)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * D


# ---------------------------------------------------------------------------
# Analytic HBM + collective traffic, per chip per step
# ---------------------------------------------------------------------------


def _policy(cfg, n_par, shape):
    big = n_par > 50e9
    small = n_par < 1e9
    accum = 1
    if shape.kind == "train":
        if small:
            accum = 1
        elif big or (cfg.moe and cfg.moe.n_experts) or n_par > 10e9:
            accum = 16
        else:
            accum = 8
    return {"accum": accum, "big": big, "small": small}


def traffic_model(cfg: ModelConfig, shape: ShapeConfig, world: int, rec: Optional[dict]) -> Dict[str, float]:
    from repro.models import model as M

    n_par = M.n_params(cfg)
    pol = _policy(cfg, n_par, shape)
    A = pol["accum"]
    dshard = 1 if pol["small"] else (world // 16)   # data(-pod) shards
    mshard = 1 if pol["small"] else 16
    p_bytes_dev = 2.0 * n_par / (1 if pol["small"] else world)  # bf16, sharded
    opt_bytes = (2.0 if pol["big"] else 4.0) * 2 * n_par / (1 if pol["small"] else world)
    B, S = shape.global_batch, shape.seq_len
    tok_dev = B * (1 if shape.kind == "decode" else S) / (
        world if pol["small"] else dshard
    )
    d = cfg.d_model

    if shape.kind == "train":
        # weights: fwd + remat-fwd + bwd reads per microbatch; grads+opt once
        w_traffic = p_bytes_dev * 3 * A + p_bytes_dev * 2 + opt_bytes * 2
        act_traffic = 30.0 * tok_dev * d * 2 * cfg.n_layers  # r/w per sublayer set
        hbm = w_traffic + act_traffic
        # collectives: FSDP all-gather per microbatch + TP ARs + grad sync
        fsdp = A * p_bytes_dev * max(dshard - 1, 0) / max(dshard, 1) * (
            0 if pol["small"] else 1
        ) * dshard  # gather the full model shard set each microbatch
        mb_act = tok_dev / A * d * 2
        tp = 0.0 if mshard == 1 else A * cfg.n_layers * 4 * mb_act * 2 * (mshard - 1) / mshard
        grad = 2.0 * (4.0 * n_par / world) * max(dshard - 1, 0) / max(dshard, 1)
        if pol["small"]:
            grad = 2.0 * 4.0 * n_par * (world - 1) / world  # DP all-reduce, replicated
        wire = fsdp + tp + grad
    elif shape.kind == "prefill":
        w_traffic = p_bytes_dev
        act_traffic = 14.0 * tok_dev * d * 2 * cfg.n_layers
        hbm = w_traffic + act_traffic
        act = tok_dev * d * 2
        tp = 0.0 if mshard == 1 else cfg.n_layers * 2 * act * 2 * (mshard - 1) / mshard
        wire = tp
    else:  # decode
        cache_dev = _cache_bytes(cfg, shape) / world
        w_traffic = _active_param_bytes(cfg) * 2.0 / (1 if pol["small"] else world)
        hbm = w_traffic + cache_dev + 20.0 * tok_dev * d * 2 * cfg.n_layers
        act = tok_dev * d * 2
        tp = 0.0 if mshard == 1 else cfg.n_layers * 2 * act * 2 * (mshard - 1) / mshard
        # seq-sharded attention: per layer all-reduce of [B,H,1] stats + ctx
        wire = tp + cfg.n_layers * act
    return {"hbm_bytes_dev": hbm, "wire_bytes_dev": wire, "accum": A,
            "params_bytes_dev": p_bytes_dev + opt_bytes}


def _active_param_bytes(cfg: ModelConfig) -> float:
    from repro.models import model as M

    n = M.n_params(cfg)
    if cfg.moe and cfg.moe.n_experts:
        m = cfg.moe
        n_moe_layers = sum(1 for l in range(cfg.n_layers) if l % m.every == 0)
        routed_total = n_moe_layers * m.n_experts * 3 * cfg.d_model * m.expert_d_ff
        n = n - routed_total + routed_total * m.top_k / m.n_experts
    return 2.0 * n


def _cache_bytes(cfg: ModelConfig, shape: ShapeConfig) -> float:
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        self_c = cfg.n_layers * 2 * B * WHISPER_DEC * cfg.n_kv_heads * cfg.hd * 2
        cross = cfg.n_layers * 2 * B * S * cfg.n_kv_heads * cfg.hd * 2
        return self_c + cross
    if cfg.mla is not None:
        return cfg.n_layers * B * S * (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim) * 2
    if cfg.family == "ssm":
        s = cfg.ssm
        di = s.expand * cfg.d_model
        H = di // s.head_dim
        return cfg.n_layers * B * (H * s.head_dim * s.d_state * 4 + 3 * di * 2)
    if cfg.family == "hybrid":
        nb = cfg.n_layers // cfg.attn_every
        s = cfg.ssm
        di = s.expand * cfg.d_model
        H = di // s.head_dim
        attn_c = nb * 2 * B * S * cfg.n_kv_heads * cfg.hd * 2
        ssm_c = (cfg.n_layers - nb) * B * (H * s.head_dim * s.d_state * 4 + 3 * di * 2)
        return attn_c + ssm_c
    return cfg.n_layers * 2 * B * S * cfg.n_kv_heads * cfg.hd * 2


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    exec_flops: float
    useful_ratio: float
    fits: Optional[bool]
    mem_gb: Optional[float]
    hlo_wire_gb: Optional[float]
    note: str = ""


def analyze_cell(arch: str, shape_name: str, mesh: str = "single", tag: str = "") -> Cell:
    cfg0 = get_config(arch)
    shape = SHAPES[shape_name]
    from repro.launch.input_specs import shape_adjusted_config

    cfg = shape_adjusted_config(cfg0, shape)
    world = 512 if mesh == "multi" else 256
    f = fwd_flops(cfg, shape)
    if shape.kind == "train":
        execf = 4.0 * f  # fwd + remat-fwd + bwd(2×)
    else:
        execf = f
    n_par_small = None
    from repro.models import model as M

    pol = _policy(cfg, M.n_params(cfg), shape)
    exec_dev = execf / world
    mf = model_flops(cfg, shape)

    rec = None
    t = f"__{tag}" if tag else ""
    path = RESULTS / f"{arch}__{shape_name}__{mesh}{t}.json"
    if path.exists():
        rec = json.loads(path.read_text())
        if "skipped" in rec:
            rec = None
    tm = traffic_model(cfg, shape, world, rec)

    compute_s = exec_dev / PEAK
    memory_s = tm["hbm_bytes_dev"] / HBM
    collective_s = tm["wire_bytes_dev"] / LINK
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Cell(
        arch=arch,
        shape=shape_name,
        mesh=mesh,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=mf,
        exec_flops=execf,
        useful_ratio=mf / execf,
        fits=(rec or {}).get("memory", {}).get("fits_16GB") if rec else None,
        mem_gb=(rec or {}).get("memory", {}).get("per_device_total_bytes", 0) / 1e9
        if rec
        else None,
        hlo_wire_gb=(rec or {}).get("collectives", {}).get(
            "total_wire_bytes_per_device", 0
        )
        / 1e9
        if rec
        else None,
    )


def roofline_fraction(c: Cell) -> float:
    """Achievable fraction of compute peak: compute / max(all terms)."""
    worst = max(c.compute_s, c.memory_s, c.collective_s)
    return c.compute_s / worst if worst > 0 else 0.0


def full_table(mesh: str = "single", tag: str = ""):
    rows = []
    for arch in all_archs():
        for shape in shapes_for(get_config(arch)):
            rows.append(analyze_cell(arch, shape, mesh, tag))
    return rows


def render_markdown(rows) -> str:
    out = [
        "| arch | shape | compute s | memory s | coll s | bound | frac | "
        "useful/exec | fits16G | memGB | HLO-wire GB |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for c in rows:
        out.append(
            f"| {c.arch} | {c.shape} | {c.compute_s:.2e} | {c.memory_s:.2e} | "
            f"{c.collective_s:.2e} | {c.dominant} | {roofline_fraction(c):.2f} | "
            f"{c.useful_ratio:.2f} | {c.fits} | "
            f"{'' if c.mem_gb is None else f'{c.mem_gb:.1f}'} | "
            f"{'' if c.hlo_wire_gb is None else f'{c.hlo_wire_gb:.1f}'} |"
        )
    return "\n".join(out)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    rows = full_table(args.mesh, args.tag)
    print(render_markdown(rows))
    worst = min(rows, key=roofline_fraction)
    coll = max(rows, key=lambda c: c.collective_s / max(c.compute_s, 1e-12))
    print(f"\nworst-fraction cell: {worst.arch} × {worst.shape} "
          f"({roofline_fraction(worst):.2f}, {worst.dominant}-bound)")
    print(f"most collective-bound: {coll.arch} × {coll.shape}")


if __name__ == "__main__":
    main()
