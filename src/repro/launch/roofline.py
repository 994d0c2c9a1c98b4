"""Roofline terms from the compiled dry-run artifact.

Hardware model (TPU v5e-class, per assignment):
  peak 197 TFLOP/s bf16 / chip, 819 GB/s HBM / chip, ~50 GB/s/link ICI.

Terms (seconds), per (arch x shape x mesh):
  compute    = HLO_FLOPs_per_chip / peak
  memory     = HBM_bytes_per_chip / hbm_bw
  collective = collective_bytes_per_chip / link_bw

HLO_FLOPs/HBM/collective bytes come from hlo_analysis (trip-expanded,
per-device module).  MODEL_FLOPS = 6·N·D (train) / 2·N·D (prefill) /
2·N_active·B (decode), N excluding the embedding gather.
"""
from __future__ import annotations

import jax

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def param_counts(model) -> tuple[int, int]:
    """(total, active) param counts, excluding the embedding table."""
    cfg = model.cfg
    total = 0
    expert = 0
    for path, leaf in jax.tree.flatten_with_path(model.abstract_params())[0]:
        keys = [getattr(p, "key", str(p)) for p in path]
        if keys[-1] == "embed" and len(keys) == 1:
            continue
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        if "moe" in keys and keys[-1] in ("w_gate", "w_in", "w_out"):
            expert += n
    active = total - expert
    if cfg.num_experts:
        active += expert * cfg.top_k / cfg.num_experts
    return int(total), int(active)


def model_flops(model, shape_cfg) -> float:
    """Global useful model FLOPs for one step of the cell."""
    total, active = param_counts(model)
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    if shape_cfg.kind == "train":
        return 6.0 * active * b * s
    if shape_cfg.kind == "prefill":
        return 2.0 * active * b * s
    return 2.0 * active * b  # decode: one token


def roofline(hlo_stats: dict, model, shape_cfg, n_chips: int) -> dict:
    f = hlo_stats["flops"]                      # per chip
    hbm = hlo_stats["hbm_bytes"]                # per chip
    coll = hlo_stats["collective_bytes"]        # per chip
    mf = model_flops(model, shape_cfg)
    terms = {
        "compute_s": f / PEAK_FLOPS,
        "memory_s": hbm / HBM_BW,
        "collective_s": coll / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    bound = terms[dominant]
    useful_s = (mf / n_chips) / PEAK_FLOPS
    return {
        **terms,
        "dominant": dominant.removesuffix("_s"),
        "model_flops_global": mf,
        "hlo_flops_per_chip": f,
        "useful_ratio": (mf / n_chips) / f if f else 0.0,
        # fraction of the roofline-limited time that is useful compute:
        "roofline_fraction": useful_s / bound if bound else 0.0,
        "collective_bytes_global": coll * n_chips,
    }
