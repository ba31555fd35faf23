"""Percentiles and cost arithmetic kept with the benchmark.

``percentile`` is nearest-rank: the smallest value with at least q% of the
sample at or below it, so +inf (a request that never got its token) is a
value like any other and pushes the tail out.

``token_cost`` is a copy of the arithmetic of the program's
``kernels/mla_decode/backends.py:token_cost`` (bytes streamed and FLOPs per
cached token per head-set of one decode step), kept here so that the
yardstick does not move with the program.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def token_cost(fmt: str, d_c: int, d_r: int, heads: int) -> tuple[int, int]:
    """(bytes, FLOPs) per cached token of one layer's decode attention:
    FP8 content byte per element + bf16 rope + f32 per-token scale; QK over
    d_c + d_r and PV over d_c, per head."""
    if fmt == "none":
        bytes_tok = (d_c + d_r) * 2
    else:
        bytes_tok = d_c * 1 + d_r * 2 + 4
    flops_tok = (2 * (d_c + d_r) + 2 * d_c) * heads
    return bytes_tok, flops_tok


def params_per_token(D: dict) -> dict:
    """Parameters a token multiplies by, per part (2 FLOPs each): attention
    projections per layer (absorbed form: q, kv down, W_uk, W_uv, W_o), the
    dense MLP, one MoE layer's router + routed top-k + shared experts, and
    the output head."""
    d, H, dh, dr, dc, ql = D["d"], D["H"], D["dh"], D["dr"], D["dc"], D["ql"]
    q = (d * ql + ql * H * (dh + dr)) if ql else d * H * (dh + dr)
    attn = q + d * (dc + dr) + dc * H * dh + dc * H * D["dv"] \
        + H * D["dv"] * d
    return {"attn": attn, "dense_mlp": 3 * d * D["f"],
            "moe": d * D["E"] + 3 * d * D["fe"] * (D["k"] + D["ns"]),
            "head": D["V"] * d}


def model_flops(D: dict, tokens: int, context_tokens: int,
                head_rows: int) -> float:
    """FLOPs the model needs for ``tokens`` tokens whose attention contexts
    sum to ``context_tokens``, ``head_rows`` of which reach the output
    head: 2 x parameters used + 2,176 x heads per context token per layer
    (at d_c 512, d_r 64)."""
    p = params_per_token(D)
    n_moe = D["L"] - D["n_dense"]
    per_tok = D["L"] * p["attn"] + D["n_dense"] * p["dense_mlp"] \
        + n_moe * p["moe"]
    _, attn_flops = token_cost("fp8_e4m3", D["dc"], D["dr"], D["H"])
    return 2.0 * (tokens * per_tok + head_rows * p["head"]) \
        + float(attn_flops) * context_tokens * D["L"]
