"""Shared arithmetic of the per-layer readers. Each reader module is named
after its metric and exposes ``read(ctx) -> float | None``; ``None`` (nothing
to read) leaves the metric out of the result line.

``ctx``: ``D`` (widths), ``peaks`` (this device's row of peaks.json),
``fmt`` (KV format), ``window_s`` and ``steps`` (host records of every step
of the window), ``trace`` (the reduction of the traced slice, or None),
``slice_s`` and ``slice_steps`` (the steps inside the traced slice).
"""
from __future__ import annotations

from perfbench import stats


def host_step_ms(ctx):
    steps = ctx["steps"]
    return ctx["window_s"] / len(steps) * 1e3 if steps else None


def idle_pct(ctx):
    red = ctx["trace"]
    if not red or not ctx["slice_s"] or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / ctx["slice_s"])


def decode_steps(ctx):
    return [s for s in ctx["slice_steps"] if s["decode_rows"]]


def decode_attn_s(ctx):
    red = ctx["trace"]
    if not red:
        return None
    k = red["kernel_s"].get("decode_attn", 0.0)
    return k if k > 0 and decode_steps(ctx) else None


def decode_attn_least_s(ctx):
    """Least time the chip needs for the slice's decode attention: per step,
    the larger of its bytes over HBM bandwidth and its FLOPs over the bf16
    peak; the context of a row is its sequence length."""
    D, pk = ctx["D"], ctx["peaks"]
    b_tok, f_tok = stats.token_cost(ctx["fmt"], D["dc"], D["dr"], D["H"])
    total = 0.0
    for s in decode_steps(ctx):
        n = s["decode_ctx"] * D["L"]
        total += max(n * b_tok / pk["hbm_bytes_per_s"],
                     n * f_tok / pk["bf16_flops_per_s"])
    return total
