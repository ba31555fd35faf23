"""Shared arithmetic of the end-to-end readers. Each reader module is named
after its metric and exposes ``read(ctx) -> float``.

``ctx``: ``requests`` (every request live in the window: ``due``, the host
time it was due or sent, ``tokens``, the host time of each output token,
``req.status``), ``t0`` and ``t_end`` (the window), ``window_s`` and
``setup_s``.
"""
from __future__ import annotations


def window_tokens(ctx, lv) -> list[float]:
    """Times of ``lv``'s output tokens inside the window."""
    return [t for t in lv.tokens if ctx["t0"] < t <= ctx["t_end"]]
