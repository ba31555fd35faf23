"""Jit'd wrappers for the fused token-preparation kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kvcache import MLACache, _sink_append
from repro.kernels.quantize import kernel as _k
from repro.kernels.quantize import ref as _ref


@partial(jax.jit, static_argnames=("d_c", "fmt", "use_kernel", "interpret"))
def fused_q_quant(q: jax.Array, d_c: int, *, fmt: str = "fp8_e4m3",
                  use_kernel: bool = True, interpret: bool | None = None):
    if use_kernel:
        return _k.fused_q_quant_pallas(q, d_c, fmt=fmt, interpret=interpret)
    return _ref.fused_q_quant_ref(q, d_c, fmt=fmt)


# NOTE: no donate_argnums here — the cache is aliased in->out inside the
# pallas_call already, and whole-pytree donation would invalidate seq_lens for
# eager callers; serve-step-level jit gets buffer reuse from XLA regardless.
@partial(jax.jit, static_argnames=("fmt", "page", "use_kernel", "interpret"))
def fused_k_append(cache: MLACache, c_kv: jax.Array, k_r: jax.Array, *,
                   fmt: str = "fp8_e4m3", page: int = 128,
                   use_kernel: bool = True,
                   interpret: bool | None = None) -> MLACache:
    if use_kernel:
        content, rope, scale = _k.fused_k_append_pallas(
            cache.content, cache.rope, cache.scale, c_kv, k_r, cache.seq_lens,
            page=page, fmt=fmt, interpret=interpret)
    else:
        content, rope, scale = _ref.fused_k_append_ref(
            cache.content, cache.rope, cache.scale, c_kv, k_r, cache.seq_lens,
            fmt=fmt)
    return cache._replace(
        content=content, rope=rope, scale=scale,
        seq_lens=cache.seq_lens + 1,
        sink=_sink_append(cache, c_kv, cache.seq_lens, None))
