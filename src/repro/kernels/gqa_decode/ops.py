"""Jit'd public wrapper: FP8 quantized GQA decode over a GQACache."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kvcache import GQACache
from repro.kernels.gqa_decode import kernel as _k
from repro.kernels.gqa_decode import ref as _ref


@partial(jax.jit, static_argnames=("window", "block_n", "fmt", "use_kernel", "interpret"))
def gqa_decode(
    q: jax.Array,            # [B, H, dh] (RoPE applied)
    cache: GQACache,
    positions: jax.Array,    # [B]
    *,
    window: int = 0,
    block_n: int = 128,
    fmt: str = "fp8_e4m3",
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    N = cache.k.shape[1]
    pad = (-N) % block_n
    k8, v8, ks, vs, sp = cache.k, cache.v, cache.k_scale, cache.v_scale, cache.slot_pos
    if pad:
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        k8, v8 = jnp.pad(k8, pad4), jnp.pad(v8, pad4)
        ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        sp = jnp.pad(sp, ((0, 0), (0, pad)), constant_values=-1)
    q = q.astype(jnp.float32)
    if use_kernel:
        return _k.gqa_decode_pallas(
            q, k8, v8, ks, vs, sp, positions,
            window=window, block_n=block_n, fmt=fmt, interpret=interpret)
    return _ref.gqa_decode_pipeline_ref(
        q, k8, v8, ks, vs, sp, positions,
        window=window, block_n=block_n, fmt=fmt)
