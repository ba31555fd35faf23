"""Fused token-preparation Pallas kernels (paper §3.3.1, Layer 1).

* ``fused_q_quant_pallas`` — Fused-Q-Quant: per-(token,head) scale statistic,
  FP8/INT8 conversion, and Scale-Domain-Alignment (RoPE dims divided by the
  content scale) in ONE kernel — the paper replaces a three-kernel sequential
  workflow (statistics → quantize → copy) with this.

* ``fused_k_append_pallas`` — Fused-K-Append: quantization + alignment +
  non-contiguous cache write in one launch. The write position comes from a
  scalar-prefetched ``seq_lens`` vector that drives the *output BlockSpec
  index map*, so only the target 128-token page is DMA'd (the TPU analogue of
  the paper's PagedAttention-style fused writes — no full-cache traffic, no
  intermediate buffers, one kernel launch). Cache buffers are aliased
  input↔output so the untouched rows of the page pass through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.mla_decode.kernel import unit_rows
from repro.runtime.platform import resolve_interpret


def _cast_block(x, fmt):
    if fmt == "fp8_e4m3":
        return jnp.clip(x, -quant.FP8_MAX, quant.FP8_MAX).astype(jnp.float8_e4m3fn)
    if fmt == "int8":
        return jnp.clip(jnp.round(x), -127, 127).astype(jnp.int8)
    return x.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Fused-Q-Quant
# ---------------------------------------------------------------------------

def _q_quant_kernel(q_ref, qc_ref, qr_ref, sq_ref, *, d_c: int, fmt: str, qmax: float):
    q = q_ref[0].astype(jnp.float32)                  # [H, d_c + d_r]
    q_c, q_r = q[:, :d_c], q[:, d_c:]
    amax = jnp.max(jnp.abs(q_c), axis=-1)             # [H]
    sq = jnp.maximum(amax, quant.EPS) / qmax
    qc_ref[0] = _cast_block(q_c / sq[:, None], fmt)
    qr_ref[0] = q_r / sq[:, None]                     # domain alignment (Eq. 6)
    sq_ref[0, 0] = sq


def fused_q_quant_pallas(
    q: jax.Array, d_c: int, *, fmt: str = "fp8_e4m3",
    interpret: bool | None = None
):
    """q [B, H, d_c + d_r] -> (q_c8, q_r_scaled f32, sigma_q)."""
    B, H, d = q.shape
    d_r = d - d_c
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0
    kernel = functools.partial(_q_quant_kernel, d_c=d_c, fmt=fmt, qmax=qmax)
    out_dtype = quant.qdtype_for(fmt) if fmt != "none" else jnp.bfloat16
    q_c, q_r, sq = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, d), lambda b: (b, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, H, d_c), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, d_r), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, d_c), out_dtype),
            jax.ShapeDtypeStruct((B, H, d_r), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q)
    return q_c, q_r, sq[:, 0]


# ---------------------------------------------------------------------------
# Fused-K-Append
# ---------------------------------------------------------------------------

def _k_append_kernel(
    seq_lens_ref,           # scalar prefetch [B]
    ckv_ref,                # [1, 1, d_c] new entry
    kr_ref,                 # [1, 1, d_r]
    content_in_ref,         # [1, page, d_c] target page (aliased to output)
    rope_in_ref,            # [1, page, d_r]
    scale_in_ref,           # [1, 1, page]
    content_ref, rope_ref, scale_ref,   # outputs (aliased)
    *,
    page: int,
    fmt: str,
    qmax: float,
):
    b = pl.program_id(0)
    slot = seq_lens_ref[b] % page                      # row within the page
    c = ckv_ref[0].astype(jnp.float32)                 # [1, d_c]
    r = kr_ref[0].astype(jnp.float32)                  # [1, d_r]
    amax = jnp.max(jnp.abs(c))
    s = jnp.maximum(amax, quant.EPS) / qmax

    row = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    is_slot = row == slot                              # [page, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)

    content_ref[0] = jnp.where(
        is_slot, _cast_block(c / s, fmt).astype(content_in_ref.dtype),
        content_in_ref[0])
    rope_ref[0] = jnp.where(is_slot, (r / s).astype(rope_in_ref.dtype),
                            rope_in_ref[0])
    scale_ref[0] = jnp.where(lane == slot, s, scale_in_ref[0])


def fused_k_append_pallas(
    content: jax.Array,    # [B, N, d_c] cache
    rope: jax.Array,       # [B, N, d_r]
    scale: jax.Array,      # [B, N]
    c_kv: jax.Array,       # [B, d_c]
    k_r: jax.Array,        # [B, d_r]
    seq_lens: jax.Array,   # [B] write positions
    *,
    page: int = 128,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
):
    B, N, d_c = content.shape
    d_r = rope.shape[-1]
    assert N % page == 0
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0
    kernel = functools.partial(_k_append_kernel, page=page, fmt=fmt, qmax=qmax)

    page_of = lambda b, sl: sl[b] // page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, d_c), lambda b, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, d_r), lambda b, sl: (b, 0, 0)),
            # only the page containing the write slot is windowed in
            pl.BlockSpec((1, page, d_c), lambda b, sl: (b, page_of(b, sl), 0)),
            pl.BlockSpec((1, page, d_r), lambda b, sl: (b, page_of(b, sl), 0)),
            pl.BlockSpec((1, 1, page), lambda b, sl: (b, 0, page_of(b, sl))),
        ],
        out_specs=[
            pl.BlockSpec((1, page, d_c), lambda b, sl: (b, page_of(b, sl), 0)),
            pl.BlockSpec((1, page, d_r), lambda b, sl: (b, page_of(b, sl), 0)),
            pl.BlockSpec((1, 1, page), lambda b, sl: (b, 0, page_of(b, sl))),
        ],
    )
    content, rope, scale = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(content.shape, content.dtype),
            jax.ShapeDtypeStruct(rope.shape, rope.dtype),
            jax.ShapeDtypeStruct((B, 1, N), scale.dtype),
        ],
        # alias cache buffers in->out: rows outside the page are untouched,
        # rows inside pass through via the jnp.where above
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=resolve_interpret(interpret),
    )(seq_lens, unit_rows(c_kv), unit_rows(k_r), content, rope,
      unit_rows(scale))
    return content, rope, scale[:, 0]
