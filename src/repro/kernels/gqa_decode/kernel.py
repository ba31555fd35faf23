"""FP8 per-token quantized GQA decode — Pallas TPU kernel.

SnapMLA Key Step 2 generalized to GQA (see gqa_decode/ref.py). Same scratch-
carried online-softmax structure as the MLA kernel; supports sliding-window
(ring-buffer) caches through per-slot absolute positions, which covers
mixtral (SWA), gemma3 local layers, and recurrentgemma local attention.

Block layout: KV blocks of ``block_n`` tokens; the full [Hkv, dh] head dim is
kept resident (dh = 128 is MXU-lane aligned; Hkv ≤ 16 for all assigned archs,
so a 128-token fp8 K block is ≤ 128*16*128 = 256 KiB in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.runtime.platform import resolve_interpret

NEG_INF = -1e30


def _gqa_decode_kernel(
    positions_ref,            # scalar prefetch: [B] int32 query positions
    q_ref,                    # [1, H, dh] f32
    k_ref, v_ref,             # [1, bn, Hkv, dh] storage dtype
    ks_ref, vs_ref,           # [1, bn, Hkv] f32
    sp_ref_in,                # [1, bn] int32 slot positions
    o_ref,                    # [1, H, dh] f32
    m_ref, l_ref, sp_ref, acc_ref,
    *,
    n_kv: int,
    block_n: int,
    window: int,
    fmt: str,
    qmax: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        sp_ref[...] = jnp.ones_like(sp_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    H, dh = q_ref.shape[1], q_ref.shape[2]
    g = H // n_kv
    qg = q_ref[0].astype(jnp.float32).reshape(n_kv, g, dh)
    k = k_ref[0].astype(jnp.float32)                   # [bn, Hkv, dh]
    v = v_ref[0].astype(jnp.float32)
    ks = ks_ref[0].astype(jnp.float32)                 # [bn, Hkv]
    vs = vs_ref[0].astype(jnp.float32)
    spos = sp_ref_in[0]                                # [bn]
    pos_b = positions_ref[b]

    # QK: batched over kv heads; K dequant via per-token scale on the logits
    kt = jnp.transpose(k, (1, 0, 2))                   # [Hkv, bn, dh]
    s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)   # [Hkv, g, bn]
    s = s * ks.T[:, None, :] * (1.0 / (dh ** 0.5))

    valid = (spos >= 0) & (spos <= pos_b)
    if window:
        valid = valid & (spos > pos_b - window)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev, l_prev, spp = m_ref[...], l_ref[...], sp_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))   # [Hkv, g]
    e = jnp.exp(s - m_new[..., None])
    e = jnp.where(valid[None, None, :], e, 0.0)

    # scale fusion + block-wise dynamic P quantization
    p_fused = e * vs.T[:, None, :]
    amax = jnp.max(jnp.abs(p_fused), axis=-1)
    if fmt == "fp8_e4m3":
        sp_new = jnp.maximum(amax, quant.EPS) / qmax
        p8 = jnp.clip(p_fused / sp_new[..., None], -quant.FP8_MAX, quant.FP8_MAX)
        p8 = p8.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif fmt == "int8":
        sp_new = jnp.maximum(amax, quant.EPS) / qmax
        p8 = jnp.clip(jnp.round(p_fused / sp_new[..., None]), -127, 127)
        p8 = p8.astype(jnp.int8).astype(jnp.float32)
    else:
        sp_new = jnp.ones_like(amax)
        p8 = p_fused

    corr = jnp.exp(m_prev - m_new) * (spp / sp_new)
    l_ref[...] = l_prev * corr + jnp.sum(e, axis=-1) / sp_new
    vt = jnp.transpose(v, (1, 0, 2))                   # [Hkv, bn, dh]
    pv = jax.lax.dot_general(p8, vt, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)  # [Hkv, g, dh]
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new
    sp_ref[...] = sp_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o = acc_ref[...] / l_ref[...][..., None]
        o_ref[0] = o.reshape(H, dh)


def gqa_decode_pallas(
    q: jax.Array,           # [B, H, dh] f32
    k8: jax.Array,          # [B, N, Hkv, dh]
    v8: jax.Array,
    k_scale: jax.Array,     # [B, N, Hkv]
    v_scale: jax.Array,
    slot_pos: jax.Array,    # [B, N] int32
    positions: jax.Array,   # [B] int32
    *,
    window: int = 0,
    block_n: int = 128,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
) -> jax.Array:
    B, H, dh = q.shape
    N, Hkv = k8.shape[1], k8.shape[2]
    assert N % block_n == 0, (N, block_n)
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0

    kernel = functools.partial(
        _gqa_decode_kernel, n_kv=Hkv, block_n=block_n, window=window,
        fmt=fmt, qmax=qmax)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, N // block_n),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, j, p: (b, 0, 0)),
            pl.BlockSpec((1, block_n, Hkv, dh), lambda b, j, p: (b, j, 0, 0)),
            pl.BlockSpec((1, block_n, Hkv, dh), lambda b, j, p: (b, j, 0, 0)),
            pl.BlockSpec((1, block_n, Hkv), lambda b, j, p: (b, j, 0)),
            pl.BlockSpec((1, block_n, Hkv), lambda b, j, p: (b, j, 0)),
            pl.BlockSpec((1, block_n), lambda b, j, p: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, j, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, H // Hkv), jnp.float32),
            pltpu.VMEM((Hkv, H // Hkv), jnp.float32),
            pltpu.VMEM((Hkv, H // Hkv), jnp.float32),
            pltpu.VMEM((Hkv, H // Hkv, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(positions, q, k8, v8, k_scale, v_scale, slot_pos)
