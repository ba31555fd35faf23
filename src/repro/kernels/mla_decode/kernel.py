"""SnapMLA FP8 MLA decode — Pallas TPU kernel (the paper's flagship kernel).

Implements the full quantized decode pipeline of §3.2.3 inside one
``pl.pallas_call``:

  grid = (batch, kv_blocks) — the KV-block loop is the *innermost, sequential*
  grid dimension, so the scale-aware online-softmax state (m, l, sigma_p, acc)
  lives in VMEM scratch and is carried across grid steps. On TPU the grid is
  executed in order by construction, which gives us the paper's Appendix-E
  "monotonic scale progression" for free (no dual-warp-group inversion exists
  to cause the bidirectional-rescale hazard).

  Per KV block (block_n = 128 tokens — §3.3.2's cache-line-aligned tile):
    1. QK with pre-scaled domain alignment (Key Step 1): one uniform
       content+rope dot, one rescale by sigma_q ⊗ sigma_k.
    2. Online softmax max/renormalization.
    3. Scale fusion p~ = e ⊙ sigma_k (V ≡ latent cache in absorbed MLA).
    4. Block-wise dynamic P quantization (sigma_p = max|p~|/qmax).
    5. FP8 PV "GEMM" + implicit dequantization via Eq. 12-13 accumulation.

Split-KV (flash-decoding) variant — ``mla_decode_splitkv_pallas``:

  grid = (batch, num_splits, kv_blocks_per_split) with the block loop still
  innermost and sequential. Each split runs the exact same scale-fused FP8
  block pipeline over its KV slice and emits partial (o, lse, sigma_p); a
  second ``lse_combine_pallas`` kernel merges the partials with the standard
  max-shift LSE rescale. The Appendix-E "monotonic scale progression"
  argument restated for the split grid: scale monotonicity is only required
  *within* one online-softmax accumulation chain (it is what makes the
  Eq. 12-13 rescale factors sp_prev/sp_new well-conditioned), and under the
  split grid each chain is confined to one (batch, split) cell whose block
  loop is still executed in order by the sequential innermost grid dimension
  — so the per-chain progression is preserved verbatim. *Across* splits no
  ordering is needed at all: each split's sigma_p is carried into its partial
  scale-carrying LSE (lse_s = m_s + log(sigma_p_s * l~_s), with o_s already
  normalized so sigma_p cancels elementwise), and the combine is an
  order-free sum of exp(lse_s - max lse) weights — the implicit
  dequantization of Eqs. 12-13 stays exact under any split interleaving.

  Block-level early exit: ``seq_lens`` is scalar-prefetched, so the BlockSpec
  index maps clamp every out-of-range block index to the last live block of
  that sequence — the pipeline then re-"fetches" an already-resident block
  (Pallas elides the DMA when the index is unchanged) and ``pl.when`` skips
  the compute. HBM traffic therefore scales with ``seq_lens``, not with the
  padded cache capacity.

  q_len > 1 (the speculative-verify shape): both split-KV wrappers accept a
  rank-4 ``[B, q_len, H, ...]`` query block — the q_len rows are the LAST
  q_len positions of each sequence, flattened head-major into ``q_len * H``
  kernel rows (each row carries its own online-softmax state, so the body is
  unchanged except for a per-row causal limit ``seq_len - (q_len-1) + t`` in
  place of the scalar and the dead-row neutrality guard in
  ``_block_pipeline``). q_len = 1 passes the scalar limit exactly as before
  — bit-identical to the PR 8 kernel by literal trace identity.

Paged split-KV — ``mla_decode_paged_splitkv_pallas``: the same split grid and
  per-split partial/combine layout over a page pool; the scalar-prefetched
  page table only relocates each block's DMA source, so the contiguous and
  paged variants share one kernel body, one early-exit predicate, and one
  combine path (``_splitkv_partials_call`` + ``lse_combine_pallas``). HBM
  traffic is proportional to ``seq_lens``, not pool capacity.

TPU adaptation notes (DESIGN.md §2): FP8 here is the *storage* dtype — blocks
are upcast to f32 on load inside the kernel (v5e has no FP8 MXU; the win is
HBM bytes, which is what decode attention is bound by at small head counts).
The paged variant uses a scalar-prefetched page table in the BlockSpec index
maps — the TPU-native PagedAttention (replaces the paper's TMA-driven
Fused-K-Append read path).

Validated in interpret mode against ref.snapmla_decode_pipeline_ref /
ref.snapmla_decode_splitkv_ref (exact same arithmetic) and
core.attention.mla_decode_dequant_ref (quantization error bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.mla_decode import amla
from repro.runtime.platform import resolve_interpret

NEG_INF = -1e30


def _quantize_block(p_fused, fmt: str, qmax: float):
    amax = jnp.max(jnp.abs(p_fused), axis=-1)
    sp = jnp.maximum(amax, quant.EPS) / qmax
    if fmt == "fp8_e4m3":
        p8 = jnp.clip(p_fused / sp[:, None], -quant.FP8_MAX, quant.FP8_MAX)
        p8 = p8.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif fmt == "int8":
        p8 = jnp.clip(jnp.round(p_fused / sp[:, None]), -127, 127)
        p8 = p8.astype(jnp.int8).astype(jnp.float32)
    else:  # "none": scale-fused but unquantized (BF16-pipeline baseline)
        sp = jnp.ones_like(sp)
        p8 = p_fused
    return p8, sp


def _block_pipeline(qc, qr, sq, c, r, sk, tok0, seq_len,
                    m_ref, l_ref, sp_ref, acc_ref, *,
                    softmax_scale: float, fmt: str, qmax: float,
                    rescale: str = "fma", row_guard: bool = False):
    """One KV block of the scale-fused FP8 pipeline (steps 1-5 of §3.2.3).

    Shared verbatim between the single-pass, split-KV, and paged kernels so
    their per-block arithmetic is bit-identical. ``tok0`` is the absolute
    token index of the block's first entry; state is carried in VMEM scratch.
    ``sq`` is the per-row query scale ``[rows]``; ``sk`` the block's
    per-token key scales as a ``[1, bn]`` row.

    ``seq_len`` is either a scalar (every query row sees the same KV prefix —
    the decode case) or a ``[rows, 1]`` per-row limit (the ``q_len > 1``
    verify case, where row ``t`` of the causally-masked query block attends
    only tokens ``< seq_len - (q_len - 1) + t``); it broadcasts against the
    ``[rows, block_n]`` token grid either way, so the masking site is shared.

    ``rescale`` selects the cross-block accumulator rescale:

      * ``"fma"`` (default, exact): the Eq. 12-13 max-shift FMA —
        ``corr = exp(m_prev - m_new) * (sp_prev / sp_new)``.
      * ``"amla"``: the running max and sigma_p live on the power-of-two grid
        (``m = i*ln2``, ``sigma_p = 2^e``; m_ref carries i, sp_ref carries e)
        so every rescale factor is an exact ``2^k`` applied via an integer
        add on the accumulator exponent bits (``amla.exp2_mul``) — no exp,
        no FMA on the [H, d_c] accumulator.

    ``row_guard`` (the q_len > 1 paths only): a row that is fully masked in a
    live block must leave its carried state EXACTLY unchanged. Without the
    guard such a row would still rescale by ``sp_prev / sp_new`` with
    ``sp_new`` floored at ``EPS / qmax`` — mathematically a no-op (it cancels
    in o = acc / l) but numerically an overflow hazard and a bit-identity
    breaker vs the q_len = 1 kernel. The guard pins ``sp_new`` (FMA) /
    ``e_new`` (AMLA) to the carried value on dead rows, making the rescale
    factor exactly 1 (FMA) / exactly ``2^0`` (AMLA) and every additive
    contribution exactly 0.
    """
    # --- Key Step 1: uniform QK + single rescale -------------------------
    s = jax.lax.dot_general(qc, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s += jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s = s * (sq[:, None] * sk) * softmax_scale                     # [H, bn]

    tok = tok0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = tok < seq_len
    s = jnp.where(valid, s, NEG_INF)
    row_live = jnp.any(valid, axis=-1) if row_guard else None

    if rescale == "amla":
        i_prev, l_prev, e_prev = m_ref[...], l_ref[...], sp_ref[...]
        # max snapped UP onto the log2 grid: monotone, and e <= 1 below
        i_new = jnp.maximum(i_prev,
                            jnp.ceil(jnp.max(s, axis=-1) * amla.LOG2E))
        e = jnp.exp(s - (i_new * amla.LN2)[:, None])
        e = jnp.where(valid, e, 0.0)
        p_fused = e * sk
        p8, e_new = amla.quantize_block_pow2(p_fused, fmt, qmax)
        if row_guard:
            e_new = jnp.where(row_live, e_new, e_prev)
        # corr = 2^k with k = (i_prev - i_new) + (e_prev - e_new): a pure
        # integer exponent add on the accumulator (l_prev == 0 -> no state
        # yet, k pinned to 0 so the sentinel i_prev never reaches int32)
        k = jnp.where(l_prev > 0.0,
                      (i_prev - i_new) + (e_prev - e_new),
                      0.0).astype(jnp.int32)                       # [H]
        l_ref[...] = (amla.exp2_mul(l_prev, k)
                      + amla.exp2_mul(jnp.sum(e, axis=-1),
                                      -e_new.astype(jnp.int32)))
        acc_ref[...] = amla.exp2_mul(acc_ref[...], k[:, None]) + \
            jax.lax.dot_general(p8, c, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = i_new
        sp_ref[...] = e_new
        return

    # --- online softmax ---------------------------------------------------
    m_prev, l_prev, sp_prev = m_ref[...], l_ref[...], sp_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))               # [H]
    e = jnp.exp(s - m_new[:, None])
    e = jnp.where(valid, e, 0.0)

    # --- Key Step 2: scale fusion + block-wise dynamic P quantization -----
    p_fused = e * sk
    p8, sp_new = _quantize_block(p_fused, fmt, qmax)
    if row_guard:
        sp_new = jnp.where(row_live, sp_new, sp_prev)

    # --- implicit dequantization (Eqs. 12-13) ------------------------------
    corr = jnp.exp(m_prev - m_new) * (sp_prev / sp_new)            # [H]
    l_ref[...] = l_prev * corr + jnp.sum(e, axis=-1) / sp_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p8, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    sp_ref[...] = sp_new


def _init_state(m_ref, l_ref, sp_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    sp_ref[...] = jnp.ones_like(sp_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _mla_decode_kernel(
    # scalar prefetch
    seq_lens_ref,           # [B] int32
    # inputs (VMEM blocks)
    q_c_ref,                # [1, H, d_c]  storage dtype
    q_r_ref,                # [1, H, d_r]  f32 (pre-divided by sigma_q)
    sigma_q_ref,            # [1, 1, H]    f32
    content_ref,            # [1, bn, d_c] storage dtype
    rope_ref,               # [1, bn, d_r] f32/bf16 (pre-divided by sigma_k)
    sigma_k_ref,            # [1, 1, bn]   f32
    # outputs
    o_ref,                  # [1, H, d_c]  f32
    lse_ref,                # [1, 1, H]    f32
    # scratch
    m_ref, l_ref, sp_ref,   # [H]
    acc_ref,                # [H, d_c]
    *,
    softmax_scale: float,
    block_n: int,
    fmt: str,
    qmax: float,
    rescale: str = "fma",
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nblocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, sp_ref, acc_ref)

    qc = q_c_ref[0].astype(jnp.float32)              # [H, d_c]
    qr = q_r_ref[0].astype(jnp.float32)              # [H, d_r]
    sq = sigma_q_ref[0, 0].astype(jnp.float32)       # [H]
    c = content_ref[0].astype(jnp.float32)           # [bn, d_c]
    r = rope_ref[0].astype(jnp.float32)              # [bn, d_r]
    sk = sigma_k_ref[0].astype(jnp.float32)          # [1, bn]

    _block_pipeline(qc, qr, sq, c, r, sk, j * block_n, seq_lens_ref[b],
                    m_ref, l_ref, sp_ref, acc_ref,
                    softmax_scale=softmax_scale, fmt=fmt, qmax=qmax,
                    rescale=rescale)

    @pl.when(j == nblocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = acc_ref[...] / l[:, None]                       # sigma_p cancels
        if rescale == "amla":
            # m_ref/sp_ref hold the integer exponents i and e: the scale-
            # carrying LSE is (i + e) * ln2 + log(l~)
            lse_ref[0, 0] = (m_ref[...] + sp_ref[...]) * amla.LN2 + jnp.log(l)
        else:
            lse_ref[0, 0] = m_ref[...] + jnp.log(sp_ref[...] * l)


def unit_rows(x: jax.Array) -> jax.Array:
    """``[B, X] -> [B, 1, X]``. The TPU lowering refuses a ``(1, X)`` block
    of a ``[B, X]`` array (the last two block dims must tile by (8, 128) or
    equal the array's), so per-row vectors — query scales, per-token key
    scales, LSEs — cross the kernel boundary with a unit middle axis and ride
    as ``(1, 1, X)`` blocks. A ``[n_pages, page]`` scale pool rides the same
    way, one ``(1, 1, page)`` block per page-table entry; at page 128 the
    reshape is a bitcast of the pool (no copy, no gather)."""
    return x[:, None, :]


def mla_decode_pallas(
    q_c8: jax.Array,        # [B, H, d_c] storage dtype
    q_r: jax.Array,         # [B, H, d_r] f32 (pre-divided by sigma_q)
    sigma_q: jax.Array,     # [B, H] f32
    content: jax.Array,     # [B, N, d_c]
    rope: jax.Array,        # [B, N, d_r]
    sigma_k: jax.Array,     # [B, N] f32
    seq_lens: jax.Array,    # [B] int32
    *,
    softmax_scale: float,
    block_n: int = 128,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    """Contiguous-cache SnapMLA decode. Returns (o [B,H,d_c] f32, lse [B,H])."""
    B, H, d_c = q_c8.shape
    d_r = q_r.shape[-1]
    N = content.shape[1]
    assert N % block_n == 0, (N, block_n)
    nblocks = N // block_n
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0

    kernel = functools.partial(
        _mla_decode_kernel, softmax_scale=softmax_scale, block_n=block_n,
        fmt=fmt, qmax=qmax, rescale=rescale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nblocks),
        in_specs=[
            pl.BlockSpec((1, H, d_c), lambda b, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, H, d_r), lambda b, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, block_n, d_c), lambda b, j, sl: (b, j, 0)),
            pl.BlockSpec((1, block_n, d_r), lambda b, j, sl: (b, j, 0)),
            pl.BlockSpec((1, 1, block_n), lambda b, j, sl: (b, 0, j)),
        ],
        out_specs=_single_pass_out_specs(H, d_c),
        scratch_shapes=_state_scratch(H, d_c),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_single_pass_out_shape(B, H, d_c),
        interpret=resolve_interpret(interpret),
    )(seq_lens, q_c8, q_r, unit_rows(sigma_q), content, rope,
      unit_rows(sigma_k))
    return o, lse[:, 0]


def _state_scratch(rows: int, d_c: int) -> list:
    """VMEM scratch of the online-softmax state: m, l, sigma_p ([rows]) and
    the [rows, d_c] accumulator."""
    return [pltpu.VMEM((rows,), jnp.float32), pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, d_c), jnp.float32)]


def _single_pass_out_specs(H: int, d_c: int) -> list:
    """(o, lse) output blocks of one batch row, for any grid led by b."""
    return [pl.BlockSpec((1, H, d_c), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, *_: (b, 0, 0))]


def _single_pass_out_shape(B: int, H: int, d_c: int) -> list:
    return [jax.ShapeDtypeStruct((B, H, d_c), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H), jnp.float32)]


# ---------------------------------------------------------------------------
# Split-KV (flash-decoding) variant
# ---------------------------------------------------------------------------

def _mla_decode_splitkv_kernel(
    # scalar prefetch
    seq_lens_ref,           # [B] int32
    # inputs (VMEM blocks)
    q_c_ref,                # [1, R, d_c]   R = q_len * H query rows
    q_r_ref,                # [1, R, d_r]
    sigma_q_ref,            # [1, 1, R]
    content_ref,            # [1, bn, d_c]
    rope_ref,               # [1, bn, d_r]
    sigma_k_ref,            # [1, 1, bn]
    # outputs (per-split partials)
    o_ref,                  # [1, 1, R, d_c] f32
    lse_ref,                # [1, 1, 1, R]   f32 (scale-carrying LSE)
    sp_ref_out,             # [1, 1, 1, R]   f32 (final per-split sigma_p)
    # scratch
    m_ref, l_ref, sp_ref,   # [R]
    acc_ref,                # [R, d_c]
    *,
    softmax_scale: float,
    block_n: int,
    blocks_per_split: int,
    fmt: str,
    qmax: float,
    rescale: str = "fma",
    q_len: int = 1,
    heads: int | None = None,
):
    b = pl.program_id(0)
    s_id = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, sp_ref, acc_ref)

    # Block-level early exit: blocks whose first token is past seq_len carry
    # no valid entries (valid tokens are a prefix), so skip their compute
    # entirely. Their DMA was already elided by the clamped index map.
    g = s_id * blocks_per_split + j                    # global KV block index
    live = g * block_n < seq_lens_ref[b]

    @pl.when(live)
    def _compute():
        qc = q_c_ref[0].astype(jnp.float32)
        qr = q_r_ref[0].astype(jnp.float32)
        sq = sigma_q_ref[0, 0].astype(jnp.float32)
        c = content_ref[0].astype(jnp.float32)
        r = rope_ref[0].astype(jnp.float32)
        sk = sigma_k_ref[0].astype(jnp.float32)
        if q_len == 1:
            # the decode fast path: a SCALAR limit, no row guard — the trace
            # (and hence the emitted kernel) is literally the PR 8 kernel's,
            # so q_len = 1 through this body is bit-identical to it.
            limit = seq_lens_ref[b]
        else:
            # causal intra-block mask: the q_len query rows are the LAST
            # q_len positions of the sequence, head-major within a position
            # (row = t * heads + h), so row t's KV prefix ends at
            # seq_len - (q_len - 1) + t. Rows whose limit is <= 0 (idle
            # slots, over-drafted tails) stay on their neutral init state
            # via the row guard and publish the empty-split partial.
            t = jax.lax.broadcasted_iota(
                jnp.int32, (q_len * heads, 1), 0) // heads
            limit = seq_lens_ref[b] - (q_len - 1) + t
        _block_pipeline(qc, qr, sq, c, r, sk, g * block_n, limit,
                        m_ref, l_ref, sp_ref, acc_ref,
                        softmax_scale=softmax_scale, fmt=fmt, qmax=qmax,
                        rescale=rescale, row_guard=q_len > 1)

    @pl.when(j == blocks_per_split - 1)
    def _finalize():
        l = l_ref[...]
        has = l > 0.0
        if rescale == "amla":
            # COMBINE-FREE emission: the partial is published UNNORMALIZED —
            # raw accumulator in the o slot, raw l~ in the lse slot, and the
            # split's integer grid exponent g = i + e in the sigma_p slot
            # (exp(m_s) * sigma_p_s == 2^(i_s + e_s) exactly). The combine
            # then needs no per-split normalization and no exp: cross-split
            # rescaling is a pure integer exponent add. Empty splits publish
            # (0, 0, 0) and contribute nothing.
            o_ref[0, 0] = acc_ref[...]
            lse_ref[0, 0, 0] = l
            sp_ref_out[0, 0, 0] = jnp.where(has, m_ref[...] + sp_ref[...],
                                            0.0)
        else:
            # Empty splits (no live block touched the state) publish a
            # neutral partial: o = 0, lse = NEG_INF — the combine weight
            # exp(lse - m*) then vanishes. l > 0 iff at least one valid
            # token was accumulated.
            # (the row mask is rebuilt from l as a column: Mosaic cannot
            # reshape a boolean vector)
            safe_l = jnp.where(has, l, 1.0)
            o_ref[0, 0] = jnp.where(l[:, None] > 0.0,
                                    acc_ref[...] / safe_l[:, None], 0.0)
            lse_ref[0, 0, 0] = jnp.where(
                has, m_ref[...] + jnp.log(sp_ref[...] * safe_l), NEG_INF)
            sp_ref_out[0, 0, 0] = sp_ref[...]


def _clamped_block_index(seq_lens_ref, b, s_id, j, blocks_per_split, block_n):
    """Global block index for (split, block), clamped to the last live block of
    sequence ``b`` so dead blocks re-address an already-resident page (the
    Pallas pipeline elides the DMA when the index map output is unchanged)."""
    g = s_id * blocks_per_split + j
    last_live = jnp.maximum((seq_lens_ref[b] + block_n - 1) // block_n - 1, 0)
    return jnp.minimum(g, last_live)


def _splitkv_partials_call(
    kernel_body,
    *,
    grid: tuple,
    in_specs: list,
    num_scalar_prefetch: int,
    B: int,
    num_splits: int,
    H: int,
    d_c: int,
    interpret: bool | None,
    operands: tuple,
):
    """One shared split/combine code path for BOTH the contiguous and the paged
    split-KV kernels: identical per-split partial layout ([B, S, H, ...] with
    the scale-carrying LSE), identical VMEM scratch for the online-softmax
    state, identical pallas_call plumbing. Callers differ only in their grid,
    input BlockSpecs (clamped contiguous block index vs page-table lookup) and
    scalar-prefetch operands. Returns the raw (o, lse, sigma_p) partials,
    ``[B, S, H, d_c]`` / ``[B, S, H]`` / ``[B, S, H]``."""
    vec = pl.BlockSpec((1, 1, 1, H), lambda b, s, j, *_: (b, s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, H, d_c), lambda b, s, j, *_: (b, s, 0, 0)),
            vec, vec,
        ],
        scratch_shapes=_state_scratch(H, d_c),
    )
    o_p, lse_p, sp_p = pl.pallas_call(
        kernel_body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, num_splits, H, d_c), jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, 1, H), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(*operands)
    return o_p, lse_p[:, :, 0], sp_p[:, :, 0]


def _flatten_q(q_c8, q_r, sigma_q):
    """[B, q_len, H, ...] query block -> head-major rows [B, q_len*H, ...].

    The kernel bodies treat the row axis exactly like the head axis (every
    row has independent online-softmax state), so a q_len > 1 query block is
    just "more heads" plus a per-row causal limit. Rank-3 queries pass
    through untouched (q_len = None marks the rank-3 no-op so rank-4 inputs
    — even with q_len == 1 — come back rank-4)."""
    if q_c8.ndim == 3:
        return q_c8, q_r, sigma_q, None, q_c8.shape[1]
    B, q_len, H = q_c8.shape[:3]
    return (q_c8.reshape(B, q_len * H, -1), q_r.reshape(B, q_len * H, -1),
            sigma_q.reshape(B, q_len * H), q_len, H)


def _unflatten_rows(q_len, H, o, lse, partials):
    """Undo ``_flatten_q`` on the outputs: rows -> [q_len, H] axes."""
    if q_len is None:
        return o, lse, partials
    B = o.shape[0]
    o = o.reshape(B, q_len, H, -1)
    lse = lse.reshape(B, q_len, H)
    if partials is not None:
        o_p, lse_p, sp_p = partials
        S = o_p.shape[1]
        partials = (o_p.reshape(B, S, q_len, H, -1),
                    lse_p.reshape(B, S, q_len, H),
                    sp_p.reshape(B, S, q_len, H))
    return o, lse, partials


def mla_decode_splitkv_pallas(
    q_c8: jax.Array,        # [B, H, d_c] or [B, q_len, H, d_c] storage dtype
    q_r: jax.Array,         # [..., d_r] f32 (pre-divided by sigma_q)
    sigma_q: jax.Array,     # [B, H] or [B, q_len, H] f32
    content: jax.Array,     # [B, N, d_c]
    rope: jax.Array,        # [B, N, d_r]
    sigma_k: jax.Array,     # [B, N] f32
    seq_lens: jax.Array,    # [B] int32
    *,
    softmax_scale: float,
    num_splits: int,
    block_n: int = 128,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
    return_partials: bool = False,
    rescale: str = "fma",
):
    """Sequence-parallel (flash-decoding) SnapMLA decode.

    Grid (batch, num_splits, kv_blocks_per_split): each split runs the
    scale-fused FP8 pipeline over its KV slice and emits partial
    (o, lse, sigma_p); ``lse_combine_pallas`` (or, under
    ``rescale="amla"``, the exponent-add ``amla_combine_pallas`` over
    unnormalized partials) merges them. Returns (o [B,H,d_c] f32,
    lse [B,H]) — plus the raw partials when ``return_partials`` (for
    oracles/telemetry).

    A rank-4 ``[B, q_len, H, ...]`` query block runs the q_len > 1 verify
    path: rows are the LAST q_len positions of each sequence under a causal
    intra-block mask (row t attends tokens < seq_lens - (q_len-1) + t), and
    outputs/partials come back with the extra q_len axis
    (o [B,q_len,H,d_c], lse [B,q_len,H], partials [B,S,q_len,H,...]).
    """
    q_c8, q_r, sigma_q, q_len, H = _flatten_q(q_c8, q_r, sigma_q)
    B, R, d_c = q_c8.shape
    d_r = q_r.shape[-1]
    N = content.shape[1]
    assert N % block_n == 0, (N, block_n)
    nblocks = N // block_n
    assert 1 <= num_splits <= nblocks, (num_splits, nblocks)
    blocks_per_split = (nblocks + num_splits - 1) // num_splits
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0

    kernel = functools.partial(
        _mla_decode_splitkv_kernel, softmax_scale=softmax_scale,
        block_n=block_n, blocks_per_split=blocks_per_split, fmt=fmt,
        qmax=qmax, rescale=rescale, q_len=q_len or 1, heads=H)

    def kv_idx(b, s, j, sl):
        return (b, _clamped_block_index(sl, b, s, j, blocks_per_split, block_n), 0)

    def sk_idx(b, s, j, sl):
        return (b, 0, _clamped_block_index(sl, b, s, j, blocks_per_split,
                                           block_n))

    o_p, lse_p, sp_p = _splitkv_partials_call(
        kernel,
        grid=(B, num_splits, blocks_per_split),
        in_specs=[
            pl.BlockSpec((1, R, d_c), lambda b, s, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, R, d_r), lambda b, s, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, s, j, sl: (b, 0, 0)),
            pl.BlockSpec((1, block_n, d_c), kv_idx),
            pl.BlockSpec((1, block_n, d_r), kv_idx),
            pl.BlockSpec((1, 1, block_n), sk_idx),
        ],
        num_scalar_prefetch=1,
        B=B, num_splits=num_splits, H=R, d_c=d_c, interpret=interpret,
        operands=(seq_lens, q_c8, q_r, unit_rows(sigma_q), content, rope,
                  unit_rows(sigma_k)),
    )
    return _combine(q_len, H, o_p, lse_p, sp_p, rescale=rescale,
                    interpret=interpret, return_partials=return_partials)


def _combine(q_len, H, o_p, lse_p, sp_p, *, rescale, interpret,
             return_partials):
    """Merge split partials (LSE max-shift, or the AMLA exponent-add) and
    restore the q_len axis of a verify block."""
    if rescale == "amla":
        o, lse = amla_combine_pallas(o_p, lse_p, sp_p, interpret=interpret)
    else:
        o, lse = lse_combine_pallas(o_p, lse_p, interpret=interpret)
    o, lse, partials = _unflatten_rows(q_len, H, o, lse, (o_p, lse_p, sp_p))
    if return_partials:
        return o, lse, partials
    return o, lse


def _lse_combine_kernel(o_p_ref, lse_p_ref, o_ref, lse_ref):
    """Max-shift LSE combine of per-split partials (one batch row per step).

    The per-split sigma_p is carried inside the scale-carrying partial LSE
    (lse_s = m_s + log(sigma_p_s * l~_s) with o_s = acc_s / l~_s, so sigma_p
    cancels elementwise in o_s and survives only in the weight) — making the
    standard flash-decoding combine exact for the quantized pipeline.
    """
    lse_p = lse_p_ref[0]                               # [S, H]
    o_p = o_p_ref[0]                                   # [S, H, d_c]
    m_star = jnp.max(lse_p, axis=0)                    # [H]
    w = jnp.exp(lse_p - m_star[None, :])               # [S, H]
    den = jnp.sum(w, axis=0)                           # [H]
    num = jnp.sum(w[:, :, None] * o_p, axis=0)         # [H, d_c]
    o_ref[0] = num / den[:, None]
    lse_ref[0, 0] = m_star + jnp.log(den)


def _combine_call(kernel_body, o_partial, *vec_partials, interpret):
    """pallas_call plumbing shared by both combines: one batch row per grid
    step, ``[B, S, H, d_c]`` + ``[B, S, H]`` partials in, (o, lse) out."""
    B, S, H, d_c = o_partial.shape
    o, lse = pl.pallas_call(
        kernel_body,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, S, H, d_c), lambda b: (b, 0, 0, 0))]
        + [pl.BlockSpec((1, S, H), lambda b: (b, 0, 0))] * len(vec_partials),
        out_specs=_single_pass_out_specs(H, d_c),
        out_shape=_single_pass_out_shape(B, H, d_c),
        interpret=resolve_interpret(interpret),
    )(o_partial, *vec_partials)
    return o, lse[:, 0]


def lse_combine_pallas(
    o_partial: jax.Array,     # [B, S, H, d_c] f32
    lse_partial: jax.Array,   # [B, S, H] f32 (scale-carrying, NEG_INF if empty)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Combine split-KV partials: returns (o [B,H,d_c], lse [B,H])."""
    return _combine_call(_lse_combine_kernel, o_partial, lse_partial,
                         interpret=interpret)


def _amla_combine_kernel(acc_p_ref, l_p_ref, g_p_ref, o_ref, lse_ref):
    """Exponent-add combine of UNNORMALIZED AMLA partials (one batch row).

    Split s's true (unnormalized) softmax numerator/denominator are
    ``2^g_s * acc_s`` and ``2^g_s * l_s`` with the integer grid exponent
    ``g_s = i_s + e_s`` (exp(m_s) * sigma_p_s == 2^g_s exactly). The
    max-shift therefore needs no exp at all: shift every split onto the
    hottest grid point K* = max g_s by adding ``(g_s - K*) << 23`` to the
    accumulator exponent bits, then sum. Replaces lse_combine's
    ``w = exp(lse_s - m*)`` FMA weights with integer adds; the single
    division and log happen once, on the combined result.
    """
    acc_p = acc_p_ref[0]                               # [S, H, d_c]
    l_p = l_p_ref[0]                                   # [S, H]
    g_p = g_p_ref[0]                                   # [S, H]
    has = l_p > 0.0
    k_star = jnp.max(jnp.where(has, g_p, NEG_INF), axis=0)       # [H]
    k = jnp.where(has, g_p - k_star[None, :], 0.0).astype(jnp.int32)
    den = jnp.sum(amla.exp2_mul(l_p, k), axis=0)                 # [H]
    num = jnp.sum(amla.exp2_mul(acc_p, k[:, :, None]), axis=0)   # [H, d_c]
    o_ref[0] = num / den[:, None]
    lse_ref[0, 0] = k_star * amla.LN2 + jnp.log(den)


def amla_combine_pallas(
    acc_partial: jax.Array,   # [B, S, H, d_c] f32 UNNORMALIZED accumulators
    l_partial: jax.Array,     # [B, S, H] f32 raw l~ (0 if empty)
    g_partial: jax.Array,     # [B, S, H] f32 integer grid exponents i + e
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Combine AMLA split-KV partials: returns (o [B,H,d_c], lse [B,H])."""
    return _combine_call(_amla_combine_kernel, acc_partial, l_partial,
                         g_partial, interpret=interpret)


def mla_decode_paged_pallas(
    q_c8: jax.Array,        # [B, H, d_c]
    q_r: jax.Array,         # [B, H, d_r]
    sigma_q: jax.Array,     # [B, H]
    content_pool: jax.Array,  # [n_pages, page, d_c]
    rope_pool: jax.Array,     # [n_pages, page, d_r]
    scale_pool: jax.Array,    # [n_pages, page]
    page_table: jax.Array,    # [B, P] int32
    seq_lens: jax.Array,      # [B]
    *,
    softmax_scale: float,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    """Paged-pool SnapMLA decode: the page table is scalar-prefetched and
    drives the BlockSpec index maps (TPU-native PagedAttention)."""
    B, H, d_c = q_c8.shape
    d_r = q_r.shape[-1]
    page = content_pool.shape[1]
    P = page_table.shape[1]
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # seq_lens, page_table
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, d_c), lambda b, j, sl, pt: (b, 0, 0)),
            pl.BlockSpec((1, H, d_r), lambda b, j, sl, pt: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, j, sl, pt: (b, 0, 0)),
            # the page table drives the DMA source: TPU-native PagedAttention
            pl.BlockSpec((1, page, d_c), lambda b, j, sl, pt: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, page, d_r), lambda b, j, sl, pt: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, page), lambda b, j, sl, pt: (pt[b, j], 0, 0)),
        ],
        out_specs=_single_pass_out_specs(H, d_c),
        scratch_shapes=_state_scratch(H, d_c),
    )

    def kernel_paged(sl_ref, pt_ref, *rest):
        del pt_ref  # only used by the index maps
        return _mla_decode_kernel(
            sl_ref, *rest, softmax_scale=softmax_scale, block_n=page,
            fmt=fmt, qmax=qmax, rescale=rescale)

    o, lse = pl.pallas_call(
        kernel_paged,
        grid_spec=grid_spec,
        out_shape=_single_pass_out_shape(B, H, d_c),
        interpret=resolve_interpret(interpret),
    )(seq_lens, page_table, q_c8, q_r, unit_rows(sigma_q), content_pool,
      rope_pool, unit_rows(scale_pool))
    return o, lse[:, 0]


# ---------------------------------------------------------------------------
# Paged split-KV (flash-decoding over a page pool)
# ---------------------------------------------------------------------------

def _paged_splitkv_body(seq_lens_ref, page_table_ref, *rest, **kw):
    """The paged split-KV kernel body IS the contiguous split-KV body: the page
    table only feeds the BlockSpec index maps (where the DMA source comes
    from), never the arithmetic — so both variants share one block pipeline,
    one early-exit predicate, and one partial-emission epilogue verbatim."""
    del page_table_ref  # only used by the index maps
    _mla_decode_splitkv_kernel(seq_lens_ref, *rest, **kw)


def _clamped_page_id(seq_lens_ref, page_table_ref, b, s_id, j,
                     pages_per_split, page):
    """Page-pool DMA source for (split, page-slot): the logical page index is
    clamped to the sequence's last live page (dead slots re-address an
    already-resident pool page, eliding the DMA — the paged analogue of
    ``_clamped_block_index``), then translated through the page table."""
    g = _clamped_block_index(seq_lens_ref, b, s_id, j, pages_per_split, page)
    return page_table_ref[b, g]


def mla_decode_paged_splitkv_pallas(
    q_c8: jax.Array,          # [B, H, d_c] or [B, q_len, H, d_c] storage dtype
    q_r: jax.Array,           # [..., d_r] f32 (pre-divided by sigma_q)
    sigma_q: jax.Array,       # [B, H] or [B, q_len, H] f32
    content_pool: jax.Array,  # [n_pages, page, d_c]
    rope_pool: jax.Array,     # [n_pages, page, d_r]
    scale_pool: jax.Array,    # [n_pages, page]
    page_table: jax.Array,    # [B, P] int32
    seq_lens: jax.Array,      # [B]
    *,
    softmax_scale: float,
    num_splits: int,
    fmt: str = "fp8_e4m3",
    interpret: bool | None = None,
    return_partials: bool = False,
    rescale: str = "fma",
):
    """Paged + split-KV SnapMLA decode: sequence parallelism over a page pool.

    Grid (batch, num_splits, pages_per_split): the logical page axis of each
    sequence (its page-table row) is cut into ``num_splits`` contiguous
    slices; each slice runs the scale-fused FP8 block pipeline over its pages
    — DMA sources resolved through the scalar-prefetched page table, dead
    slots clamped to the last live page so their DMA is elided and ``pl.when``
    skips their compute — and emits partial (o, lse, sigma_p) merged by
    ``lse_combine_pallas``. HBM traffic scales with ``seq_lens``, not with
    pool capacity. Returns (o [B,H,d_c] f32, lse [B,H]); plus raw partials
    when ``return_partials``.

    Rank-4 ``[B, q_len, H, ...]`` queries run the q_len > 1 verify path with
    the causal intra-block mask, exactly as in ``mla_decode_splitkv_pallas``
    (the paged body IS the contiguous body), and return the extra q_len axis.
    """
    q_c8, q_r, sigma_q, q_len, H = _flatten_q(q_c8, q_r, sigma_q)
    B, R, d_c = q_c8.shape
    d_r = q_r.shape[-1]
    page = content_pool.shape[1]
    P = page_table.shape[1]
    assert 1 <= num_splits <= P, (num_splits, P)
    pages_per_split = (P + num_splits - 1) // num_splits
    qmax = quant.qmax_for(fmt) if fmt != "none" else 1.0

    kernel = functools.partial(
        _paged_splitkv_body, softmax_scale=softmax_scale, block_n=page,
        blocks_per_split=pages_per_split, fmt=fmt, qmax=qmax, rescale=rescale,
        q_len=q_len or 1, heads=H)

    def kv_idx(b, s, j, sl, pt):
        return (_clamped_page_id(sl, pt, b, s, j, pages_per_split, page), 0, 0)

    o_p, lse_p, sp_p = _splitkv_partials_call(
        kernel,
        grid=(B, num_splits, pages_per_split),
        in_specs=[
            pl.BlockSpec((1, R, d_c), lambda b, s, j, sl, pt: (b, 0, 0)),
            pl.BlockSpec((1, R, d_r), lambda b, s, j, sl, pt: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, s, j, sl, pt: (b, 0, 0)),
            pl.BlockSpec((1, page, d_c), kv_idx),
            pl.BlockSpec((1, page, d_r), kv_idx),
            pl.BlockSpec((1, 1, page), kv_idx),
        ],
        num_scalar_prefetch=2,      # seq_lens, page_table
        B=B, num_splits=num_splits, H=R, d_c=d_c, interpret=interpret,
        operands=(seq_lens, page_table, q_c8, q_r, unit_rows(sigma_q),
                  content_pool, rope_pool, unit_rows(scale_pool)),
    )
    return _combine(q_len, H, o_p, lse_p, sp_p, rescale=rescale,
                    interpret=interpret, return_partials=return_partials)
