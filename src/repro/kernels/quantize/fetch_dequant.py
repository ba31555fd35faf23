"""Fused-Fetch-Dequant (paper §3.3.1, third operator) — Pallas TPU kernel.

For decode phases that need high-precision reuse of cached data (chunked
prefill, prefix caching), the paper fuses the fetch of quantized KV pages
with register-level dequantization, eliminating the two-step
load-then-dequantize round trip through memory.

TPU form: one pallas_call whose grid walks the cache pages; each page is
DMA'd (fp8 content + prescaled bf16 rope + per-token scales), dequantized in
VREGs, and written out as a contiguous BF16 [content | rope] chunk — the
operand layout the chunked-prefill attention consumes. The HBM read side is
the *quantized* bytes (the whole point: fetch traffic stays FP8-sized).

``chunked_prefill_attention`` uses it to attend a new prompt chunk against
the quantized prefix cache + itself, combining via flash-style lse math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kvcache import MLACache, PagedMLAPool
from repro.kernels.mla_decode.kernel import unit_rows
from repro.runtime.platform import resolve_interpret


def _column(row: jax.Array) -> jax.Array:
    """[1, n] -> [n, 1], exactly: each output is one value summed with
    zeros (a lane-to-sublane move without a transpose)."""
    n = row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _fetch_dequant_kernel(content_ref, rope_ref, scale_ref, out_ref, *, d_c):
    c = content_ref[0].astype(jnp.float32)              # [page, d_c]
    r = rope_ref[0].astype(jnp.float32)                 # [page, d_r]
    s = _column(scale_ref[0].astype(jnp.float32))       # [page, 1]
    out_ref[0, :, :d_c] = (c * s).astype(out_ref.dtype)
    out_ref[0, :, d_c:] = (r * s).astype(out_ref.dtype)  # undo Eq.-6 prescale


def _paged_fetch_dequant_body(pt_ref, content_ref, rope_ref, scale_ref,
                              out_ref, *, d_c):
    """The paged body IS the contiguous body: the page table only feeds the
    BlockSpec index maps (where the DMA comes from), never the arithmetic."""
    del pt_ref  # only used by the index maps
    _fetch_dequant_kernel(content_ref, rope_ref, scale_ref, out_ref, d_c=d_c)


def _bounded_paged_fetch_body(cs_ref, pt_ref, content_ref, rope_ref,
                              scale_ref, out_ref, *, d_c, page):
    """Bounded-fetch body: pages at/above the chunk boundary are DEAD — their
    output block is zeroed without touching the pool operands (and the index
    maps repeat the last live page id, so the dead cells' DMAs are elided by
    the pipeline's unchanged-index rule: fetch traffic tracks ``chunk_start``,
    not the page-table span)."""
    del pt_ref  # only used by the index maps
    b = pl.program_id(0)
    j = pl.program_id(1)
    live = j * page < cs_ref[b]

    @pl.when(live)
    def _fetch():
        _fetch_dequant_kernel(content_ref, rope_ref, scale_ref, out_ref,
                              d_c=d_c)

    @pl.when(jnp.logical_not(live))
    def _dead():
        out_ref[0] = jnp.zeros_like(out_ref[0])


def fetch_dequant_pallas(cache: MLACache, *, page: int = 128,
                         out_dtype=jnp.bfloat16,
                         interpret: bool | None = None):
    """MLACache -> dequantized [B, N, d_c + d_r] keys (content|rope) in bf16."""
    B, N, d_c = cache.content.shape
    d_r = cache.rope.shape[-1]
    assert N % page == 0
    kernel = functools.partial(_fetch_dequant_kernel, d_c=d_c)
    return pl.pallas_call(
        kernel,
        grid=(B, N // page),
        in_specs=[
            pl.BlockSpec((1, page, d_c), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, page, d_r), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, page), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, page, d_c + d_r), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, d_c + d_r), out_dtype),
        interpret=resolve_interpret(interpret),
        name="fetch_dequant",
    )(cache.content, cache.rope, unit_rows(cache.scale))


def fetch_dequant_ref(cache: MLACache, out_dtype=jnp.bfloat16):
    """Pure-jnp oracle."""
    c = cache.content.astype(jnp.float32) * cache.scale[..., None]
    r = cache.rope.astype(jnp.float32) * cache.scale[..., None]
    return jnp.concatenate([c, r], axis=-1).astype(out_dtype)


def paged_fetch_dequant_pallas(pool: PagedMLAPool, *,
                               chunk_start: jax.Array | None = None,
                               out_dtype=jnp.bfloat16,
                               interpret: bool | None = None):
    """Paged Fused-Fetch-Dequant: the page table is scalar-prefetched and
    drives the DMA source of each (batch, logical-page) grid cell — the same
    TPU-native PagedAttention addressing the paged decode kernels use, so
    chunked prefill reads the FP8 pool pages directly (no host gather, HBM
    fetch traffic stays quantized-width).

    ``chunk_start`` ([B] int32, optional) BOUNDS the fetch: only pages
    holding positions strictly below ``chunk_start[b]`` are gathered. Dead
    pages' index maps clamp to the last live page (same-index DMAs are
    elided by the Pallas pipeline) and their output blocks are zeroed under
    ``pl.when`` — so per-chunk DMA traffic is ``ceil(chunk_start/page)``
    pages, independent of the pool capacity ``P``. ``None`` keeps the
    original full-span gather.

    Returns dequantized keys [B, P*page, d_c + d_r] (content|rope) laid out
    in each sequence's LOGICAL order (row b of the page table flattened)."""
    n_pages, page, d_c = pool.content.shape
    d_r = pool.rope.shape[-1]
    B, P = pool.page_table.shape
    out_shape = jax.ShapeDtypeStruct((B, P * page, d_c + d_r), out_dtype)
    interpret = resolve_interpret(interpret)
    scales = unit_rows(pool.scale)          # [n_pages, 1, page]
    if chunk_start is None:
        kernel = functools.partial(_paged_fetch_dequant_body, d_c=d_c)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # page_table
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, page, d_c), lambda b, j, pt: (pt[b, j], 0, 0)),
                pl.BlockSpec((1, page, d_r), lambda b, j, pt: (pt[b, j], 0, 0)),
                pl.BlockSpec((1, 1, page), lambda b, j, pt: (pt[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, page, d_c + d_r),
                                   lambda b, j, pt: (b, j, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            name="fetch_dequant",
        )(pool.page_table, pool.content, pool.rope, scales)

    cs = chunk_start.astype(jnp.int32)

    def _live_page(j, cs_b):
        # last page holding a position < chunk_start (0 when none are live)
        last = jnp.maximum((cs_b + page - 1) // page - 1, 0)
        return jnp.minimum(j, last)

    kernel = functools.partial(_bounded_paged_fetch_body, d_c=d_c, page=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # chunk_start, page_table
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, page, d_c),
                         lambda b, j, cs, pt: (pt[b, _live_page(j, cs[b])],
                                               0, 0)),
            pl.BlockSpec((1, page, d_r),
                         lambda b, j, cs, pt: (pt[b, _live_page(j, cs[b])],
                                               0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, cs, pt: (pt[b, _live_page(j, cs[b])],
                                               0, 0)),
        ],
        out_specs=pl.BlockSpec((1, page, d_c + d_r),
                               lambda b, j, cs, pt: (b, j, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fetch_dequant",
    )(cs, pool.page_table, pool.content, pool.rope, scales)


def paged_fetch_dequant_ref(pool: PagedMLAPool, out_dtype=jnp.bfloat16,
                            chunk_start: jax.Array | None = None):
    """Pure-jnp oracle for the paged fetch: gather rows through the page
    table, dequantize, lay out logically [B, P*page, d_c + d_r]. With
    ``chunk_start``, mirrors the kernel's bounded fetch: pages wholly
    at/above the boundary come back zeroed (a straddling page is fetched in
    full — its tail is masked downstream by the attention's ``pre_ok``)."""
    c = pool.content[pool.page_table].astype(jnp.float32)   # [B, P, page, d_c]
    r = pool.rope[pool.page_table].astype(jnp.float32)
    s = pool.scale[pool.page_table].astype(jnp.float32)[..., None]
    B, P, page, d_c = c.shape
    kv = jnp.concatenate([c * s, r * s], axis=-1)
    if chunk_start is not None:
        live = ((jnp.arange(P) * page)[None, :]
                < chunk_start.astype(jnp.int32)[:, None])       # [B, P]
        kv = jnp.where(live[:, :, None, None], kv, 0.0)
    return kv.reshape(B, P * page, -1).astype(out_dtype)


def paged_chunked_prefill_attention(
    q_lat: jax.Array,        # [B, C, H, d_c] absorbed queries for the chunk
    q_rope: jax.Array,       # [B, C, H, d_r]
    pool: PagedMLAPool,      # quantized prefix pages (page table = per-row run)
    chunk_c_kv: jax.Array,   # [B, C, d_c] this chunk's latents (full precision)
    chunk_k_r: jax.Array,    # [B, C, d_r] this chunk's rope keys (RoPE'd)
    chunk_start: jax.Array,  # [B] first absolute position of the chunk
    valid: jax.Array,        # [B, C] False on the padded tail of a bucket
    *,
    softmax_scale: float,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Attend a prompt chunk against [quantized paged prefix] + [itself].

    The engine's chunked-prefill attention: earlier chunks are read back
    from their already-quantized FP8 pool pages through the (paged)
    Fused-Fetch-Dequant path — no bf16 re-materialization of the prefix —
    while the chunk's OWN keys participate at full precision (they are
    resident in VREGs from the projection that just produced them; the
    quantized copy is only what lands in the pool for later chunks/decode).
    Scores from both sources share ONE softmax (mathematically the
    flash-style LSE combine, assembled directly), so for a first chunk the
    result is the plain full-precision causal attention.

    ``chunk_start`` is traced: one compiled program serves every chunk of a
    given (bucketed) width. Returns o_latent [B, C, H, d_c] (f32).
    """
    B, C, H, d_c = q_lat.shape
    # bounded fetch: only pages below the chunk boundary are DMA'd — per-chunk
    # fetch traffic tracks chunk_start, not the pool capacity
    kv = (paged_fetch_dequant_pallas(pool, chunk_start=chunk_start,
                                     interpret=interpret)
          if use_kernel
          else paged_fetch_dequant_ref(pool, chunk_start=chunk_start)
          ).astype(jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    # prefix scores: every pool position strictly before the chunk is live
    n = kv.shape[1]
    s_pre = jnp.einsum("bchd,bnd->bchn", q, kv) * softmax_scale
    pre_ok = jnp.arange(n)[None, :] < chunk_start[:, None]          # [B, n]
    s_pre = jnp.where(pre_ok[:, None, None, :], s_pre, -jnp.inf)
    # in-chunk scores: full precision, causal within the chunk, padded tail
    # keys masked (padded QUERIES still see their causal prefix, so no row is
    # ever fully masked — their outputs are garbage and are never read)
    k_chunk = jnp.concatenate([chunk_c_kv, chunk_k_r],
                              axis=-1).astype(jnp.float32)
    s_chk = jnp.einsum("bchd,bkd->bchk", q, k_chunk) * softmax_scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]       # [C, C]
    chk_ok = causal[None] & valid[:, None, :]                       # [B, C, C]
    s_chk = jnp.where(chk_ok[:, :, None, :], s_chk, -jnp.inf)
    # one softmax across [prefix | chunk] — the LSE combine, assembled flat
    p = jax.nn.softmax(jnp.concatenate([s_pre, s_chk], axis=-1), axis=-1)
    o = jnp.einsum("bchn,bnd->bchd", p[..., :n], kv[..., :d_c])
    o = o + jnp.einsum("bchk,bkd->bchd", p[..., n:],
                       chunk_c_kv.astype(jnp.float32))
    return o


def paged_verify_attention(
    q_lat: jax.Array,        # [B, K, H, d_c] absorbed queries for the drafts
    q_rope: jax.Array,       # [B, K, H, d_r]
    pool: PagedMLAPool,      # quantized prefix pages
    draft_c_kv: jax.Array,   # [B, K, d_c] drafted-suffix latents (full prec.)
    draft_k_r: jax.Array,    # [B, K, d_r] drafted-suffix rope keys (RoPE'd)
    start: jax.Array,        # [B] absolute position of the first draft entry
    *,
    softmax_scale: float,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Speculative-verify attention: [FP8 prefix] + [drafted suffix], one
    softmax.

    The verify step IS the chunked-prefill shape with the drafted K-token
    block in the chunk's seat: the committed prefix streams back through the
    bounded ``paged_fetch_dequant_pallas`` path (fetch traffic ∝
    ``ceil(start/page)`` pages, FP8-width), the drafts' own keys participate
    at full precision, and the causal mask within the block is the verify
    kernel's intra-block mask. Mixed-precision twin of running the drafts
    through the q_len > 1 split-KV kernel after ``paged_mla_prefill_at`` —
    they differ only by the suffix's P-quantization rounding, which is what
    the within-tolerance verify parity gates pin. Returns o_latent
    [B, K, H, d_c] (f32)."""
    valid = jnp.ones(draft_c_kv.shape[:2], bool)
    return paged_chunked_prefill_attention(
        q_lat, q_rope, pool, draft_c_kv, draft_k_r, start, valid,
        softmax_scale=softmax_scale, use_kernel=use_kernel,
        interpret=interpret)


def chunked_prefill_attention(
    q_lat: jax.Array,        # [B, C, H, d_c] absorbed queries for the chunk
    q_rope: jax.Array,       # [B, C, H, d_r]
    cache: MLACache,         # quantized prefix (seq_lens = prefix length)
    chunk_start: int | jax.Array,
    *,
    softmax_scale: float,
    page: int = 128,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Attend a prompt chunk against [quantized prefix] + [itself], causal.

    Returns o_latent [B, C, H, d_c] (f32). The prefix keys are produced by the
    Fused-Fetch-Dequant kernel (single fused pass over the FP8 cache).
    """
    B, C, H, d_c = q_lat.shape
    kv = (fetch_dequant_pallas(cache, page=page, interpret=interpret)
          if use_kernel else fetch_dequant_ref(cache)).astype(jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bchd,bnd->bchn", q, kv) * softmax_scale
    n = kv.shape[1]
    qpos = chunk_start + jnp.arange(C)
    valid = (jnp.arange(n)[None, :] < cache.seq_lens[:, None])[:, None, :] \
        & (jnp.arange(n)[None, None, :] <= qpos[None, :, None])
    s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)            # fully-masked rows
    content = kv[..., :d_c]
    return jnp.einsum("bchn,bnd->bchd", p, content)
