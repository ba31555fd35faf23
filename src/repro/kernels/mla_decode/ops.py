"""Jit'd public wrappers for the SnapMLA MLA decode kernel.

``snapmla_decode`` consumes a quantized MLACache directly; selects between the
single-pass kernel, the split-KV (flash-decoding) kernel, and the pure-jnp
reference paths. ``snapmla_decode_paged`` is the same dispatch over a
``PagedMLAPool`` (serial-page kernel vs paged split-KV kernel vs paged
oracle). ``num_splits=None`` resolves through ``resolve_num_splits`` — the
profile-driven autotuner (``autotune.SplitProfile``, measured sweeps keyed on
(capacity, block_n, batch), emitted by the benchmarks as a JSON artifact)
with ``default_num_splits``'s context-length heuristic as fallback. On CPU
the kernels run in interpret mode and on TPU compiled (``interpret=None``
resolves through ``runtime.platform.resolve_interpret``).

Cache alignment: the cache capacity must be a multiple of ``block_n``
(``init_mla_cache`` rounds ``max_len`` up to the page size, so this holds by
construction) — the former per-step ``jnp.pad`` of the whole cache was an
O(max_len) HBM copy on every decode step and has been removed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kvcache import MLACache, PagedMLAPool, sink_patched_content
from repro.kernels.mla_decode import autotune as _autotune
from repro.kernels.mla_decode import kernel as _k
from repro.kernels.mla_decode import ref as _ref
from repro.kernels.mla_decode.autotune import SplitConfig

# Split sizing: aim for splits of ~SPLIT_TARGET_TOKENS so each split amortizes
# its combine cost, capped at MAX_SPLITS partial buffers.
SPLIT_TARGET_TOKENS = 4096
MAX_SPLITS = 8

# Contiguous-cache default KV block size (the paged kernels' block size is
# structurally the physical page, never this).
DEFAULT_BLOCK_N = 128


def default_num_splits(context_len: int, block_n: int = 128,
                       target_tokens: int = SPLIT_TARGET_TOKENS,
                       max_splits: int = MAX_SPLITS) -> int:
    """num_splits heuristic keyed on context length (cache capacity).

    Short contexts (< 2 * target) stay single-pass — bit-exact with the seed
    kernel and no combine overhead. Longer contexts get the largest power of
    two <= context/target, capped at ``max_splits`` and at the block count.

    This is the *fallback* of the profile-driven autotuner: when the measured
    split profile (``autotune.SplitProfile``) has an entry for the exact
    (capacity, block_n, batch), that measurement wins.
    """
    nblocks = max(1, -(-context_len // block_n))
    s = 1
    while s * 2 <= min(max_splits, context_len // target_tokens, nblocks):
        s *= 2
    return s


def resolve_num_splits(requested: int | None, capacity: int,
                       block_n: int, batch: int | None = None,
                       layout: str = "contiguous",
                       rescale: str = "fma") -> int:
    """Single resolution rule for every decode backend (kernel, pjit ref,
    shard_map ref, paged pool): None/0 = auto — a measured split-profile hit
    for (capacity, block_n, batch) under the cache ``layout`` and the
    kernel's ``rescale`` mode if the autotuner cache has one (exact key,
    else nearest-batch interpolation), else the context-length heuristic.
    AMLA plans come only from AMLA-timed sweeps; an un-swept rescale falls
    back to the heuristic rather than borrowing FMA timings. Fixed counts
    are clamped to the block count so a config tuned for long contexts still
    traces on a short cache."""
    nblocks = max(1, capacity // block_n)
    if requested:
        splits = requested
    else:
        splits = _autotune.tuned_num_splits(capacity, block_n, batch, layout,
                                            rescale)
        if splits is None:
            splits = default_num_splits(capacity, block_n)
    return max(1, min(splits, nblocks))


def resolve_split_config(num_splits: int | None, block_n: int | None,
                         capacity: int, *, batch: int | None = None,
                         layout: str = "contiguous",
                         page_size: int | None = None,
                         rescale: str = "fma") -> SplitConfig:
    """Joint (num_splits, block_n) resolution — the 2D generalization of
    ``resolve_num_splits`` (which stays as the fixed-block_n rule every
    resolved plan funnels through).

      * ``layout == "paged"``: block_n is STRUCTURAL — it must equal the
        physical page size; only num_splits is tunable.
      * explicit ``block_n``: splits resolve at that block size (profile hit
        for the (capacity, block_n, batch) key, else heuristic).
      * ``block_n`` None/0 (auto): the measured joint plan from the v2
        profile — the fastest (num_splits, block_n) recorded across every
        swept block_n at this (capacity, batch, layout) — else the
        DEFAULT_BLOCK_N heuristic. A profile block_n that does not divide
        this cache's capacity is ignored (profiles travel across shapes).
    """
    if layout == "paged":
        if page_size is None:
            raise ValueError("paged split resolution needs page_size "
                             "(block_n is structurally the physical page)")
        if block_n and block_n != page_size:
            raise ValueError(
                f"paged caches fix block_n to the page size ({page_size}); "
                f"got block_n={block_n} — repage the pool instead")
        return SplitConfig(
            resolve_num_splits(num_splits, capacity, page_size, batch,
                               layout, rescale), page_size)
    if block_n:
        return SplitConfig(
            resolve_num_splits(num_splits, capacity, block_n, batch, layout,
                               rescale),
            block_n)
    tuned = _autotune.tuned_split_config(capacity, batch, layout, rescale)
    if tuned is not None and capacity % tuned.block_n == 0:
        nblocks = max(1, capacity // tuned.block_n)
        splits = num_splits if num_splits else tuned.num_splits
        return SplitConfig(max(1, min(splits, nblocks)), tuned.block_n)
    bn = DEFAULT_BLOCK_N if capacity % DEFAULT_BLOCK_N == 0 \
        else max(b for b in (64, 32, 16, 8, 4, 2, 1) if capacity % b == 0)
    return SplitConfig(
        resolve_num_splits(num_splits, capacity, bn, batch, layout, rescale),
        bn)


def _check_alignment(n: int, block_n: int) -> None:
    if n % block_n:
        raise ValueError(
            f"cache capacity {n} is not a multiple of block_n={block_n}; "
            "allocate caches with init_mla_cache (it rounds max_len up to the "
            "page size) so the decode kernel never re-pads the cache per step")


def snapmla_decode(
    q_c8: jax.Array,
    q_r: jax.Array,
    sigma_q: jax.Array,
    cache: MLACache,
    *,
    softmax_scale: float,
    block_n: int = 128,
    fmt: str = "fp8_e4m3",
    num_splits: int | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    """Decode one token per sequence. Returns (o_latent [B,H,d_c] f32, lse).

    Split resolution happens OUTSIDE the jitted impl (whose jit cache keys on
    the *resolved* count), so an in-process profile update — e.g. the
    benchmarks calling ``emit_split_profile`` — takes effect on the next
    direct call instead of being shadowed by an executable traced under the
    old plan. (Callers that close over this inside their own jit still pin
    the plan at their trace time, as any static argument is.)"""
    N = cache.content.shape[1]
    _check_alignment(N, block_n)
    splits = resolve_num_splits(num_splits, N, block_n, batch=q_c8.shape[0],
                                rescale=rescale)
    return _snapmla_decode_impl(
        q_c8, q_r, sigma_q, cache, softmax_scale=softmax_scale,
        block_n=block_n, fmt=fmt, num_splits=splits, use_kernel=use_kernel,
        interpret=interpret, rescale=rescale)


@partial(jax.jit, static_argnames=("softmax_scale", "block_n", "fmt",
                                   "num_splits", "use_kernel", "interpret",
                                   "rescale"))
def _snapmla_decode_impl(
    q_c8: jax.Array,
    q_r: jax.Array,
    sigma_q: jax.Array,
    cache: MLACache,
    *,
    softmax_scale: float,
    block_n: int,
    fmt: str,
    num_splits: int,
    use_kernel: bool,
    interpret: bool | None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    splits = num_splits
    # P-Cast sink guard: substitute the guarded prefix rows in full precision
    # (no-op passthrough on unguarded caches — same jit trace as the seed).
    args = (q_c8, q_r.astype(jnp.float32), sigma_q,
            sink_patched_content(cache),
            cache.rope.astype(jnp.float32), cache.scale, cache.seq_lens)
    if use_kernel:
        # rank-4 (q_len > 1 verify) queries always take the split-KV kernel —
        # it carries the per-row causal limit; num_splits = 1 is one split.
        if splits == 1 and q_c8.ndim == 3:
            return _k.mla_decode_pallas(
                *args, softmax_scale=softmax_scale, block_n=block_n, fmt=fmt,
                interpret=interpret, rescale=rescale)
        return _k.mla_decode_splitkv_pallas(
            *args, softmax_scale=softmax_scale, num_splits=splits,
            block_n=block_n, fmt=fmt, interpret=interpret, rescale=rescale)
    if splits == 1:
        return _ref.snapmla_decode_pipeline_ref(
            *args, softmax_scale=softmax_scale, block_n=block_n, fmt=fmt,
            rescale=rescale)
    return _ref.snapmla_decode_splitkv_ref(
        *args, softmax_scale=softmax_scale, num_splits=splits,
        block_n=block_n, fmt=fmt, rescale=rescale)


def snapmla_decode_paged(
    q_c8: jax.Array,
    q_r: jax.Array,
    sigma_q: jax.Array,
    pool: PagedMLAPool,
    *,
    softmax_scale: float,
    fmt: str = "fp8_e4m3",
    num_splits: int | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    """Decode one token per sequence against a paged pool.

    ``num_splits`` follows the same resolution rule as the contiguous path
    (None/0 = autotuner profile -> heuristic; 1 = the seed serial-page
    kernel, bit-exact; >1 = the paged split-KV kernel with block-level early
    exit) and, like ``snapmla_decode``, resolves outside the jitted impl so
    profile updates aren't shadowed by the jit cache. Capacity for
    resolution is the per-sequence page-table span ``P * page`` — the pool
    may be much larger.

    Page-table rows are arbitrary per-slot mappings: batch-owned strided
    runs and the serving engine's allocator-written rows (shared refcounted
    prefix pages, idle slots parked on the page-0 scratch page) go through
    the identical kernel path — only entries below ``seq_lens`` are read.
    """
    page = pool.content.shape[1]
    capacity = pool.page_table.shape[1] * page
    splits = resolve_num_splits(num_splits, capacity, page,
                                batch=q_c8.shape[0], layout="paged",
                                rescale=rescale)
    return _snapmla_decode_paged_impl(
        q_c8, q_r, sigma_q, pool, softmax_scale=softmax_scale, fmt=fmt,
        num_splits=splits, use_kernel=use_kernel, interpret=interpret,
        rescale=rescale)


@partial(jax.jit, static_argnames=("softmax_scale", "fmt", "num_splits",
                                   "use_kernel", "interpret", "rescale"))
def _snapmla_decode_paged_impl(
    q_c8: jax.Array,
    q_r: jax.Array,
    sigma_q: jax.Array,
    pool: PagedMLAPool,
    *,
    softmax_scale: float,
    fmt: str,
    num_splits: int,
    use_kernel: bool,
    interpret: bool | None,
    rescale: str = "fma",
) -> tuple[jax.Array, jax.Array]:
    splits = num_splits
    args = (q_c8, q_r.astype(jnp.float32), sigma_q,
            pool.content, pool.rope.astype(jnp.float32), pool.scale,
            pool.page_table, pool.seq_lens)
    if use_kernel:
        # rank-4 (q_len > 1 verify) queries always take the split-KV kernel
        if splits == 1 and q_c8.ndim == 3:
            return _k.mla_decode_paged_pallas(
                *args, softmax_scale=softmax_scale, fmt=fmt,
                interpret=interpret, rescale=rescale)
        return _k.mla_decode_paged_splitkv_pallas(
            *args, softmax_scale=softmax_scale, num_splits=splits, fmt=fmt,
            interpret=interpret, rescale=rescale)
    return _ref.snapmla_decode_paged_splitkv_ref(
        *args, softmax_scale=softmax_scale, num_splits=splits, fmt=fmt,
        rescale=rescale)
