"""shard_map-explicit SnapMLA decode attention — zero-collective attention.

EXPERIMENTS §Perf found the decode bottleneck on the production mesh is
GSPMD resharding the quantized latent cache (cache-sized all-gathers,
~150 ms/step on deepseek-v3-mla x decode_32k). The fix is to take the
partitioning decision away from the compiler for the attention region:

    shard_map over ('pod','data') x 'model' with
        q (batch over dp, heads over model)       — P(dp, 'model', None)
        cache (batch over dp, replicated on model) — P(dp, None, None)
        out (batch over dp, heads over model)      — P(dp, 'model', None)

Inside the mapped region every chip attends its batch shard x its head shard
against its full local cache shard — the computation is embarrassingly
parallel and the region contains NO collectives by construction. The paper's
scale-fused FP8 pipeline (the parallel-form oracle) runs verbatim inside.

Requires B % dp == 0 and H % model == 0 (true for the MLA archs:
deepseek-v3-mla H=128, mla-7b H=32 on the 16-way model axis).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.kvcache import MLACache, sink_patched_content
from repro.kernels.mla_decode import ref as mla_ref


def shard_map_applicable(mesh, dp_axes, batch: int, n_heads: int) -> bool:
    if dp_axes is None:
        dp_size = 1
    else:
        axes = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
        dp_size = 1
        for a in axes:
            dp_size *= mesh.shape[a]
    return (batch % dp_size == 0) and (n_heads % mesh.shape["model"] == 0)


def mla_decode_shard_map(
    mesh,
    dp_axes,
    q_c8: jax.Array,      # [B, H, d_c]
    q_r: jax.Array,       # [B, H, d_r]
    sigma_q: jax.Array,   # [B, H]
    cache: MLACache,
    *,
    softmax_scale: float,
    block_n: int,
    fmt: str,
    num_splits: int = 1,
) -> jax.Array:
    """Returns o_latent [B, H, d_c] f32; attention region is collective-free.

    ``num_splits > 1`` runs the split-KV (flash-decoding) pipeline *inside*
    the mapped region: the KV axis is replicated per chip, so splits cut a
    chip-local axis and compose with the zero-collective property — the
    combine is a per-chip reduction over that chip's own partials.
    """
    dpa = dp_axes

    def local_attn(q_c8, q_r, sq, content, rope, scale, seq_lens):
        # parallel (einsum) pipeline — while-loop-free inside the mapped
        # region, same rationale as the pjit serve path; the split-vs-single
        # branch lives in the shared helper, not here
        o, _lse = mla_ref.snapmla_decode_parallel_any(
            q_c8, q_r, sq, content, rope, scale, seq_lens,
            softmax_scale=softmax_scale, num_splits=num_splits,
            block_n=block_n, fmt=fmt)
        return o

    f = jax.shard_map(
        local_attn,
        mesh=mesh,
        in_specs=(P(dpa, "model", None), P(dpa, "model", None), P(dpa, "model"),
                  P(dpa, None, None), P(dpa, None, None), P(dpa, None), P(dpa)),
        out_specs=P(dpa, "model", None),
    )
    # sink guard substitution happens OUTSIDE the mapped region (batch-major
    # elementwise op — pjit shards it over dp with no collectives).
    return f(q_c8, q_r.astype(jnp.float32), sigma_q,
             sink_patched_content(cache), cache.rope.astype(jnp.float32),
             cache.scale, cache.seq_lens)


def mla_append_shard_map(mesh, dp_axes, cache: MLACache, cache_cfg,
                         c_kv: jax.Array, k_r: jax.Array,
                         active: jax.Array | None = None) -> MLACache:
    """Collective-free quantized cache append.

    The pjit-level append (vmap'd dynamic_update_slice with per-sequence
    indices) triggers XLA SPMD's "involuntary full rematerialization": the
    sharded cache is ALL-GATHERED, updated, and re-partitioned — the
    cache-sized collective identified in EXPERIMENTS §Perf (it scales with
    cache byte-width, explaining the fp8/int8/bf16 collective ratios).
    Under shard_map each chip scatters into its own batch shard locally.

    ``active`` [B] bool gates the append per row exactly like the pjit
    ``kvcache.mla_append``: it is a batch-dim mask, so it shards over dp
    with the cache — finished rows rewrite their slot with its old value
    and freeze their ``seq_lens`` inside the mapped region, with no
    collectives introduced.
    """
    from repro.core.kvcache import mla_append

    dpa = dp_axes
    # sink guard shadow (if armed) is batch-major like content, so it shards
    # over dp with the rest of the cache; None on unguarded caches.
    cache_specs = MLACache(P(dpa, None, None), P(dpa, None, None),
                           P(dpa, None), P(dpa),
                           sink=None if cache.sink is None
                           else P(dpa, None, None))

    if active is None:
        def local_append(cache, c_kv, k_r):
            return mla_append(cache, cache_cfg, c_kv, k_r)

        f = jax.shard_map(
            local_append, mesh=mesh,
            in_specs=(cache_specs, P(dpa, None), P(dpa, None)),
            out_specs=cache_specs)
        return f(cache, c_kv, k_r)

    def local_append_gated(cache, c_kv, k_r, act):
        return mla_append(cache, cache_cfg, c_kv, k_r, active=act)

    f = jax.shard_map(
        local_append_gated, mesh=mesh,
        in_specs=(cache_specs, P(dpa, None), P(dpa, None), P(dpa)),
        out_specs=cache_specs)
    return f(cache, c_kv, k_r, active)
