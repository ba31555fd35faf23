"""SnapMLA — public API for the FP8 quantized MLA decoding pipeline.

Ties together the three paper components over one attention layer:

  prefill():      bulk-quantize the prompt's latent/rope entries into the cache
                  (RoPE-aware per-token quantization) and run exact attention
                  for the prompt itself.
  decode_step():  Fused-Q-Quant -> Fused-K-Append -> SnapMLA decode kernel
                  (scale-fused FP8 pipeline) -> absorbed output projection.

``pipeline="bf16"`` runs the same dataflow without quantization — the
FlashMLA-equivalent baseline used in all paper comparisons.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import mla as mla_lib
from repro.core.kvcache import (CacheConfig, MLACache, PagedMLAPool,
                                init_mla_cache, init_paged_mla_cache,
                                mla_prefill, paged_mla_append,
                                paged_mla_prefill)
from repro.kernels.mla_decode import backends as mla_backends
from repro.kernels.mla_decode import ref as mla_ref
from repro.kernels.quantize.ops import fused_k_append, fused_q_quant


@dataclasses.dataclass(frozen=True)
class SnapMLAConfig:
    mla: mla_lib.MLAConfig
    cache: CacheConfig = CacheConfig()
    # decode-attention backend (kernels/mla_decode/backends.py): True = the
    # Pallas split-KV kernels, False = the jnp ref twins; interpret None =
    # interpreted on CPU, compiled on TPU (runtime.platform.resolve_interpret)
    use_kernel: bool = True
    interpret: bool | None = None
    # split-KV (flash-decoding) sequence parallelism for the decode kernel:
    # None or 0 = autotuner profile with the context-length heuristic as
    # fallback (ops.resolve_num_splits), 1 = always single-pass (bit-exact
    # seed path), >1 = fixed split count. Applies to BOTH cache layouts.
    num_splits: int | None = None
    # contiguous-cache decode block size: 0 = cache.page_size (seed
    # behavior); >0 = explicit override. Paged caches are structurally
    # pinned to the physical page size.
    block_n: int = 0
    # per-block accumulator rescale: "fma" (exact seed path) | "amla"
    # (exponent-add fast path, combine-free split-KV partials)
    rescale: str = "fma"
    # paged KV: the cache is a PagedMLAPool (page-table-driven kernels) rather
    # than a contiguous per-slot MLACache.
    paged: bool = False

    @property
    def fmt(self) -> str:
        return self.cache.fmt


def init_cache(cfg: SnapMLAConfig, batch: int, max_len: int):
    """MLACache, or a batch-owned PagedMLAPool when ``cfg.paged``."""
    init = init_paged_mla_cache if cfg.paged else init_mla_cache
    return init(cfg.cache, batch, max_len, cfg.mla.d_c, cfg.mla.d_rope)


def prefill(
    params: mla_lib.MLAParams,
    cfg: SnapMLAConfig,
    h: jax.Array,                 # [B, S, d] prompt hidden states
    cache,
) -> tuple[jax.Array, "MLACache | PagedMLAPool"]:
    """Run exact prompt attention and fill the quantized cache."""
    B, S, _ = h.shape
    positions = jnp.arange(S)
    out = mla_lib.mla_attention(params, cfg.mla, h, positions, causal=True)
    c_kv, k_r = mla_lib.project_kv(params, cfg.mla, h, positions)
    fill = paged_mla_prefill if isinstance(cache, PagedMLAPool) else mla_prefill
    cache = fill(cache, cfg.cache, c_kv, k_r)
    return out, cache


def decode_step(
    params: mla_lib.MLAParams,
    cfg: SnapMLAConfig,
    h_t: jax.Array,               # [B, d] current token hidden state
    cache,
) -> tuple[jax.Array, "MLACache | PagedMLAPool"]:
    """One decode step: returns (attention output [B, d], updated cache)."""
    B = h_t.shape[0]
    positions = cache.seq_lens                         # 0-based position of h_t
    paged = isinstance(cache, PagedMLAPool)

    # -- K side: project + Fused-K-Append (quantize + align + paged write) --
    c_kv, k_r = mla_lib.project_kv(params, cfg.mla, h_t[:, None, :], positions[:, None])
    if paged:
        cache = paged_mla_append(cache, cfg.cache, c_kv[:, 0], k_r[:, 0])
    elif cfg.cache.quantized:
        cache = fused_k_append(
            cache, c_kv[:, 0], k_r[:, 0], fmt=cfg.fmt, page=cfg.cache.page_size,
            use_kernel=cfg.use_kernel, interpret=cfg.interpret)
    else:
        from repro.core.kvcache import mla_append
        cache = mla_append(cache, cfg.cache, c_kv[:, 0], k_r[:, 0])

    # -- Q side: project + absorb + Fused-Q-Quant ---------------------------
    q_c, q_r = mla_lib.project_q(params, cfg.mla, h_t[:, None, :], positions[:, None])
    q_lat = mla_lib.absorb_q(params, q_c[:, 0])        # [B, H, d_c]
    q_rope = q_r[:, 0]                                 # [B, H, d_r]
    if cfg.cache.quantized:
        q_cat = jnp.concatenate([q_lat.astype(jnp.float32),
                                 q_rope.astype(jnp.float32)], axis=-1)
        q_c8, q_r_s, sigma_q = fused_q_quant(
            q_cat, cfg.mla.d_c, fmt=cfg.fmt,
            use_kernel=cfg.use_kernel, interpret=cfg.interpret)
    else:
        q_c8, q_r_s, sigma_q = mla_ref.prepare_q(q_lat, q_rope, "none")

    # -- SnapMLA decode attention: backend-registry dispatch ----------------
    backend = mla_backends.resolve_backend(
        "kernel" if cfg.use_kernel else "ref", paged=paged, batch=B,
        n_heads=cfg.mla.n_heads)
    bcfg = mla_backends.BackendConfig(
        softmax_scale=cfg.mla.softmax_scale,
        block_n=cfg.block_n or cfg.cache.page_size,
        fmt=cfg.fmt if cfg.cache.quantized else "none",
        num_splits=cfg.num_splits, interpret=cfg.interpret,
        rescale=cfg.rescale)
    o_lat = backend.decode(
        mla_backends.DecodeQuery(q_c8, q_r_s, sigma_q), cache, bcfg)

    out = mla_lib.output_proj(params, o_lat.astype(h_t.dtype))
    return out, cache
