"""Unified decoder stack: init / train-forward / prefill / decode for every
assigned architecture family.

Structure (MaxText-style): layers are grouped into superblocks of
``cfg.layer_pattern``; full tiles are applied under ``jax.lax.scan`` with
parameters stacked along a leading superblock axis (keeps HLO size flat in
depth — essential for 100-layer dry-run compiles), plus an unscanned
remainder. Decode threads per-layer states (quantized KV caches / recurrent
states) through the same scan.

Decode attention dispatches through the backend registry
(``kernels/mla_decode/backends.py``): by default the pure-jnp einsum twins
(pjit/cost-analysis friendly), with ``cfg.use_kernels=True`` (or
``cfg.decode_backend="kernel"``, ``serve --backend kernel``) the actual
Pallas split-KV kernels run inside the jitted decode step — interpret mode
on CPU, compiled on TPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mla as mla_lib
from repro.core.kvcache import (CacheConfig, GQACache, MLACache, gqa_append,
                                gqa_prefill, init_gqa_cache, init_mla_cache,
                                init_paged_mla_cache, mla_append, mla_prefill,
                                paged_mla_append, paged_mla_prefill,
                                paged_mla_prefill_at)
from repro.core.attention import gqa_decode_dequant_ref, mla_decode_dequant_ref
from repro.kernels.gqa_decode import ref as gqa_ref
from repro.kernels.mla_decode import backends as BK
from repro.kernels.mla_decode import ref as mla_kref
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import xlstm as xlstm_lib


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig, kind: str) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_head=cfg.d_head, rope_theta=cfg.rope_theta, qkv_bias=cfg.qkv_bias,
        window=cfg.window if kind == "swa" else 0,
        use_rope=True)


def _mla_cfg(cfg: ModelConfig) -> mla_lib.MLAConfig:
    m = cfg.mla
    return mla_lib.MLAConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, d_head=cfg.d_head,
        d_rope=m.d_rope, d_c=m.d_c, q_lora_rank=m.q_lora_rank,
        rope_theta=cfg.rope_theta)


def _cache_cfg(cfg: ModelConfig, kind: str) -> CacheConfig:
    # kv_sink_tokens only arms the guard on contiguous MLA caches — GQA
    # caches and paged pools ignore it (init_gqa_cache / init_paged_mla_*
    # never allocate a sink shadow).
    return CacheConfig(fmt=cfg.kv_fmt, page_size=cfg.page_size,
                       window=cfg.window if kind == "swa" else 0,
                       sink_tokens=0 if kind != "mla" or cfg.kv_paged
                       else cfg.kv_sink_tokens)


# ---------------------------------------------------------------------------
# Per-layer parameter init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str, layer_idx_hint: int, dtype):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if kind in ("attn", "swa"):
        p["mixer"] = L.init_attn_params(ks[0], _attn_cfg(cfg, kind), dtype)
    elif kind == "mla":
        p["mixer"] = mla_lib.init_mla_params(ks[0], _mla_cfg(cfg), dtype)
    elif kind == "cross":
        p["mixer"] = L.init_attn_params(ks[0], _attn_cfg(cfg, kind), dtype)
        p["xgate"] = jnp.zeros((1,), dtype)          # tanh-gated (llama-vision)
    elif kind == "dec":
        p["mixer"] = L.init_attn_params(ks[0], _attn_cfg(cfg, kind), dtype)
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = L.init_attn_params(ks[1], _attn_cfg(cfg, kind), dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_lib.init_rglru_params(ks[0], cfg.d_model, cfg.d_model, dtype)
    elif kind == "mlstm":
        p["mixer"] = xlstm_lib.init_mlstm_params(ks[0], cfg.d_model, cfg.n_heads,
                                                 cfg.d_head, dtype)
    elif kind == "slstm":
        p["mixer"] = xlstm_lib.init_slstm_params(ks[0], cfg.d_model, cfg.n_heads,
                                                 cfg.d_head, dtype)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")

    if cfg.has_mlp and kind not in ("mlstm", "slstm"):
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.moe is not None and layer_idx_hint >= cfg.first_k_dense:
            p["mlp"] = moe_lib.init_moe_params(ks[2], cfg.d_model, cfg.moe, dtype)
        elif cfg.d_ff:
            p["mlp"] = L.init_mlp_params(ks[2], cfg.d_model, cfg.d_ff, True, dtype)
    return p


def init_model(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": L.init_embedding(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(ks[1], cfg.vocab_size, cfg.d_model, dtype)

    # scanned superblocks: stack params along a leading axis per pattern slot
    if cfg.n_superblocks > 0:
        def init_block(bkey):
            bks = jax.random.split(bkey, cfg.pattern_len)
            return [
                _init_layer(bks[i], cfg, kind, cfg.first_k_dense, dtype)
                for i, kind in enumerate(cfg.layer_pattern)
            ]
        params["scanned"] = jax.vmap(init_block)(
            jax.random.split(ks[2], cfg.n_superblocks))
    # remainder layers (unscanned)
    params["tail"] = [
        _init_layer(k, cfg, kind, cfg.first_k_dense, dtype)
        for k, kind in zip(jax.random.split(ks[3], max(1, len(cfg.remainder_kinds))),
                           cfg.remainder_kinds)
    ]
    # deepseek-style first-k-dense layers are materialized inside the scan with
    # MoE params; for simplicity first_k_dense>0 swaps those layers into tail.
    if cfg.encoder_layers:
        def init_enc(bkey):
            return _init_layer(bkey, dataclasses.replace(cfg, moe=None), "attn", 0, dtype)
        params["encoder"] = jax.vmap(init_enc)(
            jax.random.split(ks[4], cfg.encoder_layers))
        params["enc_ln_f"] = jnp.ones((cfg.d_model,), dtype)
    return params


# ---------------------------------------------------------------------------
# Train / prefill forward
# ---------------------------------------------------------------------------

def _apply_mlp(p, cfg: ModelConfig, x):
    """The layer's feed-forward half: a routed MoE (its ops under the
    ``moe`` named scope) or a dense MLP (``mlp``)."""
    if "mlp" not in p:
        return x, 0.0
    h = L.rms_norm(x, p["ln2"])
    if cfg.moe is not None and isinstance(p["mlp"], moe_lib.MoEParams):
        out, dropped = moe_lib.moe_layer(p["mlp"], cfg.moe, h,
                                         act={"silu": jax.nn.silu,
                                              "gelu": jax.nn.gelu}[cfg.act])
        return x + out, dropped
    with jax.named_scope("mlp"):
        return x + L.mlp(p["mlp"], h, cfg.act), 0.0


def _head(params, x):
    """Final norm and f32 unembedding of the rows ``x`` [..., d] ->
    logits [..., V], under the ``head`` named scope."""
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["ln_f"])
        table = params.get("unembed", params["embed"])
        return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                          table.astype(jnp.float32))


def _apply_block_train(p, cfg: ModelConfig, kind: str, x, positions, aux):
    h = L.rms_norm(x, p["ln1"])
    if kind in ("attn", "swa"):
        x = x + L.attention_block(p["mixer"], _attn_cfg(cfg, kind), h, positions,
                                  unroll=cfg.cost_exact)
    elif kind == "mla":
        x = x + mla_lib.mla_attention(p["mixer"], _mla_cfg(cfg), h, positions)
    elif kind == "cross":
        g = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype)
        x = x + g * L.cross_attention_block(p["mixer"], _attn_cfg(cfg, kind), h, aux)
    elif kind == "dec":
        x = x + L.attention_block(p["mixer"], _attn_cfg(cfg, kind), h, positions)
        hc = L.rms_norm(x, p["ln_cross"])
        x = x + L.cross_attention_block(p["cross"], _attn_cfg(cfg, kind), hc, aux)
    elif kind == "rglru":
        y, _ = rglru_lib.rglru_block(p["mixer"], h)
        x = x + y
    elif kind == "mlstm":
        y, _ = xlstm_lib.mlstm_block(p["mixer"], h)
        return x + y, 0.0                              # self-contained, no MLP
    elif kind == "slstm":
        y, _ = xlstm_lib.slstm_block(p["mixer"], h)
        return x + y, 0.0
    return _apply_mlp(p, cfg, x)


def _run_encoder(params, cfg: ModelConfig, aux_embed):
    """Whisper-style bidirectional transformer encoder over frame embeddings."""
    if cfg.encoder_layers == 0 or aux_embed is None:
        return aux_embed
    positions = jnp.arange(aux_embed.shape[1])
    enc_cfg = dataclasses.replace(cfg, moe=None)

    def body(x, p):
        h = L.rms_norm(x, p["ln1"])
        x = x + L.attention_block(p["mixer"], _attn_cfg(enc_cfg, "attn"), h,
                                  positions, causal=False)
        x, _ = _apply_mlp(p, enc_cfg, x)
        return x, None

    x, _ = jax.lax.scan(body, aux_embed, params["encoder"])
    return L.rms_norm(x, params["enc_ln_f"])


def forward(params, cfg: ModelConfig, tokens: jax.Array,
            aux_embed: jax.Array | None = None, remat: bool = True):
    """Training forward: tokens [B, S] -> logits [B, S, V] (f32)."""
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    positions = jnp.arange(S)
    aux = _run_encoder(params, cfg, aux_embed)

    aux_losses = 0.0
    if cfg.n_superblocks > 0:
        def superblock(x, block_params):
            dropped = 0.0
            for i, kind in enumerate(cfg.layer_pattern):
                x, d = _apply_block_train(block_params[i], cfg, kind, x, positions, aux)
                dropped = dropped + d
            return x, dropped

        sb = jax.checkpoint(superblock) if remat else superblock
        if cfg.cost_exact:
            # unrolled (no while loop): exact under HLO cost analysis
            for i in range(cfg.n_superblocks):
                bp = jax.tree.map(lambda a: a[i], params["scanned"])
                x, d = sb(x, bp)
                aux_losses = aux_losses + d
        else:
            x, droppeds = jax.lax.scan(sb, x, params["scanned"])
            aux_losses = jnp.sum(droppeds)
    for p, kind in zip(params["tail"], cfg.remainder_kinds):
        x, d = _apply_block_train(p, cfg, kind, x, positions, aux)
        aux_losses = aux_losses + d

    x = L.rms_norm(x, params["ln_f"])
    table = params.get("unembed", params["embed"])
    return L.unembed(table, x), aux_losses


def loss_fn(params, cfg: ModelConfig, tokens, labels, aux_embed=None, remat=True):
    """Next-token cross entropy; labels == -1 are masked."""
    logits, aux = forward(params, cfg, tokens, aux_embed, remat)
    V = logits.shape[-1]
    mask = labels >= 0
    lab = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss, {"ce": loss, "moe_dropped": aux}


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def _init_layer_state(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in ("attn", "swa"):
        return init_gqa_cache(_cache_cfg(cfg, kind), batch, max_len,
                              cfg.n_kv_heads, cfg.d_head)
    if kind == "mla":
        if cfg.kv_paged:
            # kv_pool_pages > 0 switches to the shared multi-tenant pool
            # (empty tables; the serving engine's allocator owns the rows)
            return init_paged_mla_cache(_cache_cfg(cfg, kind), batch, max_len,
                                        cfg.mla.d_c, cfg.mla.d_rope,
                                        n_pages=cfg.kv_pool_pages)
        return init_mla_cache(_cache_cfg(cfg, kind), batch, max_len,
                              cfg.mla.d_c, cfg.mla.d_rope)
    if kind == "cross":
        return init_gqa_cache(_cache_cfg(cfg, "attn"), batch,
                              max(cfg.n_aux_tokens, 1), cfg.n_kv_heads, cfg.d_head)
    if kind == "dec":
        return {
            "self": init_gqa_cache(_cache_cfg(cfg, "attn"), batch, max_len,
                                   cfg.n_kv_heads, cfg.d_head),
            "cross": init_gqa_cache(_cache_cfg(cfg, "attn"), batch,
                                    max(cfg.n_aux_tokens, 1), cfg.n_kv_heads,
                                    cfg.d_head),
        }
    if kind == "rglru":
        return rglru_lib.init_rglru_state(batch, cfg.d_model)
    if kind == "mlstm":
        return xlstm_lib.init_mlstm_state(batch, cfg.n_heads, cfg.d_head)
    if kind == "slstm":
        return xlstm_lib.init_slstm_state(batch, cfg.n_heads, cfg.d_head)
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    state: dict[str, Any] = {}
    if cfg.n_superblocks > 0:
        def one(_):
            return [
                _init_layer_state(cfg, kind, batch, max_len)
                for kind in cfg.layer_pattern
            ]
        state["scanned"] = jax.vmap(lambda i: one(i))(jnp.arange(cfg.n_superblocks))
    state["tail"] = [
        _init_layer_state(cfg, kind, batch, max_len)
        for kind in cfg.remainder_kinds
    ]
    state["aux"] = None       # encoder output / image embeddings, set at prefill
    return state


# ---------------------------------------------------------------------------
# Decode step (quantized SnapMLA pipeline semantics)
# ---------------------------------------------------------------------------

# Optional sharding-constraint context for the distributed decode path
# (set by launch/dryrun.py; see EXPERIMENTS §Perf "attention locality"):
# {"mesh": Mesh, "dp": axis-or-tuple-or-None}. Constrains per-head decode
# tensors to stay 'model'-sharded on heads, preventing GSPMD from resharding
# the (huge) KV cache through all-gathers.
SHARD_CTX = None


def _wsc(x, *spec):
    if SHARD_CTX is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = SHARD_CTX["mesh"]
    parts = []
    for p_, dim in zip(spec, x.shape):
        if p_ == "model" and dim % mesh.shape["model"] != 0:
            p_ = None
        elif p_ == "dp":
            p_ = SHARD_CTX["dp"]
        parts.append(p_)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*parts)))

@jax.named_scope("attn")
def _attn_decode(p, cfg: ModelConfig, kind: str, x_t, cache: GQACache, pos,
                 active=None):
    """One-token GQA/SWA decode against a quantized cache. ``active`` [B]
    bool gates the cache append per row (finished-row skipping in the fused
    scan); inactive rows keep a frozen cache and produce garbage (finite,
    never-read) outputs."""
    acfg = _attn_cfg(cfg, kind)
    ccfg = _cache_cfg(cfg, kind)
    q, k, v = L.project_qkv(p, acfg, x_t[:, None, :], pos[:, None])
    if active is not None:
        q = jnp.where(active[:, None, None, None], q, 0.0)
    cache = gqa_append(cache, ccfg, k[:, 0], v[:, 0], active=active)
    window = cfg.window if kind == "swa" else 0
    qd = _wsc(q[:, 0].astype(jnp.float32), "dp", "model", None)
    o = gqa_ref.gqa_decode_parallel_ref(
        qd, cache.k, cache.v, cache.k_scale,
        cache.v_scale, cache.slot_pos, pos, window=window,
        block_n=ccfg.page_size, fmt=ccfg.fmt if ccfg.quantized else "none")
    o = _wsc(o, "dp", "model", None)
    o = jnp.einsum("bhk,hkd->bd", o.astype(x_t.dtype), p.wo)
    return o, cache


@jax.named_scope("attn")
def _cross_decode(p, cfg: ModelConfig, x_t, cache: GQACache):
    """One-token cross-attention against the static (quantized) aux cache."""
    q = jnp.einsum("bd,dhk->bhk", x_t, p.wq)
    if p.bq is not None:
        q = q + p.bq
    pos = jnp.full((x_t.shape[0],), jnp.iinfo(jnp.int32).max - 1, jnp.int32)
    ccfg = _cache_cfg(cfg, "attn")
    o = gqa_ref.gqa_decode_parallel_ref(
        q.astype(jnp.float32), cache.k, cache.v, cache.k_scale,
        cache.v_scale, cache.slot_pos, pos, window=0,
        block_n=ccfg.page_size, fmt=ccfg.fmt if ccfg.quantized else "none")
    return jnp.einsum("bhk,hkd->bd", o.astype(x_t.dtype), p.wo)


@jax.named_scope("attn")
def _mla_decode(p, cfg: ModelConfig, x_t, cache, pos, active=None):
    """SnapMLA decode: Fused-Q-Quant + Fused-K-Append + backend attention.

    The attention itself is dispatched through the decode-attention backend
    registry (``kernels.mla_decode.backends.resolve_backend``) — the single
    decision point shared with ``core.snapmla.decode_step`` and
    ``serve --backend``. ``cfg.decode_backend`` / ``cfg.use_kernels`` select
    between the pjit einsum twins (``jnp_ref`` / ``jnp_paged_ref``), the
    Pallas split-KV kernels (``pallas_splitkv`` / ``pallas_paged_splitkv``,
    interpret mode on CPU, compiled on TPU — the paged kernel reads pages
    through scalar-prefetched index maps, so HBM traffic follows seq_lens,
    not pool capacity), and the collective-free ``shard_map`` region (set by
    launch/dryrun.py via SHARD_CTX; contiguous caches, shapes permitting).
    """
    mcfg = _mla_cfg(cfg)
    ccfg = _cache_cfg(cfg, "mla")
    paged = cfg.kv_paged
    ctx = SHARD_CTX
    backend = BK.resolve_backend(
        cfg.decode_backend, paged=paged, batch=x_t.shape[0],
        n_heads=cfg.n_heads,
        mesh=ctx["mesh"] if ctx else None, dp=ctx["dp"] if ctx else None,
        use_kernels=cfg.use_kernels,
        prefer_shard_map=bool(ctx and ctx.get("use_shard_map")))
    c_kv, k_r = mla_lib.project_kv(p, mcfg, x_t[:, None, :], pos[:, None])
    if paged:
        cache = paged_mla_append(cache, ccfg, c_kv[:, 0], k_r[:, 0],
                                 active=active)
    elif backend.name == "shard_map":
        # gated like the pjit append: ``active`` is a batch-dim mask, so it
        # shards over dp into the collective-free region — finished rows
        # freeze their seq_lens here too, and the split-KV early exit's
        # saving applies on every backend
        from repro.core.distributed_decode import mla_append_shard_map
        cache = mla_append_shard_map(ctx["mesh"], ctx["dp"], cache, ccfg,
                                     c_kv[:, 0], k_r[:, 0], active=active)
    else:
        cache = mla_append(cache, ccfg, c_kv[:, 0], k_r[:, 0], active=active)
    q_c, q_r = mla_lib.project_q(p, mcfg, x_t[:, None, :], pos[:, None])
    if active is not None:
        # finished rows: zero the query (quantize_per_token's EPS floor keeps
        # the scale finite, so the masked row's attention is a uniform — and
        # finite — average over its frozen live region, never read again)
        q_c = jnp.where(active[:, None, None, None], q_c, 0.0)
        q_r = jnp.where(active[:, None, None, None], q_r, 0.0)
    q_lat = _wsc(mla_lib.absorb_q(p, q_c[:, 0]), "dp", "model", None)
    fmt = ccfg.fmt if ccfg.quantized else "none"
    q_c8, q_r_s, sigma_q = mla_kref.prepare_q(q_lat, q_r[:, 0], fmt)
    q_c8 = _wsc(q_c8, "dp", "model", None)
    bcfg = BK.BackendConfig(softmax_scale=mcfg.softmax_scale,
                            block_n=cfg.kv_block_n or ccfg.page_size, fmt=fmt,
                            num_splits=cfg.kv_splits,
                            rescale=cfg.kv_rescale)
    o_lat = backend.decode(
        BK.DecodeQuery(q_c8, q_r_s, sigma_q), cache, bcfg,
        {"mesh": ctx["mesh"], "dp": ctx["dp"]} if ctx else None)
    o_lat = _wsc(o_lat, "dp", "model", None)
    return mla_lib.output_proj(p, o_lat.astype(x_t.dtype)), cache


def _freeze_inactive(active, new_state, old_state):
    """Per-row recurrent-state freeze: keep old rows where ``active`` is
    False (leaves are [B, ...], tiny next to KV caches)."""
    def sel(new, old):
        mask = active.reshape(active.shape + (1,) * (new.ndim - 1))
        return jnp.where(mask, new, old)
    return jax.tree.map(sel, new_state, old_state)


def _apply_block_decode(p, cfg: ModelConfig, kind: str, x_t, state, pos,
                        active=None):
    h = L.rms_norm(x_t, p["ln1"])
    if kind in ("attn", "swa"):
        y, state = _attn_decode(p["mixer"], cfg, kind, h, state, pos, active)
        x_t = x_t + y
    elif kind == "mla":
        y, state = _mla_decode(p["mixer"], cfg, h, state, pos, active)
        x_t = x_t + y
    elif kind == "cross":
        g = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x_t.dtype)
        x_t = x_t + g * _cross_decode(p["mixer"], cfg, h, state)
    elif kind == "dec":
        y, self_c = _attn_decode(p["mixer"], cfg, "attn", h, state["self"],
                                 pos, active)
        x_t = x_t + y
        hc = L.rms_norm(x_t, p["ln_cross"])
        x_t = x_t + _cross_decode(p["cross"], cfg, hc, state["cross"])
        state = {"self": self_c, "cross": state["cross"]}
    elif kind == "rglru":
        old = state
        y, state = rglru_lib.rglru_step(p["mixer"], h, state)
        if active is not None:
            state = _freeze_inactive(active, state, old)
        x_t = x_t + y
    elif kind == "mlstm":
        old = state
        y, state = xlstm_lib.mlstm_step(p["mixer"], h, state)
        if active is not None:
            state = _freeze_inactive(active, state, old)
        return x_t + y, state
    elif kind == "slstm":
        old = state
        y, state = xlstm_lib.slstm_step(p["mixer"], h, state)
        if active is not None:
            state = _freeze_inactive(active, state, old)
        return x_t + y, state
    x_t, _ = _apply_mlp(p, cfg, x_t)
    return x_t, state


def decode_step(params, cfg: ModelConfig, token: jax.Array, state,
                pos: jax.Array, active: jax.Array | None = None):
    """token [B] int32, pos [B] int32 -> (logits [B, V], new state).

    ``active`` [B] bool (optional) marks rows still generating: inactive
    (EOS-finished) rows skip every cache append / recurrent-state update
    (their ``seq_lens`` freeze, so length-driven early exits stop paying for
    them) and run with zeroed queries. ``active=None`` is bit-identical to
    the ungated step."""
    x_t = L.embed(params["embed"], token)
    aux = state.get("aux")

    new_state = dict(state)
    if cfg.n_superblocks > 0:
        def step(x_t, inputs):
            block_params, block_state = inputs
            new_states = []
            for i, kind in enumerate(cfg.layer_pattern):
                x_t, s = _apply_block_decode(block_params[i], cfg, kind, x_t,
                                             block_state[i], pos, active)
                new_states.append(s)
            return x_t, new_states

        if cfg.cost_exact:
            outs = []
            for i in range(cfg.n_superblocks):
                bp = jax.tree.map(lambda a: a[i], params["scanned"])
                bs = jax.tree.map(lambda a: a[i], state["scanned"])
                x_t, ns = step(x_t, (bp, bs))
                outs.append(ns)
            new_state["scanned"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            x_t, scanned_states = jax.lax.scan(
                step, x_t, (params["scanned"], state["scanned"]))
            new_state["scanned"] = scanned_states
    tail_states = []
    for p, kind, s in zip(params["tail"], cfg.remainder_kinds, state["tail"]):
        x_t, s = _apply_block_decode(p, cfg, kind, x_t, s, pos, active)
        tail_states.append(s)
    new_state["tail"] = tail_states

    return _head(params, x_t), new_state


# ---------------------------------------------------------------------------
# Prefill (prompt -> cache states + last-token logits)
# ---------------------------------------------------------------------------

def _prefill_layer_state(p, cfg: ModelConfig, kind: str, x, state, aux):
    """Compute the post-prompt state for one layer while producing its output."""
    positions = jnp.arange(x.shape[1])
    h = L.rms_norm(x, p["ln1"])
    if kind in ("attn", "swa"):
        acfg = _attn_cfg(cfg, kind)
        q, k, v = L.project_qkv(p["mixer"], acfg, h, positions)
        o = L.flash_sdpa(q, k, v, causal=True, window=acfg.window,
                         unroll=cfg.cost_exact)
        state = gqa_prefill(state, _cache_cfg(cfg, kind), k, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["mixer"].wo)
    elif kind == "mla":
        mcfg = _mla_cfg(cfg)
        x = x + mla_lib.mla_attention(p["mixer"], mcfg, h, positions)
        c_kv, k_r = mla_lib.project_kv(p["mixer"], mcfg, h, positions)
        fill = paged_mla_prefill if cfg.kv_paged else mla_prefill
        state = fill(state, _cache_cfg(cfg, "mla"), c_kv, k_r)
    elif kind == "cross":
        g = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype)
        x = x + g * L.cross_attention_block(p["mixer"], _attn_cfg(cfg, kind), h, aux)
        state = _fill_cross_cache(p["mixer"], cfg, aux, state)
    elif kind == "dec":
        acfg = _attn_cfg(cfg, kind)
        q, k, v = L.project_qkv(p["mixer"], acfg, h, positions)
        o = L.flash_sdpa(q, k, v, causal=True, unroll=cfg.cost_exact)
        self_c = gqa_prefill(state["self"], _cache_cfg(cfg, "attn"), k, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["mixer"].wo)
        hc = L.rms_norm(x, p["ln_cross"])
        x = x + L.cross_attention_block(p["cross"], acfg, hc, aux)
        state = {"self": self_c,
                 "cross": _fill_cross_cache(p["cross"], cfg, aux, state["cross"])}
    elif kind == "rglru":
        y, state = rglru_lib.rglru_block(p["mixer"], h)
        x = x + y
    elif kind == "mlstm":
        y, state = xlstm_lib.mlstm_block(p["mixer"], h)
        return x + y, state
    elif kind == "slstm":
        y, state = xlstm_lib.slstm_block(p["mixer"], h)
        return x + y, state
    x, _ = _apply_mlp(p, cfg, x)
    return x, state


def _fill_cross_cache(attn_p, cfg: ModelConfig, aux, cache: GQACache) -> GQACache:
    k = jnp.einsum("bsd,dhk->bshk", aux, attn_p.wk)
    v = jnp.einsum("bsd,dhk->bshk", aux, attn_p.wv)
    if attn_p.bk is not None:
        k, v = k + attn_p.bk, v + attn_p.bv
    return gqa_prefill(cache, _cache_cfg(cfg, "attn"), k, v)


def prefill(params, cfg: ModelConfig, tokens: jax.Array, state,
            aux_embed: jax.Array | None = None):
    """tokens [B, S] -> (last-token logits [B, V], filled decode state)."""
    x = L.embed(params["embed"], tokens)
    aux = _run_encoder(params, cfg, aux_embed)
    new_state = dict(state)
    new_state["aux"] = aux

    if cfg.n_superblocks > 0:
        def step(x, inputs):
            block_params, block_state = inputs
            new_states = []
            for i, kind in enumerate(cfg.layer_pattern):
                x, s = _prefill_layer_state(block_params[i], cfg, kind, x,
                                            block_state[i], aux)
                new_states.append(s)
            return x, new_states

        if cfg.cost_exact:
            outs = []
            for i in range(cfg.n_superblocks):
                bp = jax.tree.map(lambda a: a[i], params["scanned"])
                bs = jax.tree.map(lambda a: a[i], state["scanned"])
                x, ns = step(x, (bp, bs))
                outs.append(ns)
            new_state["scanned"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            x, scanned_states = jax.lax.scan(
                step, x, (params["scanned"], state["scanned"]))
            new_state["scanned"] = scanned_states
    tail_states = []
    for p, kind, s in zip(params["tail"], cfg.remainder_kinds, state["tail"]):
        x, s = _prefill_layer_state(p, cfg, kind, x, s, aux)
        tail_states.append(s)
    new_state["tail"] = tail_states

    return _head(params, x[:, -1]), new_state


# ---------------------------------------------------------------------------
# Chunked prefill (one bucketed prompt chunk -> paged cache writes + logits)
# ---------------------------------------------------------------------------

def _chunked_prefill_mla_layer(p, cfg: ModelConfig, x, pool, chunk_start,
                               valid):
    """One MLA layer over one prompt chunk: project the chunk's KV, land it
    in the FP8 pool pages at ``chunk_start + t``, then attend the chunk's
    queries against [quantized prefix pages] + [the chunk itself] (causal)
    through the fused fetch-dequant path."""
    with jax.named_scope("attn"):
        from repro.kernels.quantize import fetch_dequant as FD
        mcfg = _mla_cfg(cfg)
        ccfg = _cache_cfg(cfg, "mla")
        C = x.shape[1]
        positions = chunk_start[:, None] + jnp.arange(C)[None, :]
        h = L.rms_norm(x, p["ln1"])
        c_kv, k_r = mla_lib.project_kv(p["mixer"], mcfg, h, positions)
        pool = paged_mla_prefill_at(pool, ccfg, c_kv, k_r, chunk_start, valid)
        q_c, q_r = mla_lib.project_q(p["mixer"], mcfg, h, positions)
        q_lat = mla_lib.absorb_q(p["mixer"], q_c)          # [B, C, H, d_c]
        o_lat = FD.paged_chunked_prefill_attention(
            q_lat, q_r, pool, c_kv, k_r, chunk_start, valid,
            softmax_scale=mcfg.softmax_scale, use_kernel=cfg.use_kernels)
        x = x + mla_lib.output_proj(p["mixer"], o_lat.astype(x.dtype))
    x, _ = _apply_mlp(p, cfg, x)
    return x, pool


def chunked_prefill(params, cfg: ModelConfig, tokens: jax.Array, state,
                    chunk_start: jax.Array, last_idx: jax.Array):
    """One prompt CHUNK through the stack: tokens [B, C] at absolute
    positions ``chunk_start + t`` -> (logits [B, V] for the chunk's last real
    token, state with the chunk's quantized entries landed in the pool).

    The serving engine's chunked-prefill step: called once per (bucketed)
    chunk, with ``chunk_start`` / ``last_idx`` traced so ONE compiled
    program serves every chunk of a given width — prefill compiles are
    bounded by the bucket count, not the number of distinct prompt lengths.
    ``last_idx`` [B] is the index of the chunk's last REAL token (positions
    past it are bucket padding: their cache writes are routed to the scratch
    page and their keys masked out of the attention). Only the final chunk's
    logits are meaningful (the engine samples the first token from them).

    Pure-MLA + paged caches only — the same constraint as the engine."""
    bad = [k for k in cfg.layer_pattern if k != "mla"]
    if bad or not cfg.kv_paged:
        raise ValueError(
            "chunked_prefill drives the paged MLA pipeline; layer pattern "
            f"{cfg.layer_pattern} (kv_paged={cfg.kv_paged}) is unsupported")
    B, C = tokens.shape
    valid = jnp.arange(C)[None, :] <= last_idx[:, None]          # [B, C]
    x = L.embed(params["embed"], tokens)
    new_state = dict(state)

    if cfg.n_superblocks > 0:
        def step(x, inputs):
            block_params, block_state = inputs
            new_states = []
            for i in range(cfg.pattern_len):
                x, s = _chunked_prefill_mla_layer(
                    block_params[i], cfg, x, block_state[i], chunk_start,
                    valid)
                new_states.append(s)
            return x, new_states

        if cfg.cost_exact:
            outs = []
            for i in range(cfg.n_superblocks):
                bp = jax.tree.map(lambda a: a[i], params["scanned"])
                bs = jax.tree.map(lambda a: a[i], state["scanned"])
                x, ns = step(x, (bp, bs))
                outs.append(ns)
            new_state["scanned"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            x, scanned_states = jax.lax.scan(
                step, x, (params["scanned"], state["scanned"]))
            new_state["scanned"] = scanned_states
    tail_states = []
    for p, s in zip(params["tail"], state["tail"]):
        x, s = _chunked_prefill_mla_layer(p, cfg, x, s, chunk_start, valid)
        tail_states.append(s)
    new_state["tail"] = tail_states

    x_last = jnp.take_along_axis(
        x, last_idx[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]                                             # [B, d]
    return _head(params, x_last), new_state


# ---------------------------------------------------------------------------
# Speculative verify (K drafted tokens -> all-position logits, one dispatch)
# ---------------------------------------------------------------------------

def _verify_mla_layer(p, cfg: ModelConfig, x, pool, start):
    """One MLA layer over a K-token verify block: land the block's quantized
    KV entries in the pool at positions ``start + t`` (exactly the bytes a
    sequential decode would have appended — ``mla_quantize_entry`` is
    deterministic, so accepted entries never need rewriting), then attend all
    K queries against [FP8 prefix pages + the block itself] through the
    q_len>1 split-KV decode backend (causal across the block via the kernel's
    per-row limits)."""
    with jax.named_scope("attn"):
        mcfg = _mla_cfg(cfg)
        ccfg = _cache_cfg(cfg, "mla")
        B, K = x.shape[:2]
        positions = start[:, None] + jnp.arange(K)[None, :]
        h = L.rms_norm(x, p["ln1"])
        c_kv, k_r = mla_lib.project_kv(p["mixer"], mcfg, h, positions)
        # valid=ones: pool seq_lens become start + K, so every verify row's
        # kernel limit is >= 1 (idle slots attend their own first row — finite
        # garbage, discarded by the engine's acceptance rule). Entries past the
        # slot's allocated pages clip to the scratch page inside prefill_at.
        valid = jnp.ones((B, K), bool)
        pool = paged_mla_prefill_at(pool, ccfg, c_kv, k_r, start, valid)
        q_c, q_r = mla_lib.project_q(p["mixer"], mcfg, h, positions)
        q_lat = mla_lib.absorb_q(p["mixer"], q_c)           # [B, K, H, d_c]
        fmt = ccfg.fmt if ccfg.quantized else "none"
        H = q_lat.shape[2]
        q8, qr_s, sq = mla_kref.prepare_q(
            q_lat.reshape(B, K * H, -1), q_r.reshape(B, K * H, -1), fmt)
        query = BK.DecodeQuery(q8.reshape(B, K, H, -1),
                               qr_s.reshape(B, K, H, -1),
                               sq.reshape(B, K, H))
        backend = BK.resolve_backend(
            cfg.decode_backend, paged=True, batch=B, n_heads=cfg.n_heads,
            use_kernels=cfg.use_kernels, q_len=K)
        bcfg = BK.BackendConfig(softmax_scale=mcfg.softmax_scale,
                                block_n=cfg.kv_block_n or ccfg.page_size, fmt=fmt,
                                num_splits=cfg.kv_splits, rescale=cfg.kv_rescale)
        o_lat = backend.decode(query, pool, bcfg, None)     # [B, K, H, d_c]
        x = x + mla_lib.output_proj(p["mixer"], o_lat.astype(x.dtype))
    x, _ = _apply_mlp(p, cfg, x)
    return x, pool


def verify_step(params, cfg: ModelConfig, tokens: jax.Array, state,
                start: jax.Array):
    """Self-speculative verify: tokens [B, K] (row 0 = the slot's last
    committed token, rows 1..K-1 = drafted continuation) at absolute
    positions ``start + t`` -> (logits [B, K, V] for EVERY position, state
    with the block's quantized entries landed in the pool).

    One compiled program verifies all slots' drafts per engine step; the
    engine's acceptance rule decides how many of the K candidate samples to
    commit, and rejected tail entries are masked by the NEXT step's pushed
    ``seq_lens`` (rollback-by-rewind — pages never move). With K=1 this is
    semantically the ordinary decode step (append one entry, one query row).

    Pure-MLA + paged caches only — the same constraint as chunked_prefill."""
    bad = [k for k in cfg.layer_pattern if k != "mla"]
    if bad or not cfg.kv_paged:
        raise ValueError(
            "verify_step drives the paged MLA pipeline; layer pattern "
            f"{cfg.layer_pattern} (kv_paged={cfg.kv_paged}) is unsupported")
    x = L.embed(params["embed"], tokens)
    new_state = dict(state)

    if cfg.n_superblocks > 0:
        def step(x, inputs):
            block_params, block_state = inputs
            new_states = []
            for i in range(cfg.pattern_len):
                x, s = _verify_mla_layer(block_params[i], cfg, x,
                                         block_state[i], start)
                new_states.append(s)
            return x, new_states

        if cfg.cost_exact:
            outs = []
            for i in range(cfg.n_superblocks):
                bp = jax.tree.map(lambda a: a[i], params["scanned"])
                bs = jax.tree.map(lambda a: a[i], state["scanned"])
                x, ns = step(x, (bp, bs))
                outs.append(ns)
            new_state["scanned"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            x, scanned_states = jax.lax.scan(
                step, x, (params["scanned"], state["scanned"]))
            new_state["scanned"] = scanned_states
    tail_states = []
    for p, s in zip(params["tail"], state["tail"]):
        x, s = _verify_mla_layer(p, cfg, x, s, start)
        tail_states.append(s)
    new_state["tail"] = tail_states

    return _head(params, x), new_state
