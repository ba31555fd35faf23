"""Token-choice top-k Mixture-of-Experts with capacity-based sort dispatch.

XLA/pjit-friendly dropless-ish MoE: tokens are routed to their top-k experts,
packed into an [E, C, d] buffer via argsort (no [T, E, C] one-hot tensors),
processed by stacked expert MLPs, and combined with router weights. Tokens
beyond an expert's capacity are dropped (standard capacity-factor semantics;
the dropped fraction is returned as an observable metric).

Sharding: the expert axis (leading axis of expert weights and of the [E, C, d]
buffer) carries the 'model' mesh axis (EP); GSPMD inserts the dispatch
all-to-alls. See launch/sharding.py.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    n_shared_experts: int = 0      # deepseek-style always-on shared expert(s)
    renorm_topk: bool = True       # renormalize top-k router weights to sum 1


class MoEParams(NamedTuple):
    w_router: jax.Array            # [d, E]
    w_gate: jax.Array              # [E, d, f]
    w_up: jax.Array                # [E, d, f]
    w_down: jax.Array              # [E, f, d]
    shared_gate: jax.Array | None  # [d, f_shared]
    shared_up: jax.Array | None
    shared_down: jax.Array | None


def init_moe_params(key, d: int, cfg: MoEConfig, dtype=jnp.float32) -> MoEParams:
    ks = jax.random.split(key, 7)
    E, f = cfg.n_experts, cfg.d_ff_expert

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)

    fs = f * cfg.n_shared_experts
    return MoEParams(
        w_router=init(ks[0], (d, E), d),
        w_gate=init(ks[1], (E, d, f), d),
        w_up=init(ks[2], (E, d, f), d),
        w_down=init(ks[3], (E, f, d), f),
        shared_gate=init(ks[4], (d, fs), d) if fs else None,
        shared_up=init(ks[5], (d, fs), d) if fs else None,
        shared_down=init(ks[6], (fs, d), fs) if fs else None,
    )


@jax.named_scope("moe")
def moe_layer(params: MoEParams, cfg: MoEConfig, x: jax.Array,
              act=jax.nn.silu) -> tuple[jax.Array, jax.Array]:
    """x [..., T, d] -> (out [..., T, d], dropped_fraction scalar); its ops
    carry the ``moe`` named scope."""
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)                                   # [T, d]
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.top_k

    # --- routing ----------------------------------------------------------
    router_logits = (xt.astype(jnp.float32) @ params.w_router.astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)          # [T, E]
    weights, ids = jax.lax.top_k(probs, k)                  # [T, k]
    if cfg.renorm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # --- sort-based dispatch ------------------------------------------------
    flat_ids = ids.reshape(-1)                              # [T*k]
    sort_idx = jnp.argsort(flat_ids, stable=True)           # [T*k]
    sorted_ids = flat_ids[sort_idx]
    # rank of each routed pair within its expert
    first_of_expert = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank = jnp.arange(T * k) - first_of_expert
    C = max(1, int(T * k * cfg.capacity_factor / E))
    keep = rank < C
    dest = jnp.where(keep, sorted_ids * C + rank, E * C)    # overflow row
    token_of = sort_idx // k

    buf = jnp.zeros((E * C + 1, d), xt.dtype)
    buf = buf.at[dest].set(xt[token_of] * keep[:, None].astype(xt.dtype))
    buf = buf[: E * C].reshape(E, C, d)

    # --- expert MLPs (stacked einsums; E axis is EP-sharded) ---------------
    h = act(jnp.einsum("ecd,edf->ecf", buf, params.w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, params.w_up)
    eout = jnp.einsum("ecf,efd->ecd", h, params.w_down)     # [E, C, d]

    # --- combine ------------------------------------------------------------
    flat_out = jnp.concatenate([eout.reshape(E * C, d), jnp.zeros((1, d), eout.dtype)])
    pair_out = flat_out[dest] * keep[:, None].astype(eout.dtype)   # sorted order
    unsorted = jnp.zeros((T * k, d), eout.dtype).at[sort_idx].set(pair_out)
    out = jnp.einsum("tkd,tk->td", unsorted.reshape(T, k, d),
                     weights.astype(eout.dtype))

    # --- shared experts (always-on path) -----------------------------------
    if params.shared_gate is not None:
        hs = act(xt @ params.shared_gate) * (xt @ params.shared_up)
        out = out + hs @ params.shared_down

    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return out.reshape(orig_shape), dropped


def moe_ref_dense(params: MoEParams, cfg: MoEConfig, x: jax.Array, act=jax.nn.silu):
    """O(T*E) dense oracle (computes every expert for every token) — tests only."""
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ params.w_router.astype(jnp.float32), -1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_topk:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    h = act(jnp.einsum("td,edf->tef", xt, params.w_gate)) * jnp.einsum(
        "td,edf->tef", xt, params.w_up)
    every = jnp.einsum("tef,efd->ted", h, params.w_down)     # [T, E, d]
    mask = jax.nn.one_hot(ids, cfg.n_experts, dtype=every.dtype)  # [T,k,E]
    out = jnp.einsum("tke,ted,tk->td", mask, every, weights.astype(every.dtype))
    if params.shared_gate is not None:
        hs = act(xt @ params.shared_gate) * (xt @ params.shared_up)
        out = out + hs @ params.shared_down
    return out.reshape(x.shape)
