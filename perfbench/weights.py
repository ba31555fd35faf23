"""Seeded weights of a DeepSeek-V2-family model, made on the device.

One generator serves both sides of the comparison: ``model.py`` lays its
leaves out as the program's parameter pytree, and ``reference.py`` makes a
layer's leaves again from the same seed when it needs them. Nothing here
imports the program.

Leaves are named by what they are (``w_dkv``, ``e_gate``, ...) and made in
bfloat16, the type they are served in, from a key derived from the seed,
the layer and the leaf. Large leaves are made slice by slice along their
first axis, so that no float32 copy of a whole expert stack is ever live.
The yarn attention scale (mscale^2) is folded into the q up-projection and
``routed_scaling_factor`` into the routed experts' down-projections.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16
EMBED_STD = 0.02
SLICE_ELEMS = 1 << 25          # largest float32 slice made at once


def seed_key(seed: int) -> np.ndarray:
    """Raw key data for ``seed``, any whole number below 2**64: the low and
    high 32 bits both enter the key. Passed to jitted code as an array, so
    no program is compiled per seed."""
    s = int(seed) % (1 << 64)
    base = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return np.asarray(jax.random.fold_in(base, s >> 32))


def dims(cfg: dict) -> dict:
    """The widths the generator and the reference need, from a config file."""
    return {
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "dh": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "dc": cfg["kv_lora_rank"],
        "ql": cfg["q_lora_rank"] or 0, "V": cfg["vocab_size"],
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
        "ns": cfg["n_shared_experts"], "L": cfg["num_hidden_layers"],
        "n_dense": cfg["first_k_dense_replace"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "mscale2": cfg["assumed"]["yarn_mscale_fold"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "renorm": bool(cfg["norm_topk_prob"]),
    }


def _normal(key, shape, std):
    """bf16 normal(0, std) of ``shape``, made in slices of at most
    SLICE_ELEMS float32 values along the first axis."""
    rows, rest = shape[0], int(np.prod(shape[1:], dtype=np.int64))
    block = rows
    while block * rest > SLICE_ELEMS and block % 2 == 0:
        block //= 2
    if block == rows:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(DTYPE)
    n = rows // block

    def one(i):
        x = jax.random.normal(jax.random.fold_in(key, i),
                              (block,) + tuple(shape[1:]), jnp.float32)
        return (x * std).astype(DTYPE)

    return jax.lax.map(one, jnp.arange(n)).reshape(shape)


def _leaf(key, idx, shape, fan_in=None, std=None, scale=1.0):
    std = (1.0 / np.sqrt(fan_in)) if std is None else std
    return _normal(jax.random.fold_in(key, idx), shape, std * scale)


def layer_is_dense(D: dict, layer) -> bool:
    return int(layer) < D["n_dense"]


def layer_leaves(D: dict, key, layer, part: str = "all",
                 dense: bool | None = None) -> dict:
    """The leaves of decoder layer ``layer``, bf16: ``part`` "attn"
    (attention and its norm), "mlp" (the MLP and its norm) or "all". A
    leaf's value does not depend on ``part``. ``layer`` may be traced when
    ``dense`` (whether it carries the dense MLP) is given."""
    k = jax.random.fold_in(key, layer + 1)
    if dense is None:
        dense = layer_is_dense(D, layer)
    d, H, dh, dr, dc, ql = D["d"], D["H"], D["dh"], D["dr"], D["dc"], D["ql"]
    ones = lambda n: jnp.ones((n,), DTYPE)                      # noqa: E731
    p = {}
    if part in ("all", "mlp"):
        p["ln2"] = ones(d)
        p.update(_mlp_leaves(D, k, dense))
    if part == "mlp":
        return p
    p.update(ln1=ones(d), kv_norm=ones(dc))
    q_in = ql or d
    if ql:
        p["w_dq"] = _leaf(k, 0, (d, ql), d)
        p["q_norm"] = ones(ql)
    p["w_uq"] = _leaf(k, 1, (q_in, H, dh + dr), q_in, scale=D["mscale2"])
    p["w_dkv"] = _leaf(k, 2, (d, dc), d)
    p["w_kr"] = _leaf(k, 3, (d, dr), d)
    p["w_uk"] = _leaf(k, 4, (dc, H, dh), dc)
    p["w_uv"] = _leaf(k, 5, (dc, H, D["dv"]), dc)
    p["w_o"] = _leaf(k, 6, (H, D["dv"], d), H * D["dv"])
    return p


def _mlp_leaves(D: dict, k, dense: bool) -> dict:
    d, p = D["d"], {}
    if dense:
        f = D["f"]
        p["w_gate"] = _leaf(k, 10, (d, f), d)
        p["w_up"] = _leaf(k, 11, (d, f), d)
        p["w_down"] = _leaf(k, 12, (f, d), f)
    else:
        E, fe, fs = D["E"], D["fe"], D["fe"] * D["ns"]
        p["w_router"] = _leaf(k, 20, (d, E), d)
        p["e_gate"] = _leaf(k, 21, (E, d, fe), d)
        p["e_up"] = _leaf(k, 22, (E, d, fe), d)
        p["e_down"] = _leaf(k, 23, (E, fe, d), fe, scale=D["routed_scale"])
        if fs:
            p["s_gate"] = _leaf(k, 24, (d, fs), d)
            p["s_up"] = _leaf(k, 25, (d, fs), d)
            p["s_down"] = _leaf(k, 26, (fs, d), fs)
    return p


def global_leaves(D: dict, key, part: str = "all") -> dict:
    """Embedding (part "embed"), final norm gain and the untied output head
    (part "head"), or all three."""
    k = jax.random.fold_in(key, 0)
    p = {}
    if part in ("all", "embed"):
        p["embed"] = _leaf(k, 0, (D["V"], D["d"]), std=EMBED_STD)
    if part in ("all", "head"):
        p["unembed"] = _leaf(k, 1, (D["V"], D["d"]), std=EMBED_STD)
        p["ln_f"] = jnp.ones((D["d"],), DTYPE)
    return p
