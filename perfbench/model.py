"""A configuration file as the program runs it: its ``ModelConfig`` and its
parameter pytree, made on the device from the seed in one jitted call.

The program's layer pattern is one ``"mla"`` slot per layer (a single
scanned superblock), so each slot carries its own MLP: slot 0 the dense
SwiGLU of ``intermediate_size`` (``first_k_dense_replace`` 1), the others
the MoE. ``transformer._apply_mlp`` picks the MLP by the type of the slot's
parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import weights as W


def model_config(cfg: dict, **overrides):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import MLADims, ModelConfig
    from repro.models.moe import MoEConfig

    D = W.dims(cfg)
    a = cfg["assumed"]
    if D["dh"] != D["dv"]:
        raise ValueError("the program's MLA has one head width for nope q/k "
                         f"and v; config gives {D['dh']} and {D['dv']}")
    mc = ModelConfig(
        name=cfg["name"], family="mla", n_layers=D["L"], d_model=D["d"],
        n_heads=D["H"], n_kv_heads=D["H"], d_head=D["dh"], d_ff=D["f"],
        vocab_size=D["V"], layer_pattern=("mla",) * D["L"],
        rope_theta=D["theta"], act=cfg["hidden_act"],
        moe=MoEConfig(n_experts=D["E"], top_k=D["k"], d_ff_expert=D["fe"],
                      capacity_factor=float(a["capacity_factor"]),
                      n_shared_experts=D["ns"], renorm_topk=D["renorm"]),
        first_k_dense=D["n_dense"],
        mla=MLADims(d_c=D["dc"], d_rope=D["dr"], q_lora_rank=D["ql"]),
        kv_fmt=a["kv_fmt"], page_size=int(a["page_size"]), kv_paged=True,
        prefill_chunk=int(a["prefill_chunk"]), use_kernels=True,
        decode_backend="kernel",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        max_seq_len=int(cfg["max_position_embeddings"]))
    return mc.scaled(**overrides) if overrides else mc


def _program_layer(D: dict, p: dict):
    from repro.core.mla import MLAParams
    from repro.models.layers import MLPParams
    from repro.models.moe import MoEParams

    mixer = MLAParams(w_dq=p.get("w_dq"), q_norm=p.get("q_norm"),
                      w_uq=p["w_uq"], w_dkv=p["w_dkv"],
                      kv_norm=p["kv_norm"], w_kr=p["w_kr"], w_uk=p["w_uk"],
                      w_uv=p["w_uv"], w_o=p["w_o"])
    if "w_gate" in p:
        mlp = MLPParams(w_gate=p["w_gate"], w_up=p["w_up"],
                        w_down=p["w_down"])
    else:
        mlp = MoEParams(w_router=p["w_router"], w_gate=p["e_gate"],
                        w_up=p["e_up"], w_down=p["e_down"],
                        shared_gate=p.get("s_gate"),
                        shared_up=p.get("s_up"),
                        shared_down=p.get("s_down"))
    layer = {"ln1": p["ln1"], "mixer": mixer, "ln2": p["ln2"], "mlp": mlp}
    # one superblock: every leaf gets the scan's leading axis of length 1
    return jax.tree.map(lambda x: x[None], layer)


def make_params(cfg: dict, key):
    """The program's parameter pytree, bf16, from raw key data ``key``
    (``weights.seed_key``): one jitted call on the default device."""
    D = W.dims(cfg)

    def build(key):
        g = W.global_leaves(D, key)
        return {"embed": g["embed"], "unembed": g["unembed"],
                "ln_f": g["ln_f"], "tail": [],
                "scanned": [_program_layer(D, W.layer_leaves(D, key, i))
                            for i in range(D["L"])]}

    return jax.jit(build)(jnp.asarray(key))
