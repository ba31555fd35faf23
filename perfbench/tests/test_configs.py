"""Each configuration file holds its published config verbatim but for the
keys it lists as reduced, and the program is built at those widths."""
from __future__ import annotations

import json

import pytest

from perfbench.tests.tiny import PERFBENCH, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
# widths that no cut may change (the contract's list for this family)
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_key_value_heads", "n_routed_experts", "n_shared_experts",
          "num_experts_per_tok", "vocab_size")


def _load(name):
    entry = CONFIGS[name]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    stem = (ROOT / entry["file"]).stem
    pub = json.loads(
        (PERFBENCH / "configs" / "published" / f"{stem}.json").read_text())
    return entry, cfg, pub


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_file_is_the_published_config_but_for_reduced(name):
    entry, cfg, pub = _load(name)
    assert cfg["source"] == entry["source"] == pub["source"]
    differ = sorted(k for k, v in pub["config"].items() if cfg.get(k) != v)
    assert differ == sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["reduced"] == ["num_hidden_layers"]
    for k in WIDTHS:
        assert cfg[k] == pub["config"][k], k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_not_honoured_keys_keep_their_published_values(name):
    _, cfg, pub = _load(name)
    assert cfg["not_honoured"]
    for k in cfg["not_honoured"]:
        assert k in pub["config"], k
        assert cfg[k] == pub["config"][k], k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_config_has_the_published_widths(name):
    from perfbench import model as M
    _, cfg, pub = _load(name)
    p = pub["config"]
    mc = M.model_config(cfg)
    assert (mc.d_model, mc.n_heads, mc.d_head, mc.d_ff, mc.vocab_size) == (
        p["hidden_size"], p["num_attention_heads"], p["qk_nope_head_dim"],
        p["intermediate_size"], p["vocab_size"])
    assert p["v_head_dim"] == mc.d_head
    assert (mc.mla.d_c, mc.mla.d_rope, mc.mla.q_lora_rank) == (
        p["kv_lora_rank"], p["qk_rope_head_dim"], p["q_lora_rank"] or 0)
    m = mc.moe
    assert (m.n_experts, m.top_k, m.d_ff_expert, m.n_shared_experts,
            m.renorm_topk) == (
        p["n_routed_experts"], p["num_experts_per_tok"],
        p["moe_intermediate_size"], p["n_shared_experts"],
        p["norm_topk_prob"])
    assert mc.first_k_dense == p["first_k_dense_replace"]
    assert not mc.tie_embeddings and not p["tie_word_embeddings"]
    assert mc.n_layers == cfg["num_hidden_layers"]
    assert mc.layer_pattern == ("mla",) * mc.n_layers


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_capacity_never_drops_a_token(name):
    """moe.py gives each expert int(T * k * cf / E) rows: at least T for
    every call size the engine makes, so no token is ever dropped."""
    from perfbench import model as M
    _, cfg, _ = _load(name)
    m = M.model_config(cfg).moe
    for T in range(1, 4097):
        assert max(1, int(T * m.top_k * m.capacity_factor / m.n_experts)) \
            >= T, T
