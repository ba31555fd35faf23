"""Small widths of the benchmark's configurations and mixes, for tests on
the CPU (Pallas kernels in interpret mode)."""
from __future__ import annotations

import copy
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
PERFBENCH = HERE.parent
ROOT = PERFBENCH.parent


def config(name: str = "deepseek_v2_lite", q_lora: int | None = None) -> dict:
    cfg = json.loads((PERFBENCH / "configs" / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=16,
               v_head_dim=16, vocab_size=512, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=8,
               num_experts_per_tok=2, num_hidden_layers=3,
               q_lora_rank=q_lora if q_lora is not None
               else (48 if cfg["q_lora_rank"] else None))
    cfg["assumed"] = dict(cfg["assumed"], page_size=16, prefill_chunk=32,
                          capacity_factor=4)
    return cfg


def mix() -> dict:
    return {"loop": "closed", "clients": 4, "max_batch": 4,
            "slot_span_pages": 8,
            "documents": {"count": 2, "tokens": 64},
            "unique_prompt_tokens": {"dist": "fixed", "value": 16},
            "output_tokens": {"dist": "uniform", "low": 8, "high": 24},
            "prefix_cache": "documents"}


def open_mix() -> dict:
    return {"loop": "open", "rate_per_s": 4.0, "max_batch": 4,
            "slot_span_pages": 4,
            "unique_prompt_tokens": {"dist": "loguniform", "low": 8,
                                     "high": 40},
            "output_tokens": {"dist": "uniform", "low": 8, "high": 16}}


# the numbers the committed limits files compare, with limits set the same
# way at these widths (CPU, V2-Lite widths above, seed 2**33 + 12345): the
# program reads mean 0.0076, max 0.243; the control 0.327, 1.754; a token
# altered in the engine 0.190, 2.937 (planted in the reference 0.153,
# 2.937); the engine's decode state left unchanged 0.523, 2.995 (planted
# in the reference 0.256, 1.702)
LIMITS = {"mean_gap_std": 0.08, "max_gap_std": 1.5}


def limits() -> dict:
    return {"max": dict(LIMITS), "sample_requests": 2, "min_served": 10}


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())
