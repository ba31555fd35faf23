"""The plain reference computes what the program's float32 forward pass
computes (same seeded weights, published math): with a shared document
prefix and without, on the dense first layer, the MoE layers, q-LoRA, the
folded yarn scale and routed scaling."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import model as M
from perfbench import reference as R
from perfbench import weights as W
from perfbench.tests import tiny


@pytest.mark.parametrize("name", ["deepseek_v2_lite", "deepseek_v2"])
def test_reference_matches_the_programs_float32_forward(name):
    from repro.models import transformer as T
    cfg = tiny.config(name)
    mc = M.model_config(cfg, kv_paged=False)
    key = W.seed_key(2 ** 40 + 3)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                       M.make_params(cfg, key))
    g = np.random.default_rng(0)
    doc = g.integers(0, 512, 30).astype(np.int32)
    own = [g.integers(0, 512, 10).astype(np.int32),
           g.integers(0, 512, 7).astype(np.int32)]
    rows = np.zeros((2, 12), np.int32)
    rows[0, :10], rows[1, :7] = own
    starts = np.array([2, 0])
    ref = np.asarray(R.logits(cfg, key, doc, rows, starts, 5))
    nodoc = np.asarray(R.logits(cfg, key, np.zeros((0,), np.int32),
                                rows[:1], np.array([0]), 10))
    with jax.default_matmul_precision("highest"):
        for i, o in enumerate(own):
            lg = np.asarray(T.forward(p32, mc, jnp.asarray(
                np.concatenate([doc, o]))[None])[0])[0]
            s = len(doc) + starts[i]
            np.testing.assert_allclose(ref[i], lg[s:s + 5], atol=1e-5)
        lg = np.asarray(T.forward(p32, mc, jnp.asarray(own[0])[None])[0])[0]
        np.testing.assert_allclose(nodoc[0], lg[:10], atol=1e-5)


def test_gaps_in_logit_std_units():
    ref = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    std = float(np.std([0.0, 1.0, 2.0, 3.0]))
    g = R.gaps(ref, jnp.asarray([1]))
    assert float(g[0]) == pytest.approx(2.0 / std)
    assert float(R.gaps(ref, jnp.asarray([3]))[0]) == 0.0


def test_stale_state_hides_only_earlier_served_tokens():
    cfg = tiny.config()
    key = W.seed_key(2 ** 40 + 5)
    g = np.random.default_rng(1)
    doc = g.integers(0, 512, 20).astype(np.int32)
    rows = g.integers(0, 512, (1, 12)).astype(np.int32)
    starts = np.array([3])
    sound = np.asarray(R.logits(cfg, key, doc, rows, starts, 8))
    stale = np.asarray(R.logits(cfg, key, doc, rows, starts, 8, stale=True))
    # the first two served tokens come from rows that no earlier served
    # token precedes; every later one has lost some
    np.testing.assert_allclose(stale[0, :2], sound[0, :2], atol=1e-5)
    assert all(not np.allclose(stale[0, t], sound[0, t], atol=1e-3)
               for t in range(2, 8))
