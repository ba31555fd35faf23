"""A run with the timed path broken underneath has to come out not
correct. The harness's look for a chip is skipped (``run_cell`` is called
directly, on the CPU at small widths); everything else is a whole run,
judged on the numbers the committed limits files compare.

Faults a serving cell can have (``perfbench/faults.py``): a token altered
where it is produced, and a decode step that returns the cache state
unchanged (no token appended).
"""
from __future__ import annotations

import time

import pytest

from perfbench import harness
from perfbench.faults import FAULTS
from perfbench.tests import tiny

SEED = 2 ** 33 + 12345


def _run(hook):
    b = tiny.bench()
    return harness.run_cell(
        bench=b, cell=b["workloads"][0], cfg=tiny.config(), mix=tiny.mix(),
        limits=tiny.limits(),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        seed=SEED, seconds=2.0, trace=False, t_start=time.perf_counter(),
        root=tiny.HERE, engine_hook=hook)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_not_correct(fault):
    res = _run(FAULTS[fault])
    check = res["check"]
    assert set(check) == set(tiny.LIMITS) | {"served_tokens_min"}
    assert check["served_tokens_min"]["value"] >= tiny.limits()["min_served"]
    over = [k for k in tiny.LIMITS
            if check[k]["value"] > check[k]["limit"]]
    print({k: check[k]["value"] for k in tiny.LIMITS}, "over:", over)
    assert over
    assert res["correct"] is False
