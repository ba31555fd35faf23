"""The control of the comparison: the plain reference computed one step
below each precision the configuration states (float8 e4m3 matmuls for
the bfloat16 weights, an int4 latent cache for the float8 one) has to come
out not correct where the program comes out correct; so do the faults
planted in the reference put in the program's place (its third token
altered, its decode state left unchanged), which is how they are read at
each cell's own size on the chip.

On the chip this is read at each cell's own size (PERF.md gives the
readings the limits were set from). Here it runs at small widths on the
CPU, with limits set the same way (``tiny.LIMITS``) on the same numbers as
the committed limits files.
"""
from __future__ import annotations

import json
import time

from perfbench import harness
from perfbench.tests import tiny

SEED = 2 ** 33 + 12345


def test_tiny_limits_compare_what_the_cells_compare():
    for path in (tiny.PERFBENCH / "limits").glob("*.json"):
        assert set(json.loads(path.read_text())["max"]) == set(tiny.LIMITS)


def test_control_and_planted_faults_fail_where_the_program_passes():
    b = tiny.bench()
    res = harness.run_cell(
        bench=b, cell=b["workloads"][0], cfg=tiny.config(), mix=tiny.mix(),
        limits=tiny.limits(),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        seed=SEED, seconds=2.0, trace=False, t_start=time.perf_counter(),
        root=tiny.HERE, control=True)
    print(json.dumps({"program": res["check"], **res["readings"]}))
    assert res["correct"] is True
    readings = res["readings"]
    assert set(readings) == {"control", "fault_token_altered",
                             "fault_state_unchanged"}
    for name, r in readings.items():
        assert r["correct"] is False, name
    program = res["check"]["mean_gap_std"]["value"]
    assert readings["control"]["mean_gap_std"] >= 3 * program
