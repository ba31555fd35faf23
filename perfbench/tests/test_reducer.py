"""The trace reduction and the per-layer arithmetic, on a small trace whose
numbers are worked out by hand (data/small_trace.pbtxt, times in ns):

device ops  fusion.1                       0 - 1000
            _mla_decode_splitkv_kernel.7 500 - 2500
            _lse_combine_kernel.2       3000 - 3500
            fusion.2                    6000 - 7000
            _mla_decode_splitkv_kernel.9 7000 - 9000
modules     jit_decode_step 0 - 3600, jit_wrapper (the chunk step) 6000 - 9000
host        engine.step 0 - 4000

busy = [0, 2500] + [3000, 3500] + [6000, 9000] = 6000 ns; over a 10,000 ns
slice the device is idle 40%. Decode attention kernels: 2000 + 500 + 2000
= 4500 ns. Gaps: 3500 - 6000 (2500 ns, no host span open) and 2500 - 3000
(500 ns, inside engine.step).
"""
from __future__ import annotations

import time

import pytest

from perfbench import harness, stats
from perfbench import trace as TRC
from perfbench.tests.tiny import HERE, PERFBENCH
from perfbench.layer_metrics import _common as C

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LITE = {"d": 2048, "H": 16, "dh": 128, "dr": 64, "dv": 128, "dc": 512,
        "ql": 0, "V": 102400, "f": 10944, "fe": 1408, "E": 64, "k": 6,
        "ns": 2, "L": 8, "n_dense": 1}


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        (HERE / "data" / "small_trace.pbtxt").read_text())
    return TRC.reduce(pd, kernels=harness.KERNELS, programs=harness.PROGRAMS)


def _read(name, ctx):
    return harness.load_reader("layer_metrics", name)(ctx)


def test_busy_union_kernels_programs(red):
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(6000e-9)
    assert red["kernel_s"]["decode_attn"] == pytest.approx(4500e-9)
    assert red["program_s"]["decode"] == pytest.approx(3600e-9)
    assert red["program_s"]["chunk"] == pytest.approx(3000e-9)
    ops = dict(red["device_ops"])
    assert ops["_mla_decode_splitkv_kernel"] == pytest.approx(4000e-9)
    assert ops["fusion"] == pytest.approx(2000e-9)
    assert ops["_lse_combine_kernel"] == pytest.approx(500e-9)
    assert red["idle_gaps"][0] == ["no host span", pytest.approx(2500e-9)]
    assert red["idle_gaps"][1] == ["engine.step", pytest.approx(500e-9)]


def _ctx(red, steps, slice_s=10000e-9):
    return {"D": LITE, "peaks": V5E, "fmt": "fp8_e4m3", "trace": red,
            "slice_s": slice_s, "slice_steps": steps, "steps": steps,
            "window_s": 1.0}


def test_idle_share_and_decode_attention(red):
    steps = [{"decode_rows": 64, "decode_ctx": 500, "prefill_tokens": 0,
              "prefill_ctx": 0, "head_rows": 64}]
    ctx = _ctx(red, steps)
    assert _read("device_idle_pct.longdoc", ctx) == pytest.approx(40.0)
    assert _read("decode_attn_ms_per_step", ctx) == pytest.approx(4.5e-3)
    # 500 context tokens x 8 layers: 4,000 x 644 B = 2,576,000 B, 3.1453 us
    # at 819 GB/s; 4,000 x 2,176 x 16 = 139,264,000 FLOP, 0.7069 us at
    # 197 TF/s: bandwidth bounds it, 3.1453 / 4.5 = 69.90%
    assert C.decode_attn_least_s(ctx) == pytest.approx(2576000 / 819e9)
    assert _read("decode_attn_roofline", ctx) == pytest.approx(
        100 * 2576000 / 819e9 / 4500e-9)
    assert _read("decode_attn_roofline", ctx) == pytest.approx(69.90, abs=0.01)


def test_compute_bound_roofline_at_128_heads(red):
    D = dict(LITE, H=128, L=2)
    ctx = dict(_ctx(red, [{"decode_rows": 1, "decode_ctx": 1000,
                           "prefill_tokens": 0, "prefill_ctx": 0,
                           "head_rows": 1}]), D=D)
    # 2,000 token-layers: 1,288,000 B (1.5727 us) vs 2,000 x 2,176 x 128 =
    # 557,056,000 FLOP (2.8277 us): compute bounds it
    assert C.decode_attn_least_s(ctx) == pytest.approx(557056000 / 197e12)


def test_step_mfu_arithmetic(red):
    steps = [{"decode_rows": 2, "decode_ctx": 3000, "prefill_tokens": 0,
              "prefill_ctx": 0, "head_rows": 2}]
    ctx = _ctx(red, steps, slice_s=1e-3)
    p = stats.params_per_token(LITE)
    # attention: q 2048x16x192 + kv down 2048x576 + W_uk, W_uv 512x16x128
    # each + W_o 16x128x2048
    assert p["attn"] == 6291456 + 1179648 + 2 * 1048576 + 4194304
    assert p["moe"] == 2048 * 64 + 3 * 2048 * 1408 * 8
    flops = 2 * (2 * (8 * p["attn"] + p["dense_mlp"] + 7 * p["moe"])
                 + 2 * p["head"]) + 2176 * 16 * 3000 * 8
    assert _read("step_mfu", ctx) == pytest.approx(100 * flops / 197e9)


def test_nothing_to_read_gives_nothing():
    ctx = _ctx(None, [], slice_s=None)
    for name in ("device_idle_pct.longdoc", "decode_attn_ms_per_step",
                 "decode_attn_roofline", "step_mfu"):
        assert _read(name, ctx) is None


def test_every_per_layer_metric_has_a_reader():
    import json
    bench = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (PERFBENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def test_recorded_chip_trace_reduces_in_seconds():
    """A 2 s slice of a v2-longdoc run on a TPU v5e (13 engine steps), as
    the chip wrote it: reduced in well under a second there and here."""
    t = time.perf_counter()
    red = TRC.reduce(TRC.load(str(HERE / "data" / "chip_slice.xplane.pb")),
                     kernels=harness.KERNELS, programs=harness.PROGRAMS)
    assert time.perf_counter() - t < 10.0
    assert red["devices"] == 1
    # the numbers the chip run printed for this slice
    assert red["busy_s"] == pytest.approx(1.9000117420000002)
    assert red["kernel_s"]["decode_attn"] == pytest.approx(1.49022866)
    assert red["program_s"]["decode"] == pytest.approx(1.68612369)
    assert red["device_ops"][0] == ["_snapmla_decode_paged_impl",
                                    pytest.approx(1.49022866)]
    assert red["idle_gaps"][0][0].startswith("engine.step")
