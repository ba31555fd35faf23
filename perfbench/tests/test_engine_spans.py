"""The engine's own spans in a profiler trace, and the four per-layer
metrics that read them (``engine_spans.py``, ``layer_metrics/``).

A hand-worked trace (data/engine_spans.pbtxt, times in ns): two engine steps
inside the harness's own outer ``engine.step`` annotations (no step number,
so not the engine's):

step 7  [100, 11900]  admit [100, 600] > tier_drain [200, 300];
                      prefill [600, 9500] > chunk [6500, 9400] > sync
                      [8500, 9300]; grow [9500, 9700]; decode [9700, 10000];
                      sample [10000, 11800] > sync [10100, 11000], retire
                      [11000, 11500]
step 8  [12000, 16000] admit [12000, 12100]; grow [12100, 12200]; decode
                      [12200, 12400]; sample [12400, 15900] > sync
                      [12500, 15000]
device  busy [0, 5500] (fusion, mla_decode_paged_splitkv 1000-5000,
        lse_combine 5000-5500), [8000, 9000], [12300, 14500]
        (mla_decode_paged_splitkv 12300-14000, lse_combine 14000-14300);
        jit_chunk_prefill_step 7000-9000

Idle under step 7: 11800 - (5400 + 1000) = 5400, under step 8: 4000 - 2200
= 1800; under its syncs: 800 - 500 = 300 and 900, and 2500 - 2000 = 500.
So idle_host = (7200 - 1700) / 2 = 2750 ns per step. Scheduler self time:
admit 500 - 100 + tier_drain 100 + grow 200 + retire 500 = 1200 (step 7)
and 100 + 100 (step 8): 700 ns per step. Chunk program 2000 ns over the one
chunk step; combines 500 + 300 ns over two decode steps: 400 ns.
"""
from __future__ import annotations

import pathlib
import shutil
import time

import pytest

from perfbench import engine_spans as ES
from perfbench import harness
from perfbench import trace as TRC
from perfbench.tests import tiny

DATA = tiny.HERE / "data"
SEED = 2 ** 33 + 12345
NEW = ("idle_host_ms_per_step.longdoc", "sched_ms_per_step.longdoc",
       "prefill_ms_per_step.longdoc", "decode_combine_ms_per_step")


def _read(name, ctx):
    return harness.load_reader("layer_metrics", name)(ctx)


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        (DATA / "engine_spans.pbtxt").read_text())


def test_engine_steps_and_spans_of_a_small_trace(small):
    red = ES.reduce(small)
    assert red["devices"] == 1
    assert [st["step"] for st in red["steps"]] == [7, 8]
    s7, s8 = red["steps"]
    assert s7["idle_s"] == pytest.approx(5400e-9)
    assert s8["idle_s"] == pytest.approx(1800e-9)
    names = [sp["name"] for sp in s7["spans"]]
    assert names == ["engine.admit", "engine.tier_drain", "engine.prefill",
                     "engine.chunk", "engine.sync", "engine.grow",
                     "engine.decode", "engine.sample", "engine.sync",
                     "engine.retire"]
    admit, chunk = s7["spans"][0], s7["spans"][3]
    assert admit["self_s"] == pytest.approx(400e-9)
    assert admit["args"] == {"admitted": 1}
    assert chunk["args"] == {"width": 16, "pos": 64}
    assert s8["spans"][2]["args"] == {"rows": 2, "ctx_tokens": 102}
    assert red["kernel_s"]["decode_combine"] == pytest.approx(800e-9)
    assert red["program_s"]["chunk"] == pytest.approx(2000e-9)
    assert [len(ES.steps_with(red, p)) for p in ("chunk", "decode")] \
        == [1, 2]


def test_each_reader_on_the_small_trace(small, monkeypatch):
    red = ES.reduce(small)
    monkeypatch.setattr(ES, "load", lambda ctx: red)
    want = {"idle_host_ms_per_step.longdoc": 2750e-6,
            "sched_ms_per_step.longdoc": 700e-6,
            "prefill_ms_per_step.longdoc": 2000e-6,
            "decode_combine_ms_per_step": 400e-6}
    for name, value in want.items():
        assert _read(name, {}) == pytest.approx(value), name


def test_split_kernel_and_combine_both_count_as_decode_attention(small):
    """The harness's decode-attention pattern, unchanged, reads the named
    split-KV kernel and its combine as two ops of its one kernel time."""
    red = TRC.reduce(small, kernels=harness.KERNELS,
                     programs=harness.PROGRAMS)
    ops = dict(red["device_ops"])
    assert ops["mla_decode_paged_splitkv"] == pytest.approx(5700e-9)
    assert ops["lse_combine"] == pytest.approx(800e-9)
    assert red["kernel_s"]["decode_attn"] == pytest.approx(6500e-9)


@pytest.mark.parametrize("name", ["mla_decode", "mla_decode_splitkv",
                                  "mla_decode_paged",
                                  "mla_decode_paged_splitkv", "lse_combine",
                                  "amla_combine", "fetch_dequant",
                                  "quantize_append", "quantize_query",
                                  "fusion"])
def test_kernel_names_and_the_decode_attention_pattern(name):
    """Every decode kernel's name matches the harness's decode-attention
    pattern and no other kernel's does; only the combines match the
    combine pattern."""
    import re
    decode = name.startswith("mla_decode") or name.endswith("_combine")
    assert bool(re.search(harness.KERNELS["decode_attn"], name)) == decode
    assert bool(re.search(ES.KERNELS["decode_combine"], name)) \
        == name.endswith("_combine")


def _trace_dir(tmp_path: pathlib.Path, xplane: pathlib.Path):
    d = tmp_path / "plugins" / "profile" / "slice"
    d.mkdir(parents=True)
    shutil.copy(xplane, d / "slice.xplane.pb")
    return tmp_path


def test_a_trace_without_engine_spans_reads_nothing(tmp_path):
    """The slice the parent program wrote (its only ``engine.step`` is the
    harness's outer annotation) holds no engine span: each reader reads
    nothing, as it does in a run without a trace."""
    ctx = {"trace": {"busy_s": 1.0},
           "trace_dir": _trace_dir(tmp_path, DATA / "chip_slice.xplane.pb")}
    assert ES.load(ctx) is None
    for name in NEW:
        assert _read(name, ctx) is None
        assert _read(name, {"trace": None}) is None


def test_recorded_chip_slice_with_engine_spans(tmp_path):
    """A 2.2 s slice of a traced v2-longdoc run of this program on a TPU v5e
    (13 engine steps; pruned to the TPU op and module lines, the Python
    line and the profile's start). The split-KV kernel and its combine are
    two ops, both in the harness's decode-attention time; the chunk program
    has its own module name; each reader reads what the chip run printed."""
    path = DATA / "chip_slice_spans.xplane.pb"
    red = TRC.reduce(TRC.load(str(path)), kernels=harness.KERNELS,
                     programs=harness.PROGRAMS)
    ops = dict(red["device_ops"])
    split, combine = ops["mla_decode_paged_splitkv"], ops["lse_combine"]
    assert split == pytest.approx(1.483854938)
    assert combine == pytest.approx(0.004645441)
    assert red["kernel_s"]["decode_attn"] == pytest.approx(split + combine)
    assert "_snapmla_decode_paged_impl" not in ops
    ctx = {"trace": red, "trace_dir": _trace_dir(tmp_path, path)}
    spans = ES.load(ctx)
    assert len(spans["steps"]) == 13
    assert spans["program_s"]["chunk"] == pytest.approx(0.245642791)
    # the harness's own chunk pattern (^jit_wrapper) named the program
    # before it kept its step's name
    assert red["program_s"]["chunk"] == 0.0
    printed = {"idle_host_ms_per_step.longdoc": 16.290833000000003,
               "sched_ms_per_step.longdoc": 8.449707692307692,
               "prefill_ms_per_step.longdoc": 35.09182728571429,
               "decode_combine_ms_per_step": 0.3573416153846154}
    for name, value in printed.items():
        assert _read(name, ctx) == pytest.approx(value), name


# ---------------------------------------------------------------- tiny run

@pytest.fixture(scope="module")
def traced_tiny(tmp_path_factory):
    """A traced run of the tiny cell on the CPU, with the context the
    harness hands its per-layer readers."""
    root = tmp_path_factory.mktemp("root")
    seen = {}
    read_as = harness.load_reader

    def spy(kind, name):
        read = read_as(kind, name)
        if kind != "layer_metrics":
            return read

        def rd(ctx):
            seen["ctx"] = ctx
            return read(ctx)
        return rd

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "load_reader", spy)
        res = harness.run_cell(
            bench=tiny.bench(), cell=tiny.bench()["workloads"][0],
            cfg=tiny.config(), mix=tiny.mix(), limits=tiny.limits(),
            peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            seed=SEED, seconds=2.5, trace=True,
            t_start=time.perf_counter(), root=root)
    ctx = dict(seen["ctx"], trace_dir=root / ".perfbench_trace")
    return res, ctx


def test_python_line_holds_engine_steps_with_phases_inside(traced_tiny):
    _, ctx = traced_tiny
    (path,) = ctx["trace_dir"].glob("plugins/profile/*/*.xplane.pb")
    spans = ES.host_spans(TRC.load(str(path)))
    steps = [sp for sp in spans if sp["name"] == "engine.step"]
    assert len(steps) >= 3
    assert [sp["args"]["step_num"] for sp in steps] == list(
        range(steps[0]["args"]["step_num"],
              steps[0]["args"]["step_num"] + len(steps)))
    phases = [sp for sp in spans if sp["name"] != "engine.step"]
    for sp in phases:
        assert any(st["start_ns"] <= sp["start_ns"] and
                   sp["start_ns"] + sp["dur_ns"]
                   <= st["start_ns"] + st["dur_ns"] for st in steps), sp
    assert {sp["name"] for sp in phases} >= {
        "engine.admit", "engine.prefill", "engine.chunk", "engine.grow",
        "engine.decode", "engine.sample", "engine.sync", "engine.retire"}


def test_span_counts_equal_the_harness_records_step_for_step(traced_tiny):
    _, ctx = traced_tiny
    red = ES.load(ctx)
    records = ctx["slice_steps"]
    assert len(red["steps"]) == len(records) >= 3
    for st, rec in zip(red["steps"], records):
        by = {}
        for sp in st["spans"]:
            by.setdefault(sp["name"], []).append(sp["args"])
        (dec,) = by.get("engine.decode", [{"rows": 0, "ctx_tokens": 0}])
        assert (dec["rows"], dec["ctx_tokens"]) \
            == (rec["decode_rows"], rec["decode_ctx"])
        assert sum(c["width"] for c in by.get("engine.chunk", [])) \
            == rec["prefill_tokens"]
    assert any(rec["prefill_tokens"] for rec in records)


def test_traced_run_reports_what_the_cpu_trace_holds(traced_tiny):
    """On the CPU the trace holds the engine's spans but no device plane:
    the scheduler's host time is read, the device-time metrics are not."""
    res, ctx = traced_tiny
    assert res["correct"] is True
    got = {name: _read(name, ctx) for name in NEW}
    assert got["sched_ms_per_step.longdoc"] > 0
    assert got["idle_host_ms_per_step.longdoc"] is None
    assert got["prefill_ms_per_step.longdoc"] is None
    assert got["decode_combine_ms_per_step"] is None
