"""Observability layer: metrics registry, span tracer, probes.

The acceptance criteria of the telemetry PR, as tests:
  * same-seed engine runs export BYTE-identical Chrome traces and
    identical registry work snapshots (ref AND kernel backends);
  * the trace's request spans reproduce the engine's reported TTFT /
    latency exactly (virtual clock: ``ts // TICKS_PER_STEP`` = step);
  * a checkpoint -> restore -> resume run continues the SAME trace —
    byte-identical to the uninterrupted run, with no duplicate span ids;
  * arming every probe (tracer + quant health) does not perturb a single
    greedy token.
"""
import dataclasses
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.checkpoint.checkpoint import latest_checkpoint
from repro.configs import get_smoke_config
from repro.core.kvcache import page_aligned_capacity
from repro.models import transformer as T
from repro.obs import (MetricsRegistry, SpanTracer, TICKS_PER_STEP,
                       validate_chrome_trace)
from repro.serving import EngineConfig, Request, ServingEngine

CHUNK = 16


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("mla-7b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _workload(cfg, n=3, S=24, gen=5):
    key = jax.random.PRNGKey(11)
    prompts = np.asarray(jax.random.randint(key, (n, S), 0, cfg.vocab_size,
                                            jax.numpy.int32))
    return [Request(rid=i, prompt=prompts[i], max_new=gen, arrival=float(i))
            for i in range(n)], S, gen


def _engine(cfg, params, S, gen, *, tracer=None, health=0, chunk=CHUNK):
    span = page_aligned_capacity(S + gen, cfg.page_size) // cfg.page_size
    ccfg = dataclasses.replace(cfg, prefill_chunk=chunk) if chunk else cfg
    return ServingEngine(ccfg, params, EngineConfig(
        max_batch=2, max_pages_per_seq=span, quant_health_every=health),
        tracer=tracer)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_specs_names_and_conflicts():
    r = MetricsRegistry()
    c = r.counter("snapmla_test_things_total", "things")
    assert r.counter("snapmla_test_things_total", "things") is c   # idempotent
    with pytest.raises(ValueError):                # same name, different spec
        r.gauge("snapmla_test_things_total", "things")
    with pytest.raises(ValueError):                # naming convention
        r.counter("bad-name", "x")
    with pytest.raises(ValueError):                # counters only go up
        c.inc(-1)


def test_registry_wall_segregation_and_labels():
    r = MetricsRegistry()
    r.counter("snapmla_test_work_total", "w").inc(3)
    r.counter("snapmla_test_wall_seconds_total", "t", wall=True).inc(0.5)
    lab = r.counter("snapmla_test_kinds_total", "k", labels=("kind",))
    lab.labels(kind="a").inc()
    lab.labels(kind="b").inc(2)
    snap = r.snapshot()
    assert "snapmla_test_wall_seconds_total" not in snap["work"]
    assert "wall" not in snap                      # only on request
    full = r.snapshot(include_wall=True)
    assert full["wall"]["snapmla_test_wall_seconds_total"]["values"][""] == 0.5
    assert snap["work"]["snapmla_test_kinds_total"]["values"] == \
        {"a": 1, "b": 2}


def test_registry_state_roundtrip():
    r = MetricsRegistry()
    r.counter("snapmla_test_a_total", "a").inc(7)
    r.gauge("snapmla_test_b_level", "b").set(-2.5)
    h = r.histogram("snapmla_test_c_width", "c")
    h.observe(3)
    h.observe(900)
    lab = r.counter("snapmla_test_d_total", "d", labels=("kind",))
    lab.labels(kind="x").inc(4)
    state = r.export_state()
    r2 = MetricsRegistry()
    r2.counter("snapmla_test_a_total", "a")
    r2.gauge("snapmla_test_b_level", "b")
    r2.histogram("snapmla_test_c_width", "c")
    r2.counter("snapmla_test_d_total", "d", labels=("kind",))
    r2.restore_state(state)
    assert r2.export_state() == state
    assert r2.snapshot() == r.snapshot()


# ---------------------------------------------------------------------------
# tracer (no engine)
# ---------------------------------------------------------------------------

def test_tracer_virtual_clock_spans_and_validation():
    tr = SpanTracer()
    tr.req_begin(0, "QUEUED", tr.ts(2, 50), args={"prompt_len": 8})
    with pytest.raises(RuntimeError):             # double-open is a bug
        tr.req_begin(0, "PREFILL", tr.ts(3))
    tr.req_transition(0, "PREFILL", tr.ts(3, 50))
    tr.req_chunk(0, 3)
    tr.req_transition(0, "DECODE", tr.ts(4, 445))
    with pytest.raises(RuntimeError):             # open span at export
        tr.chrome_payload()
    tr.req_end(0, tr.ts(6, 860))
    tr.req_instant(0, "DONE", tr.ts(6, 860), args={"tokens": 3})
    tr.step_phase(5, "decode", args={"rows": 1})
    tr.counter(5, "pages", {"in_use": 2, "free": 6})
    payload = tr.chrome_payload()
    stats = validate_chrome_trace(payload, expect_requests=1)
    assert stats["requests"] == 1 and stats["terminal"] == 1
    # every request-event timestamp integer-divides back to its step
    spans = {e["name"]: e for e in payload["traceEvents"]
             if e.get("ph") == "X" and e.get("pid") == 2}
    assert spans["QUEUED"]["ts"] // TICKS_PER_STEP == 2
    assert spans["DECODE"]["ts"] // TICKS_PER_STEP == 4
    assert (spans["DECODE"]["ts"] + spans["DECODE"]["dur"]) \
        // TICKS_PER_STEP == 6


def test_validate_rejects_leaked_and_malformed_tracks():
    tr = SpanTracer()
    tr.req_begin(0, "QUEUED", tr.ts(0))
    tr.req_end(0, tr.ts(1))                       # closed span, NO terminal
    with pytest.raises(ValueError, match="terminal"):
        validate_chrome_trace(tr.chrome_payload())
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"traceEvents": []})


# ---------------------------------------------------------------------------
# engine integration: determinism + exact TTFT/latency reproduction
# ---------------------------------------------------------------------------

def _traced_run(cfg, params, *, kernels=False):
    c = dataclasses.replace(cfg, use_kernels=True, decode_backend="kernel") \
        if kernels else cfg
    reqs, S, gen = _workload(c)
    tracer = SpanTracer()
    engine = _engine(c, params, S, gen, tracer=tracer, health=2)
    results = engine.run(reqs)
    return engine, tracer, results


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["ref_backend", "kernel_backend"])
def test_trace_and_registry_byte_identical_across_seeded_runs(model,
                                                              kernels):
    cfg, params = model
    e1, t1, res1 = _traced_run(cfg, params, kernels=kernels)
    e2, t2, res2 = _traced_run(cfg, params, kernels=kernels)
    assert [r.tokens for r in res1] == [r.tokens for r in res2]
    dump1 = json.dumps(t1.chrome_payload(), sort_keys=True)
    assert dump1 == json.dumps(t2.chrome_payload(), sort_keys=True)
    assert e1.telemetry() == e2.telemetry()       # work subtree only

    # the trace REPRODUCES the engine's own timing numbers exactly
    payload = t1.chrome_payload()
    validate_chrome_trace(payload, expect_requests=len(res1))
    ev = [e for e in payload["traceEvents"] if e.get("pid") == 2]
    for r in res1:
        mine = [e for e in ev if e.get("tid") == r.rid]
        queued = min(e["ts"] for e in mine if e.get("name") == "QUEUED")
        first = next(e["ts"] for e in mine
                     if e.get("name") == "FIRST_TOKEN")
        done = next(e["ts"] for e in mine if e.get("name") == "DONE")
        assert first // TICKS_PER_STEP - queued // TICKS_PER_STEP \
            == r.ttft_steps
        assert done // TICKS_PER_STEP - queued // TICKS_PER_STEP \
            == r.latency_steps


def test_probes_do_not_perturb_greedy_tokens(model):
    """Arming the tracer + quant-health probe must not change a token
    (observability is read-only: probes never touch the decode state)."""
    cfg, params = model
    reqs, S, gen = _workload(cfg)
    plain = _engine(cfg, params, S, gen)          # no tracer, no probe
    base = [r.tokens for r in plain.run(reqs)]
    _, _, res = _traced_run(cfg, params)
    assert [r.tokens for r in res] == base


def test_quant_probe_sees_resident_fp8_pages(model):
    cfg, params = model
    reqs, S, gen = _workload(cfg)
    engine = _engine(cfg, params, S, gen, health=2)
    engine.run(reqs)
    probe = engine.quant_probe
    assert probe is not None and len(probe.samples) >= 2
    mid = [s for s in probe.samples if s["resident_pages"] > 0]
    assert mid, "no quant sample saw live pages"
    assert all(s["scale_max"] > 0 for s in mid)
    assert all(0.0 <= s["clip_rate_max"] <= 1.0 for s in mid)


def test_wall_clock_spans_nest_and_line_up_with_the_profiler(model,
                                                             tmp_path):
    """``clock="wall"``: every step phase has a real, positive duration
    inside its step, and each step starts where the profiler's own
    ``engine.step`` of the same step does (both on the epoch clock the
    profile starts from), within 1 ms."""
    from jax.profiler import ProfileData
    cfg, params = model
    reqs, S, gen = _workload(cfg)
    tracer = SpanTracer(clock="wall")
    engine = _engine(cfg, params, S, gen, tracer=tracer)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(reqs)
    finally:
        jax.profiler.stop_trace()
    payload = tracer.chrome_payload()
    validate_chrome_trace(payload, expect_requests=len(reqs))
    phases = [e for e in payload["traceEvents"]
              if e.get("cat") == "phase"]
    steps = {e["args"]["step"]: e for e in phases if e["name"] == "step"}
    assert sorted(steps) == list(range(engine.step_idx))
    inner = [e for e in phases if e["name"] != "step"]
    assert {e["name"] for e in inner} >= {"admit", "prefill", "chunk",
                                          "grow", "decode", "sample",
                                          "sync", "retire"}
    for e in phases:
        assert e["dur"] > 0, e
    for e in inner:
        st = steps[e["args"]["step"]]
        assert st["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= st["ts"] + st["dur"], e

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    start_ns = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                    if p.name == "Task Environment")
    prof = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                args = dict(ev.stats)
                if ev.name == "engine.step" and "step_num" in args:
                    prof[args["step_num"]] = start_ns + ev.start_ns
    assert sorted(prof) == sorted(steps)
    for step, e in steps.items():
        assert abs(e["ts"] - prof[step] / 1000) < 1000, step


# ---------------------------------------------------------------------------
# checkpoint -> restore -> resume: one contiguous trace
# ---------------------------------------------------------------------------

def test_restore_continues_same_trace(model, tmp_path):
    cfg, params = model
    reqs, S, gen = _workload(cfg)

    tracer_a = SpanTracer()
    engine_a = _engine(cfg, params, S, gen, tracer=tracer_a)
    res_a = engine_a.run(reqs, ckpt_dir=str(tmp_path), ckpt_every=3)
    full = json.dumps(tracer_a.chrome_payload(), sort_keys=True)

    # fresh engine adopts a MID-RUN snapshot (earliest retained — the
    # latest one may already be drained), resubmits the same workload
    # (seen rids skip) and drains: the resumed trace must be byte-identical
    # to the uninterrupted one — same span ids, no duplicates, contiguous
    assert latest_checkpoint(str(tmp_path)) is not None
    ckpt = sorted(p for p in tmp_path.iterdir()
                  if p.name.startswith("step_"))[0]
    tracer_b = SpanTracer()
    engine_b = _engine(cfg, params, S, gen, tracer=tracer_b)
    engine_b.restore(str(ckpt))
    assert engine_b.step_idx > 0
    assert len(engine_b.scheduler.finished) < len(reqs)   # truly mid-run
    reqs2, _, _ = _workload(cfg)          # fresh objects, same workload
    res_b = engine_b.run(reqs2)
    assert [r.tokens for r in res_b] == [r.tokens for r in res_a]
    assert json.dumps(tracer_b.chrome_payload(), sort_keys=True) == full
    sids = [e["sid"] for e in tracer_b._events]
    assert len(sids) == len(set(sids)), "duplicate span ids after restore"
    assert engine_b.faults["restores"] == 1


# ---------------------------------------------------------------------------
# trace_report consumes what the tracer exports
# ---------------------------------------------------------------------------

def _load_trace_report():
    path = pathlib.Path(__file__).resolve().parent.parent \
        / "scripts" / "trace_report.py"
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_tables_match_engine(model):
    cfg, params = model
    _, tracer, results = _traced_run(cfg, params)
    report = _load_trace_report()
    payload = tracer.chrome_payload()
    summary = report.summarize(payload)
    by_rid = {r["rid"]: r for r in summary["requests"]}
    assert sorted(by_rid) == [r.rid for r in results]
    for r in results:
        row = by_rid[r.rid]
        assert row["ttft"] == r.ttft_steps
        assert row["latency"] == r.latency_steps
        assert row["outcome"] == "DONE"
        assert row["chunks"] >= 1                 # chunked admission traced
    assert summary["occupancy"]["in_use_peak"] > 0
    text = report.render(summary,
                         validate_chrome_trace(payload,
                                               expect_requests=len(results)))
    assert "ttft" in text and "pages: peak" in text
