"""One run of one cell: build the engine, warm it up, measure a window, read
the metrics, check the served tokens against the reference.

The window drives the program's ``ServingEngine`` through ``submit()`` and
``step()`` only; token times are the host clock after each ``step()``, which
ends in the engine's own ``device_get``. The mix decides the load: a closed
loop (``clients`` that each send their next request when the last one
finishes) or an open loop (requests sent when they are due). Nothing is
waited for after the window closes: requests still running then are neither
finished nor failed.

Every metric is read by a module of its own, found by the metric's name:
``end_to_end/<name>.py`` from the requests' time records, and
``layer_metrics/<name>.py`` from the steps' records and the trace.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

from perfbench import traffic as TR
from perfbench import weights as W

HERE = pathlib.Path(__file__).resolve().parent
TRACE_SECONDS = 2.0
# the device's op names (trace.op_name) and the engine's jit names: the
# paged split-KV decode kernel and its combine run as custom calls named
# after their jitted wrapper (_snapmla_decode_paged_impl); the chunk step is
# jitted through the engine's trace-counting wrapper (jit_wrapper)
KERNELS = {"decode_attn": r"mla_decode|lse_combine|amla_combine"}
PROGRAMS = {"decode": r"^jit_decode_step", "chunk": r"^jit_wrapper"}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Live:
    """A request of the traffic while the engine has it: when it was due
    (an open loop's arrival time; a closed loop's send time) and the host
    time of each of its output tokens."""
    spec: TR.Spec
    req: object
    due: float
    tokens: list = dataclasses.field(default_factory=list)
    done_at: float | None = None


def engine_config(mix: dict, page: int, seed: int):
    from repro.serving.engine import EngineConfig
    span, batch = mix["slot_span_pages"], mix["max_batch"]
    docs = mix.get("documents")
    doc_pages = docs["tokens"] // page if docs else 0
    retain = (docs["count"] * doc_pages + batch
              if mix.get("prefix_cache") == "documents" else 0)
    n_pages = 1 + retain + batch * (span - doc_pages)
    return EngineConfig(max_batch=batch, n_pages=n_pages,
                        max_pages_per_seq=span, prefix_sharing=True,
                        prefix_cache_pages=retain, seed=seed % (1 << 31))


# the engine's postprocess (and the gather of its rows) compiles once per
# number of decoding rows. A full closed loop reaches 1 (a prefill's first
# token) and the counts near the batch, where at most a few slots are
# prefilling at once; an open loop can reach any count
WARM_ROWS_BELOW_BATCH = 8


def _warm_rows(closed: bool, batch: int) -> list[int]:
    if not closed:
        return list(range(1, batch + 1))
    return sorted({1} | set(range(max(1, batch - WARM_ROWS_BELOW_BATCH),
                                  batch + 1)))


def _warm_postprocess(engine, counts: list[int], vocab: int) -> None:
    import jax.numpy as jnp
    rows = jnp.zeros((max(counts), vocab), jnp.float32)

    class _R:
        rid, out_tokens = 0, ()

    for n in counts:
        engine._postprocess(rows[np.arange(n, dtype=np.int32)], [_R()] * n)


def load_reader(kind: str, name: str):
    """``read(ctx)`` of the metric ``name``: ``<kind>/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        HERE / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    (--trace 0) or its per-layer ones (--trace 1)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def run_cell(*, bench: dict, cell: dict, cfg: dict, mix: dict, limits: dict,
             peaks: dict, seed: int, seconds: float, trace: bool,
             t_start: float, root: pathlib.Path, engine_hook=None,
             control: bool = False) -> dict:
    """One run; ``peaks`` is this device's row of peaks.json. ``control``
    also reads the control and the faults planted in the reference at the
    same positions (calibration and tests, not the benchmark's runs);
    ``engine_hook`` sees the engine once it is built (tests plant faults
    through it)."""
    import jax
    from perfbench import model as M
    from repro.launch.steps import chunk_buckets
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request, Status

    dev = jax.devices()[0]
    D = W.dims(cfg)
    mc = M.model_config(cfg)
    page = mc.page_size
    key = W.seed_key(seed)
    params = M.make_params(cfg, key)
    jax.block_until_ready(params)
    log(f"[setup] weights made: {time.perf_counter() - t_start:.3f} s")

    traffic = TR.Traffic(mix, D["V"], seed)
    ecfg = engine_config(mix, page, seed)
    engine = ServingEngine(mc, params, ecfg)
    del params
    if engine_hook:
        engine_hook(engine)
    log(f"[setup] engine built (decode step compiled): "
        f"{time.perf_counter() - t_start:.3f} s")

    live: dict[int, Live] = {}
    finished: list[Live] = []
    next_rid = [0]

    def submit(spec: TR.Spec, due: float) -> Live:
        rid = next_rid[0]
        next_rid[0] += 1
        req = Request(rid=rid, prompt=spec.prompt, max_new=spec.max_new)
        engine.submit(req)
        lv = Live(spec, req, due)
        live[rid] = lv
        return lv

    def step(records=None):
        before = {rid: (len(lv.req.out_tokens), lv.req.prefill_pos)
                  for rid, lv in live.items()}
        ts = time.perf_counter()
        engine.step()
        te = time.perf_counter()
        rec = {"t0": ts, "t1": te, "decode_rows": 0, "decode_ctx": 0,
               "prefill_tokens": 0, "prefill_ctx": 0, "head_rows": 0}
        for rid, lv in list(live.items()):
            r = lv.req
            n0, p0 = before.get(rid, (0, 0))
            n1 = len(r.out_tokens)
            lv.tokens += [te] * (n1 - n0)
            start = max(p0, r.cached_tokens if n0 == 0 else 0)
            if r.prefill_pos > start and n0 == 0:
                w = r.prefill_pos - start
                rec["prefill_tokens"] += w
                rec["prefill_ctx"] += w * (start + r.prefill_pos) // 2
                rec["head_rows"] += 1
            dec = n1 - n0 - (1 if n0 == 0 and n1 > 0 else 0)
            if dec > 0:
                rec["decode_rows"] += dec
                rec["decode_ctx"] += dec * (r.prompt_len + n1 - 1)
                rec["head_rows"] += dec
            if r.status in (Status.DONE, Status.FAILED, Status.REJECTED):
                lv.done_at = te
                del live[rid]
                finished.append(lv)
        if records is not None:
            records.append(rec)
        return te

    def drain(what: str):
        while live:
            step()
        bad = [lv.req.status for lv in finished if lv.req.status != Status.DONE]
        if bad:
            raise RuntimeError(f"{what} failed: {bad}")
        finished.clear()

    # set-up the traffic needs: the shared documents into the prefix cache
    for doc in traffic.documents:
        submit(TR.Spec(-1, None, doc, 1), time.perf_counter())
    drain("document prefill")
    log(f"[setup] {len(traffic.documents)} documents prefilled: "
        f"{time.perf_counter() - t_start:.3f} s")

    _warm_postprocess(engine, _warm_rows(traffic.closed, ecfg.max_batch),
                      D["V"])
    if traffic.closed:
        # every client sends a first request whose output length is
        # staggered over the mean, so that completions (and with them new
        # questions' prefill) arrive at a steady rate from the window's start
        n_cl = mix["clients"]
        mean_out = traffic.mean_output()
        for c in range(n_cl):
            spec = traffic.next()
            spec.max_new = max(2, int(round((c + 1) * mean_out / n_cl)))
            submit(spec, time.perf_counter())
        while any(len(lv.req.out_tokens) < 2 for lv in live.values()):
            step()
            for _ in range(len(finished)):
                submit(traffic.next(), time.perf_counter())
            finished.clear()
    else:
        # an open loop starts the window idle: one request per chunk shape
        # through prefill and decode compiles what its arrivals will use
        g = TR.rng(seed, "warm")
        for width in chunk_buckets(mc.prefill_chunk):
            submit(TR.Spec(-1, None, g.integers(0, D["V"], width, np.int32),
                           2), time.perf_counter())
        drain("warm-up")
    log(f"[setup] warm: {len(live)} requests decoding: "
        f"{time.perf_counter() - t_start:.3f} s")

    # ---------------------------------------------------------------- window
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    in_window: list[Live] = list(live.values())
    records: list[dict] = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    trace_dir = root / ".perfbench_trace"
    tr_on = tr_t0 = tr_t1 = None
    paused_s = 0.0
    tr_start_at = t0 + max(0.0, (seconds - TRACE_SECONDS) / 2)
    arrival = None if traffic.closed else traffic.next()
    now = t0
    while now < deadline:
        while arrival is not None and t0 + arrival.due <= now:
            in_window.append(submit(arrival, t0 + arrival.due))
            arrival = traffic.next()
        if not live and arrival is not None:
            # an open loop with nothing in flight waits for its next arrival
            time.sleep(max(0.0, min(t0 + arrival.due, deadline) - now))
            now = time.perf_counter()
            continue
        if trace and tr_on is None and now >= tr_start_at:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tr_on, tr_t0 = True, time.perf_counter()
        if tr_on:
            with jax.profiler.TraceAnnotation("engine.step"):
                now = step(records)
            if now - tr_t0 >= TRACE_SECONDS or now >= deadline:
                jax.block_until_ready(engine.state)
                tr_t1 = time.perf_counter()
                jax.profiler.stop_trace()
                tr_on = False
                # writing the trace is no engine work: the host loop's
                # step time leaves it out
                paused_s = time.perf_counter() - tr_t1
        else:
            now = step(records)
        if traffic.closed:
            for _ in range(len(finished)):
                in_window.append(submit(traffic.next(), now))
        finished.clear()
    t_end = now
    window_s = t_end - t0
    step_ms = sorted((r["t1"] - r["t0"]) * 1e3 for r in records)
    log(f"[window] {window_s:.3f} s, {len(records)} steps, "
        f"{len(compiles)} compiles inside it; longest steps (ms) "
        + json.dumps([round(x, 3) for x in step_ms[-3:]]))

    # -------------------------------------------------------------- results
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    attempted = len(in_window)
    failed = sum(lv.req.status in (Status.FAILED, Status.REJECTED)
                 for lv in in_window)
    values = {}
    e2e_ctx = {"requests": in_window, "t0": t0, "t_end": t_end,
               "window_s": window_s, "setup_s": setup_s}
    for m in metrics_for(bench, cell["name"], False):
        values[m["name"]] = load_reader("end_to_end", m["name"])(e2e_ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "in_flight": len(live), "measured_s": window_s,
              "compiles_in_window": len(compiles),
              "longest_step_ms": step_ms[-1] if step_ms else None}
    breakdown = None
    if trace:
        red = None
        if tr_t0 is not None and tr_t1 is not None:
            from perfbench import trace as TRC
            t_red = time.perf_counter()
            path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
            red = TRC.reduce(TRC.load(str(path[-1])), kernels=KERNELS,
                             programs=PROGRAMS)
            log(f"[trace] {tr_t1 - tr_t0:.3f} s slice reduced in "
                f"{time.perf_counter() - t_red:.3f} s: "
                + json.dumps({k: red[k] for k in
                              ("busy_s", "kernel_s", "program_s")}))
            log("[trace] device_ops " + json.dumps(red["device_ops"]))
            device["busy_s"] = red["busy_s"]
            device["window_s"] = tr_t1 - tr_t0
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        ctx = {"D": D, "peaks": peaks, "fmt": mc.kv_fmt,
               "window_s": window_s - paused_s, "steps": records,
               "trace": red, "slice_s": (tr_t1 - tr_t0) if red else None,
               "slice_steps": [r for r in records
                               if red and r["t0"] >= tr_t0
                               and r["t1"] <= tr_t1]}
        for m in metrics_for(bench, cell["name"], True):
            v = load_reader("layer_metrics", m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
    want = metrics_for(bench, cell["name"], trace)
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in want if m["name"] in values}
    result["device"] = device
    if breakdown:
        result["breakdown"] = breakdown

    # ------------------------------------------------------ the reference
    done = [lv for lv in in_window
            if lv.done_at is not None and lv.req.status == Status.DONE]
    sample = pick_sample(done, limits["sample_requests"], seed)
    max_out = traffic.longest_output()
    own_rows = traffic.longest_unique() + max_out
    live.clear()
    in_window = e2e_ctx = done = None
    del engine
    gc.collect()
    log(f"[check] device bytes in use once the engine is freed: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use', 0)}")
    t_ref = time.perf_counter()
    chk = check_sample(cfg, key, sample, traffic.documents, own_rows,
                       limits["sample_requests"], max_out, D["V"], control)
    log(f"[check] {len(sample)} requests, {len(chk['program'])} served "
        f"tokens compared in {time.perf_counter() - t_ref:.3f} s")
    result["correct"], result["check"] = judge(chk.pop("program"), limits)
    if control:
        result["readings"] = {}
        for name, g in chk.items():
            ok, c = judge(g, limits)
            result["readings"][name] = {
                "correct": ok, **{k: v["value"] for k, v in c.items()}}
        log("[check] readings " + json.dumps(result["readings"]))
    result["check"] = result.pop("check")      # the last key of the line
    return result


def pick_sample(done: list, n: int, seed: int) -> list:
    """The finished request with the most served tokens, and up to n - 1
    others drawn from the seed among those that share its document (the
    reference then runs the document once for all of them)."""
    if not done:
        return []
    done = sorted(done, key=lambda lv: (-len(lv.req.out_tokens),
                                        lv.req.rid))
    rest = [lv for lv in done[1:] if lv.spec.doc == done[0].spec.doc]
    g = TR.rng(seed, "check")
    idx = g.permutation(len(rest))[:max(0, n - 1)].tolist()
    return [done[0]] + [rest[i] for i in sorted(idx)]


# statistics of the per-token gaps (reference logit std units) that a
# cell's limits can hold: limits["max"] names the ones compared
GAP_STATS = {
    "max_gap_std": lambda g: float(np.max(g)),
    "mean_gap_std": lambda g: float(np.mean(g)),
}


def judge(g, limits: dict) -> tuple[bool, dict]:
    """Whether the gaps ``g`` of the served tokens pass the cell's limits,
    and each number compared beside its limit."""
    check = {k: {"value": GAP_STATS[k](g) if len(g) else math.inf,
                 "limit": lim} for k, lim in limits["max"].items()}
    check["served_tokens_min"] = {"value": len(g),
                                  "limit": limits["min_served"]}
    ok = (all(c["value"] <= c["limit"] for k, c in check.items()
              if k != "served_tokens_min")
          and len(g) >= limits["min_served"])
    return ok, check


# the token a planted fault alters, and how (tests/test_faults.py plants
# the same alteration in the engine)
ALTERED_INDEX = 2


def altered(tok, vocab: int):
    return (tok + vocab // 2) % vocab


def check_sample(cfg: dict, key, sample: list, documents: list,
                 rows: int, n: int, max_out: int, vocab: int,
                 control: bool = False) -> dict:
    """Per served token of the sample, how far below the reference's best
    its reference logit lies (logit std units): ``program``. The reference
    runs the sample's shared document once and n sequences of ``rows`` rows
    each (the sample padded with repeats, whose gaps are dropped).

    With ``control``, the same gaps of what stands in the program's place
    at the same positions: ``control``, the token the reference one
    precision step lower (``reference.logits`` with ``low``) puts first;
    ``fault_state_unchanged``, the token the reference with its decode state
    left unchanged puts first; ``fault_token_altered``, the reference's own
    tokens with the third of each request altered."""
    import jax.numpy as jnp
    from perfbench import reference as R
    if not sample:
        return {"program": np.zeros((0,))}
    doc_i = sample[0].spec.doc
    doc = documents[doc_i] if doc_i is not None else np.zeros((0,), np.int32)
    seqs = np.zeros((n, rows), np.int32)
    toks = np.zeros((n, max_out), np.int32)
    starts = np.zeros((n,), np.int32)
    lens = []
    for i in range(n):
        lv = sample[i % len(sample)]
        own = np.asarray(lv.spec.prompt[len(doc):], np.int32)
        out = np.asarray(lv.req.out_tokens, np.int32)
        full = np.concatenate([own, out[:-1]])
        seqs[i, :len(full)] = full
        toks[i, :len(out)] = out
        starts[i] = len(own) - 1
        lens.append(len(out) if i < len(sample) else 0)
    ref = R.logits(cfg, key, doc, seqs, starts, max_out)
    keep = lambda g: np.concatenate(  # noqa: E731
        [np.asarray(g[i, :m]) for i, m in enumerate(lens)])
    out = {"program": keep(R.gaps(ref, jnp.asarray(toks)))}
    if control:
        best = jnp.argmax(ref, -1)
        out["fault_token_altered"] = keep(R.gaps(
            ref, best.at[:, ALTERED_INDEX].set(
                altered(best[:, ALTERED_INDEX], vocab))))
        for name, kw in (("control", {"low": True}),
                         ("fault_state_unchanged", {"stale": True})):
            other = R.logits(cfg, key, doc, seqs, starts, max_out, **kw)
            out[name] = keep(R.gaps(ref, jnp.argmax(other, -1)))
            del other
    return out
