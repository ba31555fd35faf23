"""Continuous-batching serving simulation -> BENCH_serving.json.

A seeded synthetic-arrival workload driven through the serving engine
(``repro.serving``): Poisson-ish arrivals (exponential inter-arrival gaps in
*virtual engine steps* — arrival times are generated host-side and passed
in; no wall-clock enters traced code, so a fixed ``--seed`` reproduces the
exact schedule and, under greedy decoding, the exact tokens run-to-run).

The sweep crosses request rate x prefix-sharing ratio. ``share_ratio`` is
the fraction of requests whose prompt begins with a workload-common prefix
(two full pages of it), so the allocator's refcounted prefix sharing can map
the same physical pages across concurrent requests; each cell is also run
with sharing disabled to report pages saved.

Emitted series per cell (the ``BENCH_serving.json`` schema — see README
"Serving engine"):
    throughput      decode tokens/s (wall) + tokens-per-engine-step
    latency         p50/p99 request latency and TTFT, in virtual steps
    pages           peak/capacity, utilization series, saved_by_sharing,
                    unshared_peak (same workload, sharing off), evictions

Two further sections compare this PR's perf levers against their twins on
identical workloads:

  * ``chunked_prefill`` — a mixed long+short arrival workload run through
    the engine TWICE (``prefill_chunk`` on vs monolithic admission).
    Latency is compared in deterministic WORK UNITS (tokens of prefill +
    decode compute processed between a request's submission and its first
    token — wall clock on a shared CI runner is noise, work units are not):
    p50/p99 TTFT overall and per class (short = interactive requests, the
    ones a monolithic long prefill makes wait), plus decode-stall tokens
    (prefill work done in steps with decodes in flight — the ITL-spike
    metric) per step max/p99/total. The ``delta`` block is the headline:
    chunked admission must cut the per-step decode stall and the short-class
    p99 TTFT.
  * ``fused_eos_gating`` — ``make_fused_decode(gate_finished=...)`` twins
    on an EOS-heavy batch: identical tokens, and the gated run's frozen
    ``seq_lens`` quantify the cache appends + KV blocks the split-KV early
    exit no longer touches for finished rows.
  * ``speculative`` — self-speculative (n-gram draft + q_len>1 verify)
    decoding twins on a greedy mixed random+repetitive workload:
    token-identical output, acceptance rate, committed tokens per
    slot-step (> 1.0 = real multi-token commits), and engine steps saved.
  * ``telemetry`` — the tiered shared-prefix workload with the span tracer
    and quant-health probe armed, run twice on the same seed: registry
    work-metric values for bench_gate pinning plus byte-identical
    trace/registry determinism booleans.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core.kvcache import page_aligned_capacity
from repro.launch import steps as ST
from repro.models import transformer as T
from repro.serving import (EngineConfig, FaultEvent, FaultPlan, Request,
                           ServingEngine)


def make_workload(seed: int, n_requests: int, rate: float, share_ratio: float,
                  prompt_lens: tuple[int, ...], gen_lens: tuple[int, ...],
                  page_size: int, vocab: int) -> list[Request]:
    """Seeded synthetic workload: exponential inter-arrival gaps at
    ``rate`` requests/step; ``share_ratio`` of prompts start with a common
    two-page prefix (the prefix the allocator can share)."""
    rng = np.random.default_rng(seed)
    shared_prefix = rng.integers(0, vocab, size=2 * page_size,
                                 dtype=np.int32)
    t = 0.0
    reqs = []
    for rid in range(n_requests):
        t += rng.exponential(1.0 / max(rate, 1e-9))
        S = int(rng.choice(prompt_lens))
        body = rng.integers(0, vocab, size=S, dtype=np.int32)
        if rng.random() < share_ratio:
            # clamp: prompts shorter than the prefix just share what fits
            n = min(S, len(shared_prefix))
            body[:n] = shared_prefix[:n]
        reqs.append(Request(rid=rid, prompt=body,
                            max_new=int(rng.choice(gen_lens)),
                            arrival=float(np.floor(t))))
    return reqs


def _pct(xs: list[int], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else -1.0


def run_cell(cfg, params, seed: int, n_requests: int, rate: float,
             share_ratio: float, max_batch: int, pool_pages: int,
             prompt_lens, gen_lens, prefix_sharing: bool = True) -> dict:
    span = page_aligned_capacity(max(prompt_lens) + max(gen_lens),
                                 cfg.page_size) // cfg.page_size
    reqs = make_workload(seed, n_requests, rate, share_ratio, prompt_lens,
                         gen_lens, cfg.page_size, cfg.vocab_size)
    engine = ServingEngine(cfg, params, EngineConfig(
        max_batch=max_batch, max_pages_per_seq=span, n_pages=pool_pages,
        prefix_sharing=prefix_sharing, seed=seed))
    results = engine.run(reqs)
    m = engine.metrics()
    done = [r for r in results if r.status == "done"]
    lat = [r.latency_steps for r in done]
    ttft = [r.ttft_steps for r in done]
    return {
        "rate_req_per_step": rate,
        "share_ratio": share_ratio,
        "prefix_sharing": prefix_sharing,
        "n_requests": n_requests,
        "completed": len(done),
        "evicted": m["requeues"],       # evictions are requeues now (no loss)
        "steps": m["steps"],
        "throughput": {
            "decode_tok_per_s": m["wall"]["decode_tok_per_s"],
            "decode_tokens": m["decode_tokens"],
            "tok_per_step": m["decode_tokens"] / max(m["steps"], 1),
        },
        "latency_steps": {"p50": _pct(lat, 50), "p99": _pct(lat, 99)},
        "ttft_steps": {"p50": _pct(ttft, 50), "p99": _pct(ttft, 99)},
        "pages": {
            **m["pages"],
            "mean_utilization": float(np.mean(m["utilization_series"]))
            if m["utilization_series"] else 0.0,
            "utilization_series": [round(u, 4)
                                   for u in m["utilization_series"]],
        },
    }


def _mixed_workload(seed: int, page: int, chunk: int, vocab: int,
                    n_short: int = 6) -> list[Request]:
    """Mixed long+short arrivals: two long prompts (several chunks each)
    with short interactive requests arriving at and just after them — the
    regime where a monolithic prefill stalls every in-flight decode."""
    rng = np.random.default_rng(seed)
    long_len, short_len = 6 * chunk, page
    reqs = []
    rid = 0
    for arrival, length in (
            [(0.0, long_len)]
            + [(float(i % 3), short_len) for i in range(n_short // 2)]
            + [(4.0, long_len)]
            + [(4.0 + i % 3, short_len) for i in range(n_short - n_short // 2)]):
        reqs.append(Request(
            rid=rid, prompt=rng.integers(0, vocab, size=length,
                                         dtype=np.int32),
            max_new=page // 2, arrival=arrival))
        rid += 1
    return reqs


def _ttft_stats(results, short_cutoff: int) -> dict:
    def pcts(xs):
        return {"p50": _pct(xs, 50), "p99": _pct(xs, 99)}
    works = [r.ttft_work for r in results if r.ttft_work >= 0]
    shorts = [r.ttft_work for r in results
              if r.ttft_work >= 0 and r.prompt_len <= short_cutoff]
    longs = [r.ttft_work for r in results
             if r.ttft_work >= 0 and r.prompt_len > short_cutoff]
    return {"all": pcts(works), "short": pcts(shorts), "long": pcts(longs)}


def run_chunked_twin(cfg, params, seed: int, chunk: int, budget: int,
                     max_batch: int = 4) -> dict:
    """The SAME mixed long+short workload through the engine twice:
    chunked admission vs the monolithic twin. Returns the comparison the
    ISSUE's acceptance criterion reads — work-unit TTFT + decode-stall
    deltas."""
    import dataclasses as _dc
    page = cfg.page_size
    n_requests = len(_mixed_workload(seed, page, chunk, cfg.vocab_size))
    span = page_aligned_capacity(6 * chunk + page // 2, page) // page
    runs = {}
    for mode, pchunk in (("monolithic", 0), ("chunked", chunk)):
        engine = ServingEngine(
            _dc.replace(cfg, prefill_chunk=pchunk), params,
            EngineConfig(max_batch=max_batch, max_pages_per_seq=span,
                         prefill_budget=budget if pchunk else 0, seed=seed))
        # requests carry mutable run state — each twin gets a fresh workload
        # (same seed -> identical prompts/arrivals)
        results = engine.run(_mixed_workload(seed, page, chunk,
                                             cfg.vocab_size))
        m = engine.metrics()
        stalls = m["work"]["stall_tokens_series"]
        runs[mode] = {
            "completed": len(results),
            "steps": m["steps"],
            "prefill_traces": m["prefill"]["traces"],
            "ttft_work": _ttft_stats(results, short_cutoff=2 * chunk),
            "ttft_steps_p99": _pct([r.ttft_steps for r in results
                                    if r.ttft_steps >= 0], 99),
            "stall": {
                "tokens_total": int(sum(stalls)),
                "tokens_per_step_max": int(max(stalls, default=0)),
                "tokens_per_step_p99": _pct([s for s in stalls], 99),
                "seconds": m["wall"]["stall_seconds"],
            },
            "wall": {
                "ttft_s_p99": _pct([r.ttft_s for r in results], 99),
                "decode_tok_per_s": m["wall"]["decode_tok_per_s"],
            },
            "fetch_work": m["fetch_work"],
            "tokens": {r.rid: r.tokens for r in results},
        }
    # capacity-independence twin: the SAME chunked workload on a pool with
    # twice the page-table span. The bounded prefix fetch's page traffic
    # tracks chunk_start, so pages_fetched_bounded must NOT move when the
    # capacity doubles (a full-span fetch would double with it).
    engine2x = ServingEngine(
        _dc.replace(cfg, prefill_chunk=chunk), params,
        EngineConfig(max_batch=max_batch, max_pages_per_seq=2 * span,
                     prefill_budget=budget, seed=seed))
    engine2x.run(_mixed_workload(seed, page, chunk, cfg.vocab_size))
    fetch_2x = engine2x.metrics()["fetch_work"]
    mono, chk = runs["monolithic"], runs["chunked"]
    tokens_equal = mono.pop("tokens") == chk.pop("tokens")
    fw = chk["fetch_work"]
    fetch_bound = {
        "pages_fetched_bounded": fw["pages_fetched_bounded"],
        "pages_fetched_full": fw["pages_fetched_full"],
        "fetch_savings": fw["fetch_savings"],
        "bounded_at_2x_capacity": fetch_2x["pages_fetched_bounded"],
        "full_at_2x_capacity": fetch_2x["pages_fetched_full"],
        "capacity_independent": (fw["pages_fetched_bounded"]
                                 == fetch_2x["pages_fetched_bounded"]),
    }
    return {
        "prefill_chunk": chunk,
        "prefill_budget": budget,
        "n_requests": n_requests,
        "tokens_equal": tokens_equal,
        "monolithic": mono,
        "chunked": chk,
        "fetch_bound": fetch_bound,
        # the acceptance headline: positive = chunked is better
        "delta": {
            "stall_tokens_per_step_max":
                mono["stall"]["tokens_per_step_max"]
                - chk["stall"]["tokens_per_step_max"],
            "stall_tokens_per_step_p99":
                mono["stall"]["tokens_per_step_p99"]
                - chk["stall"]["tokens_per_step_p99"],
            "ttft_work_p99_short":
                mono["ttft_work"]["short"]["p99"]
                - chk["ttft_work"]["short"]["p99"],
            "ttft_s_p99": mono["wall"]["ttft_s_p99"]
                - chk["wall"]["ttft_s_p99"],
        },
    }


def run_fused_gating_twin(cfg, params, seed: int, gen: int = 12) -> dict:
    """``make_fused_decode`` finished-row gating vs the always-append twin
    on an EOS-heavy batch: tokens must be identical; the gated run's frozen
    ``seq_lens`` measure the appends (and early-exit KV blocks) saved."""
    B, S = 4, 2 * cfg.page_size
    key = jax.random.PRNGKey(seed)
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size, jnp.int32)
    max_len = page_aligned_capacity(S + gen, cfg.page_size)
    prefill = jax.jit(ST.make_prefill_step(cfg))
    state0 = T.init_decode_state(cfg, B, max_len)
    logits, _ = prefill(params, prompts, state0)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    eos = int(np.asarray(first)[0])      # row 0 finishes immediately
    out = {}
    for gate in (True, False):
        state = T.init_decode_state(cfg, B, max_len)
        _, state = prefill(params, prompts, state)
        fused = jax.jit(ST.make_fused_decode(cfg, gen - 1, eos_id=eos,
                                             gate_finished=gate),
                        donate_argnums=(2,))
        args = (params, first, state, jnp.full((B,), S, jnp.int32))
        compiled = fused.lower(*args).compile()
        t0 = time.time()
        toks, state_out, ok = compiled(*args)
        jax.block_until_ready(toks)
        dt = time.time() - t0
        lens = np.asarray(state_out["scanned"][0].seq_lens).reshape(-1, B)[0] \
            if state_out.get("scanned") is not None \
            else np.asarray(state_out["tail"][0].seq_lens)
        out[gate] = {"tokens": np.asarray(toks).tolist(),
                     "seconds": dt,
                     "final_seq_lens": [int(x) for x in lens],
                     "finite": bool(ok)}
    gated, ungated = out[True], out[False]
    return {
        "eos_id": eos,
        "gen_steps": gen,
        "tokens_equal": gated["tokens"] == ungated["tokens"],
        "gated": {k: v for k, v in gated.items() if k != "tokens"},
        "ungated": {k: v for k, v in ungated.items() if k != "tokens"},
        # appends (== split-KV blocks the early exit keeps streaming)
        # skipped for finished rows by the gate:
        "appends_saved": int(sum(ungated["final_seq_lens"])
                             - sum(gated["final_seq_lens"])),
    }


def _prefix_workload(seed: int, page: int, vocab: int, n_requests: int,
                     gap: int, shared_pages: int = 3,
                     suffix: int | None = None) -> list[Request]:
    """'Shared system prompt, long-tail user turns': every prompt starts
    with the SAME ``shared_pages`` full pages (the system prompt) followed
    by a unique per-request suffix; arrivals are spaced ``gap`` steps apart
    so requests never overlap live — any page reuse must come from the
    refcount-0 retained cache, not from live refcount sharing."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, size=shared_pages * page, dtype=np.int32)
    if suffix is None:
        suffix = page + page // 2
    reqs = []
    for rid in range(n_requests):
        body = np.concatenate([
            shared, rng.integers(0, vocab, size=suffix, dtype=np.int32)])
        reqs.append(Request(rid=rid, prompt=body, max_new=page // 2,
                            arrival=float(rid * gap)))
    return reqs


def run_prefix_cache_workload(cfg, params, seed: int, n_requests: int = 4,
                              shared_pages: int = 3) -> dict:
    """The radix-cache headline: the SAME shared-system-prompt workload
    through the engine three times — cache off (cold), device-only retained
    cache, and a tiny device budget backed by the host tier (forcing
    offload + restore). Chunked prefill, so a cache hit skips whole chunks:
    the acceptance criterion is hit TTFT (work units, requests 2..N) below
    the cold run's, with token-identical output across all three runs."""
    import dataclasses as _dc
    page = cfg.page_size
    suffix = page + page // 2
    S = shared_pages * page + suffix
    gen = page // 2
    # arrivals spaced past the worst-case cold lifetime of one request:
    # every prefill chunk + every decode step + admission slack
    gap = S // page + 2 + gen + 8
    span = page_aligned_capacity(S + gen, page) // page
    pool_pages = 2 * span + 1
    ccfg = _dc.replace(cfg, prefill_chunk=page)
    modes = {
        "cold": dict(prefix_cache_pages=0, host_tier_pages=0),
        "cached": dict(prefix_cache_pages=pool_pages - 1, host_tier_pages=0),
        # device budget below the shared-prefix size: retained pages spill
        # to host, so later hits exercise the restore path too
        "tiered": dict(prefix_cache_pages=max(shared_pages - 1, 1),
                       host_tier_pages=pool_pages),
    }
    runs = {}
    for mode, kw in modes.items():
        engine = ServingEngine(ccfg, params, EngineConfig(
            max_batch=2, max_pages_per_seq=span, n_pages=pool_pages,
            prefill_budget=2 * page, seed=seed, **kw))
        results = engine.run(_prefix_workload(seed, page, cfg.vocab_size,
                                              n_requests, gap, shared_pages,
                                              suffix))
        m = engine.metrics()
        pc = m["prefix_cache"]
        hits = [r.ttft_work for r in results if r.rid > 0 and r.ttft_work >= 0]
        runs[mode] = {
            "completed": sum(r.status == "done" for r in results),
            # rid 0 warms the cache; rids 1..N-1 are the hit candidates
            "ttft_work_first": next((r.ttft_work for r in results
                                     if r.rid == 0), -1),
            "ttft_work_rest_mean": float(np.mean(hits)) if hits else -1.0,
            "ttft_work_rest_max": max(hits, default=-1),
            "prefill_skipped_tokens": pc["prefill_skipped_tokens"],
            "pages_reused_cached": pc["reused_cached"],
            "pages_restored_host": pc["restored_host"],
            "host_offloads": pc["offloads"],
            "hbm_peak_resident_pages": pc["peak_resident"],
            "tokens": {r.rid: r.tokens for r in results},
        }
    cold, cached, tiered = runs["cold"], runs["cached"], runs["tiered"]
    toks = cold.pop("tokens")
    tokens_equal = toks == cached.pop("tokens") \
        and toks == tiered.pop("tokens")
    return {
        "n_requests": n_requests,
        "shared_prefix_pages": shared_pages,
        "prompt_len": S,
        "pool_pages": pool_pages,
        # token-identity across cold / cached / tiered runs — cache hits
        # must not change a single sampled token
        "tokens_equal": tokens_equal,
        "cold": cold,
        "cached": cached,
        "tiered": tiered,
        # acceptance headline: positive = cache hits beat cold TTFT
        "delta": {
            "hit_ttft_work_mean": cold["ttft_work_rest_mean"]
                - cached["ttft_work_rest_mean"],
            "tiered_hit_ttft_work_mean": cold["ttft_work_rest_mean"]
                - tiered["ttft_work_rest_mean"],
        },
    }


_TELEMETRY_GATED = (
    # single-value work metrics bench_gate pins (deterministic for a seed)
    "snapmla_cache_reused_pages",
    "snapmla_tier_offload_pages",
    "snapmla_tier_restore_pages",
    "snapmla_fetch_pages_bounded_total",
    "snapmla_fetch_pages_full_total",
    "snapmla_engine_prefill_skipped_tokens_total",
    "snapmla_engine_decode_tokens_total",
)


def run_telemetry_probe(cfg, params, seed: int, n_requests: int = 4,
                        shared_pages: int = 3) -> dict:
    """Observability headline: the tiered shared-prefix chunked workload
    with EVERY probe armed (span tracer, quant-health sampler) run twice on
    the same seed. Reports the registry's single-value work metrics for
    bench_gate pinning plus the determinism cross-checks — byte-identical
    Chrome trace and registry snapshot across the twin runs, and a
    validated trace (one terminal instant per request track)."""
    import dataclasses as _dc
    from repro.obs import SpanTracer, validate_chrome_trace
    page = cfg.page_size
    suffix = page + page // 2
    S = shared_pages * page + suffix
    gen = page // 2
    gap = S // page + 2 + gen + 8
    span = page_aligned_capacity(S + gen, page) // page
    pool_pages = 2 * span + 1
    ccfg = _dc.replace(cfg, prefill_chunk=page)

    def one_run():
        tracer = SpanTracer()
        engine = ServingEngine(ccfg, params, EngineConfig(
            max_batch=2, max_pages_per_seq=span, n_pages=pool_pages,
            prefill_budget=2 * page, seed=seed,
            prefix_cache_pages=max(shared_pages - 1, 1),
            host_tier_pages=pool_pages, quant_health_every=4),
            tracer=tracer)
        engine.run(_prefix_workload(seed, page, cfg.vocab_size, n_requests,
                                    gap, shared_pages, suffix))
        return engine, tracer

    engine, tracer = one_run()
    engine2, tracer2 = one_run()
    payload = tracer.chrome_payload()
    stats = validate_chrome_trace(payload, expect_requests=n_requests)
    dump = json.dumps(payload, sort_keys=True)
    work = engine.telemetry()["work"]
    metrics = {}
    for name in _TELEMETRY_GATED:
        vals = work[name]["values"]
        metrics[name] = vals[""]
    faults = work["snapmla_engine_faults_total"]["values"]
    probe = engine.quant_probe
    return {
        "n_requests": n_requests,
        "metrics": metrics,
        "faults_total": int(sum(faults.values())),
        "trace": {
            "events": stats["events"],
            "spans": stats["spans"],
            "request_tracks": stats["requests"],
            "deterministic": dump == json.dumps(tracer2.chrome_payload(),
                                                sort_keys=True),
        },
        "registry_deterministic": (engine.telemetry()["work"]
                                   == engine2.telemetry()["work"]),
        "quant_health": {
            "samples": len(probe.samples) if probe else 0,
            "last_clip_rate_max": (probe.samples[-1]["clip_rate_max"]
                                   if probe and probe.samples else -1.0),
        },
    }


def run_speculative_twin(cfg, params, seed: int, spec_draft: int = 3,
                         n_random: int = 2, n_repeat: int = 2,
                         max_batch: int = 2) -> dict:
    """Self-speculative decoding twin: the SAME greedy mixed
    random+repetitive workload through the engine twice
    (``spec_draft_len`` 0 vs N). Speculation is rollback-by-rewind over
    the existing verify kernel, so it must be a PURE throughput
    optimization — per-request token dicts identical — while the
    repetitive traffic (the regime n-gram drafting wins on) pushes
    committed tokens per slot-step above the sequential-decode ceiling
    of exactly 1.0. Engine steps saved is the wall-free headline: the
    same tokens in fewer verify dispatches."""
    rng = np.random.default_rng(seed)
    S, gen = 24, 16
    span = page_aligned_capacity(S + gen, cfg.page_size) // cfg.page_size
    prompts = [rng.integers(0, cfg.vocab_size, size=S, dtype=np.int32)
               for _ in range(n_random)]
    patterns = ((5, 9, 2, 7), (13, 4, 6), (3, 8))
    prompts += [np.asarray((list(patterns[j % len(patterns)]) * S)[:S],
                           np.int32) for j in range(n_repeat)]

    def run(draft):
        engine = ServingEngine(cfg, params, EngineConfig(
            max_batch=max_batch, max_pages_per_seq=span,
            spec_draft_len=draft, seed=seed))
        results = engine.run([Request(rid=i, prompt=p, max_new=gen,
                                      arrival=0.0)
                              for i, p in enumerate(prompts)])
        m = engine.metrics()
        assert m["pages"]["free"] == m["pages"]["capacity"], "leaked pages"
        return {r.rid: r.tokens for r in results}, m

    base_toks, m0 = run(0)
    spec_toks, m1 = run(spec_draft)
    sp = m1["speculative"]
    return {
        "spec_draft_len": spec_draft,
        "n_requests": len(prompts),
        "gen_len": gen,
        # token-identity is the whole contract: a draft that survives an
        # incorrect verify would show up here before anywhere else
        "tokens_equal": base_toks == spec_toks,
        "baseline": {
            "steps": m0["steps"],
            "decode_tokens": m0["decode_tokens"],
        },
        "spec": {
            "steps": m1["steps"],
            "decode_tokens": m1["decode_tokens"],
            "verify_steps": sp["verify_steps"],
            "drafted_tokens": sp["drafted_tokens"],
            "accepted_tokens": sp["accepted_tokens"],
            "accept_rate": sp["accept_rate"],
            "accepted_tokens_per_step": sp["accepted_tokens_per_step"],
        },
        # positive = the speculative run drained the same workload in
        # fewer engine steps (virtual, seeded — deterministic)
        "delta": {"steps_saved": m0["steps"] - m1["steps"]},
    }


def run_fault_sweep(cfg, params, seed: int, n_requests: int = 8,
                    max_batch: int = 4) -> dict:
    """Survival metrics under deterministic fault injection: the SAME
    seeded workload run fault-free and then under each FaultPlan scenario.
    Per scenario: completed / failed-by-reason / rejected counts, recovery
    metrics (quarantines recovered via the jnp_ref retry, backend-fault
    fallback steps), whether every page drained, and — the isolation
    headline — whether every surviving request's tokens are identical to
    its fault-free twin."""
    page = cfg.page_size
    prompt_lens = (2 * page, 3 * page)
    gen_lens = (page // 2, page)
    span = page_aligned_capacity(max(prompt_lens) + max(gen_lens), page) \
        // page
    pool_pages = max_batch * span + 1

    def run_with(plan, max_queue=0, deadline=None):
        reqs = make_workload(seed, n_requests, 1.0, 0.5, prompt_lens,
                             gen_lens, page, cfg.vocab_size)
        if deadline is not None:
            for r in reqs:
                r.ttft_deadline = deadline
        engine = ServingEngine(cfg, params, EngineConfig(
            max_batch=max_batch, max_pages_per_seq=span, n_pages=pool_pages,
            max_queue=max_queue, seed=seed), fault_plan=plan)
        results = engine.run(reqs)
        return results, engine.metrics()

    clean, _ = run_with(None)
    clean_toks = {r.rid: r.tokens for r in clean}
    scenarios = {
        "nan_recovered": FaultPlan([FaultEvent("nan_logits", 4, slot=1)]),
        "nan_sticky": FaultPlan([FaultEvent("nan_logits", 4, slot=1,
                                            sticky=True)]),
        "backend_raise": FaultPlan([FaultEvent("backend_raise", 3)]),
        "alloc_storm": FaultPlan([FaultEvent("alloc_fail", 2, count=3)]),
        "random_storm": FaultPlan.random(seed, n_steps=16, n_faults=4,
                                         max_batch=max_batch,
                                         sticky_ratio=0.5),
    }
    out = {"n_requests": n_requests,
           "clean_completed": sum(r.status == "done" for r in clean)}
    for name, plan in scenarios.items():
        kw = {"max_queue": 2, "deadline": 64} if name == "random_storm" \
            else {}
        results, m = run_with(plan, **kw)
        f = m["faults"]
        done = [r for r in results if r.status == "done"]
        # survivors must be untouched by the injected faults (and a
        # recovered quarantine reproduces its fault-free token, because the
        # jnp_ref retry recomputes the same position on the same cache)
        survivors_identical = all(r.tokens == clean_toks[r.rid]
                                  for r in done)
        by_reason: dict[str, int] = {}
        for r in results:
            if r.status != "done":
                by_reason[r.fail_reason] = by_reason.get(r.fail_reason, 0) + 1
        out[name] = {
            "injected": len(f["injected"]),
            "completed": len(done),
            "failed_by_reason": by_reason,
            "rejected": f["rejected"],
            "quarantined": f["nonfinite_rows"],
            "recovered_ref": f["recovered_ref"],
            "backend_fallback_steps": f["ref_fallback_steps"],
            "deadline_cancelled": f["deadline_cancelled"],
            "requeues": m["requeues"],
            "pages_drained": m["pages"]["free"] == m["pages"]["capacity"],
            "survivors_token_identical": survivors_identical,
        }
    return out


def write_bench_serving(path: str = "BENCH_serving.json", *, seed: int = 0,
                        arch: str = "mla-7b", n_requests: int = 8,
                        max_batch: int = 4,
                        rates=(0.25, 1.0), share_ratios=(0.0, 0.75)) -> dict:
    cfg = get_smoke_config(arch)
    params = T.init_model(jax.random.PRNGKey(seed), cfg)
    page = cfg.page_size
    prompt_lens = (2 * page + page // 2, 3 * page)   # mixed, prefix-shareable
    gen_lens = (page // 2, page)
    span = page_aligned_capacity(max(prompt_lens) + max(gen_lens), page) \
        // page
    pool_pages = max_batch * span + 1
    cells = []
    for rate in rates:
        for share in share_ratios:
            cell = run_cell(cfg, params, seed, n_requests, rate, share,
                            max_batch, pool_pages, prompt_lens, gen_lens)
            # sharing-off twin of the same workload: the pages the free-list
            # allocator saved are the headline of the prefix-sharing
            # feature. At share_ratio 0 sharing cannot save anything, so
            # the twin run (a full extra engine + compile) is skipped and
            # the cell is its own baseline.
            off = cell if share == 0.0 else run_cell(
                cfg, params, seed, n_requests, rate, share, max_batch,
                pool_pages, prompt_lens, gen_lens, prefix_sharing=False)
            cell["pages"]["unshared_peak_in_use"] = \
                off["pages"]["peak_in_use"]
            cell["pages"]["unshared_total_allocs"] = \
                off["pages"]["total_allocs"]
            cells.append(cell)
    payload = {
        "bench": "serving_sim",
        "arch": cfg.name,
        "seed": seed,
        "page_size": page,
        "max_batch": max_batch,
        "pool_pages": pool_pages,
        "prompt_lens": list(prompt_lens),
        "gen_lens": list(gen_lens),
        "cells": cells,
        # budget = 3 chunks/step: the long request plus two interactive
        # requests advance every step, which is what moves BOTH headline
        # deltas (per-step decode stall AND short-class p99 TTFT) positive
        "chunked_prefill": run_chunked_twin(cfg, params, seed,
                                            chunk=page, budget=3 * page),
        "fused_eos_gating": run_fused_gating_twin(cfg, params, seed),
        # shared-system-prompt long-tail workload: cold vs retained-cache vs
        # host-tiered runs of identical requests — hit TTFT, pages
        # recomputed-vs-restored, HBM high-water
        "prefix_cache": run_prefix_cache_workload(cfg, params, seed),
        # all probes armed on the tiered shared-prefix workload: registry
        # work metrics for gating + trace/registry determinism cross-checks
        "telemetry": run_telemetry_probe(cfg, params, seed),
        # self-speculative decoding twin: greedy token-identity plus the
        # accepted-tokens-per-slot-step headline (> 1.0 = real multi-token
        # commits through the q_len>1 verify kernel)
        "speculative": run_speculative_twin(cfg, params, seed),
        "fault_sweep": run_fault_sweep(cfg, params, seed,
                                       n_requests=n_requests,
                                       max_batch=max_batch),
    }
    pathlib.Path(path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="mla-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()
    payload = write_bench_serving(args.out, seed=args.seed, arch=args.arch,
                                  n_requests=args.requests,
                                  max_batch=args.max_batch)
    for c in payload["cells"]:
        saved = c["pages"]["saved_by_sharing"]
        print(f"[serving_sim] rate={c['rate_req_per_step']:<5} "
              f"share={c['share_ratio']:<5} "
              f"tok/s={c['throughput']['decode_tok_per_s']:8.1f} "
              f"p50={c['latency_steps']['p50']:5.1f} "
              f"p99={c['latency_steps']['p99']:5.1f} "
              f"peak_pages={c['pages']['peak_in_use']}"
              f"/{c['pages']['unshared_peak_in_use']} (shared/unshared) "
              f"saved={saved} evicted={c['evicted']}")
    cp = payload["chunked_prefill"]
    print(f"[serving_sim] chunked twin: stall/step max "
          f"{cp['monolithic']['stall']['tokens_per_step_max']} -> "
          f"{cp['chunked']['stall']['tokens_per_step_max']} tokens, "
          f"short-class p99 TTFT "
          f"{cp['monolithic']['ttft_work']['short']['p99']:.0f} -> "
          f"{cp['chunked']['ttft_work']['short']['p99']:.0f} work units, "
          f"tokens_equal={cp['tokens_equal']}")
    fg = payload["fused_eos_gating"]
    print(f"[serving_sim] fused EOS gating: appends saved "
          f"{fg['appends_saved']}, tokens_equal={fg['tokens_equal']}")
    pcw = payload["prefix_cache"]
    print(f"[serving_sim] prefix cache: hit TTFT "
          f"{pcw['cold']['ttft_work_rest_mean']:.0f} (cold) -> "
          f"{pcw['cached']['ttft_work_rest_mean']:.0f} (cached) / "
          f"{pcw['tiered']['ttft_work_rest_mean']:.0f} (tiered) work units, "
          f"skipped {pcw['cached']['prefill_skipped_tokens']} tokens, "
          f"restored {pcw['tiered']['pages_restored_host']} pages from host, "
          f"HBM peak {pcw['cached']['hbm_peak_resident_pages']} pages, "
          f"tokens_equal={pcw['tokens_equal']}")
    tel = payload["telemetry"]
    print(f"[serving_sim] telemetry: trace {tel['trace']['events']} events/"
          f"{tel['trace']['spans']} spans over "
          f"{tel['trace']['request_tracks']} tracks, "
          f"trace_deterministic={tel['trace']['deterministic']} "
          f"registry_deterministic={tel['registry_deterministic']} "
          f"reused_pages={tel['metrics']['snapmla_cache_reused_pages']} "
          f"tier_restore={tel['metrics']['snapmla_tier_restore_pages']} "
          f"quant_samples={tel['quant_health']['samples']}")
    sv = payload["speculative"]
    print(f"[serving_sim] speculative twin: draft={sv['spec_draft_len']} "
          f"accept_rate={sv['spec']['accept_rate']:.3f} "
          f"tokens/slot-step={sv['spec']['accepted_tokens_per_step']:.3f} "
          f"steps {sv['baseline']['steps']} -> {sv['spec']['steps']} "
          f"(saved {sv['delta']['steps_saved']}), "
          f"tokens_equal={sv['tokens_equal']}")
    fs = payload["fault_sweep"]
    for name in ("nan_recovered", "nan_sticky", "backend_raise",
                 "alloc_storm", "random_storm"):
        s = fs[name]
        print(f"[serving_sim] fault {name:<14} completed="
              f"{s['completed']}/{fs['n_requests']} "
              f"recovered={s['recovered_ref']} "
              f"failed={s['failed_by_reason']} rejected={s['rejected']} "
              f"drained={s['pages_drained']} "
              f"survivors_identical={s['survivors_token_identical']}")
    print(f"[serving_sim] wrote {args.out} ({len(payload['cells'])} cells)")


if __name__ == "__main__":
    main()
