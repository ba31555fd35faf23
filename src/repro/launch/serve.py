"""Serving launcher: batched prefill + decode with the SnapMLA FP8 KV cache.

CPU-scale usage (real generation on the host mesh, greedy sampling):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch mla-7b --smoke --batch 4 --prompt-len 32 --gen 16 --fmt fp8_e4m3

This is deliverable (b)'s end-to-end serving driver: it exercises prefill
(bulk RoPE-aware per-token quantization into the cache), then the quantized
decode pipeline per step, and reports decode throughput + agreement with the
BF16 baseline.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.kvcache import page_aligned_capacity
from repro.launch import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.runtime.platform import configure_compile_cache


def _check_finite(ok, where: str) -> None:
    """Loud NaN gate: serving must never emit non-finite logits — a NaN here
    means the quantized decode pipeline (or a kernel change behind it) broke,
    so fail the process rather than generate garbage tokens. ``ok`` is either
    raw logits or an already-reduced boolean (the fused scan's every-step
    flag); both generation paths cover every decode step."""
    if not bool(jnp.all(jnp.isfinite(ok) if ok.ndim else ok)):
        raise SystemExit(f"[serve] FATAL: non-finite logits at {where}")


def _decode_capacity(cfg, prompt_len: int, gen_steps: int) -> int:
    """Exact page-aligned cache capacity for prompt + generation.

    Prefill writes ``prompt_len`` entries and each decode step appends one;
    the last decode step (gen_steps-1 appends after the prefill token) needs
    ``prompt_len + gen_steps - 1`` slots, so ``prompt_len + gen_steps``
    rounded to the page is always enough — the former
    ``S + gen + page_size`` sizing over-allocated a whole page whenever the
    sum was already aligned."""
    return page_aligned_capacity(prompt_len + gen_steps, cfg.page_size)


def generate(cfg, params, prompts: jax.Array, gen_steps: int, mesh=None,
             aux_embed=None, temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0, eos_id: int | None = None, seed: int = 0):
    """prompts [B, S] -> (generated tokens [B, gen_steps], decode tok/s).

    Per-step decode loop. ``temperature``/``top_k``/``top_p`` switch greedy
    argmax to sampling (one fold_in per step of a single PRNG key, nucleus
    truncation after top-k); ``eos_id`` stops
    the loop early once EVERY sequence has emitted it (finished sequences
    are padded with ``eos_id``). Note the early-stop check is a per-step
    host sync — the price of actually ending the Python loop; the fused
    path handles EOS sync-free inside the scan."""
    mesh = mesh or make_host_mesh(1)
    B, S = prompts.shape
    max_len = _decode_capacity(cfg, S, gen_steps)
    prefill_fn = jax.jit(ST.make_prefill_step(cfg))
    decode_fn = jax.jit(ST.make_decode_step(cfg))
    key = jax.random.PRNGKey(seed)

    def pick(logits, i):
        # greedy (temperature <= 0) ignores the key inside sample_logits
        return ST.sample_logits(logits, jax.random.fold_in(key, i),
                                temperature, top_k, top_p)

    state = T.init_decode_state(cfg, B, max_len)
    logits, state = prefill_fn(params, prompts, state, *(
        (aux_embed,) if aux_embed is not None else ()))
    _check_finite(logits, "prefill")
    tok = pick(logits, 0)
    done = (tok == eos_id) if eos_id is not None \
        else jnp.zeros((B,), bool)

    outs = [tok]
    if gen_steps <= 1:
        return jnp.stack(outs, axis=1)[:, :gen_steps], 0.0
    # warm up decode compile before timing
    pos = jnp.full((B,), S, jnp.int32)
    logits, state = decode_fn(params, tok, state, pos)
    # every-step NaN gate, accumulated on device (no per-step host sync
    # unless EOS early stop is requested)
    ok = jnp.all(jnp.isfinite(logits))
    tok, done = ST.apply_eos(pick(logits, 1), done, eos_id)
    outs.append(tok)
    jax.block_until_ready(tok)

    steps_run = 0
    t0 = time.time()
    for i in range(1, gen_steps - 1):
        if eos_id is not None and bool(jnp.all(done)):
            break               # EOS early stop: every sequence finished
        pos = jnp.full((B,), S + i, jnp.int32)
        logits, state = decode_fn(params, tok, state, pos)
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(logits)))
        tok, done = ST.apply_eos(pick(logits, i + 1), done, eos_id)
        outs.append(tok)
        steps_run += 1
    jax.block_until_ready(tok)
    dt = time.time() - t0
    _check_finite(ok, "decode (any step)")
    while len(outs) < gen_steps:    # EOS-stopped early: pad to [B, gen_steps]
        outs.append(jnp.full((B,), eos_id, jnp.int32))
    # 0.0, not an absurd number, when EOS ended generation before the loop
    toks_per_s = B * steps_run / max(dt, 1e-9) if steps_run else 0.0
    return jnp.stack(outs, axis=1), toks_per_s


def generate_fused(cfg, params, prompts: jax.Array, gen_steps: int, mesh=None,
                   aux_embed=None, temperature: float = 0.0, top_k: int = 0,
                   top_p: float = 0.0, eos_id: int | None = None,
                   seed: int = 0):
    """Scan-based generation: prefill + ONE fused decode dispatch.

    Token-exact with ``generate`` under greedy decoding (same decode_step
    inside a lax.scan) but the whole multi-token decode is a single compiled
    program — no per-step dispatch/host round-trip — with the decode state
    (quantized KV caches) donated so XLA updates the cache buffers in place.
    ``temperature``/``top_k``/``top_p`` sample inside the scan (PRNG key
    threaded through the carry); ``eos_id`` pins finished sequences to
    ``eos_id``.

    Returns (generated tokens [B, gen_steps], decode tok/s).
    """
    mesh = mesh or make_host_mesh(1)
    B, S = prompts.shape
    max_len = _decode_capacity(cfg, S, gen_steps)
    sampled = temperature > 0.0
    key = jax.random.PRNGKey(seed)
    prefill_fn = jax.jit(ST.make_prefill_step(cfg))
    fused_fn = jax.jit(
        ST.make_fused_decode(cfg, max(gen_steps - 1, 0),
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, eos_id=eos_id),
        donate_argnums=(2,))

    state = T.init_decode_state(cfg, B, max_len)
    logits, state = prefill_fn(params, prompts, state, *(
        (aux_embed,) if aux_embed is not None else ()))
    _check_finite(logits, "prefill")
    tok = ST.sample_logits(logits, jax.random.fold_in(key, 0),
                           temperature, top_k, top_p)
    if gen_steps <= 1:
        return tok[:, None][:, :gen_steps], 0.0

    start_pos = jnp.full((B,), S, jnp.int32)
    args = (params, tok, state, start_pos) + (
        (jax.random.fold_in(key, 1),) if sampled else ())
    # AOT-compile before timing (donation happens at execution, not lowering)
    compiled = fused_fn.lower(*args).compile()
    jax.block_until_ready((tok, state))
    t0 = time.time()
    toks, _state, ok = compiled(*args)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    _check_finite(ok, "fused decode (any step)")
    toks_per_s = B * (gen_steps - 1) / max(dt, 1e-9)
    return jnp.concatenate([tok[:, None], toks], axis=1), toks_per_s


def _engine_prompts(cfg, key, args) -> list[np.ndarray]:
    """Per-request prompts for ``serve --engine``: ``--prompt-lens`` (comma
    list, cycled over ``--batch`` requests) yields a MIXED long+short
    workload — the regime chunked prefill exists for; otherwise every
    request gets a ``--prompt-len`` prompt. ``--shared-prefix N`` makes the
    first N tokens identical across requests (the shared-system-prompt
    traffic shape the radix prefix cache exists for)."""
    if args.prompt_lens:
        lens = [int(s) for s in args.prompt_lens.split(",")]
        lens = [lens[i % len(lens)] for i in range(args.batch)]
    else:
        lens = [args.prompt_len] * args.batch
    shared = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 2**31 - 1), (max(args.shared_prefix, 0),), 0,
        cfg.vocab_size, jnp.int32))
    prompts = []
    for i, n in enumerate(lens):
        p = np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (n,), 0, cfg.vocab_size, jnp.int32))
        k = min(len(shared), n)
        if k:
            p = p.copy()
            p[:k] = shared[:k]
        prompts.append(p)
    return prompts


def _make_logger(log_json: bool):
    """Engine-mode event logging: the default is the human-readable
    ``[serve]`` lines; ``--log-json`` swaps every one for a single-line JSON
    object (``{"event": ..., ...}``) a log pipeline can parse without
    regexes. ``text`` is the legacy rendering, ``fields`` the structured
    payload."""
    def log(event: str, text: str, **fields) -> None:
        if log_json:
            print(json.dumps({"event": event, **fields}, sort_keys=True,
                             default=float))
        else:
            print(text)
    return log


def run_engine(cfg, params, args):
    """``serve --engine``: the continuous-batching engine over the shared
    paged pool, with the static-batch path as the greedy parity oracle
    (``_check_engine_parity``; skipped for sampled runs and runs that
    requeued). Arrivals are staggered every ``--arrival-gap`` engine steps so
    the run exercises admission/retirement churn; ``--prefill-chunk``
    switches admission to budgeted chunked prefill. Exits non-zero on token
    mismatch (greedy), leaked pages or a prefill compile count over the
    bucket bound, so CI can gate on it.

    Fault drills: ``--inject kind:step[:slot][:sticky]`` threads a
    deterministic ``FaultPlan`` through the engine (NaN quarantine + jnp_ref
    retry, forced pool exhaustion, backend raise, preemption);
    ``--restartable`` wraps the run in ``run_with_restarts`` + a
    ``PreemptionHandler`` with periodic snapshots to ``--ckpt-dir``, so an
    (injected or real SIGTERM) preemption restarts and restores from the
    latest checkpoint — CI gates that the survivors complete, match the
    greedy oracle, and drain every page. Returns (engine, results)."""
    from repro.checkpoint import checkpoint as CK
    from repro.obs import SpanTracer, validate_chrome_trace
    from repro.runtime.fault_tolerance import (PreemptionHandler,
                                               RestartPolicy,
                                               run_with_restarts)
    from repro.serving import (EngineConfig, FaultPlan, Request,
                               ServingEngine)

    log = _make_logger(args.log_json)
    tracer = SpanTracer(clock=args.trace_clock) if args.trace_out else None
    key = jax.random.PRNGKey(args.seed)
    prompts = _engine_prompts(cfg, key, args)
    span_pages = page_aligned_capacity(
        max(len(p) for p in prompts) + args.gen, cfg.page_size) \
        // cfg.page_size
    cfg = dataclasses.replace(cfg, prefill_chunk=args.prefill_chunk)
    ecfg = EngineConfig(
        max_batch=args.max_batch or len(prompts),
        max_pages_per_seq=span_pages,
        n_pages=args.pool_pages,
        prefix_sharing=not args.no_prefix_share,
        prefix_cache_pages=args.prefix_cache_pages,
        host_tier_pages=args.host_tier_pages,
        prefill_budget=args.prefill_budget,
        max_queue=args.max_queue,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        eos_id=args.eos_id, seed=args.seed,
        quant_health_every=args.quant_health_every,
        spec_draft_len=args.spec_draft)
    plan = FaultPlan.parse(args.inject) if args.inject else None
    reqs = [Request(rid=i, prompt=p, max_new=args.gen,
                    arrival=float(i * args.arrival_gap),
                    ttft_deadline=args.ttft_deadline or None,
                    deadline=args.deadline or None)
            for i, p in enumerate(prompts)]

    if args.restartable:
        import tempfile
        ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_ckpt_")
        handler = PreemptionHandler(install=not args.inject)
        out: dict = {}

        def attempt() -> str:
            # every attempt starts from the LATEST snapshot (none on the
            # first): the engine skips requests it has already seen, so
            # resubmitting the whole workload is idempotent
            handler.reset()
            engine = ServingEngine(cfg, params, ecfg, fault_plan=plan,
                                   preemption=handler, tracer=tracer)
            latest = CK.latest_checkpoint(ckpt_dir)
            if latest:
                engine.restore(latest)
            out["engine"] = engine
            out["results"] = engine.run(reqs, ckpt_dir=ckpt_dir,
                                        ckpt_every=args.ckpt_every)
            return "done"

        run_with_restarts(
            attempt, RestartPolicy(max_restarts=3),
            on_restart=lambda n: log(
                "engine_restart",
                f"[serve] engine restart #{n} (restoring from {ckpt_dir})",
                restart=n, ckpt_dir=ckpt_dir))
        handler.restore()
        engine, results = out["engine"], out["results"]
    else:
        engine = ServingEngine(cfg, params, ecfg, fault_plan=plan,
                               preemption=None, tracer=tracer)
        results = engine.run(reqs)
    m = engine.metrics()
    n_done = sum(1 for r in results if r.status == "done")
    log("engine_summary",
        f"[serve] engine: {len(results)} requests over "
        f"{ecfg.max_batch} slots, {m['steps']} steps, "
        f"{m['wall']['decode_tok_per_s']:.1f} tok/s (decode), "
        f"prefill {m['prefill']['mode']} "
        f"(chunk={m['prefill']['chunk']}, "
        f"traces={m['prefill']['traces']}), "
        f"pages peak {m['pages']['peak_in_use']}/{m['pages']['capacity']} "
        f"(saved by sharing: {m['pages']['saved_by_sharing']}), "
        f"evictions: {m['evictions']} "
        f"(requeued: {m['requeues']})",
        requests=len(results), slots=ecfg.max_batch, steps=m["steps"],
        decode_tok_per_s=m["wall"]["decode_tok_per_s"],
        prefill_mode=m["prefill"]["mode"], chunk=m["prefill"]["chunk"],
        prefill_traces=m["prefill"]["traces"],
        pages_peak=m["pages"]["peak_in_use"],
        pages_capacity=m["pages"]["capacity"],
        saved_by_sharing=m["pages"]["saved_by_sharing"],
        evictions=m["evictions"], requeues=m["requeues"],
        roofline=m["roofline"])
    f = m["faults"]
    if plan or args.restartable or f["rejected"] or f["deadline_cancelled"]:
        log("engine_faults",
            f"[serve] faults: injected={len(f['injected'])} "
            f"quarantined={f['nonfinite_rows']} "
            f"(recovered via jnp_ref: {f['recovered_ref']}, "
            f"failed: {f['failed_nonfinite']}), "
            f"backend faults={f['backend_faults']}, "
            f"deadline cancels={f['deadline_cancelled']}, "
            f"rejected={f['rejected']}, "
            f"preemptions={f['preemptions']}, "
            f"restores={f['restores']} -> "
            f"{n_done}/{len(results)} completed",
            completed=n_done, total=len(results),
            **{k: v for k, v in f.items() if k != "injected"},
            injected=len(f["injected"]))
    sp = m["speculative"]
    if sp["enabled"]:
        log("spec_decode",
            f"[serve] speculative: draft_len={sp['draft_len']}, "
            f"{sp['verify_steps']} verify steps, "
            f"drafted {sp['drafted_tokens']} / accepted "
            f"{sp['accepted_tokens']} "
            f"(accept rate {sp['accept_rate']:.3f}), "
            f"{sp['accepted_tokens_per_step']:.3f} tokens/slot-step",
            **{k: v for k, v in sp.items()})
    pc = m["prefix_cache"]
    if pc["budget_pages"] or pc["host_tier_pages"]:
        log("prefix_cache",
            f"[serve] prefix cache: {pc['cached']} pages retained "
            f"(budget {pc['budget_pages']}), reused {pc['reused_cached']}, "
            f"restored from host {pc['restored_host']} "
            f"(offloads {pc['offloads']}, tier "
            f"{pc['host_used']}/{pc['host_tier_pages']}), "
            f"prefill tokens skipped {pc['prefill_skipped_tokens']}, "
            f"HBM high-water {pc['peak_resident']} pages",
            **{k: v for k, v in pc.items()})
    if engine.quant_probe is not None and engine.quant_probe.samples:
        last = engine.quant_probe.samples[-1]
        log("quant_health",
            f"[serve] quant health ({cfg.kv_fmt}, every "
            f"{args.quant_health_every} steps, "
            f"{len(engine.quant_probe.samples)} samples): scale "
            f"[{last['scale_min']:.3g}, {last['scale_max']:.3g}], "
            f"clip rate max {last['clip_rate_max']:.3g}, sink err bound "
            f"{last['sink_err_bound_max']:.3g}",
            fmt=cfg.kv_fmt, every=args.quant_health_every,
            samples=len(engine.quant_probe.samples), **last)
    if tracer is not None:
        tracer.write(args.trace_out)
        stats = validate_chrome_trace(
            json.load(open(args.trace_out)), expect_requests=len(reqs))
        log("trace_written",
            f"[serve] trace: {args.trace_out} ({stats['events']} events, "
            f"{stats['requests']} request tracks, {stats['spans']} spans; "
            f"clock={tracer.clock})",
            path=args.trace_out, clock=tracer.clock, **stats)
    # drained means every page is FREE or a retained (refcount-0) cache page
    if m["pages"]["free"] + m["pages"]["cached"] != m["pages"]["capacity"]:
        raise SystemExit("[serve] FATAL: engine drained but pages leaked "
                         f"({m['pages']['free']} free + "
                         f"{m['pages']['cached']} cached != "
                         f"{m['pages']['capacity']} capacity)")
    if (plan or args.restartable) and n_done == 0:
        raise SystemExit("[serve] FATAL: fault drill left zero completed "
                         "requests")
    if args.prefill_chunk > 0:
        n_buckets = len(ST.chunk_buckets(args.prefill_chunk))
        if m["prefill"]["traces"] > n_buckets:
            raise SystemExit(
                "[serve] FATAL: chunked prefill compiled "
                f"{m['prefill']['traces']} variants > {n_buckets} buckets")
    if args.temperature <= 0 and m["requeues"] == 0:
        _check_engine_parity(cfg, params, args, prompts, results, log)
    return engine, results


# Largest teacher-forced margin ``serve --engine`` accepts off the CPU, where
# exact token parity does not hold: (best static-path logit - logit of the
# engine's token) in units of that step's logit standard deviation, so the
# limit means the same at any width. Readings on the CPU at smoke widths
# (seed 0, greedy, chunked prefill): the sound engine 0.028; an engine whose
# decode rope positions are off by one 1.14; one that reads another slot's
# first page 2.64. PERF.md has the same readings on a TPU v5e at mla-7b
# widths.
ENGINE_MARGIN_TOL = 0.25


def _teacher_forced_margin(cfg, params, prompts, results) -> float:
    """Run each completed request alone through the static prefill + decode
    path, fed the engine's own tokens, and return the largest margin by
    which an engine token trails the static path's best logit, in units of
    the logit standard deviation at that step (0 wherever both pick the same
    token)."""
    prefill_fn = jax.jit(ST.make_prefill_step(cfg))
    decode_fn = jax.jit(ST.make_decode_step(cfg))
    worst = 0.0
    for r in results:
        prompt = jnp.asarray(prompts[r.rid])[None]
        S = prompt.shape[1]
        state = T.init_decode_state(
            cfg, 1, _decode_capacity(cfg, S, len(r.tokens)))
        logits, state = prefill_fn(params, prompt, state)
        for i, tok in enumerate(r.tokens):
            _check_finite(logits, f"request {r.rid} token {i} (static path)")
            worst = max(worst, float((jnp.max(logits) - logits[0, tok])
                                     / jnp.std(logits)))
            if i + 1 < len(r.tokens):
                logits, state = decode_fn(params, jnp.asarray([tok]), state,
                                          jnp.asarray([S + i], jnp.int32))
    return worst


def _check_engine_parity(cfg, params, args, prompts, results, log) -> None:
    """Greedy parity oracle for ``serve --engine``, over completed requests
    (FAILED/REJECTED excluded: a recovered quarantine still matches, since
    the jnp_ref retry is the oracle's own numerics, so this doubles as the
    isolation gate — survivors of a fault drill must be unaffected by the
    poisoned slot). Exits non-zero on a mismatch.

    On the CPU the engine must be token-identical to the static-batch
    generate path for the same prompts/gen lengths, run per prompt-length
    group so mixed-length workloads are covered. On a TPU the engine's batch
    shapes (decode slots, one-request prefill chunks) differ from the static
    batch's and XLA's TPU numerics depend on them, so greedy tokens may part
    at near-ties; there each request is teacher-forced through the static
    path instead, and every engine token must be within
    ``ENGINE_MARGIN_TOL`` logit standard deviations of the static path's
    best."""
    done = [r for r in results if r.status == "done"]
    if jax.default_backend() != "cpu":
        margin = _teacher_forced_margin(cfg, params, prompts, done)
        n_tok = sum(len(r.tokens) for r in done)
        if not margin <= ENGINE_MARGIN_TOL:
            raise SystemExit(
                "[serve] FATAL: an engine token trails the static path's "
                f"best logit by {margin:.6f} > {ENGINE_MARGIN_TOL} logit "
                f"std (teacher-forced over {n_tok} tokens)")
        log("engine_parity",
            f"[serve] engine parity vs static path (teacher-forced): "
            f"largest margin {margin:.6f} logit std over {n_tok} tokens "
            f"(tolerance {ENGINE_MARGIN_TOL}; {len(done)} completed "
            f"requests)",
            parity="margin", margin=margin, tolerance=ENGINE_MARGIN_TOL,
            tokens=n_tok, completed=len(done))
        return
    by_len: dict[int, list[int]] = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    ref: dict[int, list[int]] = {}
    for rids in by_len.values():
        batch = jnp.asarray(np.stack([prompts[i] for i in rids]))
        toks_ref, _ = generate(cfg, params, batch, args.gen,
                               eos_id=args.eos_id, seed=args.seed)
        for row, rid in zip(np.asarray(toks_ref), rids):
            ref[rid] = list(row)
    # EOS-stopped requests are a prefix of the (eos-padded) oracle row
    bad = [r.rid for r in done if r.tokens != ref[r.rid][:len(r.tokens)]]
    if bad:
        raise SystemExit("[serve] FATAL: engine tokens diverge from the "
                         f"static-batch generate oracle for {bad}")
    log("engine_parity",
        f"[serve] engine parity vs static-batch generate: exact "
        f"({len(done)} completed requests)",
        parity="exact", completed=len(done))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mla-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="weight dtype (mla-7b needs bfloat16 to fit one "
                         "16 GB chip)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--fmt", default="fp8_e4m3",
                    choices=["fp8_e4m3", "int8", "none"])
    ap.add_argument("--fused", action="store_true",
                    help="scan-based generate_fused (one dispatch) instead of "
                         "the per-step decode loop")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "kernel", "shard-map"],
                    help="decode-attention backend "
                         "(kernels/mla_decode/backends.py): 'ref' = pure-jnp "
                         "einsum twins (pjit-friendly), 'kernel' = the Pallas "
                         "split-KV kernels inside the jitted decode step "
                         "(interpret on CPU, compiled on TPU; paged caches "
                         "use the scalar-prefetched page-table kernel), "
                         "'shard-map' = collective-free shard_map region "
                         "over the host (data, model) mesh (contiguous "
                         "caches; batch must divide the data axis), 'auto' = "
                         "ref unless a mesh/kernels flag says otherwise")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (> 0 switches greedy argmax "
                         "to temperature/top-k sampling, PRNG key threaded "
                         "through the fused scan carry)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampling (0 = full softmax)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling: keep the smallest token set with "
                         "cumulative probability >= top-p, applied after "
                         "top-k (0 or >= 1 disables; needs --temperature)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="EOS token id: generation early-stops (step loop) / "
                         "pins finished sequences (fused scan) once emitted")
    ap.add_argument("--kv-splits", type=int, default=0,
                    help="split-KV (flash-decoding) splits for decode "
                         "attention, contiguous AND paged caches "
                         "(0 = auto: measured split profile if present, else "
                         "the context-length heuristic; 1 = single-pass)")
    ap.add_argument("--block-n", type=int, default=0,
                    help="decode-attention KV block size (0 = page size). "
                         "Contiguous caches take any divisor of the cache "
                         "capacity; with --paged the block size is "
                         "structurally the physical page, so this sets the "
                         "page size itself")
    ap.add_argument("--sink-tokens", type=int, default=0,
                    help="P-Cast sink guard: keep the first k tokens' latent "
                         "KV rows in full precision (attention sinks are the "
                         "most quantization-sensitive rows). Contiguous MLA "
                         "caches only; 0 disables")
    ap.add_argument("--rescale", default="fma", choices=["fma", "amla"],
                    help="per-block accumulator rescale in the decode "
                         "kernels: fma = exact max-shift FMA (default), "
                         "amla = AMLA exponent-add fast path (power-of-two "
                         "sigma_p grid, combine-free split-KV partials; "
                         "differs from fma only at quantization-rounding "
                         "level)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache for MLA layers: latent entries live "
                         "in a page pool addressed through per-sequence page "
                         "tables (multi-tenant pool layout) instead of a "
                         "contiguous per-slot cache")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching serving engine (serving/): "
                         "multi-tenant free-list page allocator with "
                         "prefix sharing over one shared paged pool, FCFS "
                         "slot scheduler, and the jitted decode step over "
                         "staggered arrivals — greedy runs are gated "
                         "against the static-batch generate oracle")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="engine decode slots (0 = one per request)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="engine chunked prefill: split prompt admission "
                         "into chunks of this many tokens, run alongside "
                         "the slot-batched decode each engine step (later "
                         "chunks attend the FP8-quantized prefix pages "
                         "through the fused fetch-dequant path); chunk "
                         "shapes are bucketed to powers of two so compiles "
                         "stay O(log chunk). 0 = monolithic one-shot "
                         "prefill")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prefill tokens per engine step under "
                         "--prefill-chunk (granted one chunk per PREFILLING "
                         "request per FCFS round-robin pass; the head "
                         "always gets one chunk). 0 = one chunk per "
                         "prefilling request per step")
    ap.add_argument("--prompt-lens", default="",
                    help="engine-only: comma list of prompt lengths cycled "
                         "across --batch requests (mixed long+short "
                         "workload), overriding --prompt-len")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="engine pool size in physical pages (0 = auto: "
                         "max_batch full-span sequences + the scratch page)")
    ap.add_argument("--arrival-gap", type=int, default=1,
                    help="engine virtual steps between request arrivals")
    ap.add_argument("--no-prefix-share", action="store_true",
                    help="disable the engine's refcounted prefix sharing")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="engine radix prefix cache: retain up to this many "
                         "refcount-0 prefix pages in HBM for reuse across "
                         "requests (LRU-evicted under pressure; 0 = off, "
                         "pages are recycled at refcount-0 exactly as "
                         "before)")
    ap.add_argument("--host-tier-pages", type=int, default=0,
                    help="host-memory KV tier: LRU-evicted prefix-cache "
                         "pages offload to this many host slots instead of "
                         "being dropped, and re-admit via async device_put "
                         "restore (requires --prefix-cache-pages > 0; "
                         "0 = off)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="engine workload shaping: first N tokens identical "
                         "across every request (the shared-system-prompt "
                         "traffic the prefix cache serves; 0 = fully random "
                         "prompts)")
    ap.add_argument("--spec-draft", type=int, default=0,
                    help="engine-only: self-speculative decoding — host-side "
                         "n-gram proposer drafts up to this many tokens per "
                         "slot per step, verified in ONE q_len>1 split-KV "
                         "dispatch; the longest accepted prefix commits and "
                         "rejected tail positions are rolled back by rewind "
                         "(seq_lens never advance past committed tokens — "
                         "pages never move). Greedy output is token-identical "
                         "to non-speculative decoding (the parity oracle "
                         "still gates it). 0 = off")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="engine admission-queue bound: a submit that finds "
                         "this many requests already queued is load-shed "
                         "with a typed REJECTED result (0 = unbounded)")
    ap.add_argument("--ttft-deadline", type=int, default=0,
                    help="engine TTFT deadline in virtual steps from "
                         "arrival: requests still waiting for their first "
                         "token past it are cancelled FAILED('deadline') "
                         "(0 = none)")
    ap.add_argument("--deadline", type=int, default=0,
                    help="engine total-latency deadline in virtual steps "
                         "from arrival; blown requests become the preferred "
                         "eviction victim and are cancelled, freeing pages "
                         "mid-decode (0 = none)")
    ap.add_argument("--inject", action="append", default=[],
                    metavar="KIND:STEP[:SLOT][:sticky]",
                    help="engine fault injection (repeatable): "
                         "nan_logits:step:slot[:sticky] poisons a slot's "
                         "decode logits (sticky also poisons the jnp_ref "
                         "retry), alloc_fail:step[:count] forces pool "
                         "exhaustion, backend_raise:step raises from the "
                         "decode dispatch, preempt:step triggers the "
                         "preemption handler (needs --restartable)")
    ap.add_argument("--restartable", action="store_true",
                    help="engine checkpoint/restart drill: run under "
                         "run_with_restarts + PreemptionHandler with "
                         "periodic snapshots to --ckpt-dir; a preemption "
                         "(SIGTERM/SIGINT or --inject preempt:k) snapshots, "
                         "exits the attempt, and the restart restores from "
                         "the latest checkpoint token-identically")
    ap.add_argument("--ckpt-dir", default="",
                    help="engine snapshot directory for --restartable "
                         "(default: a fresh temp dir)")
    ap.add_argument("--ckpt-every", type=int, default=4,
                    help="snapshot cadence in engine steps under "
                         "--restartable (a preemption always snapshots)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for params, prompts, and sampling — "
                         "smokes, the engine, and the serving sim are "
                         "reproducible run-to-run for a fixed seed")
    ap.add_argument("--trace-out", default="",
                    help="engine-only: write a Chrome trace-event JSON of "
                         "the run (per-request lifecycle spans, engine step "
                         "phases, pool counters) to this path — loadable in "
                         "chrome://tracing or ui.perfetto.dev. Validated on "
                         "write (all spans closed, one terminal instant per "
                         "request)")
    ap.add_argument("--trace-clock", default="virtual",
                    choices=["virtual", "wall"],
                    help="trace timestamp source: 'virtual' stamps "
                         "step*1000+offset ticks (byte-identical across "
                         "same-seed runs; ts//1000 recovers the engine "
                         "step), 'wall' stamps real microseconds (readable, "
                         "not reproducible)")
    ap.add_argument("--log-json", action="store_true",
                    help="engine-only: emit every [serve] status line as a "
                         "single-line JSON event object instead of prose")
    ap.add_argument("--quant-health-every", type=int, default=0,
                    help="engine-only: sample FP8 quantization health "
                         "(per-layer KV scale min/max + exponent histogram, "
                         "clip rate, sink-row error bound) from the live "
                         "pool every N engine steps. Host-read cost per "
                         "sample; 0 = off (the default — the hot path never "
                         "pays it)")
    return ap


def config_from_args(args):
    """The ModelConfig a parsed ``serve`` command line asks for."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, kv_fmt=args.fmt, kv_splits=args.kv_splits,
                              kv_paged=args.paged,
                              kv_rescale=args.rescale,
                              kv_sink_tokens=args.sink_tokens,
                              decode_backend=args.backend,
                              use_kernels=args.backend == "kernel")
    if args.block_n:
        # paged caches have no block_n freedom — the kernel block axis IS the
        # physical page — so --block-n repages the pool there; contiguous
        # caches keep their page size and override only the decode block
        cfg = dataclasses.replace(
            cfg, page_size=args.block_n) if args.paged else \
            dataclasses.replace(cfg, kv_block_n=args.block_n)
    return cfg


def main(argv: list[str] | None = None):
    """Parse ``argv`` (default: the command line) and serve. Returns
    ``(engine, results)`` under ``--engine``, else None."""
    ap = build_parser()
    args = ap.parse_args(argv)
    configure_compile_cache()
    cfg = config_from_args(args)
    if args.backend == "shard-map":
        # the shard_map backend needs a mesh context (dryrun sets SHARD_CTX
        # for the production mesh; here: the host mesh, data = all devices)
        T.SHARD_CTX = {"mesh": make_host_mesh(1), "dp": "data",
                       "use_shard_map": True}
    key = jax.random.PRNGKey(args.seed)
    if args.param_dtype == "float32":
        params = T.init_model(key, cfg)
    else:
        # jitted, so no f32 copy of a (layer-stacked) weight is materialized
        params = jax.jit(T.init_model, static_argnums=(1, 2))(
            key, cfg, jnp.dtype(args.param_dtype))

    if args.engine:
        if args.fused:
            ap.error("--engine has no fused mode (it steps the decode loop "
                     "per engine tick); drop --fused or --engine")
        return run_engine(cfg, params, args)

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    aux = (jax.random.normal(key, (args.batch, cfg.n_aux_tokens, cfg.d_model))
           if cfg.n_aux_tokens else None)

    gen_fn = generate_fused if args.fused else generate
    sample_kw = dict(temperature=args.temperature, top_k=args.top_k,
                     top_p=args.top_p, eos_id=args.eos_id, seed=args.seed)
    toks, tps = gen_fn(cfg, params, prompts, args.gen, aux_embed=aux,
                       **sample_kw)
    mode = "fused-scan" if args.fused else "step-loop"
    cache_kind = "paged" if args.paged else "contiguous"
    print(f"[serve] {cfg.name} fmt={args.fmt} backend={args.backend} "
          f"({mode}, {cache_kind} cache): generated {toks.shape} at "
          f"{tps:.1f} tok/s (decode)")

    if args.fmt != "none":
        cfg_b = dataclasses.replace(cfg, kv_fmt="none")
        toks_b, _ = gen_fn(cfg_b, params, prompts, args.gen, aux_embed=aux,
                           **sample_kw)
        agree = float(jnp.mean((toks == toks_b).astype(jnp.float32)))
        print(f"[serve] token agreement vs BF16 pipeline: {agree * 100:.1f}%")


if __name__ == "__main__":
    main()
