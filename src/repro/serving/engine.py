"""Continuous-batching serving engine over the shared FP8 paged pool.

The engine drives the EXISTING jitted steps (``steps.make_prefill_step`` /
``steps.make_chunked_prefill_step`` / ``steps.make_decode_step`` — the same
``transformer`` code paths the static-batch ``serve.generate`` runs,
dispatching attention through the decode-backend registry) over a *dynamic*
request population:

  * the decode step is compiled ONCE for a fixed ``max_batch`` slot array and
    a fixed shared pool, with the decode-state buffers DONATED through the
    jit boundary so XLA updates the pool pages in place each iteration (no
    per-step pool copy); requests flow through slots with no *decode*
    recompiles — idle and still-prefilling slots are parked on the
    allocator's scratch page and masked by ``seq_lens``;
  * prompt admission is CHUNKED (``ModelConfig.prefill_chunk > 0``): each
    engine step runs at most a token-budgeted amount of prefill work —
    granted one bucketed chunk per PREFILLING request per FCFS round-robin
    pass — alongside the ongoing slot-batched decode, so a long-context
    arrival never stalls in-flight decodes for a whole monolithic prefill.
    Later chunks attend to earlier chunks' already-quantized FP8 pages
    through the fused fetch-dequant path (no bf16 re-materialization of the
    prefix), and chunk shapes are bucketed to powers of two up to
    ``prefill_chunk`` so the engine compiles O(log chunk) prefill variants
    total instead of one per prompt length. ``prefill_chunk == 0`` keeps the
    monolithic arrival-grouped prefill (the benchmark twin);
  * admission/retirement and the page tables are host-side bookkeeping
    (``allocator.PageAllocator`` free list + refcounted prefix sharing,
    ``scheduler.Scheduler`` FCFS lifecycle); each step the engine pushes its
    slot→pages mapping into the jitted state via ``kvcache.pool_with_tables``;
  * eviction under pool pressure is requeue, not loss: the victim's pages
    are freed but its generated tokens are kept, and its next admission
    replay-prefills prompt + generated tokens before resuming decode;
  * every step makes ONE host transfer: sampled/argmax tokens and the
    per-row finite flags come back together from a single jitted
    postprocess call (``jax.device_get`` of the pair), instead of separate
    per-purpose pulls.

Greedy engine output is token-identical to the static-batch ``generate``
oracle for the same prompts/gen lengths (pinned by tests/test_serving.py);
MLA decode is memory-bound while prefill is compute-bound, which is exactly
why piggybacking bounded prefill chunks onto decode steps recovers
throughput (see PAPERS.md, "Hardware-Centric Analysis of DeepSeek's MLA").

Virtual time = engine steps; the engine additionally accounts WORK UNITS
(tokens of prefill/decode compute) per step, which is what the serving
simulator's decode-stall / TTFT twins compare — deterministic, unlike wall
clock (which is also sampled host-side for throughput reporting).

FAULT TOLERANCE — the engine degrades per request, never per process:

  * a non-finite logits row (the per-row flags already ride the single
    postprocess transfer) QUARANTINES that slot's request instead of
    killing the engine: the row is retried once on the ``jnp_ref`` backend
    (same state, same position — the decode append is deterministic, so the
    rerun is bit-idempotent on the cache) to distinguish a kernel fault
    (ref row finite → token recovered, request continues) from genuinely
    divergent input (still non-finite → terminal FAILED("nonfinite"), pages
    freed, partial tokens kept in the result); every other slot decodes on
    undisturbed;
  * a raise out of the decode dispatch degrades the whole step to the
    ``jnp_ref`` backend (the donated buffers are only consumed once the
    primary dispatch starts executing, so a dispatch-time failure leaves
    them valid) and the engine keeps going;
  * deadlines (virtual steps) + a bounded admission queue shed load with
    typed terminal results (REJECTED / FAILED("deadline")) instead of
    queueing unboundedly or burning pool pages on answers nobody will read;
    blown-deadline requests are the preferred eviction victims and are
    cancelled (pages freed mid-decode) rather than requeued;
  * ``snapshot``/``restore`` round-trip the complete engine through the
    ``checkpoint`` machinery (host bookkeeping in the manifest, device pool
    pages in arrays.npz) so a preempted run resumes token-identically;
  * a ``FaultPlan`` (serving/faults.py) injects NaN/alloc/backend/preempt
    faults deterministically for chaos tests and the serving_sim sweep.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as CK
from repro.configs.base import ModelConfig
from repro.core.kvcache import (PagedMLAPool, page_aligned_capacity,
                                pool_read_page, pool_with_tables,
                                pool_write_page)
from repro.kernels.mla_decode import backends as BK
from repro.launch import steps as ST
from repro.models import transformer as T
from repro.obs import trace as TRC
from repro.obs.metrics import MetricsRegistry
from repro.obs.quant_health import QuantHealthProbe
from repro.serving.allocator import PageAllocator
from repro.serving.faults import EnginePreempted, FaultPlan
from repro.serving.scheduler import Request, Scheduler, Status
from repro.serving.speculative import NgramProposer
from repro.serving.tiering import HostTier

# the typed fault/degradation events the engine counts
# (snapmla_engine_faults_total{kind=...}; the metrics()["faults"] compat view
# reports exactly this set)
FAULT_KINDS = (
    "nonfinite_rows",        # quarantined decode rows seen
    "recovered_ref",         # ..recovered by the jnp_ref retry
    "failed_nonfinite",      # ..terminal (retry also non-finite)
    "failed_prefill",        # non-finite prefill logits
    "backend_faults",        # decode dispatch raised
    "ref_fallback_steps",    # steps degraded to jnp_ref
    "deadline_cancelled",    # typed FAILED("deadline")
    "rejected",              # bounded-queue load shedding
    "preemptions",           # snapshot-and-raise exits
    "restores",              # checkpoint restores into this engine
)


def _req_to_record(r: Request) -> dict:
    """JSON-safe snapshot of one request's full lifecycle state."""
    return {
        "rid": int(r.rid), "prompt": [int(t) for t in r.prompt],
        "max_new": int(r.max_new), "arrival": float(r.arrival),
        "ttft_deadline": r.ttft_deadline, "deadline": r.deadline,
        "status": r.status.value, "fail_reason": r.fail_reason,
        "slot": int(r.slot), "pages": [int(p) for p in r.pages],
        "out_tokens": [int(t) for t in r.out_tokens],
        "prefill_pos": int(r.prefill_pos), "requeues": int(r.requeues),
        "cached_tokens": int(r.cached_tokens),
        "admit_step": int(r.admit_step),
        "first_token_step": int(r.first_token_step),
        "finish_step": int(r.finish_step),
        "arrival_work": int(r.arrival_work),
        "first_token_work": int(r.first_token_work),
    }


def _req_from_record(rec: dict) -> Request:
    req = Request(
        rid=int(rec["rid"]),
        prompt=np.asarray(rec["prompt"], np.int32),
        max_new=int(rec["max_new"]), arrival=float(rec["arrival"]),
        ttft_deadline=rec["ttft_deadline"], deadline=rec["deadline"])
    req.status = Status(rec["status"])
    req.fail_reason = rec["fail_reason"]
    req.slot = int(rec["slot"])
    req.pages = [int(p) for p in rec["pages"]]
    req.out_tokens = [int(t) for t in rec["out_tokens"]]
    req.prefill_pos = int(rec["prefill_pos"])
    req.requeues = int(rec["requeues"])
    req.cached_tokens = int(rec.get("cached_tokens", 0))
    req.admit_step = int(rec["admit_step"])
    req.first_token_step = int(rec["first_token_step"])
    req.finish_step = int(rec["finish_step"])
    req.arrival_work = int(rec["arrival_work"])
    req.first_token_work = int(rec["first_token_work"])
    return req


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Host-side engine knobs (the model itself comes from ModelConfig)."""

    max_batch: int = 4             # decode slot count (static jit batch)
    n_pages: int = 0               # physical pool pages (0 = auto-size:
    #                                max_batch sequences at full span + scratch)
    max_pages_per_seq: int = 8     # page-table width (max context in pages)
    prefix_sharing: bool = True
    # radix prefix cache: refcount-0 prefix pages RETAINED (up to this many)
    # instead of freed, LRU-evicted; a later prompt matching them skips
    # their prefill chunks entirely. 0 = PR 4 behavior (pages die with
    # their last reference). Requires prefix_sharing.
    prefix_cache_pages: int = 0
    # host-memory second tier: LRU-evicted cached pages offload their FP8
    # bytes to this many host slots and restore via (prefetched)
    # jax.device_put on the next match, instead of recomputing prefill.
    # 0 = no tier. Requires prefix_cache_pages > 0.
    host_tier_pages: int = 0
    # chunked-prefill token budget per engine step (only with
    # ModelConfig.prefill_chunk > 0): each step grants bucketed chunks to
    # PREFILLING requests in FCFS round-robin passes until the budget is
    # spent. 0 = exactly one chunk per PREFILLING request per step. The FCFS
    # head always gets at least one chunk per step (progress guarantee).
    prefill_budget: int = 0
    # backpressure: bounded admission queue (0 = unbounded). A submit that
    # finds the queue full is load-shed with a typed REJECTED result
    # instead of queued; internal evict-to-requeue bypasses the bound.
    max_queue: int = 0
    # one-shot graceful degradation: retry a quarantined (non-finite) row
    # once on the jnp_ref backend before failing the request — records
    # whether the fault was the kernel's (recovered) or the input's (failed)
    ref_retry: bool = True
    # opt-in FP8 health probe (obs/quant_health.py): sample the pool's
    # scale/clip/sink stats every N engine steps. 0 = off (the default —
    # each sample is a host read of the resident pages).
    quant_health_every: int = 0
    # self-speculative decoding: draft up to this many tokens per slot per
    # step by n-gram lookup in the slot's own history and verify them all in
    # ONE q_len>1 kernel dispatch (serving/speculative.py). 0 = off (plain
    # one-token decode). Per-slot draft lengths adapt to acceptance.
    spec_draft_len: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: int | None = None
    seed: int = 0

    def resolved_n_pages(self) -> int:
        if self.n_pages:
            return self.n_pages
        return self.max_batch * self.max_pages_per_seq + 1   # + scratch page


@dataclasses.dataclass
class RequestResult:
    rid: int
    status: str                    # "done" | "failed" | "rejected"
    tokens: list[int]              # full output, or partial for FAILED
    prompt_len: int
    ttft_steps: int                # first token step - arrival (virtual)
    latency_steps: int             # finish step - arrival (virtual)
    ttft_work: int                 # work units (tokens) arrival -> first token
    requeues: int                  # evict-to-requeue round trips
    ttft_s: float                  # wall-clock first-token latency
    latency_s: float               # wall-clock total latency
    fail_reason: str = ""          # typed reason for failed/rejected results


class ServingEngine:
    """Admit → (chunked) prefill → decode → retire over one shared pool."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig, *,
                 fault_plan: FaultPlan | None = None, preemption=None,
                 tracer: TRC.SpanTracer | None = None):
        bad = [k for k in cfg.layer_pattern if k != "mla"]
        if bad or cfg.n_aux_tokens:
            raise ValueError(
                "the serving engine drives the paged MLA decode path; "
                f"layer pattern {cfg.layer_pattern} / aux tokens "
                f"{cfg.n_aux_tokens} are not pure-MLA")
        if cfg.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        self.ecfg = ecfg
        self.page = cfg.page_size
        self.chunk = cfg.prefill_chunk           # 0 = monolithic prefill
        self.span_pages = ecfg.max_pages_per_seq
        self.n_pages = ecfg.resolved_n_pages()
        self.cfg = dataclasses.replace(cfg, kv_paged=True,
                                       kv_pool_pages=self.n_pages)
        self.params = params
        span_tokens = self.span_pages * self.page
        self.state = T.init_decode_state(self.cfg, ecfg.max_batch, span_tokens)

        # unified telemetry (obs/): every scalar counter lives in ONE typed
        # registry; the legacy attributes below are read-only views over it
        # and metrics() stays a compatibility dict over the same values
        self.registry = MetricsRegistry()
        self.tracer = tracer
        self._register_metrics()
        self.quant_probe = (
            QuantHealthProbe(self.registry, fmt=cfg.kv_fmt,
                             every=ecfg.quant_health_every)
            if ecfg.quant_health_every > 0 and cfg.kv_fmt != "none" else None)

        # prefill trace counter: the wrapped python body runs at TRACE time
        # only, so this counts compiles — the recompile-bound test asserts it
        # stays <= the bucket count across any mix of prompt lengths
        def _counted(fn):
            def wrapper(*args):
                self._c_prefill_traces.inc()
                return fn(*args)
            return wrapper

        # the state argument is DONATED on every jitted step: the pool's
        # page buffers are updated in place instead of copied per call (the
        # engine re-adopts the returned buffers immediately, so the
        # invalidated inputs are never read again)
        self._prefill_fn = jax.jit(_counted(ST.make_prefill_step(self.cfg)),
                                   donate_argnums=(2,))
        self._chunk_fn = jax.jit(
            _counted(ST.make_chunked_prefill_step(self.cfg)),
            donate_argnums=(2,))
        self._decode_fn = jax.jit(ST.make_decode_step(self.cfg),
                                  donate_argnums=(2,))
        self._post_fn = jax.jit(self._make_postprocess())
        # jnp_ref twin of the decode step, compiled LAZILY on the first
        # fault (quarantine retry / backend-raise fallback) so the
        # fault-free path never pays its compile. NOT donated: the retry
        # discards the returned state, and the fallback adopts it whole.
        self._ref_fn = None

        # self-speculative decoding: the q_len>1 verify step replaces the
        # one-token decode step when spec_draft_len > 0 (ONE jitted dispatch
        # verifies every slot's draft; drafting itself is host-side n-gram
        # lookup). The ref twin compiles lazily, like _ref_fn.
        self.proposer = (NgramProposer(max_draft_len=ecfg.spec_draft_len)
                         if ecfg.spec_draft_len > 0 else None)
        self._verify_fn = (jax.jit(ST.make_verify_step(self.cfg),
                                   donate_argnums=(2,))
                           if self.proposer else None)
        self._ref_verify_fn = None

        self.tier = (HostTier(ecfg.host_tier_pages)
                     if ecfg.host_tier_pages > 0 else None)
        self.allocator = PageAllocator(
            self.n_pages, self.page, prefix_sharing=ecfg.prefix_sharing,
            prefix_cache_pages=ecfg.prefix_cache_pages, host_tier=self.tier)
        self.scheduler = Scheduler(ecfg.max_batch, max_queue=ecfg.max_queue)
        self.table = np.zeros((ecfg.max_batch, self.span_pages), np.int32)
        self.last_tok = np.zeros((ecfg.max_batch,), np.int32)

        # warm the decode jit cache on the all-idle state (every slot parked
        # on the scratch page); the input buffers are donated, so the warmed
        # state's pool pages are adopted back (its writes land on the
        # scratch page only, which is never read)
        _, warm = self._decode_fn(
            self.params, jnp.zeros((ecfg.max_batch,), jnp.int32),
            self._state_with_tables(self.table,
                                    np.zeros((ecfg.max_batch,), np.int32)),
            jnp.zeros((ecfg.max_batch,), jnp.int32))
        jax.block_until_ready(warm)
        self.state = warm
        if self._verify_fn is not None:
            # compile the verify step here too, so a compile error raises
            # at start-up instead of reaching _dispatch_verify's fallback
            idle = np.zeros((ecfg.max_batch,), np.int32)
            _, warm = self._verify_fn(
                self.params,
                jnp.zeros((ecfg.max_batch, ecfg.spec_draft_len + 1),
                          jnp.int32),
                self._state_with_tables(self.table, idle), jnp.asarray(idle))
            jax.block_until_ready(warm)
            self.state = warm

        self.step_idx = 0
        self.prefill_tokens_series: list[int] = []  # prefill work per step
        self.stall_tokens_series: list[int] = []   # prefill work per step
        #                                            while decodes in flight
        self.util_series: list[float] = []
        self._wall: dict[int, dict[str, float]] = {}   # rid -> wall marks

        # registry collectors mirror the allocator/tier/scheduler occupancy
        # counters into gauges at snapshot time (they can legally DECREMENT
        # on un-evict fast paths, so they cannot be monotonic Counters)
        self.registry.register_collector(self._collect_occupancy)

        # analytic roofline annotation: per-step model bytes/FLOPs for the
        # decode backend the decode step resolves (ref paged gather models
        # full-span traffic; kernels stream only visited tokens)
        self._backend = BK.resolve_backend(
            self.cfg.decode_backend, paged=True, batch=ecfg.max_batch,
            n_heads=cfg.n_heads, use_kernels=cfg.use_kernels)
        self._device_kind = jax.devices()[0].device_kind

        # fault tolerance: injection plan, preemption flag, survival metrics
        self.fault_plan = fault_plan
        self.preemption = preemption       # PreemptionHandler-like (.requested)
        self._seen_rids: set[int] = set()  # submitted at least once (run()
        #                                    skips these after a restore)

    # ------------------------------------------------------------------
    # telemetry (obs/metrics registry + legacy attribute views)
    # ------------------------------------------------------------------

    def _register_metrics(self) -> None:
        r = self.registry
        self._c_steps = r.counter(
            "snapmla_engine_steps_total", "engine steps executed")
        self._c_decode_tokens = r.counter(
            "snapmla_engine_decode_tokens_total",
            "tokens produced by decode steps")
        self._c_prefill_tokens = r.counter(
            "snapmla_engine_prefill_tokens_total",
            "padded chunk/prompt tokens processed")
        self._c_prefill_skipped = r.counter(
            "snapmla_engine_prefill_skipped_tokens_total",
            "prefill tokens avoided by prefix-cache hits")
        self._c_work = r.counter(
            "snapmla_engine_work_units_total",
            "total work units (tokens) processed")
        self._c_evictions = r.counter(
            "snapmla_engine_evictions_total",
            "pressure evictions (evict-to-requeue round trips)")
        self._c_prefill_traces = r.counter(
            "snapmla_engine_prefill_traces_total",
            "prefill/chunk trace-time executions (compiles)")
        self._h_chunk_width = r.histogram(
            "snapmla_engine_prefill_chunk_width",
            "padded token width of each prefill dispatch")
        # deterministic fetch-work counters: the DMA page traffic the bounded
        # prefix fetch actually issues vs what a full-span fetch would have,
        # plus the decode kernels' block-visit work (early-exit vs dense).
        # Derived from host bookkeeping — exact and hardware-independent, so
        # bench_gate can pin them as regression floors.
        self._c_fetch_bounded = r.counter(
            "snapmla_fetch_pages_bounded_total",
            "chunk-prefill pages read (bounded prefix fetch)")
        self._c_fetch_full = r.counter(
            "snapmla_fetch_pages_full_total",
            "pages a full-span fetch would have read")
        self._c_blocks_visited = r.counter(
            "snapmla_fetch_decode_blocks_visited_total",
            "KV blocks decode visits (seq_lens early exit)")
        self._c_blocks_full = r.counter(
            "snapmla_fetch_decode_blocks_full_total",
            "KV blocks a dense decode sweep would visit")
        # analytic roofline cost of the dispatched decode work (model, not
        # measurement: deterministic bytes/FLOPs from the cost annotation)
        self._c_roof_bytes = r.counter(
            "snapmla_roofline_model_bytes_total",
            "modeled HBM bytes moved by the resolved decode backend")
        self._c_roof_bytes_min = r.counter(
            "snapmla_roofline_bytes_min_total",
            "compulsory HBM bytes (visited tokens only)")
        self._c_roof_flops = r.counter(
            "snapmla_roofline_flops_total", "modeled attention FLOPs")
        self._g_roof_frac = r.gauge(
            "snapmla_roofline_achieved_fraction",
            "bytes_min / modeled bytes for the last decode dispatch")
        # speculative decoding: drafted-vs-accepted accounting (satellite of
        # the q_len>1 verify path; serving_sim's speculative twin and
        # bench_gate read these through the registry snapshot)
        self._c_spec_steps = r.counter(
            "snapmla_spec_verify_steps_total",
            "speculative verify dispatches")
        self._c_spec_slot_steps = r.counter(
            "snapmla_spec_slot_steps_total",
            "per-slot verify rows dispatched (decoding slots x steps)")
        self._c_spec_drafted = r.counter(
            "snapmla_spec_drafted_tokens_total",
            "draft tokens proposed for verification")
        self._c_spec_accepted = r.counter(
            "snapmla_spec_accepted_tokens_total",
            "draft tokens accepted by the longest-prefix rule")
        self._g_spec_accept_rate = r.gauge(
            "snapmla_spec_accept_rate",
            "cumulative accepted/drafted draft-token ratio")
        self._c_faults = r.counter(
            "snapmla_engine_faults_total",
            "fault-tolerance events by kind", labels=("kind",))
        for kind in FAULT_KINDS:      # pre-materialize for byte-stable views
            self._c_faults.labels(kind=kind)
        # wall-clock family: never eligible for gating (bench_gate asserts)
        self._w_decode_s = r.counter(
            "snapmla_wall_decode_seconds_total",
            "wall seconds inside decode dispatch", wall=True)
        self._w_prefill_s = r.counter(
            "snapmla_wall_prefill_seconds_total",
            "wall seconds inside prefill dispatch", wall=True)
        self._w_stall_s = r.counter(
            "snapmla_wall_stall_seconds_total",
            "wall seconds prefilling while decodes waited", wall=True)
        # occupancy mirrors, pushed by the collector at snapshot time
        self._g_pages_in_use = r.gauge(
            "snapmla_pages_in_use", "pool pages referenced by live requests")
        self._g_pages_free = r.gauge(
            "snapmla_pages_free", "pool pages on the free list")
        self._g_pages_cached = r.gauge(
            "snapmla_pages_cached", "refcount-0 cache-retained pages")
        self._g_pages_peak_in_use = r.gauge(
            "snapmla_pages_peak_in_use", "high-water mark of in-use pages")
        self._g_pages_peak_resident = r.gauge(
            "snapmla_pages_peak_resident",
            "high-water mark of in-use + cached pages")
        self._g_cache_saved = r.gauge(
            "snapmla_cache_saved_pages",
            "pages avoided via prefix sharing (live-hit)")
        self._g_cache_reused = r.gauge(
            "snapmla_cache_reused_pages",
            "pages re-adopted from the refcount-0 cache")
        self._g_cache_restored = r.gauge(
            "snapmla_cache_restored_pages", "pages restored from the host tier")
        self._g_cache_dropped = r.gauge(
            "snapmla_cache_dropped_pages", "cached pages dropped under pressure")
        self._g_tier_offloads = r.gauge(
            "snapmla_tier_offload_pages", "pages offloaded to host memory")
        self._g_tier_restores = r.gauge(
            "snapmla_tier_restore_pages", "pages copied back from host memory")
        self._g_tier_used = r.gauge(
            "snapmla_tier_slots_used", "host tier slots currently occupied")
        self._g_sched_requeues = r.gauge(
            "snapmla_sched_requeues", "cumulative evict-to-requeue count")
        self._g_sched_active = r.gauge(
            "snapmla_sched_active_slots", "requests in prefill/decode slots")

    def _collect_occupancy(self) -> None:
        a = self.allocator
        self._g_pages_in_use.set(a.num_in_use)
        self._g_pages_free.set(a.num_free)
        self._g_pages_cached.set(a.num_cached)
        self._g_pages_peak_in_use.set(a.peak_in_use)
        self._g_pages_peak_resident.set(a.peak_resident)
        self._g_cache_saved.set(a.pages_saved_by_sharing)
        self._g_cache_reused.set(a.pages_reused_cached)
        self._g_cache_restored.set(a.pages_restored_host)
        self._g_cache_dropped.set(a.cache_drops)
        self._g_tier_offloads.set(a.host_offloads)
        self._g_tier_restores.set(self.tier.restores if self.tier else 0)
        self._g_tier_used.set(self.tier.num_used if self.tier else 0)
        self._g_sched_requeues.set(self.scheduler.requeues)
        self._g_sched_active.set(self.scheduler.num_active)

    def _fault(self, kind: str, n: int = 1) -> None:
        self._c_faults.labels(kind=kind).inc(n)

    def telemetry(self, *, include_wall: bool = False) -> dict:
        """The registry view (``{"work": ..., "wall": ...}``); the ``work``
        subtree is byte-stable for a seeded run."""
        return self.registry.snapshot(include_wall=include_wall)

    # legacy attribute views (read-only) over the registry — kept so tests
    # and callers that predate obs/ keep reading the same numbers
    @property
    def decode_tokens(self) -> int:
        return self._c_decode_tokens.value

    @property
    def prefill_tokens(self) -> int:
        return self._c_prefill_tokens.value

    @property
    def prefill_skipped_tokens(self) -> int:
        return self._c_prefill_skipped.value

    @property
    def work_done(self) -> int:
        return self._c_work.value

    @property
    def evictions(self) -> int:
        return self._c_evictions.value

    @property
    def prefill_traces(self) -> int:
        return self._c_prefill_traces.value

    @property
    def pages_fetched_bounded(self) -> int:
        return self._c_fetch_bounded.value

    @property
    def pages_fetched_full(self) -> int:
        return self._c_fetch_full.value

    @property
    def decode_blocks_visited(self) -> int:
        return self._c_blocks_visited.value

    @property
    def decode_blocks_full(self) -> int:
        return self._c_blocks_full.value

    @property
    def spec_drafted_tokens(self) -> int:
        return self._c_spec_drafted.value

    @property
    def spec_accepted_tokens(self) -> int:
        return self._c_spec_accepted.value

    @property
    def decode_seconds(self) -> float:
        return self._w_decode_s.value

    @property
    def prefill_seconds(self) -> float:
        return self._w_prefill_s.value

    @property
    def stall_seconds(self) -> float:
        return self._w_stall_s.value

    @property
    def faults(self) -> dict[str, int]:
        return {k: self._c_faults.labels(kind=k).value for k in FAULT_KINDS}

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def required_pages(self, prompt_len: int, max_new: int) -> int:
        """Worst-case private pages a request can hold: every resident token
        (prompt + all appended generations; the final sampled token is never
        appended) page-aligned — through the ONE sizing rule
        (``kvcache.page_aligned_capacity``) serve and the cache initializers
        share."""
        return page_aligned_capacity(prompt_len + max_new - 1,
                                     self.page) // self.page

    def submit(self, req: Request) -> None:
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        need = self.required_pages(req.prompt_len, req.max_new)
        if need > self.span_pages:
            raise ValueError(
                f"request {req.rid}: {need} pages exceed the page-table "
                f"width {self.span_pages} (prompt {req.prompt_len} + "
                f"{req.max_new} new tokens)")
        if need > self.allocator.capacity:
            raise ValueError(
                f"request {req.rid}: {need} pages exceed pool capacity "
                f"{self.allocator.capacity}")
        self._wall[req.rid] = {"arrival": time.time()}
        req.arrival_work = self.work_done
        self._seen_rids.add(req.rid)
        if self.tracer:
            # the QUEUED span opens at the request's virtual arrival step
            self.tracer.req_begin(
                req.rid, "QUEUED", self.tracer.ts(max(int(req.arrival), 0)),
                args={"prompt_len": req.prompt_len, "max_new": req.max_new})
        if self.scheduler.queue_full:
            # backpressure: typed load shedding instead of unbounded queueing
            self._fault("rejected")
            self._wall[req.rid]["finish"] = time.time()
            self.scheduler.reject(req, self.step_idx, "queue_full")
            if self.tracer:
                ts = self.tracer.ts(self.step_idx, TRC.OFF_FAIL)
                self.tracer.req_end(req.rid, ts, args={"status": "rejected"})
                self.tracer.req_instant(req.rid, "REJECTED(queue_full)", ts)
            return
        self.scheduler.submit(req)

    # ------------------------------------------------------------------
    # state plumbing (host tables -> jitted pytree)
    # ------------------------------------------------------------------

    def _map_pools(self, fn, *trees):
        return jax.tree.map(
            lambda leaf, *rest: fn(leaf, *rest)
            if isinstance(leaf, PagedMLAPool) else leaf,
            *trees, is_leaf=lambda x: isinstance(x, PagedMLAPool))

    def _state_with_tables(self, table: np.ndarray, seq_lens: np.ndarray):
        return self._map_pools(
            lambda pool: pool_with_tables(pool, table, seq_lens), self.state)

    def _adopt_pool_data(self, new_state) -> None:
        """Take the (in-place-updated, donated) pool page data from a
        prefill call back into the engine state; tables/seq_lens stay
        host-owned."""
        self.state = self._map_pools(
            lambda old, new: old._replace(content=new.content, rope=new.rope,
                                          scale=new.scale),
            self.state, new_state)

    # ------------------------------------------------------------------
    # host-tier data movement (the allocator decides, the engine moves)
    # ------------------------------------------------------------------

    def _gather_page(self, page_id: int) -> list[tuple]:
        """Host copies of one physical page across every pool leaf of the
        engine state (scanned superblock stacks + tail layers), in the
        pytree traversal order ``_write_page`` replays."""
        leaves: list[tuple] = []

        def read(pool):
            c, r, s = pool_read_page(pool, page_id)
            leaves.append((np.asarray(c), np.asarray(r), np.asarray(s)))
            return pool

        self._map_pools(read, self.state)
        return leaves

    def _write_page(self, page_id: int, payload: list[tuple]) -> None:
        it = iter(payload)
        self.state = self._map_pools(
            lambda pool: pool_write_page(pool, page_id, next(it)),
            self.state)

    def _drain_tier_ops(self) -> None:
        """Execute the allocator's pending placement decisions, in decision
        order: offloads copy a just-evicted page's bytes to its host slot
        (the page id is back on the free list, but nothing has written it —
        drains run before any prefill/decode dispatch of the step); restores
        write a host slot's bytes into the freshly allocated device page
        and free the slot. ``prefetch`` starts every restore's
        host->device upload first so the transfers overlap the offload
        gathering."""
        ops = self.allocator.take_pending_tier_ops()
        if not ops:
            return
        assert self.tier is not None, "tier ops without a host tier"
        if self.tracer:
            self.tracer.step_phase(self.step_idx, "tier_drain",
                                   args={"ops": len(ops)})
        for kind, _pid, slot in ops:
            if kind == "restore" and self.tier.has_data(slot):
                self.tier.prefetch(slot)
        for kind, pid, slot in ops:
            if kind == "offload":
                self.tier.store(slot, self._gather_page(pid))
            else:
                self._write_page(pid, self.tier.take(slot))

    # ------------------------------------------------------------------
    # sampling + host sync (ONE device_get per call)
    # ------------------------------------------------------------------

    def _make_postprocess(self):
        """Jitted next-token + finiteness postprocess over [B, V] logits:
        tokens and per-row finite flags come back in a single transfer.
        Sampled draws use per-request keys folded by token index, so a
        request's continuation is independent of what it happens to be
        co-batched with — reproducible run-to-run for a fixed seed
        regardless of arrival interleaving."""
        e = self.ecfg
        base_key = jax.random.PRNGKey(e.seed)

        def post(rows, rids, counts):
            finite = jnp.all(jnp.isfinite(rows), axis=-1)
            if e.temperature <= 0.0:
                toks = jnp.argmax(rows, -1).astype(jnp.int32)
            else:
                keys = jax.vmap(lambda r, c: jax.random.fold_in(
                    jax.random.fold_in(base_key, r), c))(rids, counts)
                toks = jax.vmap(lambda row, k: ST.sample_logits(
                    row[None], k, e.temperature, e.top_k, e.top_p)[0])(
                        rows, keys)
            return toks, finite

        return post

    def _postprocess(self, rows: jax.Array, reqs: list[Request],
                     counts: np.ndarray | None = None):
        """``rows`` [n, V] aligned with ``reqs`` -> (tokens [n] np, finite
        [n] np) — one dispatch + ONE host transfer for the whole batch
        (tokens and NaN flags ride together). ``counts`` overrides the
        per-row sampling-key token index (the speculative verify passes one
        row PER CANDIDATE POSITION, so ``reqs`` may repeat a request with
        advancing counts — key usage stays identical to sequential
        decode)."""
        rids = jnp.asarray([r.rid for r in reqs], jnp.int32)
        if counts is None:
            counts = [len(r.out_tokens) for r in reqs]
        counts = jnp.asarray(counts, jnp.int32)
        toks, finite = jax.device_get(self._post_fn(rows, rids, counts))
        return toks, finite

    def _emit(self, req: Request, tok: int) -> None:
        req.out_tokens.append(tok)
        self.last_tok[req.slot] = tok
        if len(req.out_tokens) == 1:
            req.first_token_step = self.step_idx
            req.first_token_work = self.work_done
            self._wall[req.rid]["first"] = time.time()
            if self.tracer:
                self.tracer.req_instant(
                    req.rid, "FIRST_TOKEN",
                    self.tracer.ts(self.step_idx, TRC.OFF_FIRST_TOKEN),
                    args={"token": int(tok)})
        eos_hit = self.ecfg.eos_id is not None and tok == self.ecfg.eos_id
        if len(req.out_tokens) >= req.max_new or eos_hit:
            self._retire(req)

    def _drop_spec_state(self, req: Request) -> None:
        """Drop a request's speculative bookkeeping BEFORE its pages are
        freed/retained: uncommitted draft rows exist only as pool bytes past
        ``seq_len`` (rewound by the next step's pushed lengths) and as
        proposer state — neither may outlive the request's slot, and the
        prefix tree must never see rejected-draft bytes (it registers full
        PROMPT pages only; draft writes land at positions >= the effective
        prompt, i.e. private tail/grown pages)."""
        if self.proposer is not None:
            self.proposer.drop(str(req.rid))

    def _retire(self, req: Request) -> None:
        slot = req.slot
        self._drop_spec_state(req)
        self.scheduler.retire(req, self.step_idx, self.allocator)
        self._wall[req.rid]["finish"] = time.time()
        if self.tracer:
            ts = self.tracer.ts(self.step_idx, TRC.OFF_RETIRE)
            self.tracer.req_end(req.rid, ts, args={"status": "done"})
            self.tracer.req_instant(req.rid, "DONE", ts,
                                    args={"tokens": len(req.out_tokens)})
        if slot >= 0:
            self.table[slot] = 0          # park the slot on the scratch page
            self.last_tok[slot] = 0

    def _requeue(self, req: Request) -> None:
        """Evict-to-requeue: pages freed, generated tokens kept; the request
        replays prompt + generated tokens at its next admission."""
        slot = req.slot
        self._drop_spec_state(req)
        self.scheduler.requeue(req, self.allocator)
        if self.tracer:
            ts = self.tracer.ts(self.step_idx, TRC.OFF_EVICT)
            self.tracer.req_end(req.rid, ts, args={"evicted": True})
            self.tracer.req_instant(req.rid, "EVICTED", ts,
                                    args={"requeues": req.requeues})
            self.tracer.reset_chunks(req.rid)
            self.tracer.req_begin(req.rid, "QUEUED", ts,
                                  args={"requeue": req.requeues})
        if slot >= 0:
            self.table[slot] = 0
            self.last_tok[slot] = 0

    def _fail(self, req: Request, reason: str) -> None:
        """Per-request failure isolation: terminal FAILED with a typed
        reason; pages freed, slot parked on scratch, partial tokens kept.
        Every other request is untouched."""
        slot = req.slot
        self._drop_spec_state(req)
        self.scheduler.fail(req, self.step_idx, self.allocator, reason)
        self._wall.setdefault(req.rid, {"arrival": time.time()})
        self._wall[req.rid]["finish"] = time.time()
        if self.tracer:
            ts = self.tracer.ts(self.step_idx, TRC.OFF_FAIL)
            self.tracer.req_end(req.rid, ts,
                                args={"status": "failed", "reason": reason})
            self.tracer.req_instant(req.rid, f"FAILED({reason})", ts)
        if slot >= 0:
            self.table[slot] = 0
            self.last_tok[slot] = 0

    def _sweep_deadlines(self) -> None:
        """Step-boundary deadline enforcement for requests that have not
        produced their first token: a blown TTFT (or total) deadline while
        still QUEUED or PREFILLING cancels the request — its answer can no
        longer arrive in time, so its queue position / pool pages go to
        requests that can still meet theirs. Requests already DECODING are
        given grace (see ``Request`` docs) but become the preferred eviction
        victim, where the cancellation frees their pages mid-decode."""
        now = self.step_idx
        stale = [r for r in list(self.scheduler.queue)
                 + self.scheduler.active
                 if r.status in (Status.QUEUED, Status.PREFILLING)
                 and r.any_deadline_blown(now)]
        for req in stale:
            self._fault("deadline_cancelled")
            self._fail(req, "deadline")

    # ------------------------------------------------------------------
    # degraded decode paths (jnp_ref twin): quarantine retry + fallback
    # ------------------------------------------------------------------

    def _ref_decode_fn(self):
        """The jnp_ref-backend decode twin, jitted without donation (its
        callers either discard the returned state or adopt it whole)."""
        if self._ref_fn is None:
            self._ref_fn = jax.jit(ST.make_ref_decode_step(self.cfg))
        return self._ref_fn

    def _retry_ref(self, req: Request) -> tuple[bool, int]:
        """One-shot graceful degradation for a quarantined row: re-run THIS
        slot's decode step on the ``jnp_ref`` backend against the same
        pre-step cache view (the primary step's append is deterministic in
        its inputs, so the rerun rewrites the same cache entries with the
        same bytes — bit-idempotent) and re-postprocess. Returns
        (recovered?, token). A finite retry means the primary backend
        produced the fault (kernel bug / numerics of the fused path): the
        request continues with the ref token. A non-finite retry means the
        input itself diverges — the caller fails the request."""
        slot = req.slot
        table_view = np.zeros_like(self.table)
        table_view[slot] = self.table[slot]
        seq_lens = np.zeros((self.ecfg.max_batch,), np.int32)
        seq_lens[slot] = req.seq_len
        view = self._state_with_tables(table_view, seq_lens)
        logits, _ = self._ref_decode_fn()(
            self.params, jnp.asarray(self.last_tok), view,
            jnp.asarray(seq_lens))
        row = logits[slot][None]
        if self.fault_plan and self.fault_plan.retry_poisoned(
                self.step_idx, slot):
            row = row.at[0, 0].set(jnp.nan)   # sticky fault: input diverges
        toks, finite = self._postprocess(row, [req])
        return bool(finite[0]), int(toks[0])

    def _quarantine(self, req: Request) -> None:
        """A poisoned logits row: retry once on jnp_ref (if enabled), else /
        on a second failure mark the request terminal FAILED("nonfinite")."""
        self._fault("nonfinite_rows")
        if self.tracer:
            self.tracer.engine_instant(
                self.step_idx, TRC.OFF_FAIL - 20, "quarantine",
                args={"rid": req.rid, "slot": req.slot})
        if self.ecfg.ref_retry:
            recovered, tok = self._retry_ref(req)
            if recovered:
                self._fault("recovered_ref")
                self._emit(req, tok)
                return
        self._fault("failed_nonfinite")
        self._fail(req, "nonfinite")

    # ------------------------------------------------------------------
    # admission + prefill (monolithic OR chunked)
    # ------------------------------------------------------------------

    def _admit(self) -> list[Request]:
        admitted = self.scheduler.admit(self.allocator, self.step_idx)
        for r in admitted:
            row = np.zeros((self.span_pages,), np.int32)
            row[:len(r.pages)] = r.pages
            self.table[r.slot] = row
            if self.tracer:
                self.tracer.req_transition(
                    r.rid, "PREFILL",
                    self.tracer.ts(self.step_idx, TRC.OFF_ADMIT),
                    args={"slot": r.slot, "cached_tokens": r.cached_tokens})
        # land host-tier restores BEFORE any prefill chunk can read (or any
        # reallocation can overwrite) the pages involved
        self._drain_tier_ops()
        for r in admitted:
            if self.chunk <= 0 or r.cached_tokens <= 0:
                continue
            # radix-cache hit: the matched pages already hold this prefix's
            # FP8 bytes (retained, shared, or just restored), so the chunk
            # cursor starts AFTER them — TTFT tracks the uncached suffix
            eff_len = len(r.effective_prompt)
            if r.out_tokens:
                # replay after evict-to-requeue: no first-token logits
                # needed, so a fully matched prompt skips prefill outright
                r.prefill_pos = min(r.cached_tokens, eff_len)
            else:
                # always recompute at least the final token — its logits
                # seed the first sampled token (rewriting a matched page is
                # byte-identical: FP8 quantization is deterministic)
                r.prefill_pos = min(r.cached_tokens, eff_len - 1)
            self._c_prefill_skipped.inc(r.prefill_pos)
            if r.prefill_pos >= eff_len:
                self._finish_prefill(r, None)
        return admitted

    def _finish_prefill(self, req: Request, logits_row) -> None:
        """A request's prefill is complete: replayed requests resume from
        their pending last token (NO re-sampling — the token they sampled
        before eviction stands), fresh requests sample their first token
        from the final chunk's logits."""
        req.status = Status.DECODE
        if req.out_tokens:                        # replay after requeue
            self.last_tok[req.slot] = req.out_tokens[-1]
            if self.tracer:
                self.tracer.req_transition(
                    req.rid, "DECODE",
                    self.tracer.ts(self.step_idx, TRC.OFF_DECODE),
                    args={"replay": True})
            return
        toks, finite = self._postprocess(logits_row, [req])
        if not finite[0]:
            # per-request isolation (no ref retry for prefill: the chunked
            # prefix pages are already written, a divergent prompt stays
            # divergent — quarantine is decode's cheap path, prefill just
            # fails the one request). The open PREFILL span closes in _fail.
            self._fault("failed_prefill")
            self._fail(req, "nonfinite_prefill")
            return
        if self.tracer:
            self.tracer.req_transition(
                req.rid, "DECODE",
                self.tracer.ts(self.step_idx, TRC.OFF_DECODE))
        self._emit(req, int(toks[0]))

    def _run_chunk(self, req: Request) -> int:
        """One bucketed chunk of ``req``'s (effective) prompt through the
        jitted chunk step. Returns the work units spent (padded width)."""
        eff = req.effective_prompt
        remaining = len(eff) - req.prefill_pos
        width = min(self.chunk, remaining)
        bucket = ST.bucket_for(width, self.chunk)
        tok = np.zeros((1, bucket), np.int32)
        tok[0, :width] = eff[req.prefill_pos:req.prefill_pos + width]
        view = self._map_pools(
            lambda pool: pool_with_tables(
                pool, self.table[req.slot][None],
                np.asarray([req.prefill_pos], np.int32)), self.state)
        t0 = time.time()
        logits, new_state = self._chunk_fn(
            self.params, jnp.asarray(tok), view,
            jnp.asarray([req.prefill_pos], jnp.int32),
            jnp.asarray([width - 1], jnp.int32))
        logits.block_until_ready()
        self._w_prefill_s.inc(time.time() - t0)
        self._adopt_pool_data(new_state)
        # bounded prefix fetch reads ceil(chunk_start / page) pages — the
        # live prefix BELOW this chunk's start — where the full-span fetch
        # would stream the whole page-table span every chunk
        self._c_fetch_bounded.inc(-(-req.prefill_pos // self.page))
        self._c_fetch_full.inc(self.span_pages)
        self._h_chunk_width.observe(bucket)
        if self.tracer:
            self.tracer.req_chunk(req.rid, self.step_idx,
                                  args={"width": width, "bucket": bucket,
                                        "pos": req.prefill_pos})
        req.prefill_pos += width
        self.allocator.mark_ready(req.pages, req.prefill_pos)
        if req.prefill_pos == len(eff):
            self._finish_prefill(req, logits)
        return bucket

    def _prefill_chunked(self) -> int:
        """Budgeted chunk scheduling: FCFS round-robin passes over the
        PREFILLING requests, one bucketed chunk each, until the per-step
        token budget is spent (0 = exactly one pass). The FCFS head always
        gets at least one chunk, so prefill can never starve."""
        budget = self.ecfg.prefill_budget
        spent = 0
        while True:
            reqs = self.scheduler.prefilling
            if not reqs:
                break
            for req in reqs:
                if budget > 0 and spent and spent >= budget:
                    return spent
                spent += self._run_chunk(req)
            if budget <= 0:
                break                       # exactly one round-robin pass
        return spent

    def _prefill_monolithic(self, admitted: list[Request]) -> int:
        """PR-4 style one-shot prefill of this step's admissions, batched by
        (effective) prompt length — the chunked path's benchmark twin."""
        by_len: dict[int, list[Request]] = {}
        for r in admitted:
            by_len.setdefault(len(r.effective_prompt), []).append(r)
        spent = 0
        for length, group in by_len.items():
            rows = np.stack([self.table[r.slot] for r in group])
            prompts = jnp.asarray(
                np.stack([r.effective_prompt for r in group]), jnp.int32)
            view = self._map_pools(
                lambda pool: pool_with_tables(
                    pool, rows, np.zeros((len(group),), np.int32)),
                self.state)
            t0 = time.time()
            logits, new_state = self._prefill_fn(self.params, prompts, view)
            logits.block_until_ready()
            self._w_prefill_s.inc(time.time() - t0)
            self._adopt_pool_data(new_state)
            self._h_chunk_width.observe(length)
            for r in group:
                self.allocator.mark_ready(r.pages, length)
            fresh = [r for r in group if not r.out_tokens]
            replay = [r for r in group if r.out_tokens]
            for r in replay:
                r.status = Status.DECODE
                self.last_tok[r.slot] = r.out_tokens[-1]
                if self.tracer:
                    self.tracer.req_transition(
                        r.rid, "DECODE",
                        self.tracer.ts(self.step_idx, TRC.OFF_DECODE),
                        args={"replay": True})
            if fresh:
                idx = [group.index(r) for r in fresh]
                toks, finite = self._postprocess(logits[np.asarray(idx)],
                                                 fresh)
                for r, tok, ok in zip(fresh, toks, finite):
                    if not ok:           # isolate the poisoned row only
                        self._fault("failed_prefill")
                        self._fail(r, "nonfinite_prefill")
                        continue
                    r.status = Status.DECODE
                    if self.tracer:
                        self.tracer.req_transition(
                            r.rid, "DECODE",
                            self.tracer.ts(self.step_idx, TRC.OFF_DECODE))
                    self._emit(r, int(tok))
            spent += length * len(group)
        return spent

    # ------------------------------------------------------------------
    # growth / eviction
    # ------------------------------------------------------------------

    def _ensure_capacity(self) -> None:
        """Before a decode step, every decoding request must have a page
        slot for the token the step will append (position ``seq_len``).
        Grow by one page on demand; when the pool is exhausted (or a
        FaultPlan forces exhaustion), pick a victim: a blown-deadline
        request is CANCELLED (pages freed mid-decode — its answer is
        already worthless), otherwise the youngest active request is
        requeued (FCFS fairness) and the growth retried."""
        forced = bool(self.fault_plan
                      and self.fault_plan.alloc_fail(self.step_idx))
        for req in list(self.scheduler.active):
            if req.status is not Status.DECODE:
                continue
            while req.seq_len >= len(req.pages) * self.page:
                assert len(req.pages) < self.span_pages, \
                    "submit() validation bounds the page run"
                grown = None if forced else self.allocator.grow(1)
                if grown is not None:
                    req.pages.extend(grown)
                    self.table[req.slot, len(req.pages) - 1] = grown[0]
                    continue
                victim = self.scheduler.eviction_victim(self.step_idx)
                if victim is None:
                    break
                self._c_evictions.inc()
                if victim.any_deadline_blown(self.step_idx):
                    self._fault("deadline_cancelled")
                    self._fail(victim, "deadline")
                else:
                    self._requeue(victim)
                if victim is req:
                    break
                if forced and victim is not req:
                    # the injected exhaustion freed real pages; stop forcing
                    # so the freed pages are actually usable this step
                    forced = False
            if self.proposer is None or req.status is not Status.DECODE:
                continue
            # opportunistic draft coverage: grow toward room for the slot's
            # adaptive draft (entries at seq_len .. seq_len + draft), but
            # NEVER evict for it — speculation degrades to shorter drafts
            # under pool pressure instead of displacing other requests
            want = min(self.proposer.draft_len(str(req.rid)),
                       req.max_new - len(req.out_tokens) - 1)
            while (want > 0 and len(req.pages) < self.span_pages
                   and req.seq_len + want + 1 > len(req.pages) * self.page):
                grown = None if forced else self.allocator.grow(1)
                if grown is None:
                    break
                req.pages.extend(grown)
                self.table[req.slot, len(req.pages) - 1] = grown[0]

    # ------------------------------------------------------------------
    # the step loop
    # ------------------------------------------------------------------

    def _dispatch_decode(self, state, seq_lens):
        """The primary jitted decode dispatch, degraded to the jnp_ref twin
        when it raises (or a FaultPlan injects a raise) BEFORE the donated
        buffers are consumed. A failure from inside the compiled program
        (after donation) is not recoverable here and propagates."""
        tok = jnp.asarray(self.last_tok)
        lens = jnp.asarray(seq_lens)
        try:
            if self.fault_plan and self.fault_plan.backend_raise(
                    self.step_idx):
                raise RuntimeError(
                    f"injected backend failure at step {self.step_idx}")
            return self._decode_fn(self.params, tok, state, lens)
        except Exception:
            self._fault("backend_faults")
            self._fault("ref_fallback_steps")
            if self.tracer:
                self.tracer.engine_instant(
                    self.step_idx, TRC.PHASE_WINDOWS["decode"][0] + 10,
                    "backend_fault", args={"fallback": "jnp_ref"})
            return self._ref_decode_fn()(self.params, tok, state, lens)

    def _ref_verify_decode_fn(self):
        """The jnp_ref-backend verify twin (lazy, undonated — mirrors
        ``_ref_decode_fn``)."""
        if self._ref_verify_fn is None:
            self._ref_verify_fn = jax.jit(
                ST.make_verify_step(self.cfg, ref=True))
        return self._ref_verify_fn

    def _dispatch_verify(self, state, tokens, starts):
        """The jitted speculative-verify dispatch, degraded to the jnp_ref
        verify twin when it raises before consuming the donated buffers
        (same contract as ``_dispatch_decode``)."""
        try:
            if self.fault_plan and self.fault_plan.backend_raise(
                    self.step_idx):
                raise RuntimeError(
                    f"injected backend failure at step {self.step_idx}")
            return self._verify_fn(self.params, tokens, state, starts)
        except Exception:
            self._fault("backend_faults")
            self._fault("ref_fallback_steps")
            if self.tracer:
                self.tracer.engine_instant(
                    self.step_idx, TRC.PHASE_WINDOWS["decode"][0] + 10,
                    "backend_fault", args={"fallback": "jnp_ref"})
            return self._ref_verify_decode_fn()(self.params, tokens, state,
                                                starts)

    def _spec_decode(self, active: list[Request]) -> None:
        """Self-speculative step for every decoding slot: draft (host-side
        n-gram lookup), verify all drafts in ONE q_len>1 dispatch, commit
        the longest accepted prefix, roll back the rest by NOT advancing the
        host's token bookkeeping (the rejected entries' pool bytes are
        masked by the next step's pushed ``seq_lens`` — pages never move).

        Verify row t of a slot carries [last_tok, d_1..d_v, pad...][t] at
        absolute position ``seq_len + t`` with kernel limit
        ``seq_len + t + 1``; its sampled token uses the SAME fold_in key a
        sequential decode would (count = len(out_tokens) + t), so greedy
        AND sampled engine output is token-identical to non-speculative —
        the drafter only ever changes HOW MANY of those exact sequential
        samples land per step."""
        e = self.ecfg
        K = e.spec_draft_len + 1
        tokens = np.zeros((e.max_batch, K), np.int32)
        starts = np.zeros((e.max_batch,), np.int32)
        table_view = np.zeros_like(self.table)
        drafts: dict[int, list[int]] = {}
        for r in active:
            # trim the draft to what the slot can actually use: committed
            # entries land at seq_len..seq_len+v (v+1 of them), the run is
            # bounded by allocated pages, and drafting past max_new-1 new
            # tokens is wasted work
            budget = min(e.spec_draft_len,
                         r.max_new - len(r.out_tokens) - 1,
                         len(r.pages) * self.page - r.seq_len - 1)
            d: list[int] = []
            if budget > 0:
                ctx = [int(t) for t in r.prompt] + list(r.out_tokens)
                d = self.proposer.propose(str(r.rid), ctx, budget)
            drafts[r.rid] = d
            row = [int(self.last_tok[r.slot])] + d
            tokens[r.slot, :len(row)] = row
            starts[r.slot] = r.seq_len
            table_view[r.slot] = self.table[r.slot]
        state = self._state_with_tables(table_view, starts)
        t0 = time.time()
        logits, self.state = self._dispatch_verify(
            state, jnp.asarray(tokens), jnp.asarray(starts))
        if self.fault_plan:
            live = {r.slot for r in active}
            for ev in self.fault_plan.nan_slots(self.step_idx):
                if ev.slot in live:
                    self.fault_plan._log(self.step_idx, "nan_logits",
                                         ev.slot)
                    logits = logits.at[ev.slot, 0, 0].set(jnp.nan)
        # flatten to one postprocess row per CANDIDATE (slot, position):
        # counts advance by position so the sampling keys are exactly the
        # sequential ones
        flat_reqs: list[Request] = []
        flat_counts: list[int] = []
        sel_slots: list[int] = []
        sel_pos: list[int] = []
        for r in active:
            for t in range(len(drafts[r.rid]) + 1):
                flat_reqs.append(r)
                flat_counts.append(len(r.out_tokens) + t)
                sel_slots.append(r.slot)
                sel_pos.append(t)
        rows = logits[np.asarray(sel_slots), np.asarray(sel_pos)]
        toks, finite = self._postprocess(rows, flat_reqs, counts=flat_counts)
        self._w_decode_s.inc(time.time() - t0)

        # deterministic work/traffic accounting: every verify row visits
        # blocks up to its own per-row limit (seq_len + t + 1)
        self._c_blocks_visited.inc(int(sum(
            -(-(r.seq_len + t + 1) // self.page)
            for r in active for t in range(K))))
        self._c_blocks_full.inc(len(active) * K * self.span_pages)
        cost = BK.dispatch_cost(
            self._backend,
            tokens_visited=sum(r.seq_len + t + 1
                               for r in active for t in range(K)),
            tokens_full=len(active) * K * self.span_pages * self.page,
            heads=self.cfg.n_heads, d_c=self.cfg.mla.d_c,
            d_r=self.cfg.mla.d_rope, fmt=self.cfg.kv_fmt,
            device_kind=self._device_kind)
        self._c_roof_bytes.inc(cost["bytes"])
        self._c_roof_bytes_min.inc(cost["bytes_min"])
        self._c_roof_flops.inc(cost["flops"])
        self._g_roof_frac.set(cost["achieved_fraction"])

        # longest-accepted-prefix commit: emit the exact sequential samples
        # while each drafted token matches; stop at the first mismatch (its
        # corrective sample still lands — the guaranteed one-token floor),
        # at retirement (EOS/max_new), or at a non-finite row (sequential
        # quarantine semantics at the already-advanced position)
        idx = 0
        n_drafted = n_accepted = n_emitted = 0
        for r in active:
            d = drafts[r.rid]
            v = len(d)
            committed = 0
            bad = False
            for j in range(v + 1):
                fi = idx + j
                if not finite[fi]:
                    bad = True
                    break
                tok = int(toks[fi])
                self._emit(r, tok)
                n_emitted += 1
                committed += 1
                if r.status is not Status.DECODE:
                    break
                if j < v and tok == d[j]:
                    continue
                break
            idx += v + 1
            accepted = max(committed - 1, 0)
            n_drafted += v
            n_accepted += accepted
            if bad:
                self._quarantine(r)
            elif r.status is Status.DECODE:
                self.proposer.observe(str(r.rid), v, accepted)

        self._c_decode_tokens.inc(n_emitted)
        self._c_work.inc(n_emitted)
        self._c_spec_steps.inc()
        self._c_spec_slot_steps.inc(len(active))
        self._c_spec_drafted.inc(n_drafted)
        self._c_spec_accepted.inc(n_accepted)
        drafted_total = self._c_spec_drafted.value
        self._g_spec_accept_rate.set(
            self._c_spec_accepted.value / drafted_total
            if drafted_total else 0.0)
        if self.tracer:
            # verify spans ride the decode phase window (args mark them)
            self.tracer.step_phase(
                self.step_idx, "decode",
                args={"verify": True, "rows": len(active), "q_len": K,
                      "drafted": n_drafted, "accepted": n_accepted,
                      "model_bytes": cost["bytes"],
                      "achieved_fraction": cost["achieved_fraction"]})
            self.tracer.step_phase(self.step_idx, "postprocess",
                                   args={"rows": len(flat_reqs)})

    def step(self) -> None:
        """One engine iteration: sweep deadlines, admit, run (budgeted)
        prefill work, grow, one decode step for every decoding slot, retire
        finished requests. Advances virtual time even when idle (so future
        arrivals are reached)."""
        self._sweep_deadlines()
        decode_in_flight = any(r.status is Status.DECODE
                               for r in self.scheduler.active)
        finished_before = len(self.scheduler.finished)
        admitted = self._admit()
        if self.tracer and admitted:
            self.tracer.step_phase(self.step_idx, "admit",
                                   args={"admitted": len(admitted)})
        t_pre = time.time()
        if self.chunk > 0:
            spent = self._prefill_chunked()
        else:
            spent = self._prefill_monolithic(admitted)
        self._c_prefill_tokens.inc(spent)
        self._c_work.inc(spent)
        self.prefill_tokens_series.append(spent)
        # decode-stall accounting: prefill work that ran while decodes were
        # in flight is exactly the work that would have stalled them
        self.stall_tokens_series.append(spent if decode_in_flight else 0)
        if decode_in_flight:
            self._w_stall_s.inc(time.time() - t_pre)
        if self.tracer and spent:
            self.tracer.step_phase(self.step_idx, "prefill",
                                   args={"tokens": spent,
                                         "stalled_decodes": decode_in_flight})

        self._ensure_capacity()
        # growth-pressure evictions may have queued offloads: copy those
        # pages' bytes out before the decode dispatch can overwrite them
        self._drain_tier_ops()
        active = [r for r in self.scheduler.active
                  if r.status is Status.DECODE]
        if active and self.proposer is not None:
            self._spec_decode(active)
        elif active:
            seq_lens = np.zeros((self.ecfg.max_batch,), np.int32)
            table_view = np.zeros_like(self.table)
            for r in active:
                seq_lens[r.slot] = r.seq_len
                table_view[r.slot] = self.table[r.slot]
            state = self._state_with_tables(table_view, seq_lens)
            t0 = time.time()
            logits, self.state = self._dispatch_decode(state, seq_lens)
            if self.fault_plan:
                # injected numerics fault: poison the scheduled slots'
                # logits rows (models a kernel emitting NaN — the cache
                # append already ran on clean values, exactly like a real
                # attention-output fault)
                live = {r.slot for r in active}
                for ev in self.fault_plan.nan_slots(self.step_idx):
                    if ev.slot in live:
                        self.fault_plan._log(self.step_idx, "nan_logits",
                                             ev.slot)
                        logits = logits.at[ev.slot, 0].set(jnp.nan)
            slots = np.array([r.slot for r in active], np.int32)
            # split-KV early exit: each row visits ceil(seq_len / page)
            # blocks; a dense decode would sweep the full span per row
            self._c_blocks_visited.inc(int(
                sum(-(-r.seq_len // self.page) for r in active)))
            self._c_blocks_full.inc(len(active) * self.span_pages)
            # analytic roofline annotation of this dispatch (model, not
            # measurement: pure function of the visited-token counts)
            cost = BK.dispatch_cost(
                self._backend,
                tokens_visited=sum(r.seq_len for r in active),
                tokens_full=len(active) * self.span_pages * self.page,
                heads=self.cfg.n_heads, d_c=self.cfg.mla.d_c,
                d_r=self.cfg.mla.d_rope, fmt=self.cfg.kv_fmt,
                device_kind=self._device_kind)
            self._c_roof_bytes.inc(cost["bytes"])
            self._c_roof_bytes_min.inc(cost["bytes_min"])
            self._c_roof_flops.inc(cost["flops"])
            self._g_roof_frac.set(cost["achieved_fraction"])
            if self.tracer:
                self.tracer.step_phase(
                    self.step_idx, "decode",
                    args={"rows": len(active),
                          "model_bytes": cost["bytes"],
                          "achieved_fraction": cost["achieved_fraction"]})
            toks, finite = self._postprocess(logits[slots], active)
            self._w_decode_s.inc(time.time() - t0)
            self._c_decode_tokens.inc(len(active))
            self._c_work.inc(len(active))
            if self.tracer:
                self.tracer.step_phase(self.step_idx, "postprocess",
                                       args={"rows": len(active)})
            for r, tok, ok in zip(active, toks, finite):
                if not ok:
                    # per-slot quarantine: THIS request degrades (ref retry
                    # or typed FAILED); every other slot emits as usual
                    self._quarantine(r)
                    continue
                self._emit(r, int(tok))
        live = sum(r.seq_len if r.status is Status.DECODE else r.prefill_pos
                   for r in self.scheduler.active)
        self.util_series.append(self.allocator.stats(live).utilization)
        if self.tracer:
            retired = len(self.scheduler.finished) - finished_before
            if retired:
                self.tracer.step_phase(self.step_idx, "retire",
                                       args={"requests": retired})
            a = self.allocator
            self.tracer.counter(self.step_idx, "pages",
                                {"in_use": a.num_in_use, "free": a.num_free,
                                 "cached": a.num_cached})
        if self.quant_probe and self.quant_probe.due(self.step_idx):
            self.quant_probe.sample(
                self.step_idx, self._map_pools, self.state,
                resident_pages=self.allocator.resident_pages(),
                sink_pages={r.pages[0] for r in self.scheduler.active
                            if r.pages})
        self._c_steps.inc()
        self.step_idx += 1

    # ------------------------------------------------------------------
    # checkpoint / restore (host bookkeeping + device pool pages)
    # ------------------------------------------------------------------

    def _host_state(self) -> dict:
        """Everything host-owned a restore needs: the scheduler's request
        population (queue order + slot map + finished), the allocator's
        free list/refcounts/prefix registry, the page tables and pending
        tokens, counters, and wall-clock marks. JSON-safe (rides in the
        checkpoint manifest; device pool pages ride in arrays.npz)."""
        sched = self.scheduler
        return {
            "step_idx": self.step_idx,
            "queue": [_req_to_record(r) for r in sched.queue],
            "slots": [None if r is None else _req_to_record(r)
                      for r in sched.slots],
            "finished": [_req_to_record(r) for r in sched.finished],
            "sched_requeues": sched.requeues,
            "allocator": self.allocator.export_state(),
            "host_tier": (self.tier.export_state()
                          if self.tier is not None else None),
            "spec": (self.proposer.export_state()
                     if self.proposer is not None else None),
            "table": self.table.tolist(),
            "last_tok": self.last_tok.tolist(),
            "seen_rids": sorted(self._seen_rids),
            "wall": {str(rid): dict(marks)
                     for rid, marks in self._wall.items()},
            "faults": dict(self.faults),
            "counters": {
                "prefill_tokens_series": self.prefill_tokens_series,
                "stall_tokens_series": self.stall_tokens_series,
                "util_series": self.util_series,
            },
            # the registry is the single source of truth for every scalar
            # counter; the tracer state keeps span ids unique across a
            # restore so the resumed run appends to the SAME trace
            "registry": self.registry.export_state(),
            "trace": (self.tracer.export_state()
                      if self.tracer is not None else None),
        }

    def snapshot(self, directory: str, *, keep: int = 3) -> str:
        """Atomic engine checkpoint: device pool pages (the jitted state
        pytree) in arrays.npz, host bookkeeping in the manifest (including
        the host tier's offloaded page payloads). Returns the published
        checkpoint path."""
        # pending tier data movement must land before the state is captured
        self._drain_tier_ops()
        return CK.save_checkpoint(directory, self.step_idx, self.state,
                                  extra_manifest={"engine":
                                                  self._host_state()},
                                  keep=keep)

    def restore(self, path: str) -> None:
        """Adopt a snapshot into THIS engine (same ModelConfig/EngineConfig
        — the jitted functions and pool geometry are reused; only state is
        replaced). Resumed decoding is token-identical to the uninterrupted
        run: page tables, seq_lens, pending last tokens and the FP8 pool
        pages all round-trip, and sampling keys derive from (rid, token
        count) so draws continue exactly where they stopped."""
        tree, manifest = CK.load_checkpoint(path, self.state)
        self.state = tree
        host = manifest["engine"]
        sched = Scheduler(self.ecfg.max_batch, max_queue=self.ecfg.max_queue)
        by_state = [_req_from_record(rec) for rec in host["queue"]]
        for req in by_state:
            sched.queue.append(req)
        sched.slots = [None if rec is None else _req_from_record(rec)
                       for rec in host["slots"]]
        sched.finished = [_req_from_record(rec) for rec in host["finished"]]
        sched.requeues = int(host["sched_requeues"])
        self.scheduler = sched
        # tier payloads first: the allocator's invariant check cross-
        # references host-slot ownership against the restored tier
        tier_state = host.get("host_tier")
        if tier_state is not None:
            if self.tier is None:
                raise ValueError(
                    "checkpoint carries a host tier but this engine has "
                    "host_tier_pages == 0")
            self.tier.restore_state(tier_state)
        self.allocator.restore_state(host["allocator"])
        if self.proposer is not None:
            self.proposer.restore_state(host.get("spec") or {})
        self.table = np.asarray(host["table"], np.int32)
        self.last_tok = np.asarray(host["last_tok"], np.int32)
        self._seen_rids = set(host["seen_rids"])
        self._wall = {int(rid): {k: float(v) for k, v in marks.items()}
                      for rid, marks in host["wall"].items()}
        c = host["counters"]
        self.prefill_tokens_series = list(c["prefill_tokens_series"])
        self.stall_tokens_series = list(c["stall_tokens_series"])
        self.util_series = list(c["util_series"])
        # the registry round-trips every scalar counter (faults included);
        # restore the values, then re-materialize the full fault label set
        # and count this restore itself
        self.registry.restore_state(host["registry"])
        for kind in FAULT_KINDS:
            self._c_faults.labels(kind=kind)
        self._fault("restores")
        if self.tracer is not None and host.get("trace") is not None:
            self.tracer.restore_state(host["trace"])
        self.step_idx = int(host["step_idx"])

    def run(self, requests: list[Request], *, ckpt_dir: str | None = None,
            ckpt_every: int = 0) -> list[RequestResult]:
        """Run a workload to drain. ``requests`` carry virtual arrival times
        (in engine steps); a request is enqueued once the engine clock
        reaches it — deterministic for a fixed workload + seed.

        With ``ckpt_dir`` set, the engine snapshots every ``ckpt_every``
        steps (and at a preemption). A preemption request (from the
        ``PreemptionHandler`` or an injected ``preempt`` fault) makes the
        run snapshot and raise ``EnginePreempted`` at the next step
        boundary; re-running the same workload on an engine restored from
        the latest checkpoint resumes token-identically — requests already
        seen before the snapshot are skipped on resubmission."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        i = 0
        while i < len(pending) or not self.scheduler.drained:
            while i < len(pending) and pending[i].arrival <= self.step_idx:
                req = pending[i]
                i += 1
                if req.rid in self._seen_rids:
                    continue          # restored engine already carries it
                self.submit(req)
            if (self.fault_plan and self.preemption is not None
                    and self.fault_plan.preempt(self.step_idx)):
                self.preemption.trigger()
            self.step()
            preempted = (self.preemption is not None
                         and getattr(self.preemption, "requested", False))
            if preempted:
                self._fault("preemptions")
                if self.tracer:
                    self.tracer.engine_instant(
                        self.step_idx, 0, "preemption",
                        args={"snapshot": bool(ckpt_dir)})
            if ckpt_dir and (preempted or (
                    ckpt_every and self.step_idx % ckpt_every == 0)):
                self.snapshot(ckpt_dir)
            if preempted:
                raise EnginePreempted(
                    f"preempted at step {self.step_idx} "
                    f"(snapshot: {ckpt_dir or 'none'})")
        out = []
        for r in sorted(self.scheduler.finished, key=lambda r: r.rid):
            w = self._wall[r.rid]
            out.append(RequestResult(
                rid=r.rid, status=r.status.value,
                tokens=[int(t) for t in r.out_tokens],
                prompt_len=r.prompt_len,
                ttft_steps=(r.first_token_step - int(r.arrival)
                            if r.first_token_step >= 0 else -1),
                latency_steps=r.finish_step - int(r.arrival),
                ttft_work=(r.first_token_work - r.arrival_work
                           if r.first_token_work >= 0 else -1),
                requeues=r.requeues,
                ttft_s=w.get("first", w["finish"]) - w["arrival"],
                latency_s=w["finish"] - w["arrival"],
                fail_reason=r.fail_reason))
        return out

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def metrics(self) -> dict[str, Any]:
        stats = self.allocator.stats()
        tps = self.decode_tokens / self.decode_seconds \
            if self.decode_seconds else 0.0
        roof_bytes = self._c_roof_bytes.value
        return {
            "steps": self.step_idx,
            "decode_tokens": self.decode_tokens,
            "evictions": self.evictions,
            "requeues": self.scheduler.requeues,
            # wall-clock family: machine-dependent by construction, so it
            # lives under ONE subtree that gating must never reach into
            # (scripts/bench_gate.py asserts no gated path contains "wall")
            "wall": {
                "decode_tok_per_s": tps,
                "decode_seconds": self.decode_seconds,
                "prefill_seconds": self.prefill_seconds,
                "stall_seconds": self.stall_seconds,
            },
            "prefill": {
                "mode": "chunked" if self.chunk else "monolithic",
                "chunk": self.chunk,
                "budget": self.ecfg.prefill_budget,
                "traces": self.prefill_traces,
                "tokens": self.prefill_tokens,
                "tokens_series": self.prefill_tokens_series,
            },
            "work": {
                "total": self.work_done,
                "stall_tokens_total": int(sum(self.stall_tokens_series)),
                "stall_tokens_series": self.stall_tokens_series,
            },
            "roofline": {
                "backend": self._backend.name,
                "model_bytes": roof_bytes,
                "bytes_min": self._c_roof_bytes_min.value,
                "flops": self._c_roof_flops.value,
                "achieved_fraction_total": (
                    self._c_roof_bytes_min.value / roof_bytes
                    if roof_bytes else 0.0),
                "achieved_fraction_last": self._g_roof_frac.value,
            },
            "fetch_work": {
                "pages_fetched_bounded": self.pages_fetched_bounded,
                "pages_fetched_full": self.pages_fetched_full,
                "fetch_savings": (
                    1.0 - self.pages_fetched_bounded / self.pages_fetched_full
                    if self.pages_fetched_full else 0.0),
                "decode_blocks_visited": self.decode_blocks_visited,
                "decode_blocks_full": self.decode_blocks_full,
                "early_exit_savings": (
                    1.0 - self.decode_blocks_visited / self.decode_blocks_full
                    if self.decode_blocks_full else 0.0),
            },
            "pages": {
                "capacity": stats.capacity,
                "free": stats.free,
                "in_use": stats.in_use,
                "cached": stats.cached,
                "peak_in_use": stats.peak_in_use,
                "total_allocs": stats.total_allocs,
                "saved_by_sharing": stats.pages_saved_by_sharing,
            },
            "prefix_cache": {
                "budget_pages": self.ecfg.prefix_cache_pages,
                "host_tier_pages": self.ecfg.host_tier_pages,
                "cached": stats.cached,
                "resident": stats.resident,
                "peak_resident": stats.peak_resident,   # HBM high-water
                "reused_cached": stats.pages_reused_cached,
                "restored_host": stats.pages_restored_host,
                "offloads": stats.host_offloads,
                "drops": stats.cache_drops,
                "host_used": stats.host_used,
                "prefill_skipped_tokens": self.prefill_skipped_tokens,
                "nodes": (len(self.allocator.tree)
                          if self.allocator.tree is not None else 0),
            },
            "speculative": {
                "enabled": self.proposer is not None,
                "draft_len": self.ecfg.spec_draft_len,
                "verify_steps": self._c_spec_steps.value,
                "drafted_tokens": self.spec_drafted_tokens,
                "accepted_tokens": self.spec_accepted_tokens,
                "accept_rate": (
                    self.spec_accepted_tokens / self.spec_drafted_tokens
                    if self.spec_drafted_tokens else 0.0),
                # committed tokens per decoding SLOT per step: the headline
                # (non-speculative decode is exactly 1.0 by construction)
                "accepted_tokens_per_step": (
                    self.decode_tokens / self._c_spec_slot_steps.value
                    if self._c_spec_slot_steps.value else 0.0),
            },
            "utilization_series": self.util_series,
            "faults": {
                **self.faults,
                "injected": (list(self.fault_plan.fired)
                             if self.fault_plan else []),
            },
        }
