"""Profile-driven ``num_splits`` autotuner for the split-KV decode kernels.

Replaces the static context-length heuristic (``ops.default_num_splits``) as
the *primary* source of split counts: a small measured-sweep cache keyed on
``(capacity, block_n, batch)`` — the three shape parameters that move the
split/combine trade-off — persisted to a JSON artifact that the benchmarks
emit (``benchmarks/kernel_perf.py::emit_split_profile``). Resolution order in
``ops.resolve_num_splits``:

  1. exact profile hit for (capacity, block_n, batch)   -> measured best
  2. nearest-batch hit: an entry with the same capacity, block_n, and layout
     at a different batch -> its best (nearest in log-batch; the trade-off
     scales roughly with batch ratio, so 64 is "closer" to 128 than to 8)
  3. no usable entry / no profile file                  -> heuristic fallback

The profile file format (version 2); the key grows a "/paged" suffix for
sweeps measured on the paged kernel (contiguous and paged plans never mix)
and an "/amla" suffix for sweeps timed under the combine-free AMLA rescale
(FMA, the default, keeps the bare key — existing profiles stay exact hits).
"best" prefers smaller split counts within WIN_MARGIN so measurement
jitter can't flip a plan away from the bit-exact single-pass path:

    {
      "version": 2,
      "entries": {
        "<capacity>/<block_n>/<batch>": {
          "best": 4,
          "best_us": 421.9,
          "measured_us": {"1": 812.3, "2": 530.1, "4": 421.9, "8": 455.0}
        },
        "<capacity>/<block_n>/<batch>/paged": {...}
      }
    }

v2 over v1: each entry records ``best_us`` — the measured time OF the
recorded best — so entries at DIFFERENT block_n for the same
(capacity, batch, layout) are comparable and the joint 2D
``(num_splits, block_n)`` plan (``lookup_config`` / ``tuned_split_config``)
falls out of the same flat key space. v1 files still load: ``best_us`` is
derived from the v1 entry's own ``measured_us[best]`` on demand, so an
existing ``BENCH_splits_profile.json`` keeps driving plans unchanged.

The default artifact path is ``BENCH_splits_profile.json`` at the repo root
(next to BENCH_splitkv.json); override with ``SNAPMLA_SPLIT_PROFILE``. The
module-level singleton loads it lazily once; ``reset()`` drops it (tests).
"""
from __future__ import annotations

import json
import os
import pathlib
import time
from typing import NamedTuple

PROFILE_ENV = "SNAPMLA_SPLIT_PROFILE"
PROFILE_VERSION = 2
_LOADABLE_VERSIONS = (1, 2)    # v1 entries are a strict subset of v2's


class SplitConfig(NamedTuple):
    """A joint split-KV plan: how many splits, at which KV block size."""

    num_splits: int
    block_n: int

# Anchored at the repo root (autotune.py is src/repro/kernels/mla_decode/),
# NOT the process CWD — `serve` launched from any directory and `pytest` from
# the repo root must agree on which profile (if any) is in effect.
DEFAULT_PROFILE = (pathlib.Path(__file__).resolve().parents[4]
                   / "BENCH_splits_profile.json")


def profile_path() -> pathlib.Path:
    override = os.environ.get(PROFILE_ENV)
    return pathlib.Path(override) if override else DEFAULT_PROFILE


def _key(capacity: int, block_n: int, batch: int, layout: str,
         rescale: str = "fma") -> str:
    base = f"{int(capacity)}/{int(block_n)}/{int(batch)}"
    if layout != "contiguous":
        base = f"{base}/{layout}"
    # the FMA rescale is the default path and keeps the PR-8 key shape, so
    # existing profile files stay exact hits; AMLA sweeps get their own
    # suffix — the two emission paths' timings never drive each other
    return base if rescale == "fma" else f"{base}/{rescale}"


def _parse_key(key: str) -> tuple[int, int, int, str, str] | None:
    """Inverse of ``_key``: '<cap>/<bn>/<batch>[/<layout>][/amla]' ->
    (capacity, block_n, batch, layout, rescale), or None for malformed keys
    (hand-edited files must not crash resolution)."""
    parts = key.split("/")
    rescale = "fma"
    if parts and parts[-1] == "amla":
        rescale = parts.pop()
    if len(parts) == 3:
        parts = parts + ["contiguous"]
    if len(parts) != 4:
        return None
    try:
        return int(parts[0]), int(parts[1]), int(parts[2]), parts[3], rescale
    except ValueError:
        return None


# A smaller split count must be beaten by at least this margin before a larger
# one is recorded as "best": ties within measurement noise go to fewer splits,
# so num_splits=1 (the bit-exact seed path) is only abandoned for a real win
# and re-measuring doesn't flip the plan on jitter.
WIN_MARGIN = 0.05


def _pick_best(measured_us: dict[int, float]) -> int:
    best = None
    for s in sorted(measured_us):
        if best is None or measured_us[s] < measured_us[best] * (1 - WIN_MARGIN):
            best = s
    return best


def _entry_best_us(entry: dict) -> float | None:
    """Measured microseconds of an entry's recorded best — v2 entries carry
    it as ``best_us``; for v1 entries it is derived from the entry's own
    sweep (``measured_us[best]``). None for malformed entries."""
    try:
        if "best_us" in entry:
            return float(entry["best_us"])
        return float(entry["measured_us"][str(int(entry["best"]))])
    except (TypeError, KeyError, ValueError):
        return None


class SplitProfile:
    """In-memory measured-sweep cache: (capacity, block_n, batch, layout) ->
    best num_splits, with the raw measured microseconds kept for the
    benchmarks. ``layout`` separates the contiguous and paged kernels — their
    DMA patterns differ, so a best measured on one never drives the other."""

    def __init__(self, entries: dict | None = None):
        self.entries: dict[str, dict] = dict(entries or {})

    # -- queries ----------------------------------------------------------
    def lookup(self, capacity: int, block_n: int, batch: int | None,
               layout: str = "contiguous", rescale: str = "fma") -> int | None:
        """Measured best split count, or None (-> heuristic fallback)."""
        if batch is None:
            return None
        e = self.entries.get(_key(capacity, block_n, batch, layout, rescale))
        try:
            return int(e["best"]) if e else None
        except (TypeError, KeyError, ValueError):
            return None          # malformed entry -> heuristic fallback

    def lookup_nearest(self, capacity: int, block_n: int, batch: int | None,
                       layout: str = "contiguous",
                       rescale: str = "fma") -> int | None:
        """Exact hit, else nearest-neighbor batch interpolation: among the
        entries sharing (capacity, block_n, layout, rescale), the best of the
        batch nearest in log-space (ties go to the smaller batch — closer to
        the conservative fewer-splits regime). The split/combine trade-off
        moves with the batch *ratio*, not the difference, hence log distance.
        None if no comparable entry exists (-> heuristic fallback)."""
        exact = self.lookup(capacity, block_n, batch, layout, rescale)
        if exact is not None or batch is None:
            return exact
        candidates: list[tuple[float, int, int]] = []
        for key, entry in self.entries.items():
            parsed = _parse_key(key)
            if parsed is None or parsed[:2] != (capacity, block_n) \
                    or parsed[3] != layout or parsed[4] != rescale:
                continue
            b = parsed[2]
            try:
                best = int(entry["best"])
            except (TypeError, KeyError, ValueError):
                continue         # malformed neighbor -> skip it
            hi, lo = max(b, batch, 1), max(min(b, batch), 1)
            candidates.append((hi / lo, b, best))  # ratio == exp(log dist)
        if not candidates:
            return None
        return min(candidates)[2]

    def lookup_config(self, capacity: int, batch: int | None,
                      layout: str = "contiguous",
                      rescale: str = "fma") -> "SplitConfig | None":
        """Joint 2D plan: among ALL entries sharing (capacity, layout,
        rescale) — any block_n — pick the (num_splits, block_n) whose
        recorded best ran fastest. Exact-batch entries win; otherwise the
        nearest batch in log-space is used (same interpolation rule as
        ``lookup_nearest``), and only that batch's entries compete. Ties in
        measured time go to the smaller block_n. None when no comparable
        entry exists."""
        if batch is None:
            return None
        by_batch: dict[int, list[tuple[float, int, int]]] = {}
        for key, entry in self.entries.items():
            parsed = _parse_key(key)
            if parsed is None or parsed[0] != capacity or parsed[3] != layout \
                    or parsed[4] != rescale:
                continue
            us = _entry_best_us(entry)
            try:
                best = int(entry["best"])
            except (TypeError, KeyError, ValueError):
                continue
            if us is None:
                continue
            by_batch.setdefault(parsed[2], []).append((us, parsed[1], best))
        if not by_batch:
            return None
        if batch in by_batch:
            pool = by_batch[batch]
        else:
            def log_dist(b):
                hi, lo = max(b, batch, 1), max(min(b, batch), 1)
                return (hi / lo, b)
            pool = by_batch[min(by_batch, key=log_dist)]
        us, bn, best = min(pool)
        return SplitConfig(num_splits=best, block_n=bn)

    def record(self, capacity: int, block_n: int, batch: int,
               measured_us: dict[int, float],
               layout: str = "contiguous", rescale: str = "fma") -> int:
        """Store one sweep; best = fastest split count, with ties within
        WIN_MARGIN going to the smaller count. Returns the best."""
        if not measured_us:
            raise ValueError("empty sweep")
        best = _pick_best(measured_us)
        self.entries[_key(capacity, block_n, batch, layout, rescale)] = {
            "best": int(best),
            "best_us": float(measured_us[best]),
            "measured_us": {str(k): float(v) for k, v in measured_us.items()},
        }
        return int(best)

    # -- persistence ------------------------------------------------------
    def save(self, path: str | os.PathLike | None = None) -> pathlib.Path:
        p = pathlib.Path(path) if path else profile_path()
        p.write_text(json.dumps(
            {"version": PROFILE_VERSION, "entries": self.entries},
            indent=2, sort_keys=True) + "\n")
        return p

    @classmethod
    def load(cls, path: str | os.PathLike | None = None) -> "SplitProfile":
        p = pathlib.Path(path) if path else profile_path()
        try:
            payload = json.loads(p.read_text())
        except (OSError, ValueError):
            return cls()
        if payload.get("version") not in _LOADABLE_VERSIONS:
            return cls()
        entries = payload.get("entries", {})
        return cls(entries if isinstance(entries, dict) else {})


_PROFILE: SplitProfile | None = None


def get_profile() -> SplitProfile:
    """Lazily-loaded singleton backing ``ops.resolve_num_splits``."""
    global _PROFILE
    if _PROFILE is None:
        _PROFILE = SplitProfile.load()
    return _PROFILE


def reset(profile: SplitProfile | None = None) -> None:
    """Drop (or swap in) the singleton — tests and benchmark re-runs."""
    global _PROFILE
    _PROFILE = profile


def tuned_num_splits(capacity: int, block_n: int, batch: int | None,
                     layout: str = "contiguous",
                     rescale: str = "fma") -> int | None:
    """Measured best for the shape: exact (capacity, block_n, batch, layout,
    rescale) hit, else nearest-batch interpolation; None -> heuristic
    fallback. AMLA plans only come from AMLA-timed sweeps — its combine-free
    rescaling shifts the split/combine trade-off, so FMA timings never drive
    it (and an un-swept rescale simply falls back to the heuristic)."""
    return get_profile().lookup_nearest(capacity, block_n, batch, layout,
                                        rescale)


def tuned_split_config(capacity: int, batch: int | None,
                       layout: str = "contiguous",
                       rescale: str = "fma") -> SplitConfig | None:
    """Joint measured 2D plan (num_splits, block_n) for the shape — the
    fastest recorded best across every block_n the profile has measured at
    this (capacity, layout, rescale); None -> heuristic fallback."""
    return get_profile().lookup_config(capacity, batch, layout, rescale)


# ---------------------------------------------------------------------------
# Measured sweep (the benchmarks call this to populate the artifact)
# ---------------------------------------------------------------------------

def candidate_splits(capacity: int, block_n: int,
                     max_splits: int = 8) -> list[int]:
    """Powers of two up to min(max_splits, block count) — the shapes the split
    grid can actually take."""
    nblocks = max(1, capacity // block_n)
    out, s = [], 1
    while s <= min(max_splits, nblocks):
        out.append(s)
        s *= 2
    return out

def measure_split_sweep(capacity: int, block_n: int, batch: int,
                        *, d_c: int = 64, d_r: int = 16, heads: int = 8,
                        fmt: str = "fp8_e4m3", fill: float = 0.75,
                        iters: int = 3, profile: SplitProfile | None = None,
                        layout: str = "contiguous",
                        interpret: bool | None = None,
                        rescale: str = "fma", timer=None) -> dict[int, float]:
    """Time the real split-KV kernel over the candidate split counts and
    record the winner into ``profile`` (default: the singleton) under
    ``layout`` ("contiguous" times ``snapmla_decode`` on an MLACache,
    "paged" times ``snapmla_decode_paged`` on a page pool — each layout's
    plan only ever comes from its own kernel's measurements).

    On CPU this times interpret-mode Pallas — relative ordering at small sizes
    is what seeds the cache; on TPU the same sweep measures compiled kernels.
    ``fill`` sets seq_lens = fill * capacity so early exit is in play exactly
    as it would be in serving.

    ``timer`` is the measurement seam: ``timer(num_splits, run) -> float``
    microseconds, where ``run()`` executes the kernel once at that split
    count. The default wall-clock timer compiles then averages ``iters``
    runs; tests inject fixed synthetic timings here so the recorded plan
    (and the WIN_MARGIN tie rule it feeds) is deterministic — wall-clock
    jitter on a shared CI runner must never flip a profile assertion."""
    if timer is None:
        timer = _wall_clock_timer(iters)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kvcache import (CacheConfig, init_mla_cache,
                                    init_paged_mla_cache, mla_prefill,
                                    paged_mla_prefill)
    from repro.kernels.mla_decode import ref as kref
    from repro.kernels.mla_decode.ops import (snapmla_decode,
                                              snapmla_decode_paged)

    key = jax.random.PRNGKey(0)
    cfg = CacheConfig(fmt=fmt, page_size=block_n)
    ks = jax.random.split(key, 4)
    ckv = jax.random.normal(ks[0], (batch, capacity, d_c))
    kr = jax.random.normal(ks[1], (batch, capacity, d_r))
    lens = jnp.asarray(
        np.full((batch,), max(1, int(capacity * fill)), np.int32))
    if layout == "paged":
        cache = paged_mla_prefill(
            init_paged_mla_cache(cfg, batch, capacity, d_c, d_r), cfg, ckv, kr)
    else:
        cache = mla_prefill(
            init_mla_cache(cfg, batch, capacity, d_c, d_r), cfg, ckv, kr)
    cache = cache._replace(seq_lens=lens)
    q_c8, q_r, sq = kref.prepare_q(
        jax.random.normal(ks[2], (batch, heads, d_c)),
        jax.random.normal(ks[3], (batch, heads, d_r)), fmt)
    scale = 1.0 / float(np.sqrt(d_c + d_r))

    def run(s):
        if layout == "paged":
            return snapmla_decode_paged(q_c8, q_r, sq, cache,
                                        softmax_scale=scale, fmt=fmt,
                                        num_splits=s, rescale=rescale,
                                        interpret=interpret)
        return snapmla_decode(q_c8, q_r, sq, cache, softmax_scale=scale,
                              block_n=block_n, fmt=fmt, num_splits=s,
                              rescale=rescale, interpret=interpret)

    measured: dict[int, float] = {}
    for s in candidate_splits(capacity, block_n):
        measured[s] = float(timer(s, lambda: run(s)))

    (profile if profile is not None else get_profile()).record(
        capacity, block_n, batch, measured, layout=layout, rescale=rescale)
    return measured


def _wall_clock_timer(iters: int):
    """Default ``measure_split_sweep`` timer: one warm-up (compile) run,
    then the mean wall-clock of ``iters`` synchronized runs, in us."""
    import jax

    def timer(_s, run):
        o, _ = run()                                        # compile
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(iters):
            o, _ = run()
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / iters * 1e6
    return timer


def synthetic_timer(timings_us: dict[int, float]):
    """Deterministic ``timer`` for tests: fixed microseconds per split count,
    no kernel execution at all."""
    def timer(s, _run):
        return timings_us[s]
    return timer


# ---------------------------------------------------------------------------
# Joint (num_splits, block_n) sweep — the 2D autotuner
# ---------------------------------------------------------------------------

def candidate_block_ns(capacity: int,
                       block_ns: tuple[int, ...] = (32, 64, 128, 256)
                       ) -> list[int]:
    """Block sizes the contiguous kernel can take at this capacity: the
    standard candidates that divide it (paged layouts never sweep block_n —
    there it is structurally pinned to the physical page size)."""
    out = [bn for bn in block_ns if bn <= capacity and capacity % bn == 0]
    return out or [capacity]


def measure_config_sweep(capacity: int, batch: int,
                         *, block_ns: list[int] | None = None,
                         d_c: int = 64, d_r: int = 16, heads: int = 8,
                         fmt: str = "fp8_e4m3", fill: float = 0.75,
                         iters: int = 3,
                         profile: SplitProfile | None = None,
                         layout: str = "contiguous",
                         interpret: bool | None = None,
                         rescale: str = "fma",
                         timer=None) -> dict[tuple[int, int], float]:
    """Joint 2D sweep: run ``measure_split_sweep`` at every candidate
    ``block_n`` so the profile holds one entry per (capacity, block_n,
    batch, layout) and ``lookup_config`` can pick the joint winner.

    ``interpret=None`` resolves to COMPILED measurement on TPU (interpret
    on CPU; ``runtime.platform.resolve_interpret``) — production shapes
    should be timed as the hardware runs them. ``timer`` here takes
    ``timer(block_n, num_splits, run)`` (tests inject a fixed 2D grid via
    ``synthetic_timer_2d``). Returns {(block_n, num_splits): us}."""
    if block_ns is None:
        block_ns = (candidate_block_ns(capacity) if layout == "contiguous"
                    else [block_ns_for_paged(capacity)])
    measured: dict[tuple[int, int], float] = {}
    for bn in block_ns:
        bn_timer = None if timer is None else \
            (lambda s, run, _bn=bn: timer(_bn, s, run))
        sweep = measure_split_sweep(
            capacity, bn, batch, d_c=d_c, d_r=d_r, heads=heads, fmt=fmt,
            fill=fill, iters=iters, profile=profile, layout=layout,
            interpret=interpret, rescale=rescale, timer=bn_timer)
        for s, us in sweep.items():
            measured[(bn, s)] = us
    return measured


def block_ns_for_paged(capacity: int, page_size: int = 128) -> int:
    """Paged layouts have no block_n freedom: the kernel's block axis IS the
    physical page. Kept as a function so call sites state the constraint."""
    return min(page_size, capacity)


def synthetic_timer_2d(timings_us: dict[tuple[int, int], float]):
    """Deterministic 2D ``timer`` for tests: fixed microseconds per
    (block_n, num_splits) cell, no kernel execution at all."""
    def timer(bn, s, _run):
        return timings_us[(bn, s)]
    return timer
