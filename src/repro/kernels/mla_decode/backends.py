"""Unified decode-attention backend registry.

Every way this repo can compute one step of SnapMLA decode attention is a
named :class:`DecodeBackend` with ONE uniform signature

    backend.decode(q: DecodeQuery, cache, cfg: BackendConfig, ctx=None)
        -> o_latent [B, H, d_c] f32

plus a ``supports(cfg, mesh, batch, ...)`` predicate, and
:func:`resolve_backend` is the single kernel-selection rule every caller
routes through (``transformer._mla_decode``, ``core.snapmla.decode_step``,
and — via the model config — ``launch/steps.py`` / ``serve --backend``).

Backends:

  jnp_ref               contiguous MLACache, parallel (einsum) pipeline refs —
                        the pjit/cost-analysis-friendly twin
  jnp_paged_ref         PagedMLAPool, page-table gather + the same refs
                        (materializes the full page-table span; reference only)
                        — page-table rows are arbitrary per-slot mappings, so
                        batch-owned pools and the serving engine's
                        allocator-owned (prefix-shared) tables both work
  pallas_splitkv        contiguous Pallas kernels (single-pass or split-KV,
                        interpret mode on CPU, compiled on TPU)
  pallas_paged_splitkv  paged Pallas kernels — scalar-prefetched page-table
                        index maps, HBM traffic proportional to seq_lens
  shard_map             collective-free shard_map region over dp x model
                        (contiguous caches, requires a mesh + divisibility)

``num_splits`` resolution stays in ``ops.resolve_num_splits`` (profile
autotuner -> heuristic) and is applied inside each backend, so the split plan
is chosen per (capacity, block_n, batch, layout) regardless of which backend
runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kvcache import (MLACache, PagedMLAPool, paged_gather,
                                sink_patched_content)
from repro.kernels.mla_decode import ops as _ops
from repro.kernels.mla_decode import ref as _ref


class DecodeQuery(NamedTuple):
    """Prepared decode query (post Fused-Q-Quant / ``ref.prepare_q``).

    Rank-3 ``[B, H, ...]`` is the one-token decode shape; rank-4
    ``[B, q_len, H, ...]`` is the speculative-verify block (the q_len query
    rows are the LAST q_len positions of each sequence, causally masked) —
    kernel and ref backends accept both, shard_map rejects q_len > 1."""

    q_c8: jax.Array      # [B, (q_len,) H, d_c] quantized content query
    q_r: jax.Array       # [B, (q_len,) H, d_r] rope query, / sigma_q
    sigma_q: jax.Array   # [B, (q_len,) H] per-(token, head) content scale

    @property
    def q_len(self) -> int:
        return self.q_c8.shape[1] if self.q_c8.ndim == 4 else 1


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Static (trace-time) decode-attention parameters shared by every
    backend. ``num_splits`` None/0 = autotuner profile -> heuristic;
    ``block_n`` 0 = joint 2D (num_splits, block_n) plan from the v2 profile
    (contiguous caches only — paged block_n is structurally the page size);
    ``interpret`` None = interpreted on CPU, compiled on TPU (any other
    backend raises — ``runtime.platform.resolve_interpret``); ``rescale``
    "fma" = the exact per-block FMA rescale, "amla" = the AMLA exponent-add
    (combine-free split-KV emission) fast path."""

    softmax_scale: float
    block_n: int = 128
    fmt: str = "fp8_e4m3"
    num_splits: int | None = None
    interpret: bool | None = None
    rescale: str = "fma"


def _split_plan(cfg: BackendConfig, capacity: int, batch: int,
                layout: str, page_size: int | None = None) -> _ops.SplitConfig:
    """The one place every backend resolves its (num_splits, block_n) plan."""
    return _ops.resolve_split_config(
        cfg.num_splits, cfg.block_n if layout == "contiguous" else None,
        capacity, batch=batch, layout=layout, page_size=page_size,
        rescale=cfg.rescale)


@dataclasses.dataclass(frozen=True)
class DecodeBackend:
    """A named decode-attention implementation.

    ``decode(q, cache, cfg, ctx)`` computes o_latent; ``supports(cfg, mesh,
    batch, paged=..., n_heads=..., dp=...)`` returns (ok, reason) — the
    predicate ``resolve_backend`` consults before dispatching."""

    name: str
    layout: str            # "contiguous" | "paged" — the cache type consumed
    kind: str              # "ref" | "kernel" | "shard_map"
    decode: Callable[..., jax.Array]
    supports: Callable[..., tuple[bool, str]]


_REGISTRY: dict[str, DecodeBackend] = {}


def register(backend: DecodeBackend) -> DecodeBackend:
    if backend.name in _REGISTRY:
        raise ValueError(f"decode backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> DecodeBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown decode backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def backend_names() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# supports predicates
# ---------------------------------------------------------------------------

def _layout_ok(layout: str, paged: bool) -> tuple[bool, str]:
    want_paged = layout == "paged"
    if paged != want_paged:
        have = "PagedMLAPool" if paged else "MLACache"
        need = "a paged pool" if want_paged else "a contiguous MLACache"
        return False, f"consumes {need}, cache is a {have}"
    return True, ""


def _supports_ref(layout):
    def supports(cfg=None, mesh=None, batch=None, *, paged=False,
                 n_heads=None, dp=None, q_len=None):
        return _layout_ok(layout, paged)
    return supports


def _supports_kernel(layout):
    def supports(cfg=None, mesh=None, batch=None, *, paged=False,
                 n_heads=None, dp=None, q_len=None):
        ok, why = _layout_ok(layout, paged)
        if not ok:
            return ok, why
        if mesh is not None and mesh.size > 1:
            return False, ("Pallas decode kernels run per device; under a "
                           f"{mesh.size}-device pjit mesh use the jnp_ref "
                           "pjit twin (or the shard_map backend)")
        return True, ""
    return supports


def _supports_shard_map(cfg=None, mesh=None, batch=None, *, paged=False,
                        n_heads=None, dp=None, q_len=None):
    ok, why = _layout_ok("contiguous", paged)
    if not ok:
        return ok, why
    if q_len is not None and q_len > 1:
        return False, ("the shard_map region computes one query token per "
                       f"slot; q_len={q_len} verify blocks need the kernel "
                       "or jnp_ref backends")
    if mesh is None:
        return False, "requires a device mesh (SHARD_CTX / dryrun variants)"
    from repro.core.distributed_decode import shard_map_applicable
    if batch is None or n_heads is None:
        return False, "requires static batch and n_heads for divisibility"
    if not shard_map_applicable(mesh, dp, batch, n_heads):
        return False, (f"batch={batch} / n_heads={n_heads} do not divide the "
                       "(dp, model) mesh axes")
    return True, ""


# ---------------------------------------------------------------------------
# decode implementations (uniform signature)
# ---------------------------------------------------------------------------

def _jnp_ref_decode(q: DecodeQuery, cache: MLACache, cfg: BackendConfig,
                    ctx: Any = None) -> jax.Array:
    plan = _split_plan(cfg, cache.capacity, q.q_c8.shape[0], "contiguous")
    o, _lse = _ref.snapmla_decode_parallel_any(
        q.q_c8, q.q_r.astype(jnp.float32), q.sigma_q,
        sink_patched_content(cache),
        cache.rope.astype(jnp.float32), cache.scale, cache.seq_lens,
        softmax_scale=cfg.softmax_scale, num_splits=plan.num_splits,
        block_n=plan.block_n, fmt=cfg.fmt)
    return o


def _jnp_paged_ref_decode(q: DecodeQuery, pool: PagedMLAPool,
                          cfg: BackendConfig, ctx: Any = None) -> jax.Array:
    page = pool.page_size
    plan = _split_plan(cfg, pool.capacity, q.q_c8.shape[0], "paged",
                       page_size=page)
    splits = plan.num_splits
    content, rope, scale = paged_gather(pool)
    o, _lse = _ref.snapmla_decode_parallel_any(
        q.q_c8, q.q_r.astype(jnp.float32), q.sigma_q, content,
        rope.astype(jnp.float32), scale, pool.seq_lens,
        softmax_scale=cfg.softmax_scale, num_splits=splits, block_n=page,
        fmt=cfg.fmt)
    return o


def _pallas_decode(q: DecodeQuery, cache: MLACache, cfg: BackendConfig,
                   ctx: Any = None) -> jax.Array:
    plan = _split_plan(cfg, cache.capacity, q.q_c8.shape[0], "contiguous")
    o, _lse = _ops.snapmla_decode(
        q.q_c8, q.q_r, q.sigma_q, cache, softmax_scale=cfg.softmax_scale,
        block_n=plan.block_n, fmt=cfg.fmt, num_splits=plan.num_splits,
        use_kernel=True, interpret=cfg.interpret, rescale=cfg.rescale)
    return o


def _pallas_paged_decode(q: DecodeQuery, pool: PagedMLAPool,
                         cfg: BackendConfig, ctx: Any = None) -> jax.Array:
    o, _lse = _ops.snapmla_decode_paged(
        q.q_c8, q.q_r, q.sigma_q, pool, softmax_scale=cfg.softmax_scale,
        fmt=cfg.fmt, num_splits=cfg.num_splits, use_kernel=True,
        interpret=cfg.interpret, rescale=cfg.rescale)
    return o


def _shard_map_decode(q: DecodeQuery, cache: MLACache, cfg: BackendConfig,
                      ctx: Any = None) -> jax.Array:
    if q.q_c8.ndim == 4:
        raise ValueError("shard_map backend does not take q_len > 1 verify "
                         "blocks; resolve with q_len to route elsewhere")
    if not ctx or ctx.get("mesh") is None:
        raise ValueError("shard_map backend needs ctx={'mesh': ..., 'dp': ...}")
    from repro.core.distributed_decode import mla_decode_shard_map
    plan = _split_plan(cfg, cache.capacity, q.q_c8.shape[0], "contiguous")
    return mla_decode_shard_map(
        ctx["mesh"], ctx.get("dp"), q.q_c8, q.q_r, q.sigma_q, cache,
        softmax_scale=cfg.softmax_scale, block_n=plan.block_n, fmt=cfg.fmt,
        num_splits=plan.num_splits)


register(DecodeBackend("jnp_ref", "contiguous", "ref",
                       _jnp_ref_decode, _supports_ref("contiguous")))
register(DecodeBackend("jnp_paged_ref", "paged", "ref",
                       _jnp_paged_ref_decode, _supports_ref("paged")))
register(DecodeBackend("pallas_splitkv", "contiguous", "kernel",
                       _pallas_decode, _supports_kernel("contiguous")))
register(DecodeBackend("pallas_paged_splitkv", "paged", "kernel",
                       _pallas_paged_decode, _supports_kernel("paged")))
register(DecodeBackend("shard_map", "contiguous", "shard_map",
                       _shard_map_decode, _supports_shard_map))


# ---------------------------------------------------------------------------
# analytic dispatch cost (telemetry annotation; see obs/)
# ---------------------------------------------------------------------------

# Published per-chip peaks, keyed by ``jax.Device.device_kind``: (HBM
# bytes/s, bf16 FLOP/s). TPU v5e: 819 GB/s HBM, 197 TFLOP/s bf16 (Google
# Cloud TPU documentation, "TPU v5e"). A device not listed here gets no
# modeled time.
PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (819e9, 197e12),
}


def token_cost(fmt: str, d_c: int, d_r: int, heads: int
               ) -> tuple[int, int]:
    """(bytes streamed, FLOPs computed) per CACHED TOKEN of one decode
    dispatch: quantized content byte/elem + bf16 rope + f32 per-token
    scale, QK + PV per head — the Eq. 12–13 pipeline's traffic model."""
    if fmt == "none":
        bytes_tok = (d_c + d_r) * 2
    else:
        bytes_tok = d_c * 1 + d_r * 2 + 4
    flops_tok = (2 * (d_c + d_r) + 2 * d_c) * heads
    return bytes_tok, flops_tok


def dispatch_cost(backend: "DecodeBackend | str", *, tokens_visited: int,
                  tokens_full: int, heads: int, d_c: int, d_r: int,
                  fmt: str, device_kind: str | None = None) -> dict:
    """Analytic bytes/FLOPs annotation for ONE decode dispatch.

    ``tokens_visited`` is the KV-token work the split-KV early exit
    actually touches (``sum(seq_lens)``, which the engine's blocks-visited
    counters already track); ``tokens_full`` is the dense full-span sweep.
    Kernel backends stream only the visited tokens; the paged REF backend
    materializes the whole page-table span (``paged_gather``), so its
    modeled traffic is the full sweep — the annotation makes that
    structural difference visible per step. ``achieved_fraction`` is
    roofline-minimum bytes over modeled bytes: 1.0 = the dispatch streams
    exactly the live context, lower = dead traffic. ``t_model_us`` is the
    roofline time on ``device_kind``'s published peaks (``PEAKS``), None
    for a device without an entry."""
    b = get_backend(backend) if isinstance(backend, str) else backend
    bytes_tok, flops_tok = token_cost(fmt, d_c, d_r, heads)
    streamed = tokens_full if (b.kind == "ref" and b.layout == "paged") \
        else tokens_visited
    streamed = max(streamed, tokens_visited)
    model_bytes = streamed * bytes_tok
    min_bytes = tokens_visited * bytes_tok
    flops = tokens_visited * flops_tok
    peaks = PEAKS.get(device_kind or "")
    t_model_us = (max(model_bytes / peaks[0], flops / peaks[1]) * 1e6
                  if peaks else None)
    return {
        "backend": b.name,
        "bytes": model_bytes,
        "bytes_min": min_bytes,
        "flops": flops,
        "achieved_fraction": (min_bytes / model_bytes
                              if model_bytes else 1.0),
        "t_model_us": t_model_us,
    }


# ---------------------------------------------------------------------------
# resolution — the ONE decode-dispatch decision point
# ---------------------------------------------------------------------------

def canonical_name(request: str, paged: bool) -> str:
    """Map a user-facing request ('ref' / 'kernel' / 'shard-map' or an exact
    registry name) to a registry name for the given cache layout."""
    if request == "ref":
        return "jnp_paged_ref" if paged else "jnp_ref"
    if request == "kernel":
        return "pallas_paged_splitkv" if paged else "pallas_splitkv"
    if request == "shard-map":
        return "shard_map"
    return request


def resolve_backend(request: str = "auto", *, paged: bool = False,
                    batch: int | None = None, n_heads: int | None = None,
                    mesh=None, dp=None, use_kernels: bool = False,
                    prefer_shard_map: bool = False,
                    cfg: BackendConfig | None = None,
                    q_len: int | None = None) -> DecodeBackend:
    """Pick the decode backend. Static (trace-time) decision.

    ``request`` is ``serve --backend``'s vocabulary — "auto", "ref",
    "kernel", "shard-map" — or an exact registry name. "auto" prefers, in
    order: the shard_map collective-free region (when a mesh context asked
    for it and the shapes divide), the Pallas kernels (when ``use_kernels``
    and no multi-device pjit mesh is in the way), else the jnp pjit twin —
    auto never fails, it degrades to the reference path. An explicit request
    whose ``supports`` predicate rejects the configuration raises at trace
    time with the reason. ``q_len`` > 1 (the speculative-verify block shape)
    routes away from backends that only take one query token per slot
    (shard_map) — under "auto" it silently degrades, an explicit request
    raises.
    """
    kw = dict(paged=paged, n_heads=n_heads, dp=dp, q_len=q_len)
    if request in (None, "", "auto"):
        if prefer_shard_map:
            sm = get_backend("shard_map")
            if sm.supports(cfg, mesh, batch, **kw)[0]:
                return sm
        if use_kernels:
            k = get_backend(canonical_name("kernel", paged))
            if k.supports(cfg, mesh, batch, **kw)[0]:
                return k
        return get_backend(canonical_name("ref", paged))
    backend = get_backend(canonical_name(request, paged))
    ok, why = backend.supports(cfg, mesh, batch, **kw)
    if not ok:
        raise ValueError(f"decode backend {backend.name!r} (requested "
                         f"{request!r}) unsupported here: {why}")
    return backend
