"""Smoke run of the serving engine on one TPU chip.

    python chip_smoke.py [--seed N]

Run from the repository root on a machine whose JAX backend is a TPU. It
drives the main serving path once, in this one process, at the published
widths of ``mla-7b`` with bf16 weights made from ``--seed``:

1. ``repro.launch.serve.main`` with ``--engine --backend kernel --paged``:
   the continuous-batching engine answers four requests of 512 and 1024
   prompt tokens with chunked prefill, 32 greedy tokens each, and serve's
   own gates hold it to a leak-free pool and to the static prefill + decode
   path (teacher-forced on the engine's tokens off the CPU: every engine
   token within ``serve.ENGINE_MARGIN_TOL`` logits of the static best).
2. A few decode steps of the static-batch path on the ``kernel`` backend are
   compared with the ``ref`` backend on the same prompts and weights: the
   largest absolute logit difference must stay within ``LOGIT_TOL``.

It fails (non-zero exit, no result line) when JAX finds no TPU, when it is
not run from a checkout, and when any phase or check fails: a decode backend
other than the compiled paged split-KV kernel, any fault counter that moved,
a request not done, a non-finite logit. The last line of a passing run is
``{"ok": true, "device": {...}}``. Everything before it is a smoke figure,
not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

# Largest |kernel logit - ref logit| allowed in phase 2, stated before the
# run. Both backends compute the same quantized decode but round differently
# on the chip (the ref backend's f32 einsums default to bf16 MXU passes),
# and the difference is carried through 30 layers.
LOGIT_TOL = 0.25
KERNEL_VS_REF_STEPS = 4
KV_SPLITS = 2          # pinned: the split plan never comes from a profile


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def _kernel_vs_ref(cfg, params, prompts, steps: int) -> tuple[float, bool]:
    """Prefill once, then decode ``steps`` tokens on the kernel and the ref
    backend from the same state, both fed the kernel's greedy tokens.
    Returns (max |logit difference|, all logits finite)."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.launch import steps as ST
    from repro.models import transformer as T

    B, S = prompts.shape
    state = T.init_decode_state(
        cfg, B, serve._decode_capacity(cfg, S, steps + 1))
    logits, state = jax.jit(ST.make_prefill_step(cfg))(params, prompts, state)
    kernel_fn = jax.jit(ST.make_decode_step(cfg))
    ref_fn = jax.jit(ST.make_ref_decode_step(cfg))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    k_state = r_state = state
    gap, finite = 0.0, bool(jnp.all(jnp.isfinite(logits)))
    for i in range(steps):
        pos = jnp.full((B,), S + i, jnp.int32)
        k_logits, k_state = kernel_fn(params, tok, k_state, pos)
        r_logits, r_state = ref_fn(params, tok, r_state, pos)
        finite &= bool(jnp.all(jnp.isfinite(k_logits))
                       & jnp.all(jnp.isfinite(r_logits)))
        gap = max(gap, float(jnp.max(jnp.abs(k_logits - r_logits))))
        tok = jnp.argmax(k_logits, -1).astype(jnp.int32)
    return gap, finite


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for weights and prompts")
    opts = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        return _fail(f"{src / 'repro'} not found: run from a checkout")
    sys.path.insert(0, str(src))

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform!r}")

    from repro.launch import serve
    from repro.runtime.platform import configure_compile_cache, \
        resolve_interpret

    cache_dir = configure_compile_cache()
    compile_s: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}")

    argv_serve = ["--arch", "mla-7b", "--param-dtype", "bfloat16",
                  "--engine", "--backend", "kernel", "--paged", "--batch", "4",
                  "--prompt-lens", "512,1024", "--prefill-chunk", "256",
                  "--gen", "32", "--kv-splits", str(KV_SPLITS),
                  "--seed", str(opts.seed)]
    args = serve.build_parser().parse_args(argv_serve)
    cfg = serve.config_from_args(args)
    widths = dict(d_model=cfg.d_model, n_heads=cfg.n_heads, d_c=cfg.mla.d_c,
                  d_rope=cfg.mla.d_rope, vocab=cfg.vocab_size,
                  page=cfg.page_size, kv_fmt=cfg.kv_fmt)
    published = dict(d_model=4096, n_heads=32, d_c=512, d_rope=64,
                     vocab=102400, page=128, kv_fmt="fp8_e4m3")
    if widths != published:
        return _fail(f"mla-7b widths {widths} != published {published}")
    print(f"model: {cfg.name} {cfg.param_count() / 1e9:.3f} B params, "
          f"{args.param_dtype} weights, {cfg.n_layers} layers (no layer cut: "
          f"the described-chip compile puts the serving steps' arguments at "
          f"~12 GB of the 16 GB HBM), widths {widths}")

    # phase 1: the serving engine through serve's entry point, whose gates
    # (parity with the static path, leaked pages) raise SystemExit
    t0 = time.time()
    engine, results = serve.main(argv_serve)
    m = engine.metrics()
    print(f"engine: {len(results)} requests in {time.time() - t0:.1f} s "
          f"wall (weight init and compiles included), backend "
          f"{m['roofline']['backend']}, interpret={resolve_interpret()}")
    print(f"engine decode: {m['wall']['decode_tok_per_s']:.1f} tok/s "
          f"(smoke figure from the engine's wall clock, not a benchmark)")
    if m["roofline"]["backend"] != "pallas_paged_splitkv":
        return _fail(f"decode backend {m['roofline']['backend']}")
    if resolve_interpret():
        return _fail("Pallas kernels resolved to interpret mode")
    not_done = [(r.rid, r.status) for r in results if r.status != "done"]
    if not_done or len(results) != 4:
        return _fail(f"requests not done: {not_done}")
    f = m["faults"]
    moved = {k: f[k] for k in ("backend_faults", "ref_fallback_steps",
                               "nonfinite_rows", "failed_prefill") if f[k]}
    print(f"faults: {moved or 'none'}")
    if moved:
        return _fail(f"fault counters moved: {moved}")

    # phase 2: kernel vs ref decode logits on the 512-token prompts
    prompts = serve._engine_prompts(cfg, jax.random.PRNGKey(opts.seed), args)
    short = jnp.asarray(np.stack([p for p in prompts if len(p) == 512]))
    gap, finite = _kernel_vs_ref(cfg, engine.params, short,
                                 KERNEL_VS_REF_STEPS)
    print(f"kernel vs ref: max |logit diff| {gap:.6f} over "
          f"{KERNEL_VS_REF_STEPS} decode steps x {short.shape[0]} rows "
          f"(tolerance {LOGIT_TOL}); finite={finite}")
    if not finite:
        return _fail("non-finite logits")
    if not gap <= LOGIT_TOL:
        return _fail(f"kernel vs ref logit gap {gap} > {LOGIT_TOL}")

    stats = dev.memory_stats() or {}
    print(f"compile: {sum(compile_s):.1f} s over {len(compile_s)} backend "
          f"compiles; peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"{stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
