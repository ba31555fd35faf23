import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the 512 placeholder devices are host (CPU) devices: never touch a TPU,
# which belongs to one process at a time
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch mixtral-8x7b --shape decode_32k --mesh multipod --out out.json

Proves the distribution config is coherent without hardware: builds the
production mesh from placeholder host devices, lowers the appropriate step
function against ShapeDtypeStruct inputs (zero allocation), compiles it, and
reports memory analysis, cost analysis, and the per-collective byte counts
parsed from the partitioned HLO — the inputs to the §Roofline terms.
"""
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of_shape(text: str) -> int:
    """Sum byte sizes of all typed shapes in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind output bytes from partitioned HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s*(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        kind = m.group(2)
        # ignore -start/-done duplicates by counting only '-start' or plain
        if re.search(rf"{kind}-done", line):
            continue
        out[kind] += _bytes_of_shape(m.group(1))
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _lower_and_compile(cfg, shape, mesh, remat, variant="baseline"):
    """One lowering pass. Returns (compiled, kind, timings)."""
    from repro.models import transformer as _T
    kind, args = ST.input_specs(cfg, shape)
    step = ST.step_fn_for(cfg, kind, remat=remat)
    ws = variant.startswith("serve_ws") and kind in ("decode", "prefill")
    if variant.endswith(("_local", "_smap")) and kind == "decode":
        _T.SHARD_CTX = {"mesh": mesh,
                        "dp": SH.dp_axes_for(args[1].shape[0], mesh),
                        "use_shard_map": variant.endswith("_smap")}
    else:
        _T.SHARD_CTX = None

    # --- shardings -----------------------------------------------------
    if kind == "train":
        params, opt, batch, stepc = args
        in_specs = (SH.param_pspecs(params, mesh), SH.param_pspecs(opt, mesh),
                    SH.batch_pspecs(batch, mesh), P())
        metrics_spec = jax.tree.map(
            lambda _: P(), jax.eval_shape(step, *args)[2])
        out_specs = (in_specs[0], in_specs[1], metrics_spec)
    elif kind == "prefill":
        params, tokens, state = args[:3]
        dpa = SH.dp_axes_for(tokens.shape[0], mesh)
        # prefill is flash-attention-heavy: replicate fallback (like train)
        in_specs = (SH.param_pspecs(params, mesh, weight_stationary=ws),
                    SH.batch_pspecs({"t": tokens}, mesh)["t"],
                    SH.state_pspecs(state, mesh, cfg))
        out_state = jax.eval_shape(step, *args)[1]
        out_specs = (P(dpa, None), SH.state_pspecs(out_state, mesh, cfg))
        if cfg.n_aux_tokens:
            in_specs = in_specs + (SH.batch_pspecs({"a": args[3]}, mesh)["a"],)
    else:  # decode
        params, token, state, pos = args
        dpa = SH.dp_axes_for(token.shape[0], mesh)
        in_specs = (SH.param_pspecs(params, mesh, weight_stationary=ws,
                                    attn_fallback="shard_dh"), P(dpa),
                    SH.state_pspecs(state, mesh, cfg), P(dpa))
        out_state = jax.eval_shape(step, *args)[1]
        out_specs = (P(dpa, None), SH.state_pspecs(out_state, mesh, cfg))

    in_named = SH.to_named(in_specs, mesh)
    out_named = SH.to_named(out_specs, mesh)
    t0 = time.time()
    try:
        with mesh:
            jitted = jax.jit(step, in_shardings=in_named, out_shardings=out_named)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
    finally:
        _T.SHARD_CTX = None
    return compiled, kind, {"lower_s": round(t_lower, 1),
                            "compile_s": round(t_compile, 1)}


def run_cell(arch: str, shape: str, mesh_kind: str, remat: bool = True,
             extra: dict | None = None, cost_pass: bool = True,
             variant: str = "baseline") -> dict:
    """variant: 'baseline' (FSDP x TP everywhere) or 'serve_ws'
    (weight-stationary DP x TP for serving kinds — §Perf hillclimb)."""
    cfg = get_config(arch)
    if extra:
        cfg = cfg.scaled(**extra)
    ok, why = ST.shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))

    # Pass 1 — production lowering (scan-over-layers): the compile proof and
    # the memory analysis. cost_analysis here UNDERCOUNTS while-loop bodies
    # (counted once), so FLOP/byte/collective totals come from pass 2.
    compiled, kind, times = _lower_and_compile(cfg, shape, mesh, remat, variant)
    mem = compiled.memory_analysis()

    result = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "kind": kind,
        "variant": variant,
        "status": "ok",
        "n_chips": int(mesh.devices.size),
        **times,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "kv_fmt": cfg.kv_fmt,
    }

    # Pass 2 — cost-exact lowering (unrolled layer/flash scans), GLOBAL logical
    # FLOPs/bytes via lowered.cost_analysis() — no compile, no sharding, exact
    # (validated against 6ND analytics in EXPERIMENTS.md §Dry-run).
    if cost_pass:
        cfg_exact = cfg.scaled(cost_exact=True)
        kind2, args2 = ST.input_specs(cfg_exact, shape)
        step2 = ST.step_fn_for(cfg_exact, kind2, remat=remat)
        lowered2 = jax.jit(step2).lower(*args2)
        cost = lowered2.cost_analysis() or {}
        result.update({
            "flops_global": cost.get("flops", 0.0),
            "bytes_global_unfused": cost.get("bytes accessed", 0.0),
            "flops": cost.get("flops", 0.0) / result["n_chips"],
            "cost_pass": {"exact": True, "method": "lowered-global/chips",
                          "caveat": "slstm sequential scans still counted once"},
        })

        # Pass 3 — collective bytes: compile cost-exact at two reduced depths
        # and extrapolate linearly in superblock count (collectives are
        # per-layer homogeneous; scan-free so nothing is undercounted).
        try:
            result["collectives"] = _extrapolated_collectives(
                cfg, shape, mesh, remat, variant)
        except Exception as e:     # pragma: no cover - diagnostic path
            result["collectives"] = {"error": f"{type(e).__name__}: {e}",
                                     "total_bytes": 0}
    else:
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        result.update({
            "flops": cost.get("flops", 0.0),
            "collectives": coll,
            "cost_pass": {"exact": False,
                          "caveat": "scan bodies counted once; use pod-mesh "
                                    "cost-exact numbers for roofline"},
        })
    return result


def _reduced_cfg(cfg, k: int):
    """Same family at k superblocks (+ original remainder)."""
    r = len(cfg.remainder_kinds)
    extra = {}
    if cfg.encoder_layers:
        extra["encoder_layers"] = max(1, round(
            cfg.encoder_layers * k / max(cfg.n_superblocks, 1)))
    return cfg.scaled(n_layers=k * cfg.pattern_len + r, cost_exact=True, **extra)


def _extrapolated_collectives(cfg, shape, mesh, remat, variant="baseline") -> dict:
    """Fit coll(k) = c0 + c1*k over k in {1, 2} and evaluate at full depth."""
    k_full = cfg.n_superblocks
    if k_full <= 2:
        compiled, _, _ = _lower_and_compile(cfg.scaled(cost_exact=True),
                                            shape, mesh, remat, variant)
        out = collective_bytes(compiled.as_text())
        out["method"] = "direct-cost-exact-compile"
        return out
    samples = {}
    for k in (1, 2):
        compiled, _, _ = _lower_and_compile(_reduced_cfg(cfg, k), shape, mesh,
                                            remat, variant)
        samples[k] = collective_bytes(compiled.as_text())
    bytes_full, counts_full = {}, {}
    for key in _COLLECTIVES:
        c1 = samples[2]["bytes"][key] - samples[1]["bytes"][key]
        c0 = samples[1]["bytes"][key] - c1
        bytes_full[key] = max(0, int(c0 + c1 * k_full))
        n1 = samples[2]["counts"][key] - samples[1]["counts"][key]
        n0 = samples[1]["counts"][key] - n1
        counts_full[key] = max(0, int(n0 + n1 * k_full))
    return {"bytes": bytes_full, "counts": counts_full,
            "total_bytes": sum(bytes_full.values()),
            "method": "linear-extrapolation-k1-k2",
            "samples": {str(k): v["total_bytes"] for k, v in samples.items()}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(ST.SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-cost-pass", action="store_true",
                    help="skip the unrolled cost-exact second lowering")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    res = run_cell(args.arch, args.shape, args.mesh, remat=not args.no_remat,
                   cost_pass=not args.no_cost_pass)
    print(json.dumps(res, indent=1, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1, default=str)
    return 0 if res["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
