"""Run one benchmark cell once on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. Everything is found by name from
``BENCHMARK.json``: the cell's configuration file, its traffic mix
(``perfbench/mixes/<traffic>.json``), its limits
(``perfbench/limits/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``perfbench/layer_metrics/<metric>.py``).

One process holds the chip: it makes the weights from ``--seed``, warms up,
measures for ``--seconds``, checks the served tokens against the plain
reference, prints its wall time and set-up, then one JSON line (the last
line of standard output) with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` (traced runs) and ``check``, the
numbers compared beside their limits, which are also the last lines of
standard error. It exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or outside a checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 2


def load_cell(name: str, root: pathlib.Path = ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    here = root / "perfbench"
    mix = json.loads((here / "mixes" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((here / "limits" / f"{name}.json").read_text())
    return bench, cell, cfg, mix, limits


def main(argv=None, calibrate: bool = False) -> int:
    """One run; ``calibrate`` (``calibrate.py``) also reads the control and
    the planted faults, and takes ``--fault`` to plant one in the engine."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    if calibrate:
        ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"{ROOT / 'src' / 'repro'} not found: the benchmark "
                    "runs the program from a checkout")
    try:
        bench, cell, cfg, mix, limits = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(str(e))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        return fail(f"{cell['name']} needs {cell['chips']} chips, JAX "
                    f"finds {len(devices)}")
    peaks = json.loads((ROOT / "perfbench" / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        return fail(f"no peaks for device kind {devices[0].device_kind!r} "
                    "in perfbench/peaks.json")
    cache = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; compile cache {cache}", file=sys.stderr)

    from perfbench import harness
    hook = None
    if calibrate and args.fault:
        from perfbench.faults import FAULTS
        hook = FAULTS[args.fault]
    res = harness.run_cell(bench=bench, cell=cell, cfg=cfg, mix=mix,
                           limits=limits,
                           peaks=peaks[devices[0].device_kind],
                           seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START, root=ROOT, engine_hook=hook,
                           control=calibrate)
    wall = time.perf_counter() - T_START
    print(f"wall_s {wall:.3f} setup_s "
          f"{res['metrics'].get('setup_s', {}).get('value', 'n/a')}")
    for name, c in res["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
