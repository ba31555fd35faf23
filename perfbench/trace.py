"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. Kept with the benchmark and checked on a small
recorded trace (``tests/data``).

* busy: the union of the intervals of the device's op events ("XLA Ops"
  line of each ``/device:TPU:n`` plane), averaged over the chips;
* kernel time: the summed durations of the op events whose name matches a
  kernel's pattern;
* program time: the summed durations of the "XLA Modules" events whose name
  matches a program's pattern (the jit names of the engine's steps);
* device_ops: the op names (numeric suffix dropped) that took most time;
* idle_gaps: the longest gaps in the busy union, each named by the spans
  of the Python thread (``TraceAnnotation`` and JAX's own) open at its
  midpoint, outermost first.
"""
from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SUFFIX = re.compile(r"[.\-_]\d+$")


def op_name(event_name: str) -> str:
    """An op event's name is its HLO instruction text on the chip
    (``%fusion.12 = f32[...] fusion(...)``): keep the instruction's name
    without its numeric suffix."""
    return SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(pd, *, kernels: dict[str, str], programs: dict[str, str],
           top: int = 10) -> dict:
    """``kernels`` / ``programs``: label -> regex on event names."""
    ops_by_dev, mods = [], []
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    mods += _events(line)
            ops_by_dev.append(ops)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host += _events(line)
    if not ops_by_dev or not any(ops_by_dev):
        return {"devices": len(ops_by_dev), "busy_s": 0.0, "kernel_s": {},
                "program_s": {}, "device_ops": [], "idle_gaps": []}
    busy, gaps = [], []
    for ops in ops_by_dev:
        merged = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    all_ops = [ev for ops in ops_by_dev for ev in ops]
    kernel_s = {k: sum(d for n, _, d in all_ops
                       if re.search(rx, op_name(n))) * 1e-9
                / len(ops_by_dev) for k, rx in kernels.items()}
    program_s = {k: sum(d for n, _, d in mods if re.search(rx, n)) * 1e-9
                 / len(ops_by_dev) for k, rx in programs.items()}
    by_name = collections.Counter()
    for n, _, d in all_ops:
        by_name[op_name(n)] += d * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        spans = sorted((hs, n) for n, hs, hd in host if hs <= mid <= hs + hd)
        label = ("/".join(n for _, n in spans[:3]) if spans
                 else "no host span")
        idle.append([label, (e - s) * 1e-9])
    return {"devices": len(ops_by_dev), "busy_s": sum(busy) / len(busy),
            "kernel_s": kernel_s, "program_s": program_s,
            "device_ops": [[n, s] for n, s in by_name.most_common(top)],
            "idle_gaps": idle}
