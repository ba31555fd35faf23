"""The serving engine's own spans in a traced slice, and the device time
under them.

The engine opens ``engine.step`` (a step annotation that carries the
engine's step number, ``step_num``) around each step, and ``engine.<phase>``
around each of its phases, with the phase's counts as arguments. They land
on the profiler's Python thread line, on the clock of the device planes.
This module reads them back from the run's ``.xplane.pb``:

* ``steps``: each engine step with the device idle time under it and its
  phase spans (name, start, duration, self time, device idle, arguments);
  self time leaves out the engine spans nested in a span;
* ``kernel_s`` / ``program_s``: device time of the op events and of the
  "XLA Modules" events whose names match ``KERNELS`` / ``PROGRAMS``.

A trace with no engine step annotation (a program older than them; an outer
``engine.step`` annotation without a step number is not the engine's)
reduces to None, so every reader of it reads nothing. ``trace.reduce`` is
left as it is: the busy union and the op names are computed the same way.
"""
from __future__ import annotations

import bisect
import functools
import os
import pathlib
import re

from perfbench import trace as TRC

# where harness.run_cell writes the traced slice when run.py runs it
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".perfbench_trace"
SPAN = "engine."
STEP = "engine.step"
# device op names (trace.op_name) and jit program names read here
KERNELS = {"decode_combine": r"lse_combine|amla_combine"}
PROGRAMS = {"chunk": r"^jit_chunk_prefill_step"}


class _Busy:
    """A device's busy union, for the busy time inside any interval."""

    def __init__(self, ops):
        merged = TRC._union([(s, s + d) for _, s, d in ops])
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + e - s)

    def _upto(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.cum[i] + min(t, self.ends[i]) - self.starts[i]

    def within(self, s, e):
        return self._upto(e) - self._upto(s)


def _args(ev) -> dict:
    return {k: v for k, v in ev.stats if not k.startswith("_")}


def host_spans(pd) -> list[dict]:
    """The engine's spans on the Python line, outermost first: name,
    ``start_ns``, ``dur_ns``, arguments, and ``self_ns`` (the duration less
    that of the engine spans directly inside). An ``engine.step`` without a
    step number is left out."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if not ev.name.startswith(SPAN):
                    continue
                args = _args(ev)
                if ev.name == STEP and "step_num" not in args:
                    continue
                spans.append({"name": ev.name, "start_ns": float(ev.start_ns),
                              "dur_ns": float(ev.duration_ns),
                              "args": args})
    spans.sort(key=lambda sp: (sp["start_ns"], -sp["dur_ns"]))
    stack: list[dict] = []
    for sp in spans:
        sp["self_ns"] = sp["dur_ns"]
        end = sp["start_ns"] + sp["dur_ns"]
        while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] < end:
            stack.pop()
        if stack:
            stack[-1]["self_ns"] -= sp["dur_ns"]
        stack.append(sp)
    return spans


def reduce(pd) -> dict | None:
    """The engine's steps in the slice ``pd`` with the device time under
    them (averaged over the chips), or None when it holds no engine step.
    Times in seconds from the start of the slice."""
    spans = host_spans(pd)
    steps = [sp for sp in spans if sp["name"] == STEP]
    if not steps:
        return None
    busy, ops, mods = [], [], []
    for plane in pd.planes:
        if TRC.DEVICE_PLANE.match(plane.name):
            dev_ops = []
            for line in plane.lines:
                if line.name == TRC.OPS_LINE:
                    dev_ops += TRC._events(line)
                elif line.name == TRC.MODULES_LINE:
                    mods += TRC._events(line)
            busy.append(_Busy(dev_ops))
            ops += dev_ops
    n = len(busy)

    def idle_ns(sp):
        s, e = sp["start_ns"], sp["start_ns"] + sp["dur_ns"]
        return (sum(e - s - b.within(s, e) for b in busy) / n) if n else 0.0

    def out(sp):
        return {"name": sp["name"], "start_s": sp["start_ns"] * 1e-9,
                "dur_s": sp["dur_ns"] * 1e-9, "self_s": sp["self_ns"] * 1e-9,
                "idle_s": idle_ns(sp) * 1e-9, "args": sp["args"]}

    result = []
    for st in steps:
        s, e = st["start_ns"], st["start_ns"] + st["dur_ns"]
        inside = [out(sp) for sp in spans if sp is not st
                  and s <= sp["start_ns"]
                  and sp["start_ns"] + sp["dur_ns"] <= e]
        result.append({**out(st), "step": st["args"]["step_num"],
                       "spans": inside})
    return {
        "devices": n, "steps": result,
        "kernel_s": {k: sum(d for name, _, d in ops
                            if re.search(rx, TRC.op_name(name)))
                     * 1e-9 / max(n, 1) for k, rx in KERNELS.items()},
        "program_s": {k: sum(d for name, _, d in mods if re.search(rx, name))
                      * 1e-9 / max(n, 1) for k, rx in PROGRAMS.items()},
    }


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime_ns: int) -> dict | None:
    return reduce(TRC.load(path))


def load(ctx) -> dict | None:
    """The reduction of this run's traced slice (the newest ``.xplane.pb``
    under ``ctx["trace_dir"]``, by default where the harness writes it), or
    None for a run without one."""
    if not ctx.get("trace"):
        return None
    root = pathlib.Path(ctx.get("trace_dir", TRACE_DIR))
    paths = sorted(root.glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        return None
    path = str(paths[-1])
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def steps_with(red: dict, phase: str) -> list[dict]:
    """The engine steps that hold a span of ``phase``."""
    name = SPAN + phase
    return [st for st in red["steps"]
            if any(sp["name"] == name for sp in st["spans"])]


def per_step_ms(total_s: float, steps: list) -> float | None:
    """``total_s`` per step of ``steps`` in ms, or None when either is
    empty (nothing to read)."""
    return total_s / len(steps) * 1e3 if steps and total_s > 0 else None
