"""Reduce a JAX profiler trace to device busy time, program time and idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. On a TPU each chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
that ran, and its ``XLA Modules`` line one event per program execution,
named after the program (``jit_<function>``). Host threads are lines of the
``/host:CPU`` plane, and the benchmark's own spans (``TraceAnnotation``
named ``bench.<what>``) appear there on the same clock.

* The window is the host span ``bench.window``; every interval is clipped
  to it.
* ``busy_s`` is the union of the operation intervals of each chip, averaged
  over the chips: the seconds in which an operation ran.
* ``module_s`` is the device time of each program: the sum of its module
  executions, which cover all of its operations.
* ``system_s`` is the device time of every program that is not the
  benchmark's own (the benchmark names its programs ``bench_*``).
* ``top_ops`` are the operations that took most device time, each named
  ``<program>/<operation>``;
* ``idle_gaps`` are the longest intervals of the first chip with no
  operation running, each named by the innermost benchmark span open over
  most of it (``-`` where none was).
"""

from __future__ import annotations

import bisect
import pathlib
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OWN_PROGRAM_PREFIX = "jit_bench_"
TOP = 10


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir``, parsed."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit_query(12)`` -> ``jit_query``: the program, not its build."""
    return name.split("(", 1)[0].strip()


def op_name(hlo: str) -> str:
    """``%fusion.1 = u32[...] fusion(...)`` -> ``fusion.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _owner(modules, starts, t) -> str:
    """The program whose execution holds time ``t`` (``-`` for none)."""
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and modules[k][2] > t:
        return module_name(modules[k][0])
    return "-"


def reduce(profile) -> dict:
    """Window, busy time, per-program time, top operations and idle gaps."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((lines[OPS_LINE], lines.get(MODULES_LINE, [])))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]

    busy, module_s, op_s = [], defaultdict(float), defaultdict(float)
    gaps = []
    for i, (ops, modules) in enumerate(devices):
        cover = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in cover))
        for name, s, e in modules:
            for cs, ce in _clip([(s, e)], lo, hi):
                module_s[module_name(name)] += (ce - cs) / 1e9
        modules = sorted(modules, key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for name, s, e in ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                key = f"{_owner(modules, starts, s)}/{op_name(name)}"
                op_s[key] += (ce - cs) / 1e9
        if i == 0:
            edges = [lo] + [x for iv in cover for x in iv] + [hi]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_span_over(inner, s, e), (e - s) / 1e9] for s, e in gaps[:TOP]]
    system = sum(v for k, v in module_s.items()
                 if not k.startswith(OWN_PROGRAM_PREFIX))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "devices": len(devices),
            "module_s": dict(module_s), "system_s": system,
            "top_ops": [[k, v] for k, v in top], "idle_gaps": named}


def _span_over(spans, s, e) -> str:
    """The benchmark span that covers most of ``[s, e)``; the innermost
    (shortest) one among equals."""
    best, best_key = "-", (0, 0)
    for name, a, b in spans:
        overlap = min(b, e) - max(a, s)
        if overlap > 0:
            key = (overlap, -(b - a))
            if key > best_key:
                best, best_key = name, key
    return best
