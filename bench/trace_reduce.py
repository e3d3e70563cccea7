"""Reduce a profiler trace to device busy time, idle share, top device ops
and idle gaps labelled by what the host was doing.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists; ``reduce(...)`` works on those lists alone, so a test can
hand-build them.  Times are nanoseconds on the profiler's clock, which the
host annotations and the device planes share.

* Device ops: the events of the op line (``XLA Ops``) of every
  ``/device:TPU:<i>`` plane, named ``<module>/<op>`` by the jitted program
  (``XLA Modules`` line) that runs around them.  Busy time is the union of their intervals,
  averaged over the devices; idle share is 1 - busy / window.
* Host spans: ``jax.profiler.TraceAnnotation`` scopes whose names start
  with ``bench.`` (the harness) or ``repro.`` (the program's ``obs``
  phases, such as ``repro.prepare`` and ``repro.solve``).
* An idle gap is a stretch of the window in which no op runs on a device.
  It is labelled by the innermost host span open at its midpoint, or
  ``host`` where none is, and gaps are summed by label.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "repro.")
TOP = 10


@dataclasses.dataclass
class Trace:
    """Events of one trace: ``ops[device] = [(name, start, end), ...]`` and
    ``spans = [(name, start, end), ...]``, all in ns."""

    ops: dict
    spans: list


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(name: str, stats: dict, module: str = None) -> str:
    """``<module>/<op>`` of a device op: the HLO module and instruction
    names, without the instruction's text."""
    op = str(stats.get("hlo_op") or name.split(" = ")[0]).lstrip("%")
    module = stats.get("hlo_module") or module
    return (f"{module}/{op}" if module else op)[:200]


def module_at(modules: list, t: float):
    """Name of the module among sorted ``[(start, end, name), ...]`` that
    runs at ``t``, or None."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and t < modules[i][1]:
        return modules[i][2]
    return None


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              e.name.split("(")[0])
                             for line in plane.lines
                             if line.name == MODULE_LINE
                             for e in line.events)
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend((op_name(e.name, dict(e.stats),
                                        module_at(modules, e.start_ns)),
                                e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(ops=ops, spans=spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def window_of(trace: Trace, name: str = "bench.window"):
    """``(start, end)`` of the first span called ``name``."""
    for n, s, e in trace.spans:
        if n == name:
            return s, e
    raise ValueError(f"the trace holds no {name!r} span")


def label_gaps(spans, gaps) -> dict:
    """Sum ``[(start, end), ...]`` gaps by the innermost span (the latest
    started) open at each gap's midpoint; ``host`` where none is."""
    if not gaps:
        return {}
    g = np.asarray(gaps, dtype=np.float64)
    mid = g.mean(axis=1)
    best = np.full(len(g), -1)
    best_start = np.full(len(g), -np.inf)
    for i, (_, s, e) in enumerate(spans):
        hit = (mid >= s) & (mid < e) & (s >= best_start)
        best[hit] = i
        best_start[hit] = s
    out = defaultdict(float)
    for i, d in zip(best.tolist(), (g[:, 1] - g[:, 0]).tolist()):
        out[spans[i][0] if i >= 0 else "host"] += d
    return out


def reduce(trace: Trace, lo: float, hi: float) -> dict:
    """Busy and idle over ``[lo, hi)``.

    Returns ``busy_s`` and ``window_s`` (busy averaged over the devices),
    ``idle_share`` (0..1), ``device_ops`` and ``idle_gaps`` (lists of
    ``[name, seconds]``, largest first, at most ``TOP``), and
    ``busy_within_s``: for each host span name, the device busy seconds
    that fall inside spans of that name."""
    window = hi - lo
    n_dev = max(len(trace.ops), 1)
    busy = 0.0
    per_op = defaultdict(float)
    gaps = defaultdict(float)
    spans = [sp for sp in trace.spans if sp[0] != "bench.window"]
    inside = {name: union([(s, e) for n, s, e in spans if n == name], lo, hi)
              for name in {n for n, _, _ in spans}}
    busy_within = dict.fromkeys(inside, 0.0)
    for evs in trace.ops.values():
        merged = union([(s, e) for _, s, e in evs], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, ivs in inside.items():
            busy_within[name] += overlap(merged, ivs)
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] += d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for k, v in label_gaps(spans, idle).items():
            gaps[k] += v
    busy /= n_dev

    def top(d):
        return [[k, v / n_dev / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy / 1e9, "window_s": window / 1e9,
            "idle_share": 1.0 - busy / window if window > 0 else None,
            "device_ops": top(per_op), "idle_gaps": top(gaps),
            "busy_within_s": {k: v / n_dev / 1e9
                              for k, v in busy_within.items()},
            "n_devices": len(trace.ops)}
