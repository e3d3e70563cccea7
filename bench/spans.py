"""Per-call readings of the program's named host spans and device scopes.

* Host spans: the ``RunTrace`` phases of each call (``prepare.relabel``,
  ``prepare.layout``, ``prepare.upload`` inside ``prepare``).
* Device scopes: the ``jax.named_scope`` names of the static solve
  (``SCOPES``, kept here, apart from the program).  Each device op runs an HLO
  instruction whose ``op_name`` metadata is the scope path of the code that
  made it, for example ``jit(_rsoc_loop)/repair/while/body/while/body/
  closed_call/mex/reduce_min``.  A scope's device time is the union of the
  intervals of the ops whose path holds the scope's name, clipped to the
  window and averaged over the devices, so a ``while`` that holds other ops
  in its scope does not count their time twice.  Only the parts before the
  last one are scopes; the last one names the op (``.../overflow/gather``
  is an op called ``gather`` in scope ``overflow``).  Where XLA fuses ops
  of two scopes, the fusion carries the ``op_name`` of its root.

``load_scoped(path)`` reads the ops' paths from the ``.xplane.pb`` the
harness wrote: on a TPU the profiler keeps an op's ``op_name`` in the
``tf_op`` stat of the op's event metadata, as ``<op_name>:`` (the part
after the colon, an op type, is empty for JAX).  ``busy_by_scope`` works
on plain lists, so a test can hand-build them.
This module leaves ``bench/trace_reduce.py`` and its outputs as they are.
"""
from __future__ import annotations

import os
from collections import defaultdict

from bench import trace_reduce

# the stat, on a device op's event metadata, that holds its HLO op_name
PATH_STAT = "tf_op"

# the solve's scope names that the readers know: the neighbor gather, the
# forbidden set and mex, the COO overflow, and the two loops around them
SCOPES = ("gather", "mex", "overflow", "round0", "repair")

# the fields read here of tsl/profiler/protobuf/xplane.proto: (name,
# number, type), with "*" before a message type for a repeated field; a map
# is a repeated entry of key 1 and value 2
_PROTO = {
    "XSpace": [("planes", 1, "*XPlane")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "*XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
}

_CACHE = {}


def message(name: str):
    """The message class ``name`` of ``_PROTO``.  ``ProfileData`` shows an
    event's own stats only, and a TPU op keeps its path on its metadata, so
    the file is read here."""
    from google.protobuf import message_factory
    if "pool" not in _CACHE:
        from google.protobuf import descriptor_pb2, descriptor_pool
        fd = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package="bench_xplane",
            syntax="proto3")
        F = descriptor_pb2.FieldDescriptorProto
        for msg, fields in _PROTO.items():
            m = fd.message_type.add(name=msg)
            for fname, number, kind in fields:
                f = m.field.add(name=fname, number=number,
                                label=F.LABEL_OPTIONAL)
                if kind.lstrip("*") in _PROTO:
                    f.type = F.TYPE_MESSAGE
                    f.type_name = ".bench_xplane." + kind.lstrip("*")
                    if kind.startswith("*"):
                        f.label = F.LABEL_REPEATED
                else:
                    f.type = getattr(F, "TYPE_" + kind.upper())
        _CACHE["pool"] = descriptor_pool.DescriptorPool()
        _CACHE["pool"].Add(fd)
    return message_factory.GetMessageClass(
        _CACHE["pool"].FindMessageTypeByName("bench_xplane." + name))


def phase_per_call(run, name: str):
    """Mean wall seconds of the ``RunTrace`` phase ``name`` over the
    window's calls, or None where no call recorded such a phase (a program
    without that span)."""
    traces = run.samples.get("run_traces")
    if not traces or not any(p.name == name for t in traces
                             for p in t.phases):
        return None
    return sum(t.phase_wall_s(name) for t in traces) / len(traces)


def scopes_of(path: str) -> set:
    """The scope names on an ``op_name`` path: every part but the last."""
    return set(path.split("/")[:-1])


def _stat(stats, names: dict, want: str):
    """The string value of the stat called ``want`` (held, or the name a
    reference points to), or None."""
    for st in stats:
        if names.get(st.metadata_id) == want:
            return st.str_value or names.get(st.ref_value) or None
    return None


def load_scoped(path: str) -> tuple:
    """``(ops, window)`` of an ``.xplane.pb``: ``ops[device] = [(op_name
    path, start, end), ...]`` for the device ops that carry one, and
    ``window`` the ``(start, end)`` of the first ``bench.window`` span, or
    None; times in ns on the profiler's clock, as ``trace_reduce.load``
    gives them."""
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if _CACHE.get("key") == key:
        return _CACHE["scoped"]
    space = message("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, window = {}, None
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            paths = {k: _stat(m.stats, names, PATH_STAT)
                     for k, m in metas.items()}
            ops[plane.name] = [(paths[e.metadata_id],) + _span(line, e)
                               for line in plane.lines
                               if line.name in trace_reduce.OP_LINES
                               for e in line.events
                               if paths.get(e.metadata_id)]
        elif plane.name.startswith("/host:") and window is None:
            ids = {k for k, m in metas.items() if m.name == "bench.window"}
            window = next((_span(line, e) for line in plane.lines
                           for e in line.events if e.metadata_id in ids),
                          None)
    _CACHE.update(key=key, scoped=(ops, window))
    return ops, window


def _span(line, e) -> tuple:
    s = line.timestamp_ns + e.offset_ps / 1e3
    return s, s + e.duration_ps / 1e3


def busy_by_scope(ops: dict, lo: float, hi: float, names) -> dict:
    """Seconds of device time in each scope of ``names`` over ``[lo,
    hi)``: per device, the union of the intervals of the ops whose path
    holds the scope; averaged over the devices."""
    n_dev = max(len(ops), 1)
    out = dict.fromkeys(names, 0.0)
    for evs in ops.values():
        by = defaultdict(list)
        for path, s, e in evs:
            for name in scopes_of(path) & out.keys():
                by[name].append((s, e))
        for name, ivs in by.items():
            out[name] += sum(e - s for s, e in
                             trace_reduce.union(ivs, lo, hi))
    return {k: v / n_dev / 1e9 for k, v in out.items()}


def scope_per_call(run, name: str):
    """Device seconds per call in the solve scope ``name``.

    None where the trace holds no device op, or where the window's ops
    carry ``op_name`` paths and none of them holds a name of ``SCOPES`` (a
    program that predates the scopes); otherwise the scope's time, 0.0
    where it is idle, as the overflow is in a graph without hubs, or where
    the trace carries no op paths at all, as on the CPU, which has no TPU
    plane."""
    calls = run.samples.get("calls", 0)
    if run.trace is None or run.trace["busy_s"] <= 0 or not calls:
        return None
    from bench import run as harness
    ops, window = load_scoped(trace_reduce.find_xplane(harness.TRACE_DIR))
    if window is None:
        return None
    lo, hi = window
    paths = [p for evs in ops.values() for p, s, e in evs
             if s < hi and e > lo]
    if paths and not any(scopes_of(p) & set(SCOPES) for p in paths):
        return None
    return busy_by_scope(ops, lo, hi, [name])[name] / calls
