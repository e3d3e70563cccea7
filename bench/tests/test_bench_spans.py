"""Device time by named scope, and the readers of the program's spans and
scopes, on hand-built traces and on one recorded here."""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import run, spans
from bench import trace_reduce as tr
from repro.obs.trace import PhaseEvent

LOOP = "jit(_rsoc_loop)"
BODY = LOOP + "/repair/while/body/while/body/closed_call"
# a repair while that holds its leaves, a round-0 leaf, and an overflow
# gather, which is an op called gather in scope overflow
OPS = [(LOOP + "/repair/while", 0, 100),
       (BODY + "/gather/gather", 10, 30),
       (BODY + "/gather/and", 20, 40),
       (BODY + "/mex/reduce_min", 40, 60),
       (LOOP + "/repair/while/body/overflow/gather", 60, 70),
       (LOOP + "/round0/while/body/closed_call/mex/or", 100, 120),
       (LOOP + "/broadcast_in_dim", 120, 125)]
ALL = ("gather", "mex", "overflow", "round0", "repair")
DEVICE_READERS = ["solve." + name + "_s" for name in ALL]


def test_scopes_are_the_parts_before_the_op():
    assert spans.scopes_of(BODY + "/gather/gather") == {
        "jit(_rsoc_loop)", "repair", "while", "body", "closed_call",
        "gather"}
    assert "gather" not in spans.scopes_of(LOOP + "/overflow/gather")
    assert spans.scopes_of("add") == set()


def test_busy_by_scope_is_the_union_per_scope():
    got = spans.busy_by_scope({"/device:TPU:0": OPS}, 0, 200, ALL)
    # repair: the while covers its leaves, which count once; gather: the
    # union [10, 40); the overflow gather is not in scope gather
    assert got == pytest.approx({"gather": 30e-9, "mex": 40e-9,
                                 "overflow": 10e-9, "round0": 20e-9,
                                 "repair": 100e-9})


def test_busy_by_scope_clips_and_averages_over_devices():
    ops = {"/device:TPU:0": OPS,
           "/device:TPU:1": [(BODY + "/mex/or", 0, 200)]}
    got = spans.busy_by_scope(ops, 50, 110, ["mex", "gather", "round0"])
    # device 0: mex [50, 60) + [100, 110); device 1: mex [50, 110)
    assert got == pytest.approx({"mex": 40e-9, "gather": 0.0,
                                 "round0": 5e-9})


def test_a_scope_on_every_op_reads_the_busy_union():
    """The scope reduction agrees with the harness's busy time on the same
    events, and leaves what ``trace_reduce.reduce`` gives unchanged."""
    ops = [(n, s, e) for n, s, e in OPS]
    trace = tr.Trace(ops={"/device:TPU:0": [(n.split("/")[-1], s, e)
                                            for n, s, e in ops]},
                     spans=[("bench.window", 0, 200)])
    got = spans.busy_by_scope({"/device:TPU:0": ops}, 0, 200, [LOOP])
    assert got[LOOP] == pytest.approx(tr.reduce(trace, 0, 200)["busy_s"])


def _run(phases_per_call, busy_s=1.0, calls=None):
    traces = [types.SimpleNamespace(
        phases=tuple(PhaseEvent(n, w) for n, w in ph),
        phase_wall_s=lambda name, ph=ph: sum(w for n, w in ph if n == name))
        for ph in phases_per_call]
    calls = len(traces) if calls is None else calls
    return types.SimpleNamespace(
        samples={"run_traces": traces, "calls": calls},
        trace={"busy_s": busy_s})


def _reader(name):
    return run.load_file(os.path.join(run.HERE, "metrics", name + ".py"))


@pytest.fixture
def scoped(monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(spans, "load_scoped", lambda p: (
        {"/device:TPU:0": OPS}, (0, 200)))


def test_program_span_readers():
    r = _run([[("prepare.relabel", 2.0), ("prepare.layout", 1.0),
               ("prepare.upload", 0.5), ("prepare", 3.6)],
              [("prepare.relabel", 4.0), ("prepare.layout", 3.0),
               ("prepare.upload", 0.0), ("prepare", 7.1)]])
    assert _reader("prepare.relabel_s").read(r) == pytest.approx(3.0)
    assert _reader("prepare.layout_s").read(r) == pytest.approx(2.0)
    assert _reader("prepare.upload_s").read(r) == pytest.approx(0.25)
    assert _reader("prepare_s").read(r) == pytest.approx(5.35)


def test_program_span_readers_silent_without_the_span():
    r = _run([[("prepare", 3.6), ("solve", 1.0)]])
    for name in ("prepare.relabel_s", "prepare.layout_s",
                 "prepare.upload_s"):
        assert _reader(name).read(r) is None
    assert _reader("prepare.relabel_s").read(_run([])) is None


def test_device_scope_readers(scoped):
    r = _run([[]] * 2)
    assert _reader("solve.gather_s").read(r) == pytest.approx(15e-9)
    assert _reader("solve.mex_s").read(r) == pytest.approx(20e-9)
    assert _reader("solve.overflow_s").read(r) == pytest.approx(5e-9)
    assert _reader("solve.round0_s").read(r) == pytest.approx(10e-9)
    assert _reader("solve.repair_s").read(r) == pytest.approx(50e-9)


def test_device_scope_reader_reads_zero_for_an_idle_scope(monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: "trace.xplane.pb")
    no_ovf = [op for op in OPS if "overflow" not in op[0]]
    monkeypatch.setattr(spans, "load_scoped", lambda p: (
        {"/device:TPU:0": no_ovf}, (0, 200)))
    assert _reader("solve.overflow_s").read(_run([[]])) == 0.0
    assert _reader("solve.gather_s").read(_run([[]])) > 0


def test_device_scope_readers_silent_without_device_ops(scoped):
    r = _run([[]], busy_s=0.0)
    for name in DEVICE_READERS:
        assert _reader(name).read(r) is None
    untraced = _run([[]])
    untraced.trace = None
    assert _reader("solve.gather_s").read(untraced) is None


def test_device_scope_readers_silent_for_a_program_without_scopes(
        monkeypatch):
    """Ops whose paths hold none of the benchmark's scope names come from a
    program that predates them: every reader is silent.  A trace with no op
    paths at all (the CPU's) reads 0.0."""
    monkeypatch.setattr(tr, "find_xplane", lambda d: "trace.xplane.pb")
    unscoped = [(LOOP + "/while/body/fusion", 0, 100)]
    monkeypatch.setattr(spans, "load_scoped", lambda p: (
        {"/device:TPU:0": unscoped}, (0, 200)))
    for name in DEVICE_READERS:
        assert _reader(name).read(_run([[]])) is None
    monkeypatch.setattr(spans, "load_scoped", lambda p: ({}, (0, 200)))
    for name in DEVICE_READERS:
        assert _reader(name).read(_run([[]])) == 0.0


def test_scope_readers_do_not_read_the_program_names(monkeypatch):
    """The readers decide from the trace and the benchmark's own names,
    not from a constant of the program under test."""
    from repro.core import coloring
    monkeypatch.setattr(tr, "find_xplane", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(spans, "load_scoped", lambda p: (
        {"/device:TPU:0": OPS}, (0, 200)))
    monkeypatch.delattr(coloring, "SOLVE_SCOPES")
    assert _reader("solve.mex_s").read(_run([[]])) == pytest.approx(40e-9)


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 90000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 7 offset_ps: 60000 duration_ps: 10000 }
    events { metadata_id: 8 offset_ps: 5000 duration_ps: 20000 }
    events { metadata_id: 6 offset_ps: 70000 duration_ps: 5000 }
    events { metadata_id: 5 offset_ps: 80000 duration_ps: 1000 } }
  event_metadata { key: 5 value { id: 5 name: "copy.1" } }
  event_metadata { key: 6 value { id: 6 name: "or.2"
      stats { metadata_id: 2 str_value: "%s/mex/or:" } } }
  event_metadata { key: 7 value { id: 7 name: "fusion.84"
      stats { metadata_id: 2 str_value: "%s/gather/gather:" } } }
  event_metadata { key: 8 value { id: 8 name: "fusion.86"
      stats { metadata_id: 2 ref_value: 3 } } }
  event_metadata { key: 9 value { id: 9 name: "jit__rsoc_loop" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "%s/repair/while:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "bench.window" } }
}
""" % (BODY, BODY, LOOP)


def test_load_scoped_reads_the_op_name_from_the_op_metadata(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops, window = spans.load_scoped(str(path))
    # on the op's metadata, as a string or as a reference to a stat name,
    # with the colon of an empty op type; an op with no path is left out
    assert ops == {"/device:TPU:0": [
        (BODY + "/gather/gather:", 1000, 1050),
        (BODY + "/gather/gather:", 1060, 1070),
        (LOOP + "/repair/while:", 1005, 1025),
        (BODY + "/mex/or:", 1070, 1075)]}
    t = tr.load(str(path))
    assert window == tr.window_of(t) == (950, 1050)
    assert sorted((s, e) for _, s, e in ops["/device:TPU:0"]) == sorted(
        (s, e) for n, s, e in t.ops["/device:TPU:0"] if "copy" not in n)
    # the window holds [1000, 1050) of gather, inside repair as well
    assert spans.busy_by_scope(ops, *window, ["gather", "repair"]) == \
        pytest.approx({"gather": 50e-9, "repair": 50e-9})


def test_load_scoped_reads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())

    x = jnp.ones((256,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    ops, window = spans.load_scoped(path)
    assert ops == {}                    # the CPU has no TPU device plane
    assert window == tr.window_of(tr.load(path))
    assert spans.load_scoped(path) == (ops, window)
