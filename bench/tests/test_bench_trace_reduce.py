"""Trace reduction on a hand-built trace and on one recorded here."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import trace_reduce as tr

OPS = [("fusion.1", 0, 10), ("gather.2", 5, 20), ("fusion.1", 30, 40),
       ("mex.3", 60, 90)]
SPANS = [("bench.window", 0, 100), ("bench.call", 0, 50),
         ("repro.prepare", 20, 30), ("bench.call", 50, 100),
         ("repro.solve", 55, 95)]


def _trace(*devices):
    return tr.Trace(ops={f"/device:TPU:{i}": ops
                         for i, ops in enumerate(devices)},
                    spans=list(SPANS))


def test_busy_union_and_idle_share():
    red = tr.reduce(_trace(OPS), *tr.window_of(_trace(OPS)))
    # [0, 20) + [30, 40) + [60, 90): overlapping ops count once
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["idle_share"] == pytest.approx(0.4)
    assert red["n_devices"] == 1


def test_busy_is_averaged_over_devices():
    red = tr.reduce(_trace(OPS, [("x", 0, 100)]), 0, 100)
    assert red["busy_s"] == pytest.approx(80e-9)
    assert red["idle_share"] == pytest.approx(0.2)


def test_window_clips_ops():
    red = tr.reduce(_trace(OPS), 35, 70)
    assert red["busy_s"] == pytest.approx(15e-9)       # [35, 40) + [60, 70)


def test_top_ops_ranked_by_device_time():
    red = tr.reduce(_trace(OPS), 0, 100)
    names = [n for n, _ in red["device_ops"]]
    assert names == ["mex.3", "fusion.1", "gather.2"]
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(20e-9)


def test_gaps_labelled_by_innermost_host_span():
    red = tr.reduce(_trace(OPS), 0, 100)
    gaps = dict(red["idle_gaps"])
    # [20, 30) falls inside repro.prepare, nested in the first bench.call;
    # [40, 60) and [90, 100) inside a bench.call and no phase
    assert gaps == pytest.approx({"repro.prepare": 10e-9,
                                  "bench.call": 30e-9})
    assert red["idle_gaps"][0][0] == "bench.call"


def test_gap_outside_every_span_is_host():
    t = tr.Trace(ops={"/device:TPU:0": [("a", 0, 10), ("b", 20, 25)]},
                 spans=[("bench.window", 0, 40), ("bench.call", 0, 18)])
    gaps = dict(tr.reduce(t, 0, 40)["idle_gaps"])
    assert gaps == pytest.approx({"bench.call": 10e-9, "host": 15e-9})


def test_busy_within_named_spans():
    red = tr.reduce(_trace(OPS), 0, 100)
    assert red["busy_within_s"]["bench.call"] == pytest.approx(60e-9)
    assert red["busy_within_s"]["repro.solve"] == pytest.approx(30e-9)
    assert red["busy_within_s"]["repro.prepare"] == 0.0


def test_top_keeps_at_most_ten():
    ops = [(f"op{i}", 10 * i, 10 * i + i + 1) for i in range(15)]
    red = tr.reduce(_trace(ops), 0, 200)
    assert len(red["device_ops"]) == tr.TOP
    assert red["device_ops"][0][0] == "op14"


def test_loads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((256,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.call"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in t.spans]
    assert "bench.window" in names and "bench.call" in names
    lo, hi = tr.window_of(t)
    call = next((s, e) for n, s, e in t.spans if n == "bench.call")
    assert lo <= call[0] <= call[1] <= hi
    red = tr.reduce(t, lo, hi)
    assert red["window_s"] > 0
    assert t.ops == {}                  # the CPU has no TPU device plane
    assert red["busy_s"] == 0.0 and red["idle_share"] == 1.0


def test_ops_named_by_the_module_around_them():
    mods = [(0, 10, "jit__rsoc_loop"), (20, 30, "jit__mega_step")]
    assert tr.module_at(mods, 5) == "jit__rsoc_loop"
    assert tr.module_at(mods, 20) == "jit__mega_step"
    assert tr.module_at(mods, 15) is None and tr.module_at(mods, -1) is None
    text = "%while.53 = (s32[1048576]{0}, pred[]) while(%tuple.128)"
    assert tr.op_name(text, {}, "jit__rsoc_loop") == "jit__rsoc_loop/while.53"
    assert tr.op_name(text, {"hlo_op": "while.53"}) == "while.53"
