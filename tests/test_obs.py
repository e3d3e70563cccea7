"""Observability layer (DESIGN.md §12): ``repro.obs`` tracing + metrics.

Covers the acceptance criteria of the obs PR:
  * ``trace=False`` produces bit-identical colors to ``trace=True`` for
    every registered engine, and attaches no trace artifact — the untraced
    loop still returns the pre-obs 5-tuple (no new device outputs);
  * ``trace=True`` returns a ``RunTrace`` whose per-round conflict counts
    exactly match ``ColoringResult.conflicts_per_round`` for every
    registered engine;
  * trace truncation past MAX_ROUNDS_TRACE is explicit (flag + warn-once),
    never silent;
  * the twohop VMEM fallback warns once per process naming the overflowing
    shape and counts every occurrence;
  * ``ColoringService`` memo semantics (hit/miss across versions,
    invalidation on mutation, queries never observing a half-applied
    batch) are asserted through the new memo counters.
"""
import dataclasses
import json
import re
import warnings

import numpy as np
import pytest

from repro import api, obs, registry
from repro.core import coloring as col
from repro.core import frontier as fr
from repro.core.context import PassContext
from repro.dynamic.service import ColoringService
from repro.graphs import generators as gen
from repro.kernels import ops
from repro.obs import export, metrics

MESH = gen.mesh2d(12, 12)
BIP = gen.bipartite_random(40, 30, 3.0, seed=7)
N_LEFT = 40

# one row per registered local combo (the distributed slice is covered by
# test_trace_parity_distributed); a new engine registration must add a row
# here or test_trace_cases_are_exhaustive fails
CASES = {
    "rsoc/1/static/local": (MESH, dict(algorithm="rsoc")),
    "cat/1/static/local": (MESH, dict(algorithm="cat")),
    "gm/1/static/local": (MESH, dict(algorithm="gm")),
    "jp/1/static/local": (MESH, dict(algorithm="jp", max_rounds=10000)),
    "rsoc_compact/1/static/local": (MESH, dict(algorithm="rsoc_compact")),
    "rsoc/2/static/local": (MESH, dict(algorithm="rsoc", distance=2)),
    "rsoc/2/partial/local": (BIP, dict(algorithm="rsoc", distance=2,
                                       mode="partial", n_left=N_LEFT)),
    "rsoc/1/incremental/local": (MESH, dict(algorithm="rsoc",
                                            mode="incremental")),
}


def _no_env_trace(monkeypatch):
    # CI forces REPRO_TRACE=1 through the whole suite; tests that assert
    # *untraced* behavior must clear it
    monkeypatch.delenv("REPRO_TRACE", raising=False)


def test_trace_cases_are_exhaustive():
    covered = set(CASES) | {"rsoc/1/static/distributed",
                            "cat/1/static/distributed",
                            # multi-device subprocess combo, exercised by
                            # tests/test_sharded.py
                            "rsoc/1/incremental/distributed"}
    registered = {f"{a}/{d}/{m}/{b}"
                  for (a, d, m, b) in registry.engine_keys()}
    assert registered == covered, registered ^ covered


@pytest.mark.parametrize("combo", sorted(CASES))
def test_trace_on_off_parity(combo, monkeypatch):
    """trace=False is bit-identical to trace=True and carries no artifact;
    trace=True attaches a RunTrace whose conflicts match the result's."""
    _no_env_trace(monkeypatch)
    g, kw = CASES[combo]
    off = api.color(g, seed=3, **kw)
    on = api.color(g, seed=3, trace=True, **kw)
    assert off.trace is None
    np.testing.assert_array_equal(off.colors, on.colors, err_msg=combo)
    t = on.trace
    assert t is not None
    np.testing.assert_array_equal(
        t.conflicts_per_round,
        np.asarray(on.conflicts_per_round).reshape(-1), err_msg=combo)
    assert t.n_rounds == on.n_rounds
    assert t.retries == on.retries and t.final_C == on.final_C
    assert t.n_colors == on.n_colors and not t.truncated
    assert t.spec_key == on.spec.spec_key()
    assert f"algorithm={kw['algorithm']!r}" in t.engine
    names = {p.name for p in t.phases}
    assert "solve" in names, (combo, names)
    assert all(p.wall_s >= 0 for p in t.phases)


@pytest.mark.parametrize("algo", ["rsoc", "cat"])
def test_trace_parity_distributed(algo, monkeypatch):
    _no_env_trace(monkeypatch)
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    kw = dict(algorithm=algo, backend="distributed", mesh=mesh, axis="data",
              seed=3, n_chunks=2, max_rounds=64)
    off = api.color(MESH, **kw)
    on = api.color(MESH, trace=True, **kw)
    assert off.trace is None
    np.testing.assert_array_equal(off.colors, on.colors)
    np.testing.assert_array_equal(
        on.trace.conflicts_per_round,
        np.asarray(on.conflicts_per_round).reshape(-1))
    assert {"prepare", "solve"} <= {p.name for p in on.trace.phases}


def test_frontier_trace_rsoc_compact(monkeypatch):
    """The compacted engine's RunTrace carries per-round frontier sizes and
    the compacted-vs-full decision per round."""
    _no_env_trace(monkeypatch)
    res = api.color(MESH, algorithm="rsoc_compact", seed=3, trace=True)
    rounds = res.trace.rounds
    assert len(rounds) == res.n_rounds
    for ev in rounds:
        assert ev.frontier >= 0          # collected, not the -1 sentinel
        assert ev.compacted is not None  # cap known -> decision recorded


def test_untraced_loop_is_pre_obs_program():
    """The untraced loops return the original 5-tuple — the static
    ctx.trace=False program has no extra outputs (and hence none of the
    frontier-trace allocations); traced loops splice the frontier trace
    before the trailing (total, overflow) pair."""
    prob = col.prepare(MESH, 3, 4)
    off = col._prob_runner(col._rsoc_loop, prob, 4, 100, "bitset",
                           trace=False)(prob.C)
    on = col._prob_runner(col._rsoc_loop, prob, 4, 100, "bitset",
                          trace=True)(prob.C)
    assert len(off) == 5 and len(on) == 6
    np.testing.assert_array_equal(np.asarray(off[0]), np.asarray(on[0]))
    # same contract for the frontier-compacted loop
    cap = fr.frontier_cap(prob.n_pad, 4)
    mk = lambda tr: PassContext.for_problem(prob, n_chunks=4, C=prob.C,
                                            forbidden_impl="bitset",
                                            trace=tr)
    off = fr._rsoc_compact_loop(prob.ell, prob.ovf_src, prob.ovf_dst,
                                prob.pri, mk(False), cap, 100)
    on = fr._rsoc_compact_loop(prob.ell, prob.ovf_src, prob.ovf_dst,
                               prob.pri, mk(True), cap, 100)
    assert len(off) == 5 and len(on) == 6
    np.testing.assert_array_equal(np.asarray(off[0]), np.asarray(on[0]))


PREPARE_PARTS = ("prepare.relabel", "prepare.layout", "prepare.upload")


@pytest.mark.parametrize("algo", ["rsoc", "cat"])
def test_traced_call_phases_nest_in_prepare(algo, monkeypatch):
    """A traced static call times relabel, layout and upload inside
    ``prepare``, and the work after the solve as ``finish``; a nested phase
    is an event of its own, so the total by name keeps its meaning."""
    _no_env_trace(monkeypatch)
    with obs.trace() as tc:
        api.color(MESH, algorithm=algo, seed=3)
    t = tc.traces[0]
    by_name = {}
    for p in t.phases:
        by_name.setdefault(p.name, []).append(p.wall_s)
    for name in PREPARE_PARTS + ("prepare", "solve", "finish"):
        assert len(by_name.get(name, ())) == 1, (name, by_name)
        assert by_name[name][0] >= 0
    assert t.phase_wall_s("prepare") == by_name["prepare"][0]
    assert sum(t.phase_wall_s(n) for n in PREPARE_PARTS) <= \
        t.phase_wall_s("prepare")


def test_phase_event_recorded_when_body_raises():
    from repro.obs.trace import RunTracer
    tracer = RunTracer()
    with pytest.raises(ZeroDivisionError):
        with tracer.phase("prepare", attempt=0):
            with tracer.phase("prepare.relabel"):
                1 / 0
    names = [p.name for p in tracer._phases]
    assert names == ["prepare.relabel", "prepare"]
    assert tracer._phases[1].meta == {"attempt": 0}
    assert all(p.wall_s >= 0 for p in tracer._phases)


def _hub_graph():
    from repro.graphs.csr import from_edges
    edges = [(0, v) for v in range(1, 41)] + [(v, v + 1) for v in range(1, 40)]
    return from_edges(41, np.asarray(edges))


def _loop_hlo(g, ell_cap):
    prob = col.prepare(g, 3, 4, ell_cap)
    ctx = PassContext.for_problem(prob, n_chunks=4, C=prob.C,
                                  forbidden_impl="bitset")
    return prob, col._rsoc_loop.lower(prob.ell, prob.ovf_src, prob.ovf_dst,
                                      prob.pri, ctx, 100).compile().as_text()


@pytest.mark.parametrize("case", ["mesh", "hub"])
def test_solve_scopes_name_the_untraced_loop(case):
    """The untraced ``_rsoc_loop`` carries the solve's named scopes in its
    ``op_name`` metadata, ``overflow`` only where hubs pass ``ell_cap``.
    The compacted repair pass's block loop (the only loop three deep under
    ``repair``) files its gathers under ``gather``, its snapshot reads
    under ``overflow`` and its pack, mex and write-back under ``mex``."""
    g, cap = (MESH, 512) if case == "mesh" else (_hub_graph(), 8)
    prob, hlo = _loop_hlo(g, cap)
    assert (prob.ovf_src.shape[0] > 0) == (case == "hub")
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scoped = {part for n in names for part in n.split("/")[:-1]}
    want = {"gather", "mex", "round0", "repair"}
    assert want <= scoped, scoped
    assert ("overflow" in scoped) == (case == "hub")
    assert set(col.SOLVE_SCOPES) == want | {"overflow"}

    def in_blocks(path):
        parts = path.split("/")
        return "repair" in parts and parts[parts.index("repair"):].count(
            "while") == 3

    ops = re.findall(r"= \S+ (gather|scatter)\(.*?op_name=\"([^\"]*)\"", hlo)
    block_ops = [(op, path.split("/")) for op, path in ops if in_blocks(path)]
    assert {op for op, _ in block_ops} == {"gather", "scatter"}
    for op, parts in block_ops:
        assert ({"gather", "overflow"} if op == "gather" else {"mex"}) \
            & set(parts), (op, parts)
    block_scopes = {p for n in names if in_blocks(n)
                    for p in n.split("/")[:-1]} & set(col.SOLVE_SCOPES)
    assert block_scopes == {"gather", "mex", "repair"} | (
        {"overflow"} if case == "hub" else set())


def _repair_rows_share():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "metrics",
        "repair_rows_share.py")
    spec = importlib.util.spec_from_file_location("repair_rows_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_round_rows_count_the_compacted_repair(monkeypatch):
    """A traced rsoc call records the ELL rows each repair pass gathered:
    ``n_pad`` in round 1, whose frontier is every vertex, and fewer in the
    rounds after it, which gather whole blocks of the frontier's rows; the
    bench's ``repair_rows_share`` reads their share of the trace's
    ``n_pad``, and reads None for a program whose trace carries no such
    count."""
    import types
    _no_env_trace(monkeypatch)
    g = gen.mesh2d(32, 32)
    res = api.color(g, algorithm="rsoc", seed=3, trace=True)
    prob = col.prepare(g, 3, 16)
    rounds = res.trace.rounds
    assert len(rounds) == res.n_rounds >= 2
    assert res.trace.n_pad == prob.n_pad
    assert rounds[0].rows == prob.n_pad and rounds[0].compacted is False
    B = col._repair_block(prob.ell.shape[1], prob.n_pad // 16)
    for ev in rounds[1:]:
        assert ev.compacted is True
        assert ev.frontier <= ev.rows < prob.n_pad and ev.rows % B == 0
    reader = _repair_rows_share()
    run = types.SimpleNamespace(n=g.n_vertices,
                                samples={"run_traces": [res.trace]})
    share = 100 * sum(e.rows for e in rounds) / (len(rounds) * prob.n_pad)
    assert reader.read(run) == pytest.approx(share)
    assert 100 / len(rounds) < share < 100

    @dataclasses.dataclass(frozen=True)
    class OldRoundEvent:              # a RoundEvent without ``rows``
        round: int
        conflicts: int
        frontier: int = -1
        compacted: object = None

    old = dataclasses.replace(res.trace, rounds=tuple(
        OldRoundEvent(e.round, e.conflicts, e.frontier) for e in rounds))
    assert reader.read(types.SimpleNamespace(
        n=g.n_vertices, samples={"run_traces": [old]})) is None
    no_pad = dataclasses.replace(res.trace, n_pad=-1)
    assert reader.read(types.SimpleNamespace(
        n=g.n_vertices, samples={"run_traces": [no_pad]})) is None
    assert reader.read(types.SimpleNamespace(
        n=g.n_vertices, samples={"run_traces": []})) is None


# --------------------------------------------------------------------------
# satellite 1: explicit trace truncation
# --------------------------------------------------------------------------

def test_trim_trace_truncation_flag_and_warn_once(monkeypatch):
    monkeypatch.setattr(col, "_trace_truncation_warned", False)
    buf = np.arange(col.MAX_ROUNDS_TRACE, dtype=np.int32)
    with pytest.warns(RuntimeWarning, match="MAX_ROUNDS_TRACE"):
        trimmed, truncated = col._trim_trace(buf, col.MAX_ROUNDS_TRACE + 9)
    assert truncated and len(trimmed) == col.MAX_ROUNDS_TRACE
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second overrun: silent by design
        trimmed, truncated = col._trim_trace(buf, col.MAX_ROUNDS_TRACE + 1)
    assert truncated


def test_trim_trace_no_truncation():
    buf = np.arange(col.MAX_ROUNDS_TRACE, dtype=np.int32)
    trimmed, truncated = col._trim_trace(buf, 3)
    assert not truncated
    np.testing.assert_array_equal(trimmed, [0, 1, 2])


def test_result_trace_truncated_default():
    res = api.color(MESH, seed=3)
    assert res.trace_truncated is False


# --------------------------------------------------------------------------
# satellite 2: loud twohop VMEM fallback
# --------------------------------------------------------------------------

def _twohop_inputs():
    # 4-cycle adjacency on a vertex count whose (n,) color/priority vectors
    # alone bust the VMEM budget — the degenerate shape that STILL falls
    # back after paging (the table itself no longer matters: it is paged)
    n_all = 2**20 + 1
    ell_all = np.full((n_all, 2), -1, np.int32)
    for i in range(4):
        ell_all[i] = [(i + 1) % 4, (i - 1) % 4]
    colors = np.full((n_all,), -1, np.int32)
    pri = np.arange(n_all, dtype=np.int32)
    U = np.ones((4,), bool)
    return ell_all[:4], ell_all, colors, pri, U


def test_twohop_vmem_fallback_warns_once_and_counts():
    ell_rows, ell_all, colors, pri, U = _twohop_inputs()
    assert 2 * colors.size * 4 > ops.VMEM_BUDGET_BYTES
    ops._fallback_warned.discard("twohop")
    before = metrics.counter_value("kernels.fallback", kernel="twohop",
                                   reason="vmem")
    with pytest.warns(RuntimeWarning,
                      match=r"twohop: .*n=1048577.*not pageable"):
        out_pallas = ops.twohop(ell_rows, ell_all, colors, pri, U, 0, C=8,
                                backend="pallas")
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # once per process per kernel
        out_again = ops.twohop(ell_rows, ell_all, colors, pri, U, 0, C=8,
                               backend="pallas")
    # every occurrence is counted even after the warning goes quiet
    after = metrics.counter_value("kernels.fallback", kernel="twohop",
                                  reason="vmem")
    assert after == before + 2
    # the fallback output is the jnp reference, bit-for-bit
    out_jnp = ops.twohop(ell_rows, ell_all, colors, pri, U, 0, C=8,
                         backend="jnp")
    for a, b, c in zip(out_pallas, out_again, out_jnp):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_dispatch_counter():
    ell = np.array([[1, -1], [0, -1]], np.int32)
    colors = np.array([-1, -1], np.int32)
    before = metrics.counter_value("kernels.dispatch", kernel="firstfit",
                                   backend="jnp")
    ops.firstfit(ell, colors, C=8, backend="jnp")
    after = metrics.counter_value("kernels.dispatch", kernel="firstfit",
                                  backend="jnp")
    assert after == before + 1


def test_cap_retry_counter():
    # C=1 on a mesh must overflow and double at least once
    before = metrics.counter_value("engine.cap_retry", engine="rsoc")
    res = api.color(MESH, algorithm="rsoc", seed=3, C=1)
    after = metrics.counter_value("engine.cap_retry", engine="rsoc")
    assert res.retries >= 1 and after == before + res.retries


# --------------------------------------------------------------------------
# satellite 3: ColoringService memo semantics via the memo counters
# --------------------------------------------------------------------------

def _memo_counts(kind):
    return (metrics.counter_value("service.memo", kind=kind, outcome="hit"),
            metrics.counter_value("service.memo", kind=kind, outcome="miss"))


def test_service_memo_hit_miss_and_invalidation():
    svc = ColoringService(seed=3)
    svc.add_graph("g", gen.mesh2d(8, 8))
    h0, m0 = _memo_counts("vertex_schedule")

    sched = svc.vertex_schedule("g")             # cold -> miss
    assert _memo_counts("vertex_schedule") == (h0, m0 + 1)
    again = svc.vertex_schedule("g")             # same version -> hit
    assert _memo_counts("vertex_schedule") == (h0 + 1, m0 + 1)
    assert all(np.array_equal(a, b) for a, b in zip(sched, again))

    # mutation invalidates: version bump -> next query rebuilds
    v = svc.version("g")
    svc.submit("g", inserts=[[0, 9]])
    svc.step("g")
    assert svc.version("g") == v + 1
    svc.vertex_schedule("g")
    assert _memo_counts("vertex_schedule") == (h0 + 1, m0 + 2)


def test_service_queries_never_observe_half_applied_batch():
    svc = ColoringService(seed=3)
    svc.add_graph("g", gen.mesh2d(8, 8))
    colors0 = svc.colors("g").copy()
    v0 = svc.version("g")
    svc.vertex_schedule("g")                     # populate the memo
    h0, m0 = _memo_counts("vertex_schedule")

    svc.submit("g", inserts=[[0, 9], [3, 17]])   # queued, NOT applied
    assert svc.version("g") == v0
    np.testing.assert_array_equal(svc.colors("g"), colors0)
    svc.vertex_schedule("g")                     # memo still valid -> hit
    assert _memo_counts("vertex_schedule") == (h0 + 1, m0)

    svc.step("g")                                # now it applies atomically
    assert svc.version("g") == v0 + 1
    svc.vertex_schedule("g")
    assert _memo_counts("vertex_schedule") == (h0 + 1, m0 + 1)


def test_service_step_latency_histogram():
    svc = ColoringService(seed=3)
    svc.add_graph("g", gen.mesh2d(8, 8))
    n0 = svc.step_latency("g")["count"]
    svc.step("g")                                # zero batches: not observed
    assert svc.step_latency("g")["count"] == n0
    svc.submit("g", inserts=[[1, 40]])
    svc.step("g")
    s = svc.step_latency("g")
    assert s["count"] == n0 + 1
    assert s["p50"] is not None and s["p99"] >= s["p50"] >= 0
    with pytest.raises(KeyError):
        svc.step_latency("nope")


# --------------------------------------------------------------------------
# collector scope, export, metrics primitives
# --------------------------------------------------------------------------

def test_trace_collector_scope(monkeypatch):
    _no_env_trace(monkeypatch)
    with obs.trace() as tc:
        r1 = api.color(MESH, algorithm="cat", seed=3)
        r2 = api.color(MESH, algorithm="rsoc", seed=3)
    assert len(tc) == 2
    assert r1.trace is tc.traces[0] and r2.trace is tc.traces[1]
    # scope over: back to untraced
    assert api.color(MESH, seed=3).trace is None
    assert obs.active_collector() is None


def test_env_forced_tracing(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    res = api.color(MESH, seed=3)
    assert res.trace is not None
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert not obs.tracing_enabled(False)


def test_export_jsonl_roundtrip(tmp_path, monkeypatch):
    _no_env_trace(monkeypatch)
    with obs.trace() as tc:
        api.color(MESH, algorithm="rsoc", seed=3)
        api.color(MESH, algorithm="cat", seed=3)
    path = tmp_path / "traces.jsonl"
    assert export.write_jsonl(tc.traces, str(path)) == 2
    back = export.read_jsonl(str(path))
    assert len(back) == 2
    for t, d in zip(tc.traces, back):
        assert d["spec_key"] == t.spec_key
        assert d["n_rounds"] == t.n_rounds
        assert [r["conflicts"] for r in d["rounds"]] == \
            t.conflicts_per_round.tolist()
    json.dumps(export.metrics_snapshot())        # snapshot is JSON-ready


def test_summary_line(monkeypatch):
    _no_env_trace(monkeypatch)
    res = api.color(MESH, algorithm="rsoc", seed=3, trace=True)
    line = res.trace.summary_line()
    assert line.startswith("trace[") and "rounds=" in line
    assert f"colors={res.n_colors}" in line and "TRUNCATED" not in line


def test_metrics_qualified_and_counters():
    assert metrics.qualified("a.b") == "a.b"
    assert metrics.qualified("a.b", z=1, a="x") == "a.b{a=x,z=1}"
    c = metrics.counter("test.obs_unit", case="q")
    v0 = c.value
    c.inc()
    c.inc(3)
    assert metrics.counter_value("test.obs_unit", case="q") == v0 + 4
    assert metrics.counter_value("test.obs_unit", case="absent") == 0
    assert metrics.total_matching("test.obs_unit") >= v0 + 4
    assert "test.obs_unit{case=q}" in metrics.counters_matching("test.obs_")


def test_metrics_histogram_percentiles():
    h = metrics.histogram("test.obs_hist")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count >= 100
    s = h.summary()
    assert s["max"] >= 100 and s["p99"] <= s["max"]
    assert s["p50"] <= s["p99"]
    assert metrics.histogram("test.obs_empty").percentile(50) is None
