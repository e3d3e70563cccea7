#!/usr/bin/env python3
"""Smoke run of the coloring system's main paths on a TPU.

    python chip_smoke.py             one chip: static coloring at paper scale
                                     (RMAT-ER 2^22 with rsoc and cat, RMAT-B
                                     2^20 through the COO overflow path) and a
                                     multi-tenant ColoringService
    python chip_smoke.py --chips 4   four chips: distributed rsoc and a
                                     sharded incremental tenant on a 4-device
                                     mesh, against their local references

Every earlier output line is a first-run observation (sizes, seconds, rounds,
colors, peak device bytes), not a benchmark metric.  The last line is
``{"ok": true, "device": {...}}``, printed only when JAX found a TPU, every
phase ran, every coloring is proper and within max_degree + 1 colors, and no
kernel fallback, rollback, degradation, quarantine or failed megabatch group
was counted.  Anything else exits non-zero without that line.  There is no
CPU mode: ``tests/test_chip_smoke.py`` rehearses the phases at a tiny size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import api, compile_cache  # noqa: E402
from repro.core import coloring as col  # noqa: E402
from repro.core.context import PassContext  # noqa: E402
from repro.dynamic import (ColoringService, recolor_incremental,  # noqa: E402
                           slot_key)
from repro.graphs import generators as gen  # noqa: E402
from repro.graphs.csr import to_edge_list  # noqa: E402
from repro.obs import metrics  # noqa: E402

# paper-scale sizes (scale = log2 of the vertex count)
STATIC_ER_SCALE = 22        # RMAT-ER, edge factor 8: 4.19M vertices
STATIC_B_SCALE = 20         # RMAT-B: hubs past ell_cap spill to COO
SERVICE_SCALE = 20          # incremental tenants
SERVICE_TENANTS = 2
MESH_ER_SCALE = 20          # distributed static rsoc, 4 chips
MESH_SIDE = 1024            # mesh2d side of the sharded tenant, 4 chips
BATCHES, INSERTS, DELETES = 5, 2048, 512

# counters that must not move: each means a device path was bypassed or a
# step failed and was retried (DESIGN.md §14)
GUARDED = (("kernels.fallback", None), ("resilience.rollback", None),
           ("resilience.degrade", None), ("resilience.quarantine", None),
           ("service.mega", "group_fail"))


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def guarded_counts() -> dict:
    out = {}
    for name, outcome in GUARDED:
        if outcome is None:
            out[name] = metrics.total_matching(name)
        else:
            out[f"{name}{{outcome={outcome}}}"] = metrics.counter_value(
                name, outcome=outcome)
    return out


def check_counters(baseline: dict) -> dict:
    """Raise unless every guarded counter is where it was at start."""
    now = guarded_counts()
    moved = {k: now[k] - baseline[k] for k in now if now[k] != baseline[k]}
    report("counters", **{k: now[k] - baseline[k] for k in now})
    check(not moved, f"guarded counters moved: {moved}")
    return now


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def report(phase: str, **fields) -> None:
    print("first-run observation, not a benchmark metric: "
          + json.dumps({"phase": phase, **fields}, default=str), flush=True)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

_LOOPS = {"rsoc": col._rsoc_loop, "cat": col._cat_loop}


def static_phase(label: str, g, algorithm: str = "rsoc", prob=None,
                 warm: bool = True):
    """``api.color(g, algorithm=...)`` once cold, then (``warm``) the
    engine's own jitted loop once more on the same prepared problem.
    Returns the prepared problem so a second algorithm can reuse it."""
    spec = api.ColoringSpec(algorithm=algorithm)
    prep_s = None
    if prob is None:
        t0 = time.perf_counter()
        prob = col.prepare(g, spec.seed, spec.n_chunks, spec.ell_cap,
                           spec.C, spec.relabel)
        prep_s = time.perf_counter() - t0
    c0 = compile_cache.counts()
    t0 = time.perf_counter()
    res = api.color(g, spec)
    first_s = time.perf_counter() - t0
    c1 = compile_cache.counts()
    ctx = PassContext.for_problem(prob, n_chunks=spec.n_chunks,
                                  C=res.final_C,
                                  forbidden_impl=spec.forbidden_impl)
    warm_s = None
    if warm:
        t0 = time.perf_counter()
        out = jax.block_until_ready(_LOOPS[algorithm](
            prob.ell, prob.ovf_src, prob.ovf_dst, prob.pri, ctx,
            spec.max_rounds))
        warm_s = time.perf_counter() - t0
        check(np.array_equal(col._unpermute(out[0], prob.perm, prob.n),
                             res.colors),
              f"{label}/{algorithm}: warm solve differs from the first call")
    proper = col.is_proper(g, res.colors)
    report(label, algorithm=algorithm, n=g.n_vertices, m=g.n_edges // 2,
           W=int(prob.ell.shape[1]), overflow_len=int(prob.ovf_src.shape[0]),
           C=prob.C, final_C=res.final_C, host_prepare_s=prep_s,
           first_call_s=first_s,
           backend_compile_s=c1["compile_s"] - c0["compile_s"],
           cache_hits=c1["hits"] - c0["hits"], warm_solve_s=warm_s,
           rounds=res.n_rounds, n_colors=res.n_colors,
           max_degree=g.max_degree, degrade_rung=res.degrade_rung,
           proper=proper, peak_bytes_in_use=peak_bytes())
    check(proper, f"{label}/{algorithm}: improper coloring")
    check(res.n_colors <= g.max_degree + 1,
          f"{label}/{algorithm}: {res.n_colors} colors > max_degree + 1")
    check(res.degrade_rung == 0, f"{label}/{algorithm}: degraded")
    return prob


def _batch(rng, n: int, edges: np.ndarray, n_ins: int, n_del: int):
    """``n_ins`` random inserts (no self-loops) and ``n_del`` deletes of
    edges the graph started with."""
    ins = rng.integers(0, n, size=(n_ins, 2), dtype=np.int64)
    loop = ins[:, 0] == ins[:, 1]
    ins[loop, 1] = (ins[loop, 1] + 1) % n
    dels = edges[rng.choice(len(edges), size=n_del, replace=False)]
    return ins, dels


def service_phase(g, n_tenants: int = SERVICE_TENANTS,
                  n_batches: int = BATCHES, n_ins: int = INSERTS,
                  n_del: int = DELETES, seed: int = 0) -> None:
    """Tenants of one slot class (same graph and spec, their own seeded
    update streams) stepped together; every step must bump each tenant's
    version and leave it proper, and some steps must run megabatched."""
    check(n_tenants >= 2, "the megabatch path needs two tenants")
    svc = ColoringService()
    names = [f"tenant{i}" for i in range(n_tenants)]
    t0 = time.perf_counter()
    for nm in names:
        svc.add_graph(nm, g, seed=seed)
    add_s = time.perf_counter() - t0
    check(len({slot_key(svc.snapshot(nm)) for nm in names}) == 1,
          "service tenants do not share one slot class")
    report("service_add", n=g.n_vertices, m=g.n_edges // 2,
           tenants=n_tenants, add_graph_s=add_s,
           W=int(svc.snapshot(names[0]).ell.shape[1]),
           final_C=svc.snapshot(names[0]).C, peak_bytes_in_use=peak_bytes())
    e = to_edge_list(g)
    edges = e[e[:, 0] < e[:, 1]].astype(np.int64)
    rngs = [np.random.default_rng(seed + 1 + i) for i in range(n_tenants)]
    outcomes = ("batched", "escaped", "solo", "loop")
    mega0 = {o: metrics.counter_value("service.mega", outcome=o)
             for o in outcomes}
    for b in range(n_batches):
        before = {nm: svc.version(nm) for nm in names}
        for nm, rng in zip(names, rngs):
            svc.submit(nm, *_batch(rng, g.n_vertices, edges, n_ins, n_del))
        c0 = compile_cache.counts()
        t0 = time.perf_counter()
        stats = svc.step()
        step_s = time.perf_counter() - t0
        c1 = compile_cache.counts()
        rows = {}
        for nm in names:
            st = stats[nm]
            proper = col.is_proper(svc.graph(nm), svc.colors(nm))
            rows[nm] = {"version": svc.version(nm), "rounds": st["rounds"],
                        "n_colors": st["colors"], "final_C": st["final_C"],
                        "degrade_rung": st["degrade_rung"],
                        "proper": proper}
            check("rolled_back" not in st and "quarantined" not in st,
                  f"{nm}: step {b} failed: {dict(st)}")
            check(svc.version(nm) == before[nm] + 1,
                  f"{nm}: version did not advance on step {b}")
            check(st["degrade_rung"] == 0, f"{nm}: degraded on step {b}")
            check(proper, f"{nm}: improper after step {b}")
        report("service_step", step=b, inserts=n_ins, deletes=n_del,
               step_s=step_s,
               backend_compile_s=c1["compile_s"] - c0["compile_s"],
               cache_hits=c1["hits"] - c0["hits"], tenants=rows,
               peak_bytes_in_use=peak_bytes())
    mega = {o: metrics.counter_value("service.mega", outcome=o) - mega0[o]
            for o in outcomes}
    report("service_megabatch", **mega)
    check(mega["batched"] > 0, "no step ran through the megabatched path")


def one_chip(er_scale: int = STATIC_ER_SCALE, b_scale: int = STATIC_B_SCALE,
             service_scale: int = SERVICE_SCALE, **service_kw) -> None:
    t0 = time.perf_counter()
    g = gen.rmat_er(er_scale)
    report("generate", graph=f"rmat_er({er_scale})",
           host_s=time.perf_counter() - t0)
    # the warm repeat runs on the main path only: cat's and RMAT-B's
    # solves take 48 s and 240 s on v5e, and repeating them does not fit
    # the run's time limit
    prob = static_phase(f"rmat_er({er_scale})", g, "rsoc")
    static_phase(f"rmat_er({er_scale})", g, "cat", prob=prob, warm=False)
    del g, prob
    t0 = time.perf_counter()
    g = gen.rmat_b(b_scale)
    report("generate", graph=f"rmat_b({b_scale})",
           host_s=time.perf_counter() - t0)
    static_phase(f"rmat_b({b_scale})", g, "rsoc", warm=False)
    del g
    service_phase(gen.rmat_er(service_scale), **service_kw)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def _on_distinct_devices(arr, k: int) -> bool:
    return len({s.device for s in arr.addressable_shards}) == k


def distributed_phase(g, devices) -> None:
    """Static distributed rsoc on a mesh of ``devices`` against the local
    engine on the same graph."""
    mesh = Mesh(np.array(devices), ("data",))
    local = api.color(g)
    c0 = compile_cache.counts()
    t0 = time.perf_counter()
    res = api.color(g, backend="distributed", mesh=mesh)
    first_s = time.perf_counter() - t0
    c1 = compile_cache.counts()
    t0 = time.perf_counter()
    again = api.color(g, backend="distributed", mesh=mesh)
    warm_s = time.perf_counter() - t0
    proper = col.is_proper(g, res.colors)
    report("distributed_rsoc", n=g.n_vertices, m=g.n_edges // 2,
           devices=len(devices), first_call_s=first_s,
           backend_compile_s=c1["compile_s"] - c0["compile_s"],
           warm_call_s=warm_s, rounds=res.n_rounds, n_colors=res.n_colors,
           local_n_colors=local.n_colors, local_rounds=local.n_rounds,
           max_degree=g.max_degree, proper=proper,
           peak_bytes_in_use=peak_bytes())
    check(proper, "distributed rsoc: improper coloring")
    check(col.is_proper(g, local.colors), "local rsoc: improper coloring")
    check(res.n_colors <= g.max_degree + 1,
          "distributed rsoc: more than max_degree + 1 colors")
    check(np.array_equal(res.colors, again.colors),
          "distributed rsoc: a second call gave different colors")


def sharded_phase(g, devices, n_batches: int = BATCHES,
                  n_ins: int = INSERTS, n_del: int = DELETES,
                  seed: int = 0) -> None:
    """A sharded incremental service tenant over all ``devices`` (proper
    after every batch, per-shard arrays on distinct devices), and a
    1-shard tenant that must match ``mode="incremental"`` bit for bit."""
    D = len(devices)
    svc = ColoringService()
    t0 = time.perf_counter()
    svc.add_graph("sharded", g, mesh=Mesh(np.array(devices), ("data",)),
                  seed=seed)
    svc.add_graph("one_shard", g, mesh=Mesh(np.array(devices[:1]),
                                            ("data",)), seed=seed)
    add_s = time.perf_counter() - t0
    ref = api.color(g, mode="incremental", seed=seed).state
    st = svc.snapshot("sharded")
    placed = all(_on_distinct_devices(a, D)
                 for a in (st.ell, st.colors_tab, st.pri_tab))
    report("sharded_add", n=g.n_vertices, m=g.n_edges // 2, shards=D,
           add_graph_s=add_s, on_distinct_devices=placed,
           devices=sorted(str(s.device) for s in
                          st.colors_tab.addressable_shards))
    check(placed, f"sharded state is not spread over {D} devices")
    e = to_edge_list(g)
    edges = e[e[:, 0] < e[:, 1]].astype(np.int64)
    rng = np.random.default_rng(seed + 1)
    for b in range(n_batches):
        ins, dels = _batch(rng, g.n_vertices, edges, n_ins, n_del)
        svc.submit("sharded", ins, dels)
        svc.submit("one_shard", ins, dels)
        t0 = time.perf_counter()
        stats = svc.step()
        step_s = time.perf_counter() - t0
        ref = recolor_incremental(ref, ins, dels)
        st = svc.snapshot("sharded")
        proper = col.is_proper(svc.graph("sharded"), svc.colors("sharded"))
        same = bool(np.array_equal(svc.colors("one_shard"), ref.colors))
        placed = _on_distinct_devices(st.colors_tab, D) and \
            _on_distinct_devices(st.ell, D)
        report("sharded_step", step=b, step_s=step_s,
               rounds=stats["sharded"]["rounds"],
               n_colors=stats["sharded"]["colors"],
               halo_bytes=st.last_halo_bytes, proper=proper,
               one_shard_identical=same, on_distinct_devices=placed,
               peak_bytes_in_use=peak_bytes())
        for nm in ("sharded", "one_shard"):
            check("rolled_back" not in stats[nm],
                  f"{nm}: step {b} failed: {dict(stats[nm])}")
            check(stats[nm]["degrade_rung"] == 0, f"{nm}: degraded")
        check(proper, f"sharded: improper after step {b}")
        check(same, f"1-shard colors differ from mode='incremental' "
                    f"after step {b}")
        check(placed, f"sharded arrays left {D} devices after step {b}")


def four_chips(er_scale: int = MESH_ER_SCALE, side: int = MESH_SIDE,
               **sharded_kw) -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX has "
                             f"{len(devices)}")
    distributed_phase(gen.rmat_er(er_scale), devices[:4])
    sharded_phase(gen.mesh2d(side, side), devices[:4], **sharded_kw)


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: static coloring + service; 4: the mesh phase")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    baseline = guarded_counts()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips()
        else:
            one_chip()
        check_counters(baseline)
    except Exception:
        traceback.print_exc()
        return 1
    report("total", run_s=time.perf_counter() - t0)
    c = compile_cache.counts()
    print(f"compile cache: dir={cache_dir} hits={c['hits']} "
          f"misses={c['misses']} hit={'yes' if c['hits'] else 'no'} "
          f"backend_compile_s={c['compile_s']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
