"""The paper's algorithms: correctness, termination, quality, and the
claimed RSOC-vs-CAT behaviour (fewer gather passes, same color quality).
Includes property tests over random graphs — via hypothesis when it is
installed, via seeded numpy sampling otherwise (the container has no
network; hard-requiring hypothesis made the whole module uncollectable)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro import api, obs
from repro.core import coloring as col
from repro.core.distance2 import color_distance_d, is_distance_d_proper
from repro.graphs import generators as gen
from repro.graphs.csr import (CSRGraph, from_edges, power_graph, to_edge_list,
                              to_ell)


GRAPHS = {
    "mesh2d": gen.mesh2d(32, 32),
    "mesh3d": gen.mesh3d(8, 8, 8),
    "rmat_b": gen.rmat_b(10, edge_factor=8),
    "er": gen.erdos_renyi(2000, 8.0),
}
ALGOS = ["gm", "cat", "rsoc"]


# --------------------------------------------------------------------------
# correctness: proper colorings, all algorithms, all graph classes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("algo", ALGOS + ["jp"])
def test_proper_coloring(gname, algo):
    g = GRAPHS[gname]
    res = col.ALGORITHMS[algo](g, seed=1)
    assert col.is_proper(g, res.colors), f"{algo} defective on {gname}"
    assert res.n_colors <= g.max_degree + 1      # greedy bound


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_serial_oracle_proper(gname):
    g = GRAPHS[gname]
    colors = col.greedy_sequential(g)
    assert col.is_proper(g, colors)
    assert col.n_colors_used(colors) <= g.max_degree + 1


# --------------------------------------------------------------------------
# paper claims
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_rsoc_quality_matches_cat(gname):
    """Paper: both algorithms produce colorings with about the same number
    of colors, near the serial greedy level (<= +20% tolerance band)."""
    g = GRAPHS[gname]
    serial = col.n_colors_used(col.greedy_sequential(g))
    r = api.color(g, algorithm="rsoc", seed=2).n_colors
    c = api.color(g, algorithm="cat", seed=2).n_colors
    assert r <= max(serial * 1.25 + 2, c * 1.25 + 2)
    assert c <= serial * 1.25 + 2


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_rsoc_fewer_gather_passes(gname):
    """The structural speedup: RSOC does ~half the neighbor-gather sweeps
    (1/round vs CAT's 2/round) and never more rounds (paper Figs 5-6)."""
    g = GRAPHS[gname]
    r = api.color(g, algorithm="rsoc", seed=3)
    c = api.color(g, algorithm="cat", seed=3)
    assert r.gather_passes < c.gather_passes
    assert r.n_rounds <= c.n_rounds + 1


def test_lockstep_termination():
    """Paper §5: fully-lockstep execution (n_chunks=1, every vertex in one
    simultaneous wave) livelocks WITHOUT asymmetric tie-breaking; our hashed
    priority guarantees termination.  The 2-vertex example of Fig. 7."""
    g = from_edges(2, np.array([[0, 1]]))
    res = api.color(g, algorithm="rsoc", seed=0, n_chunks=1, max_rounds=50)
    assert col.is_proper(g, res.colors)
    assert res.n_rounds < 10
    # and a dense lockstep case
    g2 = gen.erdos_renyi(256, 16.0, seed=5)
    res2 = api.color(g2, algorithm="rsoc", seed=0, n_chunks=1, max_rounds=200)
    assert col.is_proper(g2, res2.colors)


def test_conflicts_decrease_with_chunks():
    """More sequential chunks = fresher data = fewer conflicts (the paper's
    freshness argument, recovered deterministically)."""
    g = GRAPHS["rmat_b"]
    lockstep = api.color(g, algorithm="rsoc", seed=4, n_chunks=1)
    chunked = api.color(g, algorithm="rsoc", seed=4, n_chunks=32)
    assert chunked.total_conflicts <= lockstep.total_conflicts


# --------------------------------------------------------------------------
# frontier compaction + distance-2
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_frontier_compact_proper(gname):
    g = GRAPHS[gname]
    res = api.color(g, algorithm="rsoc_compact", seed=5)
    assert col.is_proper(g, res.colors)


def test_distance2_coloring():
    g = gen.mesh2d(16, 16)
    res, gd = color_distance_d(g, d=2, algorithm="rsoc", seed=0)
    assert is_distance_d_proper(g, res.colors, 2)
    # G^2 is denser; needs at least as many colors as G
    res1 = api.color(g, algorithm="rsoc", seed=0)
    assert res.n_colors >= res1.n_colors


# --------------------------------------------------------------------------
# compacted repair passes: bit-identical to the full-width pass
# --------------------------------------------------------------------------

def _full_width_repair(ctx, ell, osrc, odst, pri, colors, U, max_rounds,
                       ovf0=False):
    """The rsoc repair loop with every round a full-width
    ``_chunked_pass(detect=True)``: the reference the compacted rounds must
    match bit for bit."""
    def cond(s):
        return (s[-2] > 0) & (s[-4] < max_rounds)

    def body(s):
        colors, U, trace, r, tot, last, ovf = s
        force = U & (colors < 0)
        colors2, recolored, n_def, ovf2 = col._chunked_pass(
            ctx, ell, osrc, odst, pri, colors, U, force, detect=True)
        trace = trace.at[jnp.minimum(r, col.MAX_ROUNDS_TRACE - 1)].set(n_def)
        return (colors2, recolored, trace, r + 1, tot + n_def,
                n_def + force.sum(dtype=jnp.int32), ovf | ovf2)

    s = (colors, U, jnp.zeros((col.MAX_ROUNDS_TRACE,), jnp.int32),
         jnp.int32(0), jnp.int32(0), jnp.int32(1), jnp.bool_(ovf0))
    colors, U, trace, r, tot, _, ovf = jax.lax.while_loop(cond, body, s)
    return colors, r, trace, tot, ovf


@functools.partial(jax.jit, static_argnames=("ctx", "max_rounds"))
def _full_width_rsoc_loop(ell, osrc, odst, pri, ctx, max_rounds):
    n, n_pad = ctx.n, ctx.n_pad
    valid = jnp.arange(n_pad) < n
    colors1, U, _, ovf0 = col._chunked_pass(
        ctx, ell, osrc, odst, pri, jnp.full((n_pad,), -1, jnp.int32),
        jnp.zeros((n_pad,), bool), valid, detect=False)
    out = _full_width_repair(ctx, ell, osrc, odst, pri, colors1, U,
                             max_rounds, ovf0)
    return (out[0][:n],) + out[1:]


@functools.partial(jax.jit, static_argnames=("ctx", "max_rounds"))
def _full_width_repair_loop(ell, osrc, odst, pri, colors, U, ctx, max_rounds):
    return _full_width_repair(ctx, ell, osrc, odst, pri, colors, U,
                              max_rounds)


def _hub_graph():
    """A hub of degree 40 on a path: past ``ell_cap`` 8, its row spills
    into the COO overflow."""
    edges = [(0, v) for v in range(1, 41)] + [(v, v + 1) for v in range(1, 40)]
    return from_edges(41, np.asarray(edges))


def _wide_graph():
    """1024 vertices on two rings and a hub of degree 600: ELL width 512
    (blocks of 64 rows) and 88 overflow entries."""
    v = np.arange(1, 1024)
    edges = np.concatenate([
        np.stack([np.zeros(600, np.int64), np.arange(1, 601)], axis=1),
        np.stack([v, v % 1023 + 1], axis=1),
        np.stack([v, (v + 6) % 1023 + 1], axis=1)])
    return from_edges(1024, edges)


REPAIR_GRAPHS = {"mesh": (gen.mesh2d(16, 16), 512), "hub": (_hub_graph(), 8),
                 "wide": (_wide_graph(), 512)}
REPAIR_CASES = (
    [pytest.param(g, impl, k, entry, None, id=f"{g}-{impl}-{k}-{entry}")
     for g in ("mesh", "hub") for impl in ("bitset", "dense")
     for k in (1, 4, 16) for entry in ("scratch", "seeded")]
    + [pytest.param("wide", impl, 4, "blocks", None, id=f"wide-{impl}-blocks")
       for impl in ("bitset", "dense")]
    + [pytest.param("mesh", "bitset", 4, "scratch", 2, id="mesh-cap2"),
       pytest.param("wide", "bitset", 4, "blocks", 2, id="wide-cap2")])


def _seed_frontier(prob, n_chunks, entry):
    """A proper coloring with a frontier planted on it, in relabeled space.

    ``seeded``: a fifth of the vertices, half of them uncolored (forced) and
    half set to color 0.  ``blocks``: all of chunk 0 (several blocks), none
    of chunk 1, a partial block in chunk 2, every third row uncolored."""
    n, n_pad = prob.n, prob.n_pad
    ctx = col.PassContext.for_problem(prob, n_chunks=n_chunks, C=256)
    colors = np.full(n_pad, -1, np.int32)
    colors[:n] = np.asarray(col._rsoc_loop(prob.ell, prob.ovf_src,
                                           prob.ovf_dst, prob.pri, ctx,
                                           1000)[0])
    rng = np.random.default_rng(7)
    U = np.zeros(n_pad, bool)
    if entry == "seeded":
        rows = rng.choice(n, size=max(2, n // 5), replace=False)
        colors[rows[::2]] = -1
        colors[rows[1::2]] = 0
    else:
        cs = n_pad // n_chunks
        B = col._repair_block(prob.ell.shape[1], cs)
        rows = np.concatenate([np.arange(cs),
                               2 * cs + rng.choice(cs, size=B + 5,
                                                   replace=False)])
        assert cs >= 3 * B, (cs, B)           # chunk 0 takes several blocks
        colors[rows[::3]] = -1
        colors[rows[1::3]] = 0
    U[rows] = True
    assert U.sum() <= col._compact_cap(n_pad)  # round 1 is compacted
    return jnp.asarray(colors), jnp.asarray(U)


@pytest.mark.parametrize("gname,impl,n_chunks,entry,C", REPAIR_CASES)
def test_compacted_repair_matches_full_width(gname, impl, n_chunks, entry, C):
    """Every output of the rsoc loops, whose repair rounds with a frontier
    of at most half the rows gather only the frontier's rows, equals that
    of the same loops with every round full width: colors, rounds, the
    conflict trace, total defects, the overflow flag, and through
    ``_run_with_retry`` the final cap and its doublings.  Covers the COO
    overflow, both forbidden-set impls, 1/4/16 chunks, uncolored seeds,
    a chunk with several blocks beside one with none, and a cap that
    overflows and doubles."""
    g, ell_cap = REPAIR_GRAPHS[gname]
    prob = col.prepare(g, seed=3, n_chunks=n_chunks, ell_cap=ell_cap, C=C)
    assert (prob.ovf_src.shape[0] > 0) == (gname != "mesh")
    if entry == "scratch":
        loops = (col._rsoc_loop, _full_width_rsoc_loop)
        args = ()
    else:
        loops = (col._rsoc_repair_loop, _full_width_repair_loop)
        args = _seed_frontier(prob, n_chunks, entry)

    def runner(loop):
        def run(C_):
            ctx = col.PassContext.for_problem(prob, n_chunks=n_chunks, C=C_,
                                              forbidden_impl=impl)
            return loop(prob.ell, prob.ovf_src, prob.ovf_dst, prob.pri,
                        *args, ctx, 1000)
        return run

    first = [runner(loop)(prob.C) for loop in loops]
    got, want = [col._run_with_retry(runner(loop), prob.C) for loop in loops]
    assert got[1:] == want[1:]                    # final C, retries
    if C is not None:
        assert bool(first[0][-1]) and got[2] >= 1   # the cap overflowed
    for out, ref in ((first[0], first[1]), (got[0], want[0])):
        assert len(out) == len(ref) == 5
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if entry == "scratch":
        assert col.is_proper(g, col._unpermute(np.asarray(got[0][0]),
                                               prob.perm, prob.n))


# --------------------------------------------------------------------------
# prepare: relabel straight into the ELL, bit-identical to the edge path
# --------------------------------------------------------------------------

def _prepare_via_edges(g, seed, n_chunks, ell_cap, relabel):
    """``col.prepare`` as it was before relabel built the ELL from rows:
    relabel through an edge list and ``from_edges``, then the ELL and COO
    overflow split of the relabeled CSR."""
    n = g.n_vertices
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n).astype(np.int64) if relabel else np.arange(n)
    if relabel:
        g = from_edges(n, perm[to_edge_list(g).astype(np.int64)],
                       symmetrize=False)
    n_pad = -(-max(n, n_chunks) // n_chunks) * n_chunks
    W = max(1, min(g.max_degree, ell_cap))
    osrc = odst = np.zeros((0,), np.int32)
    if g.max_degree <= ell_cap:
        ell = to_ell(g, max_degree=W, pad_vertices_to=n_pad)
    else:
        deg = g.degrees
        ell = np.full((n_pad, W), -1, np.int32)
        row = np.repeat(np.arange(n), deg)
        slot = np.arange(g.n_edges) - np.repeat(g.indptr[:-1], deg)
        in_ell = slot < W
        ell[row[in_ell], slot[in_ell]] = g.indices[in_ell]
        osrc = row[~in_ell].astype(np.int32)
        odst = g.indices[~in_ell].astype(np.int32)
    pri = np.full(n_pad, -1, np.int32)
    pri[:n] = rng.permutation(n).astype(np.int32)
    return dict(ell=ell, ovf_src=osrc, ovf_dst=odst, pri=pri, perm=perm,
                n=n, n_pad=n_pad, C=col._pick_C(g, None))


def _edit_rows(g, edit):
    """``g`` with each row replaced by ``edit(u, row)``: a CSR that
    ``from_edges`` would never build."""
    rows = [edit(u, g.neighbors(u).tolist()) for u in range(g.n_vertices)]
    indptr = np.zeros(g.n_vertices + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=indptr[1:])
    return CSRGraph(indptr=indptr,
                    indices=np.asarray(sum(rows, []), np.int32),
                    n_vertices=g.n_vertices)


def _isolated_graph():
    """A 3-D mesh with every fifth id an isolated vertex."""
    m = gen.mesh3d(5, 5, 5)
    ids = np.arange(m.n_vertices) + np.arange(m.n_vertices) // 4
    n = int(ids[-1]) + 3
    return from_edges(n, ids[to_edge_list(m)], symmetrize=False)


_MESH = gen.mesh3d(6, 6, 6)
_TOP = int(np.argmax(_MESH.degrees))
PREPARE_GRAPHS = {
    "rmat_er": gen.rmat_er(10),
    "mesh3d": _MESH,
    "isolated": _isolated_graph(),
    "odd_n": gen.erdos_renyi(1001, 6.0),
    # the widest row repeats a neighbor, so the dedup narrows the ELL
    "dup": _edit_rows(_MESH, lambda u, r: r + r[:1] if u == _TOP else r),
    "self_loop": _edit_rows(_MESH, lambda u, r: sorted(r + [u])
                            if u % 7 == 0 else r),
    # rows in descending order: sorted within each row, nothing dropped
    "unsorted": _edit_rows(_MESH, lambda u, r: r[::-1]),
    "hub": _hub_graph(),
}
PREPARE_CASES = (
    [pytest.param(name, seed, 16, 512, True, "rows",
                  id=f"{name}-seed{seed}")
     for name in ("rmat_er", "mesh3d", "isolated", "odd_n", "unsorted")
     for seed in (0, 1, 5)]
    + [pytest.param("odd_n", 2, 12, 512, True, "rows", id="odd_n-12chunks")]
    + [pytest.param(name, 3, 16, 512, True, "edges", id=name)
       for name in ("dup", "self_loop")]
    + [pytest.param("hub", 3, 16, 8, True, "edges", id="hub-over-cap"),
       pytest.param("rmat_er", 4, 16, 512, False, None, id="no-relabel")])


@pytest.mark.parametrize("gname,seed,n_chunks,ell_cap,relabel,path",
                         PREPARE_CASES)
def test_prepare_matches_edge_path(gname, seed, n_chunks, ell_cap, relabel,
                                   path):
    """``prepare`` gives the problem the edge-list relabel gave, field for
    field, on every path: the row path (isolated vertices, padded rows,
    unsorted input rows), the fallback to the edge path on a repeated
    neighbor or a self-loop (which it drops and merges as before), a hub
    over ``ell_cap`` (COO overflow) and no relabel.  The traced
    ``prepare.relabel`` phase names the path that ran."""
    g = PREPARE_GRAPHS[gname]
    with obs.run_tracer() as tracer:
        prob = col.prepare(g, seed=seed, n_chunks=n_chunks, ell_cap=ell_cap,
                           relabel=relabel)
    want = _prepare_via_edges(g, seed, n_chunks, ell_cap, relabel)
    for field, ref in want.items():
        got = getattr(prob, field)
        if isinstance(ref, np.ndarray):
            got = np.asarray(got)
            assert got.dtype == ref.dtype, field
            np.testing.assert_array_equal(got, ref, err_msg=field)
        else:
            assert got == ref, field
    meta = [p.meta for p in tracer._phases if p.name == "prepare.relabel"]
    assert meta == [{} if path is None else {"path": path}]
    if gname == "dup":
        assert prob.ell.shape[1] == g.max_degree - 1


# --------------------------------------------------------------------------
# regressions
# --------------------------------------------------------------------------

def test_gm_repair_includes_overflow_edges():
    """Regression: with ell_cap small enough to spill hub rows into the COO
    overflow side-channel, GM's serial repair used to rebuild forbidden sets
    from the ELL rows only, producing improper colorings."""
    g = gen.rmat_b(9, edge_factor=16)
    assert g.max_degree > 8  # the cap below really forces overflow
    res = api.color(g, algorithm="gm", seed=1, ell_cap=8)
    assert col.is_proper(g, res.colors)


def test_cap_doubling_recorded():
    """K_48 under C=32 must double the cap and report it in the result."""
    n = 48
    ii, jj = np.meshgrid(np.arange(n), np.arange(n))
    g = from_edges(n, np.stack([ii[ii != jj], jj[ii != jj]], axis=1))
    res = api.color(g, algorithm="rsoc", seed=0, C=32)
    assert col.is_proper(g, res.colors) and res.n_colors == n
    assert res.retries >= 1 and res.overflow and res.final_C >= n
    s = res.summary()
    assert s["final_C"] == res.final_C and s["retries"] == res.retries
    # no doubling needed -> retries 0 and final_C is the requested cap
    res2 = api.color(g, algorithm="rsoc", seed=0, C=64)
    assert res2.retries == 0 and not res2.overflow and res2.final_C == 64


# --------------------------------------------------------------------------
# property tests (hypothesis when available, seeded numpy otherwise)
# --------------------------------------------------------------------------

def _np_random_graph(rng):
    n = int(rng.integers(2, 120))
    m = int(rng.integers(0, 4 * n))
    edges = rng.integers(0, n, size=(m, 2))
    return from_edges(n, edges.astype(np.int64))


if HAVE_HYPOTHESIS:
    @st.composite
    def random_graph(draw):
        n = draw(st.integers(2, 120))
        m = draw(st.integers(0, 4 * n))
        edges = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        return from_edges(n, np.array(edges, dtype=np.int64).reshape(-1, 2))

    @given(random_graph(), st.sampled_from(ALGOS), st.integers(0, 3),
           st.sampled_from([1, 2, 16]))
    @settings(max_examples=40, deadline=None)
    def test_property_proper_and_bounded(g, algo, seed, n_chunks):
        """Invariant: any algorithm, any seed, any chunking -> proper
        coloring with <= max_degree+1 colors, terminating."""
        kw = {} if algo == "jp" else {"n_chunks": n_chunks}
        res = col.ALGORITHMS[algo](g, seed=seed, **kw)
        assert col.is_proper(g, res.colors)
        assert res.n_colors <= g.max_degree + 1

    @given(random_graph(), st.integers(0, 2))
    @settings(max_examples=20, deadline=None)
    def test_property_power_graph_contains_base(g, seed):
        """G^2 proper coloring is also proper on G (power graph ⊇ G)."""
        gd = power_graph(g, 2)
        res = api.color(gd, algorithm="rsoc", seed=seed)
        assert col.is_proper(g, res.colors)

    @given(st.integers(2, 40), st.integers(0, 3))
    @settings(max_examples=20, deadline=None)
    def test_property_complete_graph_needs_n_colors(n, seed):
        """K_n requires exactly n colors — tests the mex/overflow retry."""
        ii, jj = np.meshgrid(np.arange(n), np.arange(n))
        edges = np.stack([ii[ii != jj], jj[ii != jj]], axis=1)
        g = from_edges(n, edges)
        res = api.color(g, algorithm="rsoc", seed=seed, C=32)
        assert col.is_proper(g, res.colors)
        assert res.n_colors == n
else:
    @pytest.mark.parametrize("case", range(12))
    def test_property_proper_and_bounded(case):
        rng = np.random.default_rng(1000 + case)
        g = _np_random_graph(rng)
        algo = ALGOS[case % len(ALGOS)]
        n_chunks = [1, 2, 16][case % 3]
        kw = {} if algo == "jp" else {"n_chunks": n_chunks}
        res = col.ALGORITHMS[algo](g, seed=case, **kw)
        assert col.is_proper(g, res.colors)
        assert res.n_colors <= g.max_degree + 1

    @pytest.mark.parametrize("case", range(6))
    def test_property_power_graph_contains_base(case):
        rng = np.random.default_rng(2000 + case)
        g = _np_random_graph(rng)
        gd = power_graph(g, 2)
        res = api.color(gd, algorithm="rsoc", seed=case)
        assert col.is_proper(g, res.colors)

    @pytest.mark.parametrize("n,seed", [(2, 0), (17, 1), (33, 2), (40, 3)])
    def test_property_complete_graph_needs_n_colors(n, seed):
        ii, jj = np.meshgrid(np.arange(n), np.arange(n))
        edges = np.stack([ii[ii != jj], jj[ii != jj]], axis=1)
        g = from_edges(n, edges)
        res = api.color(g, algorithm="rsoc", seed=seed, C=32)
        assert col.is_proper(g, res.colors)
        assert res.n_colors == n
