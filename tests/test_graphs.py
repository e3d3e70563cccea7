"""Host graph construction stays bit-identical to its plain formulation.

``from_edges`` sorts flat keys in place of argsort + gather, and ``rmat``
thresholds uniform draws in place of ``rng.choice``; both are speedups for
paper-scale graphs that must not change a single edge.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.graphs import generators as gen
from repro.graphs.csr import CSRGraph, from_edges, shuffle_vertices


def _from_edges_argsort(n, edges, symmetrize=True) -> CSRGraph:
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    edges = edges[edges[:, 0] != edges[:, 1]]
    if symmetrize:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
    key = edges[:, 0] * n + edges[:, 1]
    order = np.argsort(key, kind="stable")
    key = key[order]
    keep = np.ones(len(key), dtype=bool)
    keep[1:] = key[1:] != key[:-1]
    edges = edges[order][keep]
    counts = np.bincount(edges[:, 0], minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return CSRGraph(indptr=indptr, indices=edges[:, 1].astype(np.int32),
                    n_vertices=n)


def _rmat_choice(scale, edge_factor, a, b, c, seed) -> CSRGraph:
    n, m = 1 << scale, (1 << scale) * edge_factor
    rng = np.random.default_rng(seed)
    probs = np.array([a, b, c, max(1.0 - a - b - c, 0.0)])
    probs = probs / probs.sum()
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        q = rng.choice(4, size=m, p=probs)
        src = (src << 1) | (q >> 1)
        dst = (dst << 1) | (q & 1)
    return shuffle_vertices(from_edges(n, np.stack([src, dst], 1)),
                            seed=seed + 1)


def _same(g: CSRGraph, h: CSRGraph) -> bool:
    return (g.n_vertices == h.n_vertices
            and np.array_equal(g.indptr, h.indptr)
            and np.array_equal(g.indices, h.indices))


@pytest.mark.parametrize("symmetrize", [True, False])
@pytest.mark.parametrize("n,m,seed", [(1, 4, 0), (50, 400, 1), (997, 20000, 2)])
def test_from_edges_matches_argsort_dedup(n, m, seed, symmetrize):
    edges = np.random.default_rng(seed).integers(0, n, size=(m, 2))
    g = from_edges(n, edges, symmetrize=symmetrize)
    g.validate()
    assert _same(g, _from_edges_argsort(n, edges, symmetrize))


@pytest.mark.parametrize("scale,edge_factor,abc,seed", [
    (8, 8, (0.25, 0.25, 0.25), 0),       # RMAT-ER
    (10, 8, (0.45, 0.15, 0.15), 1),      # RMAT-G
    (10, 4, (0.55, 0.15, 0.15), 3),      # RMAT-B
    (9, 16, (0.6, 0.2, 0.2), 7),         # d = 0 after clipping
])
def test_rmat_matches_choice_sampler(scale, edge_factor, abc, seed):
    assert _same(gen.rmat(scale, edge_factor, *abc, seed=seed),
                 _rmat_choice(scale, edge_factor, *abc, seed))
