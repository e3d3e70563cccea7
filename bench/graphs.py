"""The benchmark's own R-MAT sampler and CSR build.

A copy of the program's generator (``repro.graphs.generators.rmat`` with
``csr.from_edges`` and ``csr.shuffle_vertices``), kept here so that a later
change to the program cannot move the graphs every cell is measured on.
``tests/test_bench_graphs.py`` pins the two as bit-identical at small scales.

A graph is a pair of numpy arrays ``(indptr, indices)``: undirected, both
directions stored, sorted by (source, target), no self-loops, no duplicates.
A configuration names one fixed instance of its class (``graph_seed``).  It
is cached under ``bench/.cache/`` keyed by the configuration's content, so
a later run in the same checkout loads it instead of sampling.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


def from_edges(n: int, edges: np.ndarray, symmetrize: bool = True):
    """(m, 2) edges -> (indptr, indices), deduplicated, self-loops dropped."""
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    edges = edges[edges[:, 0] != edges[:, 1]]
    key = edges[:, 0] * n + edges[:, 1]
    if symmetrize:
        key = np.concatenate([key, edges[:, 1] * n + edges[:, 0]])
    key.sort()
    keep = np.ones(len(key), dtype=bool)
    keep[1:] = key[1:] != key[:-1]
    key = key[keep]
    src = key // max(n, 1)
    dst = key - src * n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst.astype(np.int32)


def edge_list(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """(nnz, 2) directed (source, target) pairs."""
    n = len(indptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    return np.stack([src, indices], axis=1)


def rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
         seed: int):
    """R-MAT (Chakrabarti and Faloutsos) on 2**scale vertices with
    ``edge_factor * 2**scale`` sampled edges, ids shuffled with ``seed + 1``
    as the paper does to destroy locality."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    d = 1.0 - a - b - c
    if d < -1e-9:
        raise ValueError("R-MAT probabilities must sum to at most 1")
    probs = np.array([a, b, c, max(d, 0.0)])
    cdf = (probs / probs.sum()).cumsum()
    cdf /= cdf[-1]
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        u = rng.random(m)
        hi = u >= cdf[1]
        lo = (u >= cdf[0]) ^ hi ^ (u >= cdf[2])
        src <<= 1
        src |= hi
        dst <<= 1
        dst |= lo
    indptr, indices = from_edges(n, np.stack([src, dst], 1))
    perm = np.random.default_rng(seed + 1).permutation(n).astype(np.int64)
    return from_edges(n, perm[edge_list(indptr, indices).astype(np.int64)],
                      symmetrize=False)


def build(config: dict):
    """The configuration's graph as ``(indptr, indices)``."""
    if config["graph"] != "rmat":
        raise ValueError(f"unknown graph class {config['graph']!r}")
    return rmat(config["scale"], config["edge_factor"], config["a"],
                config["b"], config["c"], config["graph_seed"])


def _cache_path(config: dict) -> str:
    keys = ("graph", "scale", "edge_factor", "a", "b", "c", "graph_seed")
    blob = json.dumps({k: config[k] for k in keys}, sort_keys=True)
    digest = hashlib.sha256(blob.encode()).hexdigest()[:16]
    return os.path.join(CACHE_DIR, f"{config['name']}-{digest}.npz")


def load(config: dict):
    """``build(config)``, from the cache when a run has built it before."""
    path = _cache_path(config)
    if os.path.exists(path):
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    indptr, indices = build(config)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, indptr=indptr, indices=indices)
    os.replace(tmp, path)
    return indptr, indices


def to_program(indptr: np.ndarray, indices: np.ndarray):
    """The program's ``CSRGraph`` over the same arrays."""
    from repro.graphs.csr import CSRGraph
    return CSRGraph(indptr=indptr, indices=indices,
                    n_vertices=len(indptr) - 1)
