"""The plain reference that decides ``correct``.

Straight numpy over the benchmark's own graph arrays: it imports nothing of
the program and takes none of its tables.  A coloring is judged against the
guarantees every configuration states:

* ``conflicts``   no edge joins two vertices of one color;
* ``uncolored``   every vertex holds a color >= 0, and the answer has one
                  color per vertex;
* ``over_degree`` first fit: no vertex holds a color above its degree, so
                  at most max_degree + 1 colors.
"""
from __future__ import annotations

import numpy as np


def coloring_faults(indptr: np.ndarray, indices: np.ndarray,
                    colors) -> dict:
    """Counts of broken guarantees of ``colors`` on the graph; all 0 when
    the coloring is sound."""
    n = len(indptr) - 1
    colors = np.asarray(colors)
    if colors.shape != (n,):
        return {"conflicts": n, "uncolored": n, "over_degree": n}
    deg = np.diff(indptr)
    src = np.repeat(np.arange(n), deg)
    return {"conflicts": int((colors[src] == colors[indices]).sum()) // 2,
            "uncolored": int((colors < 0).sum()),
            "over_degree": int((colors > deg).sum())}


def colors_used(colors) -> int:
    """Distinct colors of an answer, counted here and not taken from the
    program's report."""
    c = np.asarray(colors)
    return int(np.count_nonzero(np.bincount(c[c >= 0])))
