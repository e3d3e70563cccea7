"""The benchmark's R-MAT copy starts equal to the program's generator."""
import numpy as np
import pytest

from bench import graphs
from repro.graphs import generators as gen

CLASSES = {"rmat_er": (0.25, 0.25, 0.25), "rmat_g": (0.45, 0.15, 0.15),
           "rmat_b": (0.55, 0.15, 0.15)}


@pytest.mark.parametrize("scale", [9, 10, 11, 12])
@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_rmat_bit_identical_to_program(cls, scale):
    a, b, c = CLASSES[cls]
    indptr, indices = graphs.rmat(scale, 8, a, b, c, seed=0)
    ref = getattr(gen, cls)(scale)
    assert indptr.dtype == ref.indptr.dtype
    assert indices.dtype == ref.indices.dtype
    np.testing.assert_array_equal(indptr, ref.indptr)
    np.testing.assert_array_equal(indices, ref.indices)


def test_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE_DIR", str(tmp_path))
    config = {"name": "t", "graph": "rmat", "scale": 8, "edge_factor": 8,
              "a": 0.55, "b": 0.15, "c": 0.15, "graph_seed": 3}
    built = graphs.load(config)
    assert len(list(tmp_path.iterdir())) == 1
    loaded = graphs.load(config)
    for x, y in zip(built, loaded):
        np.testing.assert_array_equal(x, y)
    g = graphs.to_program(*loaded)
    assert g.n_vertices == 256 and g.n_edges == len(loaded[1])
    g.validate()

