"""Multi-device coloring (shard_map engines) on host CPU devices.

Uses a subprocess-free trick: these tests run in their own pytest process
where conftest leaves device count at 1 — so we spawn a dedicated
subprocess with XLA_FLAGS for the multi-device cases."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from repro import api
from repro.core import coloring as col
from repro.graphs import generators as gen

mesh = jax.make_mesh((8,), ("data",))
out = {}
for gname, g in [("mesh2d", gen.mesh2d(24, 24)),
                 ("rmat", gen.rmat_b(9, 8))]:
    for algo in ("rsoc", "cat"):
        res = api.color(g, algorithm=algo, backend="distributed",
                        mesh=mesh, axis="data", seed=1, n_chunks=2,
                        max_rounds=64)
        out[f"{gname}.{algo}"] = {
            "proper": bool(col.is_proper(g, res.colors)),
            "colors": int(res.n_colors),
            "rounds": int(res.n_rounds),
            "gather_passes": int(res.gather_passes),
            "bound": int(g.max_degree + 1),
        }

# halo-exchange GNN == replicated GNN (EXPERIMENTS.md §Perf B).
# Ring graph: every vertex has degree 2, so per-shard edge counts are
# exactly equal -> no padding needed and the comparison is exact.
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core.partition import block_partition, build_halo
from repro.graphs.csr import from_edges, to_edge_list
from repro.models import gnn as GNN

n = 256
ring = from_edges(n, np.stack([np.arange(n), (np.arange(n) + 1) % n], 1))
D = 8
part = block_partition(ring, D, seed=0)
plan = build_halo(part)
cfg = GNN.GatedGCNConfig(n_layers=3, d_hidden=8, d_in=6, d_out=3)
params = GNN.gatedgcn_init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
n_loc = part.n_loc
feats_g = rng.standard_normal((part.n_pad, 6)).astype(np.float32)
labels_g = rng.integers(0, 3, part.n_pad).astype(np.int32)
mask_g = np.ones(part.n_pad, np.float32)
W = plan.ell_local.shape[-1]
src_l, dst_l = [], []
for d in range(D):
    ell = plan.ell_local[d]
    srcs = ell.reshape(-1)
    dsts = np.repeat(np.arange(n_loc, dtype=np.int32), W)
    keep = srcs >= 0
    src_l.append(srcs[keep])
    dst_l.append(dsts[keep])
counts = [len(x) for x in src_l]
assert len(set(counts)) == 1, counts
batch = {
    "feats": feats_g,
    "src": np.stack(src_l).reshape(-1).astype(np.int32),
    "dst": np.stack(dst_l).reshape(-1).astype(np.int32),
    "boundary": plan.boundary.reshape(-1).astype(np.int32),
    "ghost_flat": np.where(
        plan.ghost_owner >= 0,
        plan.ghost_owner * plan.max_b + plan.ghost_slot, -1
    ).reshape(-1).astype(np.int32),
    "labels": labels_g,
    "train_mask": mask_g,
}
batch = {k: jnp.asarray(v) for k, v in batch.items()}
shard = P("data")
halo_loss = shard_map(
    lambda p, b: GNN.gatedgcn_halo_loss(p, cfg, b, ("data",), D),
    mesh=mesh, in_specs=(P(), {k: shard for k in batch}),
    out_specs=P(), check_vma=False)
lv = float(halo_loss(params, batch))
e = to_edge_list(part.graph)
logits = GNN.gatedgcn_apply(params, cfg, jnp.asarray(feats_g),
                            jnp.asarray(e[:, 0].astype(np.int32)),
                            jnp.asarray(e[:, 1].astype(np.int32)),
                            part.n_pad)
lo = float(GNN.node_classification_loss(logits, jnp.asarray(labels_g),
                                        jnp.asarray(mask_g)))
out["halo_gnn"] = {"halo_loss": lv, "oracle_loss": lo,
                   "rel_err": abs(lv - lo) / max(abs(lo), 1e-9)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=500)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_distributed_proper(dist_results):
    for key, r in dist_results.items():
        if "." not in key:
            continue
        assert r["proper"], key
        assert r["colors"] <= r["bound"], key


def test_distributed_rsoc_fewer_collectives(dist_results):
    """DESIGN §2: RSOC-JAX runs 1 collective/round vs CAT's 2 — with rounds
    comparable, total gather passes must be lower."""
    for gname in ("mesh2d", "rmat"):
        r = dist_results[f"{gname}.rsoc"]
        c = dist_results[f"{gname}.cat"]
        assert r["gather_passes"] < c["gather_passes"], gname


def test_halo_gnn_matches_replicated(dist_results):
    """§Perf B: the halo-exchange GatedGCN equals the replicated oracle."""
    r = dist_results["halo_gnn"]
    assert r["rel_err"] < 1e-5, r
