"""Sharded incremental recoloring: step latency and halo traffic vs scale.

The claim under test (DESIGN.md §15): a sharded tenant's repair pays one
collective per round whose payload is O(boundary), not O(n).  On a 2-D mesh
family the boundary of a block partition grows like √n per cut, so the
halo bytes/round at the largest shard count must grow with n but
*sublinearly* — that curve is recorded in BENCH_sharded.json and asserted
here.  The 1-shard column is the differential bar: identical colors to the
single-device ``mode="incremental"`` engine on the same update stream.

The sweep runs in this process on ``jax.devices()`` — shard counts 1, 2 and
4, capped at the device count — so on a chip host the process that holds
the chips is the one that measures them.  On the CPU, force host devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before JAX
starts; with one device only the 1-shard column runs.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Csv

SCALES = {"tiny": (16, 24), "small": (32, 48), "medium": (48, 96)}
SHARDS = (1, 2, 4)


def _batches(n: int, k: int = 5, bs: int = 64):
    rng = np.random.default_rng(17)
    for _ in range(k + 1):
        ins = rng.integers(0, n, size=(bs, 2)).astype(np.int64)
        dels = rng.integers(0, n, size=(bs // 4, 2)).astype(np.int64)
        yield ins[ins[:, 0] != ins[:, 1]], dels


def sweep(sides, shard_counts) -> list[dict]:
    import jax
    from jax.sharding import Mesh
    from repro import api
    from repro.core import coloring as col
    from repro.dynamic import delta, recolor_sharded
    from repro.dynamic.incremental import recolor_incremental
    from repro.graphs import generators as gen

    devices = jax.devices()
    rows = []
    for s in sides:
        g = gen.mesh2d(s, s)
        n = g.n_vertices
        # reference stream for the 1-shard differential
        ref = api.color(g, mode="incremental", seed=0).state
        ref_colors = []
        for ins, dels in _batches(n):
            ref = recolor_incremental(ref, ins, dels)
            ref_colors.append(ref.colors)

        for D in shard_counts:
            mesh = Mesh(np.array(devices[:D]), ("data",))
            st = api.color(g, mode="incremental", backend="distributed",
                           mesh=mesh, seed=0).state
            times, identical = [], True
            for i, (ins, dels) in enumerate(_batches(n)):
                t0 = time.perf_counter()
                st = recolor_sharded(st, ins, dels)
                st.colors_dev.block_until_ready()
                dt = time.perf_counter() - t0
                if i > 0:            # first batch is the jit warmup
                    times.append(dt)
                if D == 1:
                    identical = identical and bool(
                        np.array_equal(st.colors, ref_colors[i]))
            rows.append({
                "graph": f"mesh2d_{s}x{s}", "n": n, "shards": D,
                "p50_step_ms": float(np.median(times)) * 1e3,
                "halo_bytes_per_round": int(st.halo_bytes_per_round),
                "last_halo_bytes": int(st.last_halo_bytes),
                "colors": int(st.n_colors),
                "proper": bool(col.is_proper(delta.state_to_csr(st),
                                             st.colors)),
                "identical_1shard": bool(identical) if D == 1 else None,
                "replans": int(st.replans),
            })
    return rows


def main(scale: str = "small") -> None:
    import jax

    if scale not in SCALES:
        raise SystemExit(f"unknown scale {scale!r}; known: {sorted(SCALES)}")
    shards = [d for d in SHARDS if d <= len(jax.devices())]
    rows = sweep(SCALES[scale], shards)
    csv = Csv(["graph", "n", "shards", "p50_step_ms", "halo_bytes_per_round",
               "last_halo_bytes", "colors", "proper", "identical_1shard",
               "replans"])
    for r in rows:
        csv.row(*[r[h] for h in csv.header])

    # acceptance: every run proper; 1-shard bit-identical; halo bytes/round
    # at the largest shard count grows with n but sublinearly
    # (boundary ~ sqrt(n))
    assert all(r["proper"] for r in rows), "improper sharded coloring"
    assert all(r["identical_1shard"] for r in rows if r["shards"] == 1), \
        "1-shard sharded stream diverged from mode='incremental'"
    D = max(shards)
    if D == 1:
        print("# acceptance: halo growth not checked — one device, so no "
              "multi-shard column ran", flush=True)
        return
    by_n = sorted((r["n"], r["halo_bytes_per_round"])
                  for r in rows if r["shards"] == D)
    (n0, h0), (n1, h1) = by_n[0], by_n[-1]
    ok = h0 < h1 and (h1 / h0) < (n1 / n0)
    print(f"# acceptance: {D}-shard halo bytes/round {h0} -> {h1} over "
          f"n {n0} -> {n1}: growing={h0 < h1} "
          f"sublinear={(h1 / h0):.2f}x < {(n1 / n0):.2f}x -> "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit("sharded halo-traffic acceptance failed")


if __name__ == "__main__":
    main()
