"""Paper §6 outlook: distance-2 coloring.  G^2 is much denser than G, and
the paper predicts RSOC's advantage (fewer conflicts/rounds/passes) grows
with density — we measure exactly that on the mesh classes, and compare the
native two-hop engine (DESIGN.md §8) against the materialized power_graph
path on both time and peak working set.

The materialized rows' ``ms`` includes the G² build (paid on every call in
production); G² is built ONCE per (graph, d) here and shared between the
degree statistic and every algorithm row — it used to be rebuilt per row.

The second table exercises the VMEM-paged two-hop KERNEL directly
(DESIGN.md §8.3): a synthetic ELL table sized past the old 8 MB residency
bound that used to force the jnp fallback is paged through
``kernels.ops.twohop`` on the Pallas path, timed end-to-end (time_fn
blocks), checked bit-identical against ``ref.twohop_ref``, and asserted to
dispatch with ZERO ``kernels.fallback`` increments — the acceptance row
for the paging work.  ``bytes_moved`` is the exact HBM traffic of the
paged schedule (every row-block streams the whole padded table), which
roofline_report.py prefers over the ws_mb lower bound.  On a TPU the
kernel does not lower yet (``ops.MOSAIC_UNSUPPORTED``), so that table prints
"not measured" with the compiler's reason instead.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Csv, forb_ws_mb, suite, time_fn
from repro import api
from repro.core import distance2
from repro.graphs.csr import CSRGraph, power_graph


def ws_mb_materialized(gd: CSRGraph, ell_cap: int = 512) -> float:
    """Peak working set of the materialized path: G²'s CSR plus what the
    coloring loop actually allocates — an ELL capped at ``ell_cap`` columns
    with hub rows spilling into the COO side-channel (see
    ``coloring.prepare``)."""
    W = max(min(gd.max_degree, ell_cap), 1)
    ell_bytes = gd.n_vertices * W * 4
    ovf_bytes = int(np.maximum(gd.degrees - W, 0).sum()) * 8   # src+dst int32
    csr_bytes = gd.indices.nbytes + gd.indptr.nbytes
    return (ell_bytes + ovf_bytes + csr_bytes) / 2**20


# Synthetic hop-2 tables for the paged-kernel rows: every scale's table
# exceeds the old 8 MB VMEM residency bound (n_all * W * 4 bytes), so a
# pre-paging dispatcher would have silently fallen back to jnp.  The (n,)
# color/priority vectors stay far under budget — these shapes are pageable,
# not degenerate.
KERNEL_SHAPES = {
    "tiny":   dict(n_all=144 * 1024, W=16, R=512),    # 9 MB table
    "small":  dict(n_all=160 * 1024, W=16, R=1024),   # 10 MB table
    "medium": dict(n_all=320 * 1024, W=16, R=2048),   # 20 MB table
}


def kernel_rows(scale: str) -> None:
    """Time the paged two-hop kernel on an above-the-old-bound table and
    prove (in-bench, loudly) that it ran on the Pallas path with zero
    fallbacks and bit-identical outputs to the reference."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels import twohop as twohop_mod
    from repro.obs import metrics as obs_metrics

    if jax.default_backend() == "tpu" and "twohop" in ops.MOSAIC_UNSUPPORTED:
        # never time the reference under the kernel's name
        print(f"# twohop paged kernel: not measured: "
              f"{ops.MOSAIC_UNSUPPORTED['twohop']}", flush=True)
        return
    shp = KERNEL_SHAPES[scale]
    n_all, W, R = shp["n_all"], shp["W"], shp["R"]
    C = 64
    rng = np.random.default_rng(7)
    ell_all = jnp.asarray(rng.integers(-1, n_all, size=(n_all, W)),
                          dtype=jnp.int32)
    colors = jnp.asarray(rng.integers(-1, C, size=(n_all,)), dtype=jnp.int32)
    pri = jnp.asarray(rng.permutation(n_all), dtype=jnp.int32)
    U_rows = jnp.ones((R,), dtype=bool)
    ell_rows = ell_all[:R]
    row_start = 0

    backend = "pallas" if jax.default_backend() == "tpu" else \
        "pallas_interpret"
    table_mb = n_all * W * 4 / 2**20
    page_rows = twohop_mod.default_page_rows(n_all, W)
    n_pages = -(-n_all // page_rows)
    csv = Csv(["graph", "algo", "kernel", "backend", "n_all", "W",
               "table_mb", "page_rows", "n_pages", "ms", "ws_mb",
               "bytes_moved_mb", "parity"])

    fb0 = obs_metrics.total_matching("kernels.fallback")
    ms, out = time_fn(ops.twohop, ell_rows, ell_all, colors, pri, U_rows,
                      row_start, C=C, backend=backend, repeats=2)
    fb = obs_metrics.total_matching("kernels.fallback") - fb0
    assert fb == 0, (
        f"paged twohop fell back {fb}x on a pageable {table_mb:.1f}MB table "
        f"— the paging dispatch regressed (backend={backend})")

    want = ref.twohop_ref(ell_rows, ell_all, colors, pri, row_start, U_rows,
                          C)
    parity = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(out, want))
    assert parity, "paged twohop kernel diverged from ref.twohop_ref"

    # exact paged-schedule traffic: each of the ceil(R/128) row-blocks
    # streams the whole padded table once, plus the row tiles, the two (n,)
    # vectors, and the three outputs
    n_blocks = -(-R // 128)
    bytes_moved = (n_blocks * n_pages * page_rows * W * 4
                   + R * W * 4 + 2 * n_all * 4 + R * (4 + 1 + 1))
    ws_mb = ops.twohop_vmem_bytes(R, W, n_all, C, n_all=n_all) / 2**20
    csv.row(f"synth_{n_all}x{W}", "twohop_paged", "twohop", backend, n_all,
            W, table_mb, page_rows, n_pages, ms * 1e3, ws_mb,
            bytes_moved / 2**20, parity,
            extra={"bytes_moved": int(bytes_moved)})
    print(f"# twohop paged kernel [{backend}]: {table_mb:.1f}MB table "
          f"(> 8MB old bound) in {n_pages} pages x {page_rows} rows, "
          f"{ms * 1e3:.1f}ms, fallbacks=0, bit-identical to ref",
          flush=True)


def main(scale: str = "small") -> None:
    graphs = {k: v for k, v in suite(scale).items()
              if k in ("mesh2d", "bmw3_2", "pwtk")}
    csv = Csv(["graph", "d", "path", "avg_degree_gd", "algo", "ms", "rounds",
               "gather_passes", "conflicts", "colors", "ws_mb",
               "forb_ws_mb"])
    for gname, g in graphs.items():
        for d in (1, 2):
            build_s, gd = time_fn(power_graph, g, d, repeats=1, warmup=0)
            avg_deg = gd.n_edges / max(gd.n_vertices, 1)
            ws_mat = ws_mb_materialized(gd)
            mat_ms = {}
            for algo in ("cat", "rsoc"):
                # materialized path: distance-1 coloring of the explicit G^d
                spec = api.ColoringSpec(algorithm=algo, seed=1)
                sec, res = time_fn(api.color, gd, spec, repeats=2)
                mat_ms[algo] = (build_s + sec) * 1e3
                csv.row(gname, d, "materialized", avg_deg, algo,
                        mat_ms[algo], res.n_rounds, res.gather_passes,
                        res.total_conflicts, res.n_colors, ws_mat,
                        forb_ws_mb(gd.n_vertices, 16, res.final_C),
                        spec=res.spec, result=res)
            if d != 2:
                continue
            spec = api.ColoringSpec(algorithm="rsoc", distance=2, seed=1)
            sec, res = time_fn(api.color, g, spec, repeats=2)
            nat_ms = sec * 1e3
            # the honest engine working set (distance2.native_ws_mb): ELL +
            # (n,) vectors + gathered color/priority panels + packed
            # forbidden rows — the old local estimate dropped all but the
            # first and half the second
            ws_nat = distance2.native_ws_mb(g, n_chunks=16, C=res.final_C)
            csv.row(gname, d, "native", avg_deg, "rsoc", nat_ms,
                    res.n_rounds, res.gather_passes, res.total_conflicts,
                    res.n_colors, ws_nat,
                    forb_ws_mb(g.n_vertices, 16, res.final_C),
                    spec=res.spec, result=res)
            print(f"# native-vs-materialized {gname} d=2: "
                  f"native {nat_ms:.1f}ms / {ws_nat:.2f}MB ws  vs  "
                  f"materialized(rsoc) {mat_ms['rsoc']:.1f}ms / "
                  f"{ws_mat:.2f}MB ws  "
                  f"(time {mat_ms['rsoc'] / max(nat_ms, 1e-9):.2f}x, "
                  f"ws {ws_mat / max(ws_nat, 1e-9):.2f}x)", flush=True)
    kernel_rows(scale)


if __name__ == "__main__":
    main()
