"""Pallas TPU kernel: fused two-hop detect-and-recolor (native distance-2),
with the hop-2 ELL table **paged through VMEM**.

Two nested W-loops over the (BV, W) ELL tile feed ONE packed (BV, C//32)
forbidden bitset (DESIGN.md §10): hop 1 gathers each row's neighbor colors,
hop 2 re-gathers every neighbor's own ELL row — so G²'s adjacency is
consumed on the fly inside VMEM and never materialized (|E(G²)| ≈ n·deg²
would not fit anyway).

The old kernel required the *whole* (n_all, W) table VMEM-resident, so the
ops.py dispatcher fell back to the jnp reference above ~8 MB — exactly the
high-degree graphs the paper's speedup claims are about.  The table is now
split into ``page_rows``-row pages and the grid is

    (row blocks, table pages)        # pages minor: for each row block i,
                                     # pages p = 0 .. n_pages-1 in order

with per-page BlockSpec index maps: the Pallas pipeline double-buffers the
page input, DMA-ing page p+1 from HBM while the kernel gathers through page
p.  Neighbor j's hop-2 row lives in exactly one page (``lo <= ell[i,j] <
lo + page_rows``), so accumulating the masked per-page contributions visits
every two-hop edge exactly once.  The packed forbidden words and the defect
flags live in VMEM scratch across the page sweep and the branch-free mex
epilogue (``bitset.recolor_epilogue``) runs on the final page — the
forbidden words never round-trip through HBM.

Resident per program: one (BV, W) row tile, two (page_rows, W) page
buffers, the (n,) color/priority vectors, and the (BV, C//32) accumulator —
``ops.twohop_vmem_bytes`` is the honest account, and the only remaining
jnp fallback is for degenerate shapes (the un-pageable (n,) vectors
themselves busting the budget, or empty tiles).

Hop-1 contributions (neighbor colors + the hop-1 defect test) are masked to
the first page visit so they are counted once per row block, not once per
page.  A vertex is always its own two-hop neighbor (v -> w -> v through any
neighbor w); those slots are masked so a row never forbids its own color.

Not lowerable for TPU yet: see ``ops.MOSAIC_UNSUPPORTED``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitset

# Target bytes of one hop-2 table page (two pages are resident: compute +
# prefetch).  2 MB keeps pages + vectors + accumulators comfortably inside
# the ~8 MB per-invocation envelope ops.py budgets (DESIGN.md §8.3).
PAGE_TARGET_BYTES = 2 * 2**20


def default_page_rows(n_all: int, W: int,
                      page_bytes: int = PAGE_TARGET_BYTES) -> int:
    """Rows per hop-2 table page: ~page_bytes worth of (W,) int32 rows,
    multiple-of-128 aligned (TPU sublane friendliness), never exceeding the
    table itself."""
    rows = max(page_bytes // max(W * 4, 1), 128)
    rows = max(rows // 128, 1) * 128
    return min(rows, max(n_all, 1))


def _twohop_kernel(ell_ref, page_ref, colors_ref, pri_ref, U_ref,
                   rowc_ref, rowp_ref, rowid_ref,
                   newc_ref, rec_ref, ovf_ref,
                   forb_ref, defect_ref,
                   *, C: int, n: int, page_rows: int):
    p = pl.program_id(1)                      # table page index (minor axis)
    ell = ell_ref[...]                        # (BV, W) rows being recolored
    page = page_ref[...]                      # (page_rows, W) hop-2 page
    colors = colors_ref[...]                  # (n,)
    pri = pri_ref[...]                        # (n,)
    U = U_ref[...]                            # (BV,)
    c_r = rowc_ref[...]                       # (BV,) this block's colors
    p_r = rowp_ref[...]                       # (BV,)
    vid = rowid_ref[...]                      # (BV,) global ids (self-mask)
    BV, W = ell.shape

    first = p == 0
    lo = p * page_rows
    # scratch persists across the page sweep of one row block; page 0
    # re-initializes (scratch contents from the previous row block are
    # discarded by the where, never read into the accumulation).
    forb0 = jnp.where(first, bitset.init_words(BV, C), forb_ref[...])
    defect0 = jnp.where(first, False, defect_ref[...] != 0)

    def hop1(j, carry):
        forb, defect = carry
        idx = ell[:, j]
        live = idx >= 0
        # hop-1 colors count once per row block: first page visit only
        safe = jnp.clip(idx, 0, n - 1)
        nc = jnp.where(live & first, colors[safe], -1)
        npr = jnp.where(live & first, pri[safe], -1)
        defect = defect | ((nc == c_r) & (c_r >= 0) & (npr > p_r))
        forb = bitset.or_color(forb, nc, C)
        # hop 2: gather neighbor j's own ELL row iff it lives in this page
        in_page = (idx >= lo) & (idx < lo + page_rows)
        row2 = page[jnp.clip(idx - lo, 0, page_rows - 1)]   # (BV, W)

        def hop2(jj, carry2):
            forb2, defect2 = carry2
            idx2 = row2[:, jj]
            live2 = in_page & (idx2 >= 0) & (idx2 != vid)
            safe2 = jnp.clip(idx2, 0, n - 1)
            nc2 = jnp.where(live2, colors[safe2], -1)
            np2 = jnp.where(live2, pri[safe2], -1)
            defect2 = defect2 | ((nc2 == c_r) & (c_r >= 0) & (np2 > p_r))
            return bitset.or_color(forb2, nc2, C), defect2

        return jax.lax.fori_loop(0, W, hop2, (forb, defect))

    forb, defect = jax.lax.fori_loop(0, W, hop1, (forb0, defect0))
    forb_ref[...] = forb
    defect_ref[...] = defect.astype(jnp.int32)
    # fused epilogue on the accumulated words — only the final page's write
    # survives in the (row-block-indexed) output buffers, flushed to HBM
    # when the row block advances.  The (BV, C//32) words never leave VMEM.
    newc, rec, ovf = bitset.recolor_epilogue(forb, defect, U, c_r, C)
    newc_ref[...] = newc
    rec_ref[...] = rec
    ovf_ref[...] = ovf


@functools.partial(jax.jit,
                   static_argnames=("C", "row_start", "block_rows",
                                    "page_rows", "interpret"))
def twohop_detect_recolor(ell_rows, ell_all, colors, pri, U_rows,
                          row_start: int, C: int = 64, block_rows: int = 128,
                          page_rows: int | None = None,
                          interpret: bool = False):
    """Fused two-hop pass for rows [row_start, row_start + R).

    ell_rows:  (R, W) neighbor tile for those rows
    ell_all:   (n_all, W) full neighbor table (hop-2 gathers), n_all >= n
    colors:    (n,) global colors;  pri: (n,) priorities
    U_rows:    (R,) bool, in-frontier mask for those rows
    page_rows: rows per VMEM page of ell_all (None -> ~2 MB pages); the
               table is FILL-padded to a whole number of pages.
    Returns (new row colors (R,), recolored (R,), overflow (R,)).
    """
    R, W = ell_rows.shape
    n = colors.shape[0]
    n_all = ell_all.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    if page_rows is None:
        page_rows = default_page_rows(n_all, W)
    n_pages = -(-n_all // page_rows)
    pad = n_pages * page_rows - n_all
    if pad:
        # FILL-padded rows are unreachable (vertex ids < n_all) — padding
        # only squares the table up to whole pages for the BlockSpec.
        ell_all = jnp.pad(ell_all, ((0, pad), (0, 0)), constant_values=-1)
    rowc = jax.lax.dynamic_slice_in_dim(colors, row_start, R, 0)
    rowp = jax.lax.dynamic_slice_in_dim(pri, row_start, R, 0)
    rowid = row_start + jnp.arange(R, dtype=jnp.int32)
    grid = (R // block_rows, n_pages)
    kernel = functools.partial(_twohop_kernel, C=C, n=n, page_rows=page_rows)
    blk = lambda: pl.BlockSpec((block_rows,), lambda i, p: (i,))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, W), lambda i, p: (i, 0)),  # row tile
            pl.BlockSpec((page_rows, W), lambda i, p: (p, 0)),   # table page
            pl.BlockSpec((n,), lambda i, p: (0,)),               # colors
            pl.BlockSpec((n,), lambda i, p: (0,)),               # priorities
            blk(), blk(), blk(), blk(),
        ],
        out_specs=[blk(), blk(), blk()],
        out_shape=[
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, bitset.n_words(C)), jnp.int32),
            pltpu.VMEM((block_rows,), jnp.int32),
        ],
        interpret=interpret,
    )(ell_rows, ell_all, colors, pri, U_rows, rowc, rowp, rowid)
