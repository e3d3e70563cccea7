"""Pallas TPU kernel: RSOC's fused detect-and-recolor over one chunk.

One VMEM round-trip does both the paper's conflict detection and the
immediate repair — the kernel-level expression of merging Alg. 2's two phases
into Alg. 3's single phase: neighbor colors are gathered ONCE and feed both
the defect test (same color as a higher-priority neighbor) and the first-fit
re-color.  The forbidden accumulator is the packed (BV, C//32) bitset of
DESIGN.md §10 (inline pack + branch-free mex via ``core/bitset.py``).
Not lowerable for TPU yet: see ``ops.MOSAIC_UNSUPPORTED``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitset


def _detect_recolor_kernel(ell_ref, colors_ref, pri_ref, U_ref, rowc_ref,
                           rowp_ref, newc_ref, rec_ref, ovf_ref,
                           *, C: int, n: int):
    ell = ell_ref[...]                        # (BV, W)
    colors = colors_ref[...]                  # (n,)
    pri = pri_ref[...]                        # (n,)
    U = U_ref[...]                            # (BV,)
    c_r = rowc_ref[...]                       # (BV,) this block's colors
    p_r = rowp_ref[...]                       # (BV,)
    BV, W = ell.shape

    def body(j, carry):
        forb, defect = carry
        idx = ell[:, j]
        safe = jnp.clip(idx, 0, n - 1)
        nc = jnp.where(idx >= 0, colors[safe], -1)
        np_ = jnp.where(idx >= 0, pri[safe], -1)
        defect = defect | ((nc == c_r) & (c_r >= 0) & (np_ > p_r))
        return bitset.or_color(forb, nc, C), defect

    forb, defect = jax.lax.fori_loop(
        0, W, body,
        (bitset.init_words(BV, C), jnp.zeros((BV,), jnp.bool_)))
    # fused epilogue: mex runs on the packed words while they are still
    # VMEM-resident — the (BV, C//32) forbidden table never reaches HBM
    newc, rec, ovf = bitset.recolor_epilogue(forb, defect, U, c_r, C)
    newc_ref[...] = newc
    rec_ref[...] = rec
    ovf_ref[...] = ovf


@functools.partial(jax.jit,
                   static_argnames=("C", "row_start", "block_rows", "interpret"))
def detect_recolor(ell, colors, pri, U_rows, row_start: int, C: int = 64,
                   block_rows: int = 256, interpret: bool = False):
    """Fused RSOC pass for rows [row_start, row_start + R).

    ell:    (R, W) neighbor tile for those rows
    colors: (n,) global colors;  pri: (n,) priorities
    U_rows: (R,) bool, in-frontier mask for those rows
    Returns (new row colors (R,), recolored (R,), overflow (R,)).
    """
    R, W = ell.shape
    n = colors.shape[0]
    assert R % block_rows == 0
    rowc = jax.lax.dynamic_slice_in_dim(colors, row_start, R, 0)
    rowp = jax.lax.dynamic_slice_in_dim(pri, row_start, R, 0)
    grid = (R // block_rows,)
    kernel = functools.partial(_detect_recolor_kernel, C=C, n=n)
    blk = lambda: pl.BlockSpec((block_rows,), lambda i: (i,))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            blk(), blk(), blk(),
        ],
        out_specs=[blk(), blk(), blk()],
        out_shape=[
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ],
        interpret=interpret,
    )(ell, colors, pri, U_rows, rowc, rowp)
