"""Pallas TPU kernel: first-fit tentative coloring over an ELL vertex tile.

The paper's hot loop (gather neighbor colors -> forbidden set -> smallest free
color).  TPU adaptation (DESIGN.md §2, §10): rectangular (BV, W) ELL tiles in
VMEM; the forbidden set is a packed (BV, C//32) int32 bitset built by W
vectorized compare+OR steps on the VPU — 32× fewer compare lanes and 8× less
VMEM than the old (BV, C) one-hot bool table, which is what lets the tile
take bigger BV/C without spilling.  First-fit = branch-free mex over the
packed words (isolate-lowest-zero-bit + float-exponent bit index,
``core/bitset.py`` — the identical code path the jnp engines trace), fused
into the kernel epilogue so the packed words never round-trip through HBM
(the degenerate no-defect case of ``bitset.recolor_epilogue``).  The color
vector is VMEM-resident per invocation; ``ops.firstfit_vmem_bytes`` is the
honest account and the ops.py wrapper falls back to the jnp path when it
busts the budget.

Grid: one program per BV-row block of the chunk being colored.

The TPU compiler does not lower this kernel yet (the per-row gather from the
(n,) vector); ``ops.MOSAIC_UNSUPPORTED`` records why, and on TPU the
dispatcher runs the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitset


def _firstfit_kernel(ell_ref, colors_ref, out_ref, ovf_ref, *, C: int, n: int):
    ell = ell_ref[...]                       # (BV, W) int32
    colors = colors_ref[...]                 # (n,) int32
    BV, W = ell.shape

    def body(j, forb):
        idx = ell[:, j]
        nc = colors[jnp.clip(idx, 0, n - 1)]
        nc = jnp.where(idx >= 0, nc, -1)
        return bitset.or_color(forb, nc, C)

    forb = jax.lax.fori_loop(0, W, body, bitset.init_words(BV, C))
    mex, ovf = bitset.mex_words(forb, C)
    out_ref[...] = mex
    ovf_ref[...] = ovf


@functools.partial(jax.jit, static_argnames=("C", "block_rows", "interpret"))
def firstfit(ell, colors, C: int = 64, block_rows: int = 256,
             interpret: bool = False):
    """First-fit colors for every ELL row. Returns (mex (R,), overflow (R,))."""
    R, W = ell.shape
    n = colors.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    grid = (R // block_rows,)
    kernel = functools.partial(_firstfit_kernel, C=C, n=n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, W), lambda i: (i, 0)),   # ELL tile
            pl.BlockSpec((n,), lambda i: (0,)),                # full colors
        ],
        out_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ],
        interpret=interpret,
    )(ell, colors)
