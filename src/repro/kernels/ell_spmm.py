"""Pallas TPU kernel: ELL neighbor aggregation (GNN message passing).

Reuses the coloring kernels' rectangular ELL layout: out[v] = reduce over
feats[nbr[v, :]].  Grid is (vertex blocks, feature blocks); each program
gathers a (BV, W) neighbor tile against a (n, BF) feature column panel held
in VMEM and reduces on the VPU.  Feature panels bound VMEM use to n*BF*4
bytes; the ops.py wrapper picks BF accordingly and falls back to the
segment-sum jnp path for graphs whose node count makes any panel too large
(page-indirected DMA design for that regime is documented in DESIGN.md).
Not lowerable for TPU yet: see ``ops.MOSAIC_UNSUPPORTED``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ell_spmm_kernel(ell_ref, feats_ref, out_ref, *, op: str, n: int):
    ell = ell_ref[...]                       # (BV, W)
    feats = feats_ref[...]                   # (n, BF)
    BV, W = ell.shape
    BF = feats.shape[1]
    if op == "max":
        init = jnp.full((BV, BF), -jnp.inf, feats.dtype)
    else:
        init = jnp.zeros((BV, BF), feats.dtype)

    def body(j, acc):
        idx = ell[:, j]
        valid = idx >= 0
        row = feats[jnp.clip(idx, 0, n - 1)]
        if op == "max":
            row = jnp.where(valid[:, None], row, -jnp.inf)
            return jnp.maximum(acc, row)
        row = jnp.where(valid[:, None], row, 0)
        return acc + row

    acc = jax.lax.fori_loop(0, W, body, init)
    if op == "mean":
        cnt = jnp.maximum((ell >= 0).sum(axis=1), 1).astype(feats.dtype)
        acc = acc / cnt[:, None]
    if op == "max":
        acc = jnp.where(jnp.isfinite(acc), acc, 0)
    out_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("op", "block_rows", "block_feats",
                                    "interpret"))
def ell_spmm(ell, feats, op: str = "sum", block_rows: int = 128,
             block_feats: int = 128, interpret: bool = False):
    """Aggregate neighbor features over an ELL table.

    ell: (R, W) int32; feats: (n, d) float32/bf16 -> (R, d)
    """
    R, W = ell.shape
    n, d = feats.shape
    br = min(block_rows, R)
    bf = min(block_feats, d)
    assert R % br == 0 and d % bf == 0, (R, d, br, bf)
    grid = (R // br, d // bf)
    kernel = functools.partial(_ell_spmm_kernel, op=op, n=n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, W), lambda i, j: (i, 0)),
            pl.BlockSpec((n, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, d), feats.dtype),
        interpret=interpret,
    )(ell, feats)
