"""Pallas TPU kernel: blockwise-softmax (flash) attention, forward.

VMEM tiling: (BQ, D) query block resident; KV streamed in (BK, D) blocks with
running max / running sum (log-sum-exp) accumulation — the standard
IO-aware schedule, MXU-aligned (BQ, BK multiples of 128; D = head_dim).
Supports GQA via a query-head -> kv-head grid mapping and causal masking with
a decode offset (Lk >= Lq).

Training uses the chunked pure-jnp path (models/layers.py) with native
autodiff + remat; this kernel is the serving-path hot spot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, Lq, Lk,
                  block_k):
    q = q_ref[0, 0]                     # (BQ, D)
    BQ, D = q.shape
    nk = Lk // block_k
    qi = pl.program_id(2)               # query-block index
    q_off = qi * BQ + (Lk - Lq)         # causal diag offset (decode-friendly)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]  # (BK, D)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (BQ, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (BQ, block_k), 1)
            mask = (j * block_k + cols) <= (q_off + rows)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((BQ, D), jnp.float32)
    m0 = jnp.full((BQ,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D); GQA when Hq > Hkv."""
    B, Hq, Lq, D = q.shape
    _, Hkv, Lk, _ = k.shape
    G = Hq // Hkv
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0
    scale = 1.0 / (D ** 0.5)
    grid = (B, Hq, Lq // bq)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               Lq=Lq, Lk=Lk, block_k=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Lq, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
