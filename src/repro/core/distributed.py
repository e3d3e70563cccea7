"""Multi-device graph coloring via shard_map.

Collective schedules (DESIGN.md §2 — the paper's barrier analysis, in
collectives):

  RSOC  : one fused detect-and-recolor pass per round; the updated local color
          slice and the local defect count ride the SAME ``all_gather``
          (payload = [colors_local, n_defects_local]).   => 1 collective/round
  CAT   : phase A re-colors the defect set, whose colors must be re-replicated
          before phase B can detect (all_gather #1); phase B's defect count
          feeds the termination test, a global consensus (psum #2).  The data
          dependency detect-after-exchange is structural — exactly the second
          barrier of the paper's Algorithm 2.            => 2 collectives/round

Two color-exchange strategies:
  * ``replicated``: the full color vector is re-gathered each round
    (bytes/round = n*4).  Simple, the baseline.
  * ``halo``: only boundary colors are exchanged (bytes/round = D*max_b*4),
    using the static HaloPlan (partition.py).  This is the collective-term
    optimization recorded in EXPERIMENTS.md §Perf.

Both run under ``jax.jit`` + ``shard_map`` over a 1-D logical device axis
(callers flatten (data, model[, pod]) meshes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro import registry
from repro.graphs.csr import CSRGraph, FILL, to_ell
from repro.core import coloring as col
from repro.core.context import PassContext
from repro.core.partition import Partition, HaloPlan, block_partition, build_halo
from repro import obs

MAX_ROUNDS_TRACE = col.MAX_ROUNDS_TRACE


# --------------------------------------------------------------------------
# local fused pass (shared)
# --------------------------------------------------------------------------

def _local_fused_pass(ell_loc, colors_glb, pri_glb, U_loc, force_loc,
                      row_base, ctx: PassContext, *, detect: bool):
    """Chunked detect-and-recolor of this shard's rows against global colors.

    ell_loc:   (n_loc, W) global neighbor ids
    colors_glb:(n_glb,)   replicated (or local+ghost) color table
    row_base:  first global row of this shard
    ctx:       ``ctx.n`` bounds the valid global rows; ``ctx.n_pad`` is the
               table the caller sliced this shard from (unused here — the
               chunking runs over ell_loc's own rows)
    Returns (new local colors (n_loc,), recolored mask, n_defects).
    """
    n, _, C, n_chunks, impl = ctx.unpack()
    n_loc = ell_loc.shape[0]
    cs = n_loc // n_chunks
    colors_loc = jax.lax.dynamic_slice_in_dim(colors_glb, row_base, n_loc, 0)
    pri_loc = jax.lax.dynamic_slice_in_dim(pri_glb, row_base, n_loc, 0)
    valid_loc = (jnp.arange(n_loc) + row_base) < n

    def chunk_body(k, carry):
        colors_l, colors_g, recolored, n_def = carry
        lo = k * cs
        ell_k = jax.lax.dynamic_slice_in_dim(ell_loc, lo, cs, 0)
        c_k = jax.lax.dynamic_slice_in_dim(colors_l, lo, cs, 0)
        pri_k = jax.lax.dynamic_slice_in_dim(pri_loc, lo, cs, 0)
        U_k = jax.lax.dynamic_slice_in_dim(U_loc, lo, cs, 0)
        force_k = jax.lax.dynamic_slice_in_dim(force_loc, lo, cs, 0)
        valid_k = jax.lax.dynamic_slice_in_dim(valid_loc, lo, cs, 0)
        nbrc, nbrp = col._gather_nbr(ell_k, colors_g, pri_glb)
        if detect:
            defect = ((nbrc == c_k[:, None]) & (c_k[:, None] >= 0)
                      & (nbrp > pri_k[:, None])).any(axis=1)
            work = valid_k & ((U_k & defect) | force_k)
            n_def = n_def + (valid_k & U_k & defect).sum(dtype=jnp.int32)
        else:
            work = valid_k & (U_k | force_k)
        forb = col._forbidden(nbrc, C, impl)
        mex, _ = col._mex_of(forb, C, impl)
        newc = jnp.where(work, mex, c_k)
        colors_l = jax.lax.dynamic_update_slice_in_dim(colors_l, newc, lo, 0)
        # keep the *global* view fresh for later chunks of this shard
        colors_g = jax.lax.dynamic_update_slice_in_dim(
            colors_g, newc, row_base + lo, 0)
        recolored = jax.lax.dynamic_update_slice_in_dim(recolored, work, lo, 0)
        return colors_l, colors_g, recolored, n_def

    init = (colors_loc, colors_glb, jnp.zeros((n_loc,), bool), jnp.int32(0))
    colors_l, _, recolored, n_def = jax.lax.fori_loop(0, n_chunks, chunk_body, init)
    return colors_l, recolored, n_def


# --------------------------------------------------------------------------
# replicated-exchange engines
# --------------------------------------------------------------------------

def build_rsoc_distributed(mesh: Mesh, axis: str, ctx: PassContext,
                           max_rounds: int = 64):
    """Returns a jittable fn(ell (n_pad, W), pri (n_pad,)) -> (colors, rounds,
    conflicts). ONE fused collective per round (colors slice + defect count).

    ``ctx`` carries (n, n_pad, C, n_chunks, forbidden_impl) for the whole
    (unsharded) problem; each shard owns n_pad / D rows.
    """
    n_pad = ctx.n_pad
    D = int(np.prod([mesh.shape[a] for a in axis.split(",")]))
    axes = tuple(axis.split(","))
    n_loc = n_pad // D
    spec_rows = P(axes if len(axes) > 1 else axes[0])

    def body(ell_loc, pri):
        axname = axes if len(axes) > 1 else axes[0]
        idx = jax.lax.axis_index(axname)
        row_base = idx * n_loc
        colors0 = jnp.full((n_pad,), -1, jnp.int32)
        zeros = jnp.zeros((n_loc,), bool)
        ones = jnp.ones((n_loc,), bool)

        def exchange(colors_l, n_def_l):
            payload = jnp.concatenate(
                [colors_l, n_def_l[None].astype(jnp.int32)])
            allp = jax.lax.all_gather(payload, axname, tiled=False)
            allp = allp.reshape(D, n_loc + 1)
            colors = allp[:, :n_loc].reshape(n_pad)
            return colors, allp[:, n_loc].sum()

        # round 0: color everything; 1 collective
        c_l, _, _ = _local_fused_pass(ell_loc, colors0, pri, zeros, ones,
                                      row_base, ctx, detect=False)
        colors, _ = exchange(c_l, jnp.int32(0))
        U0 = ones

        def cond(s):
            _, _, _, r, _, last = s
            return (last > 0) & (r < max_rounds)

        def body_fn(s):
            colors, U, trace, r, tot, _ = s
            c_l, recolored, n_def_l = _local_fused_pass(
                ell_loc, colors, pri, U, jnp.zeros((n_loc,), bool),
                row_base, ctx, detect=True)
            colors2, n_def = exchange(c_l, n_def_l)      # ONE collective
            trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(
                n_def.astype(jnp.int32))
            return (colors2, recolored, trace, r + 1,
                    tot + n_def.astype(jnp.int32), n_def.astype(jnp.int32))

        trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
        s = (colors, U0, trace, jnp.int32(0), jnp.int32(0), jnp.int32(1))
        colors, _, trace, r, tot, _ = jax.lax.while_loop(cond, body_fn, s)
        return colors, r, trace, tot

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(*((axes if len(axes) > 1 else (axes[0],)) + (None,))), P()),
                  out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(f)


def build_cat_distributed(mesh: Mesh, axis: str, ctx: PassContext,
                          max_rounds: int = 64):
    """CAT with the structural 2-collectives-per-round schedule."""
    n_pad = ctx.n_pad
    axes = tuple(axis.split(","))
    D = int(np.prod([mesh.shape[a] for a in axes]))
    n_loc = n_pad // D

    def body(ell_loc, pri):
        axname = axes if len(axes) > 1 else axes[0]
        idx = jax.lax.axis_index(axname)
        row_base = idx * n_loc
        colors0 = jnp.full((n_pad,), -1, jnp.int32)
        zeros = jnp.zeros((n_loc,), bool)
        ones = jnp.ones((n_loc,), bool)

        def gather_colors(colors_l):
            allc = jax.lax.all_gather(colors_l, axname, tiled=False)
            return allc.reshape(n_pad)

        def detect_local(colors):
            c_l = jax.lax.dynamic_slice_in_dim(colors, row_base, n_loc, 0)
            p_l = jax.lax.dynamic_slice_in_dim(pri, row_base, n_loc, 0)
            nbrc, nbrp = col._gather_nbr(ell_loc, colors, pri)
            return ((nbrc == c_l[:, None]) & (c_l[:, None] >= 0)
                    & (nbrp > p_l[:, None])).any(axis=1)

        # round 0
        c_l, _, _ = _local_fused_pass(ell_loc, colors0, pri, zeros, ones,
                                      row_base, ctx, detect=False)
        colors = gather_colors(c_l)                       # collective 1
        U = detect_local(colors)
        n_def = jax.lax.psum(U.sum(dtype=jnp.int32), axname)  # collective 2

        def cond(s):
            return (s[4] > 0) & (s[2] < max_rounds)

        def body_fn(s):
            colors, U, r, tot, n_def, trace = s
            trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(n_def)
            # phase A: recolor defect set
            c_l, _, _ = _local_fused_pass(ell_loc, colors, pri, U, zeros,
                                          row_base, ctx, detect=False)
            colors2 = gather_colors(c_l)                  # collective 1
            # phase B: detect + global consensus
            U2 = detect_local(colors2) & U
            n_def2 = jax.lax.psum(U2.sum(dtype=jnp.int32), axname)  # coll. 2
            return colors2, U2, r + 1, tot + n_def, n_def2, trace

        trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
        s = (colors, U, jnp.int32(0), jnp.int32(0), n_def, trace)
        colors, U, r, tot, n_def, trace = jax.lax.while_loop(cond, body_fn, s)
        return colors, r, trace, tot

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(*((axes if len(axes) > 1 else (axes[0],)) + (None,))), P()),
                  out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(f)


# --------------------------------------------------------------------------
# halo-exchange RSOC (collective-term optimized; EXPERIMENTS.md §Perf)
# --------------------------------------------------------------------------

def build_rsoc_halo(mesh: Mesh, axis: str, plan_shapes: dict,
                    ctx: PassContext, max_rounds: int = 64):
    """RSOC exchanging only boundary colors.

    Inputs per shard (leading dim D, sharded): ell_local (n_loc, W) with
    local/ghost slot ids; boundary (max_b,); ghost flat index (max_g,) into the
    gathered (D*max_b,) boundary payload.  Color table per shard has
    n_loc + max_g slots (ghosts at the tail).  ``ctx`` supplies
    (C, n_chunks, forbidden_impl); its row counts are re-derived per shard.
    """
    axes = tuple(axis.split(","))
    D, n_loc = plan_shapes["D"], plan_shapes["n_loc"]
    max_b, max_g = plan_shapes["max_b"], plan_shapes["max_g"]
    # every local row is a valid candidate; the shard's color table carries
    # max_g ghost slots at the tail
    lctx = dataclasses.replace(ctx, n=n_loc, n_pad=n_loc + max_g)

    def body(ell_loc, pri_loc, pri_ghost, boundary, ghost_flat, valid_loc):
        axname = axes if len(axes) > 1 else axes[0]
        n_tab = n_loc + max_g
        colors_tab0 = jnp.full((n_tab,), -1, jnp.int32)
        pri_tab = jnp.concatenate([pri_loc, pri_ghost])
        zeros = jnp.zeros((n_loc,), bool)

        def exchange(colors_tab, n_def_l):
            b = jnp.where(boundary >= 0,
                          colors_tab[jnp.clip(boundary, 0, n_loc - 1)], -1)
            payload = jnp.concatenate([b, n_def_l[None].astype(jnp.int32)])
            allp = jax.lax.all_gather(payload, axname, tiled=False)
            allp = allp.reshape(D, max_b + 1)
            flat = allp[:, :max_b].reshape(D * max_b)
            ghosts = jnp.where(ghost_flat >= 0,
                               flat[jnp.clip(ghost_flat, 0, D * max_b - 1)], -1)
            colors_tab = jax.lax.dynamic_update_slice_in_dim(
                colors_tab, ghosts, n_loc, 0)
            return colors_tab, allp[:, max_b].sum()

        def fused(colors_tab, U, force, detect):
            return _local_fused_pass(ell_loc, colors_tab, pri_tab, U, force,
                                     0, lctx, detect=detect)

        # round 0
        c_l, _, _ = fused(colors_tab0, zeros, valid_loc, False)
        tab = jax.lax.dynamic_update_slice_in_dim(colors_tab0, c_l, 0, 0)
        tab, _ = exchange(tab, jnp.int32(0))              # 1 collective

        def cond(s):
            return (s[4] > 0) & (s[2] < max_rounds)

        def body_fn(s):
            tab, U, r, tot, _, trace = s
            c_l, recolored, n_def_l = fused(tab, U, zeros, True)
            tab = jax.lax.dynamic_update_slice_in_dim(tab, c_l, 0, 0)
            tab, n_def = exchange(tab, n_def_l)           # 1 collective
            trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(
                n_def.astype(jnp.int32))
            return (tab, recolored, r + 1, tot + n_def.astype(jnp.int32),
                    n_def.astype(jnp.int32), trace)

        trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
        s = (tab, valid_loc, jnp.int32(0), jnp.int32(0), jnp.int32(1), trace)
        tab, _, r, tot, _, trace = jax.lax.while_loop(cond, body_fn, s)
        colors_l = jax.lax.dynamic_slice_in_dim(tab, 0, n_loc, 0)
        return colors_l, r, trace, tot

    row = P(*((axes if len(axes) > 1 else (axes[0],)) + (None,)))
    vec = P(axes if len(axes) > 1 else axes[0])
    f = shard_map(body, mesh=mesh,
                  in_specs=(row, vec, vec, vec, vec, vec),
                  out_specs=(vec, P(), P(), P()), check_vma=False)
    return jax.jit(f)


# --------------------------------------------------------------------------
# sharded mutable-state passes (dynamic/sharded.py; DESIGN.md §15)
#
# Same halo protocol as build_rsoc_halo — ONE all_gather per round carrying
# [boundary colors, n_defects, work, overflow] — but over the *mutable*
# encode: per-shard overflow COO alongside the ELL, external (colors, U)
# seeds instead of a from-scratch start, and the overflow flag threaded out
# last so ``col._run_with_retry`` can drive cap doubling.  Builders are
# lru_cached: rebuilding a shard_map per call would mint a fresh function
# identity and recompile on every service step.
# --------------------------------------------------------------------------

def _sharded_exchange(axname, D, n_loc, max_b, boundary, ghost_flat):
    """Shared halo exchange: publish my boundary colors + (n_def, work, ovf)
    scalars, gather all shards' payloads, refresh my ghost tail.  Returns a
    closure ``exchange(tab, n_def_l, work_l, ovf_l) -> (tab, n_def, work,
    ovf)`` with the scalars globally summed/or-ed."""

    def exchange(tab, n_def_l, work_l, ovf_l):
        b = jnp.where(boundary >= 0,
                      tab[jnp.clip(boundary, 0, n_loc - 1)], -1)
        tail = jnp.stack([n_def_l.astype(jnp.int32),
                          work_l.astype(jnp.int32),
                          ovf_l.astype(jnp.int32)])
        allp = jax.lax.all_gather(jnp.concatenate([b, tail]), axname,
                                  tiled=False).reshape(D, max_b + 3)
        flat = allp[:, :max_b].reshape(D * max_b)
        ghosts = jnp.where(ghost_flat >= 0,
                           flat[jnp.clip(ghost_flat, 0, D * max_b - 1)], -1)
        tab = jax.lax.dynamic_update_slice_in_dim(tab, ghosts, n_loc, 0)
        return (tab, allp[:, max_b].sum(), allp[:, max_b + 1].sum(),
                allp[:, max_b + 2].sum() > 0)

    return exchange


@functools.lru_cache(maxsize=None)
def build_sharded_scratch(mesh: Mesh, axis: str, D: int, n_loc: int,
                          max_b: int, max_g: int, ctx: PassContext,
                          max_rounds: int):
    """From-scratch coloring of a sharded mutable state: round 0 force-colors
    every valid local row, then fused detect-and-recolor rounds with one halo
    exchange each.  On a 1-shard mesh this replays ``col._rsoc_loop``'s
    program bit-for-bit (same chunked pass, same carry schedule).

    Returns jit fn(ell (D*n_loc, W), ovf_src (D*cap,), ovf_dst (D*cap,),
    pri_tab (D*n_tab,), valid_loc (D*n_loc,), boundary (D*max_b,),
    ghost_flat (D*max_g,)) -> (colors_tab (D*n_tab,), rounds, trace,
    total_conflicts, overflowed)."""
    axes = tuple(axis.split(","))
    axname = axes if len(axes) > 1 else axes[0]
    n_tab = n_loc + max_g
    lctx = dataclasses.replace(ctx, n=n_loc, n_pad=n_loc, trace=False)

    def body(ell, osrc, odst, pri_tab, valid_loc, boundary, ghost_flat):
        exchange = _sharded_exchange(axname, D, n_loc, max_b, boundary,
                                     ghost_flat)
        tab0 = jnp.full((n_tab,), -1, jnp.int32)
        zeros = jnp.zeros((n_loc,), bool)

        # round 0: color every valid local row against fresh local colors
        tab, U, _, ovf0 = col._chunked_pass(
            lctx, ell, osrc, odst, pri_tab, tab0, zeros, valid_loc,
            detect=False, valid=valid_loc)
        tab, _, _, ovf_g = exchange(tab, jnp.int32(0), jnp.int32(0), ovf0)

        def cond(s):
            return (s[4] > 0) & (s[3] < max_rounds)

        def body_fn(s):
            tab, U, trace, r, _, tot, ovf = s
            colors_loc = jax.lax.dynamic_slice_in_dim(tab, 0, n_loc, 0)
            force = U & (colors_loc < 0)
            tab2, recolored, n_def_l, ovf_l = col._chunked_pass(
                lctx, ell, osrc, odst, pri_tab, tab, U, force,
                detect=True, valid=valid_loc)
            tab2, n_def, work, ovf2 = exchange(
                tab2, n_def_l, n_def_l + force.sum(dtype=jnp.int32),
                ovf | ovf_l)
            trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(
                n_def.astype(jnp.int32))
            return (tab2, recolored, trace, r + 1, work.astype(jnp.int32),
                    tot + n_def.astype(jnp.int32), ovf2)

        trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
        s = (tab, U, trace, jnp.int32(0), jnp.int32(1), jnp.int32(0), ovf_g)
        tab, _, trace, r, _, tot, ovf = jax.lax.while_loop(cond, body_fn, s)
        return tab, r, trace, tot, ovf

    row = P(*((axes if len(axes) > 1 else (axes[0],)) + (None,)))
    vec = P(axes if len(axes) > 1 else axes[0])
    f = shard_map(body, mesh=mesh,
                  in_specs=(row, vec, vec, vec, vec, vec, vec),
                  out_specs=(vec, P(), P(), P(), P()), check_vma=False)
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def build_sharded_repair(mesh: Mesh, axis: str, D: int, n_loc: int,
                         max_b: int, max_g: int, ctx: PassContext,
                         cap: int, max_rounds: int):
    """Incremental repair of a sharded mutable state from external
    (colors, U) seeds: the sharded counterpart of
    ``frontier._repair_compact_loop``, with a halo exchange per round.

    An up-front exchange freshens ghost colors before the first detect
    (newly-allocated ghost slots start at -1 on the referencing shard), then
    each round recolors the frontier — compacted to ``cap`` slots when small
    enough, full chunked sweep otherwise — and exchanges boundary colors +
    termination scalars in one collective.  On a 1-shard mesh this replays
    ``frontier._repair_compact_loop`` bit-for-bit.

    Returns jit fn(ell, ovf_src, ovf_dst, pri_tab, colors_tab, U, valid_loc,
    boundary, ghost_flat) -> (colors_tab, rounds, trace, total_conflicts,
    overflowed)."""
    from repro.core import frontier

    axes = tuple(axis.split(","))
    axname = axes if len(axes) > 1 else axes[0]
    n_tab = n_loc + max_g
    lctx = dataclasses.replace(ctx, n=n_loc, n_pad=n_loc, trace=False)

    def body(ell, osrc, odst, pri_tab, colors_tab, U, valid_loc, boundary,
             ghost_flat):
        exchange = _sharded_exchange(axname, D, n_loc, max_b, boundary,
                                     ghost_flat)
        tab0, _, _, _ = exchange(colors_tab, jnp.int32(0), jnp.int32(0),
                                 jnp.bool_(False))

        def cond(s):
            return (s[4] > 0) & (s[3] < max_rounds)

        def body_fn(s):
            tab, U, trace, r, _, tot, ovf = s
            count = U.sum(dtype=jnp.int32)
            colors_loc = jax.lax.dynamic_slice_in_dim(tab, 0, n_loc, 0)
            n_forced = (U & (colors_loc < 0)).sum(dtype=jnp.int32)

            def small(args):
                tab, U = args
                # fill_value = n_tab (NOT n_loc): dead frontier slots must
                # fall off the table, not alias ghost slot 0
                idx = jnp.nonzero(U, size=cap, fill_value=n_tab)[0].astype(
                    jnp.int32)
                tab2, rec, n_def, o = frontier._compact_pass(
                    lctx, ell, osrc, odst, pri_tab, tab, idx, idx < n_tab)
                return tab2, rec[:n_loc], n_def, o

            def big(args):
                tab, U = args
                force = U & (jax.lax.dynamic_slice_in_dim(
                    tab, 0, n_loc, 0) < 0)
                return col._chunked_pass(
                    lctx, ell, osrc, odst, pri_tab, tab, U, force,
                    detect=True, valid=valid_loc)

            tab2, recolored, n_def_l, ovf_l = jax.lax.cond(
                count <= cap, small, big, (tab, U))
            tab2, n_def, work, ovf2 = exchange(
                tab2, n_def_l, n_def_l + n_forced, ovf | ovf_l)
            trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(
                n_def.astype(jnp.int32))
            return (tab2, recolored, trace, r + 1, work.astype(jnp.int32),
                    tot + n_def.astype(jnp.int32), ovf2)

        trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
        s = (tab0, U, trace, jnp.int32(0), jnp.int32(1), jnp.int32(0),
             jnp.bool_(False))
        tab, _, trace, r, _, tot, ovf = jax.lax.while_loop(cond, body_fn, s)
        return tab, r, trace, tot, ovf

    row = P(*((axes if len(axes) > 1 else (axes[0],)) + (None,)))
    vec = P(axes if len(axes) > 1 else axes[0])
    f = shard_map(body, mesh=mesh,
                  in_specs=(row, vec, vec, vec, vec, vec, vec, vec, vec),
                  out_specs=(vec, P(), P(), P(), P()), check_vma=False)
    return jax.jit(f)


# --------------------------------------------------------------------------
# host-level drivers
# --------------------------------------------------------------------------

def _color_distributed(g: CSRGraph, mesh: Mesh, axis: str = "data",
                       algorithm: str = "rsoc", seed: int = 0,
                       n_chunks: int = 4, C: Optional[int] = None,
                       max_rounds: int = 64,
                       forbidden_impl: Optional[str] = None):
    """Run distributed coloring on real devices (tests use host platforms)."""
    axes = tuple(axis.split(","))
    D = int(np.prod([mesh.shape[a] for a in axes]))
    with obs.phase("prepare"):
        part = block_partition(g, D, seed)
        gg = part.graph
        W = max(1, gg.max_degree)
        n_loc = -(-part.n_pad // D)
        n_loc = -(-n_loc // n_chunks) * n_chunks
        n_pad = n_loc * D
        ell = to_ell(gg, max_degree=W, pad_vertices_to=n_pad)
        rng = np.random.default_rng(seed + 1)
        pri = np.full(n_pad, -1, np.int32)
        pri[:part.n] = rng.permutation(part.n).astype(np.int32)
    ctx = PassContext(n=part.n, n_pad=n_pad,
                      C=C or col._pick_C(gg, None), n_chunks=n_chunks,
                      forbidden_impl=col._resolve_impl(forbidden_impl))
    build = {"rsoc": build_rsoc_distributed, "cat": build_cat_distributed}[algorithm]
    fn = build(mesh, axis, ctx, max_rounds)
    ell_sharding = NamedSharding(mesh, P(*((axes if len(axes) > 1 else (axes[0],)) + (None,))))
    ellj = jax.device_put(jnp.asarray(ell), ell_sharding)
    prij = jax.device_put(jnp.asarray(pri), NamedSharding(mesh, P()))
    with obs.phase("solve", C=ctx.C, devices=D):
        colors, r, trace, tot = jax.block_until_ready(fn(ellj, prij))
    conf, truncated = col._trim_trace(trace, r)
    # back to original ids: perm maps old->new, colors_old[i] = colors_new[perm[i]]
    colors = np.asarray(colors)[part.perm]
    return col.ColoringResult(
        colors=colors, n_rounds=int(r), conflicts_per_round=conf,
        total_conflicts=int(tot), n_colors=col.n_colors_used(colors),
        overflow=False,
        gather_passes=(1 + int(r)) * (1 if algorithm == "rsoc" else 2),
        final_C=ctx.C, retries=0, distance=1, trace_truncated=truncated)


def _distributed_engine(algorithm: str):
    def engine(g: CSRGraph, spec, *, mesh: Optional[Mesh] = None,
               axis: str = "data") -> col.ColoringResult:
        if mesh is None:
            raise ValueError(
                "backend='distributed' requires a device mesh: "
                "repro.api.color(g, spec, mesh=<jax.sharding.Mesh>)")
        return _color_distributed(
            g, mesh, axis=axis, algorithm=algorithm, seed=spec.seed,
            n_chunks=spec.n_chunks, C=spec.C, max_rounds=spec.max_rounds,
            forbidden_impl=spec.forbidden_impl)
    engine.__name__ = f"_{algorithm}_distributed_engine"
    return engine


registry.register_engine("rsoc", distance=1, mode="static",
                         backend="distributed",
                         replaces="color_distributed")(
    _distributed_engine("rsoc"))
registry.register_engine("cat", distance=1, mode="static",
                         backend="distributed",
                         replaces="color_distributed")(
    _distributed_engine("cat"))


def color_distributed(g: CSRGraph, mesh: Mesh, axis: str = "data",
                      algorithm: str = "rsoc", seed: int = 0,
                      n_chunks: int = 4, C: Optional[int] = None,
                      max_rounds: int = 64):
    """Deprecated: use ``repro.api.color(g, backend="distributed",
    mesh=...)``."""
    return registry.legacy_entry(
        "color_distributed", "backend='distributed', mesh=...", g,
        algorithm=algorithm, backend="distributed", mesh=mesh, axis=axis,
        seed=seed, n_chunks=n_chunks, C=C, max_rounds=max_rounds)
