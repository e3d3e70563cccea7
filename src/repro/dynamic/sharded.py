"""Sharded incremental recoloring: distributed × dynamic (DESIGN.md §15).

``ShardedColoringState`` is the mesh-distributed counterpart of
``DynamicColoringState``: the mutable ELL+overflow encode is laid out
per-shard in *slot space* (local slots [0, n_loc), ghost slots n_loc+g for
remote neighbors), and every repair round exchanges exactly one collective
carrying boundary colors plus three termination scalars — bytes per round
∝ boundary, never ∝ n.  Çatalyürek-style speculation is what makes this
sound: the fused detect-and-recolor pass tolerates stale cross-shard colors,
so a round may read ghost colors one exchange old and the next round's
detect repairs any conflict it caused (core/distributed.py docstring).

The differential bar that keeps this honest: on a 1-shard mesh the whole
stack — encode, from-scratch solve, wave-applied updates, frontier-compacted
repair, cap doubling — replays the single-device ``mode="incremental"``
engine bit-for-bit.  That works because ``block_partition`` threads the same
numpy stream ``prepare`` draws from, ``build_halo_mutable`` reproduces the
mutable encode exactly, the sharded loops in ``core/distributed.py`` mirror
the single-device carry schedules, and ``delta.plan_group(directed=True)``
dedups a routed batch to the same wave set ``plan_updates`` emits.

Routing (host side): an undirected update (u, v) becomes two *directed*
slot-space mutations, one per owning shard — (u_loc, slot-of-v-in-u's-shard)
and (v_loc, slot-of-u-in-v's-shard).  Cross-shard targets resolve through
the ghost table; inserts allocate ghost/boundary slots append-only (existing
ghost pointers never move), and a batch that outgrows the slack capacity
re-plans the halo once (``sharded.replan`` counter) with doubled caps —
colors and priorities are per-vertex, so a re-plan never perturbs them.

Budget exhaustion degrades through the same ladder as the single-device
engine (``resilience/ladder.py`` dispatches here): rung 1 re-encodes the
updated graph from scratch through ``api.color``'s front door, rung 2 is
the serial oracle + pure encode.  Rung attribution is preserved verbatim.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs, registry
from repro.core import coloring as col
from repro.core import distributed as dist
from repro.core import frontier
from repro.core import partition as part_mod
from repro.core.context import PassContext
from repro.dynamic import delta
from repro.dynamic.incremental import _check_edges
from repro.graphs.csr import CSRGraph, FILL, from_edges, to_edge_list
from repro.resilience import faults
from repro.resilience.errors import CapRetryExhausted, OvfGrowthExhausted


# --------------------------------------------------------------------------
# state
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedColoringState:
    """Device-resident sharded mutable-graph coloring state.

    Device arrays carry a leading shard axis; ``boundary`` / ``ghost_*``
    halo metadata is authoritative on the host (it changes only on slot
    allocation and re-plan, both host decisions) and is shipped to the
    device per repair call — these arrays are boundary-sized, not n-sized.
    Immutable-by-convention exactly like ``DynamicColoringState``: every
    batch returns a new state, so service snapshot/rollback is free.
    """

    # -- device arrays (leading axis = shard) -------------------------------
    ell: jnp.ndarray          # (D, n_loc, W) slot-space neighbors, FILL pad
    ovf_src: jnp.ndarray      # (D, ovf_cap) overflow COO local rows
    ovf_dst: jnp.ndarray      # (D, ovf_cap) overflow COO slot targets
    pri_tab: jnp.ndarray      # (D, n_tab) priorities: local rows + ghost tail
    colors_tab: jnp.ndarray   # (D, n_tab) colors: local rows + ghost tail
    # -- host halo metadata (copy-on-write) ---------------------------------
    boundary: np.ndarray      # (D, max_b_cap) int32 local slots, FILL pad
    n_boundary: np.ndarray    # (D,) live boundary slots
    ghost_ids: np.ndarray     # (D, max_g_cap) int64 global relabeled ids
    ghost_flat: np.ndarray    # (D, max_g_cap) int32 owner*max_b_cap + slot
    n_ghost: np.ndarray       # (D,) live ghost slots
    # -- geometry / statics -------------------------------------------------
    n: int
    blk: int                  # shard-membership block size (v // blk)
    n_loc: int                # chunk-aligned row-table height per shard
    n_shards: int
    mesh: object              # jax.sharding.Mesh (hashable jit-cache key)
    axis: str
    C: int
    n_chunks: int
    frontier_cap: int         # per-shard compacted-frontier capacity
    delta_cap: int
    ell_cap: int              # encode parameters, persisted for re-plans
    ell_slack: int
    perm: np.ndarray          # old id -> relabeled id
    inv_perm: np.ndarray      # relabeled id -> old id
    pri_global: np.ndarray    # (n,) priority of each relabeled id
    row_of: np.ndarray        # (n,) relabeled id -> flat row d*n_loc + slot
    forbidden_impl: str = "bitset"
    max_rounds: int = 1000
    version: int = 0
    last_rounds: int = 0
    last_conflicts: int = 0
    last_gather_passes: int = 0
    total_gather_passes: int = 0
    retries: int = 0
    ovf_grows: int = 0
    replans: int = 0              # cumulative halo re-plans
    last_halo_bytes: int = 0      # collective payload bytes of the last step
    total_halo_bytes: int = 0
    max_cap_retries: Optional[int] = None
    max_ovf_growth: Optional[int] = None
    last_degrade_rung: int = 0

    # -- derived geometry ---------------------------------------------------

    @property
    def n_tab(self) -> int:
        return int(self.colors_tab.shape[1])

    @property
    def max_b_cap(self) -> int:
        return int(self.boundary.shape[1])

    @property
    def max_g_cap(self) -> int:
        return int(self.ghost_flat.shape[1])

    @property
    def halo_bytes_per_round(self) -> int:
        """One exchange's payload: (boundary colors + 3 scalars) int32 per
        shard, all_gathered — the O(boundary) claim, as a number."""
        return self.n_shards * (self.max_b_cap + 3) * 4

    # -- views --------------------------------------------------------------

    @property
    def colors_dev(self) -> jnp.ndarray:
        """Device color table (the service's sync handle)."""
        return self.colors_tab

    @property
    def colors(self) -> np.ndarray:
        """Current coloring over original vertex ids."""
        flat = np.asarray(self.colors_tab[:, :self.n_loc]).reshape(-1)
        return flat[self.row_of[self.perm[:self.n]]]

    @property
    def n_colors(self) -> int:
        return col.n_colors_used(self.colors)

    def summary(self) -> dict:
        return {"version": self.version, "colors": self.n_colors,
                "rounds": self.last_rounds,
                "conflicts": self.last_conflicts,
                "gather_passes": self.last_gather_passes,
                "total_gather_passes": self.total_gather_passes,
                "final_C": self.C, "retries": self.retries,
                "ovf_grows": self.ovf_grows,
                "degrade_rung": self.last_degrade_rung,
                "ovf_load": delta.overflow_load(self.ovf_src),
                "n_shards": self.n_shards,
                "halo_bytes_per_round": self.halo_bytes_per_round,
                "last_halo_bytes": self.last_halo_bytes,
                "replans": self.replans}

    def to_csr(self) -> CSRGraph:
        """Decode the live slot-space edge set back to a host CSRGraph over
        original ids (``delta.state_to_csr`` dispatches here)."""
        D, n_loc, blk = self.n_shards, self.n_loc, self.blk
        ell = np.asarray(self.ell)
        osrc = np.asarray(self.ovf_src)
        odst = np.asarray(self.ovf_dst)
        srcs, dsts = [], []
        for d in range(D):
            row, slot = np.nonzero(ell[d] >= 0)
            tgt = ell[d][row, slot].astype(np.int64)
            live = (osrc[d] >= 0) & (odst[d] >= 0)
            row = np.concatenate([row.astype(np.int64),
                                  osrc[d][live].astype(np.int64)])
            tgt = np.concatenate([tgt, odst[d][live].astype(np.int64)])
            ghost = tgt >= n_loc
            gidx = np.clip(tgt - n_loc, 0, self.max_g_cap - 1)
            srcs.append(row + d * blk)
            dsts.append(np.where(ghost, self.ghost_ids[d][gidx],
                                 tgt + d * blk))
        edges = np.stack([np.concatenate(srcs), np.concatenate(dsts)],
                         axis=1)
        # cross-shard edges appear once per direction (one per owning
        # shard); symmetrize dedups the union back to the undirected set
        return from_edges(self.n, self.inv_perm[edges], symmetrize=True)


# --------------------------------------------------------------------------
# geometry helpers
# --------------------------------------------------------------------------

def _mesh_size(mesh, axis: str) -> int:
    return int(np.prod([mesh.shape[a] for a in axis.split(",")]))


def _on_mesh(mesh, axis: str, *arrays):
    """Place leading-shard-axis arrays so shard d's rows live on device d of
    ``mesh`` (not all on the default device).  The placement mesh has the
    same devices with automatic axes, so the host-planned wave arrays that
    meet these in the wave kernels need no sharding of their own."""
    sh = NamedSharding(Mesh(mesh.devices, mesh.axis_names), P(axis))
    return tuple(jax.device_put(np.asarray(a), sh) for a in arrays)


def _aligned_n_loc(n: int, D: int, n_chunks: int) -> int:
    """Per-shard row-table height: the block size rounded up so every
    shard's sweep divides into n_chunks (at D=1 this IS ``prepare``'s
    n_pad, which the bit-identity bar depends on)."""
    blk = -(-n // D)
    return -(-max(blk, n_chunks) // n_chunks) * n_chunks


def _valid_mask(n: int, D: int, blk: int, n_loc: int) -> np.ndarray:
    valid = np.zeros((D, n_loc), bool)
    for d in range(D):
        k = min(blk, n - d * blk)
        if k > 0:
            valid[d, :k] = True
    return valid


def _row_of(n: int, D: int, blk: int, n_loc: int) -> np.ndarray:
    v = np.arange(n, dtype=np.int64)
    d = np.minimum(v // blk, D - 1)
    return d * n_loc + (v - d * blk)


def _pri_table(pri_global: np.ndarray, plan, n: int, D: int,
               blk: int) -> np.ndarray:
    """(D, n_tab) priority table: local rows then ghost tail.  Ghost
    priorities ride in-table because the fused detect's asymmetric
    tie-break reads the *neighbor's* priority through the same gather as
    its color."""
    n_tab = plan.n_loc + plan.max_g_cap
    pri = np.full((D, n_tab), -1, np.int32)
    for d in range(D):
        lo, hi = d * blk, min((d + 1) * blk, n)
        if hi > lo:
            pri[d, :hi - lo] = pri_global[lo:hi]
        ng = int(plan.n_ghost[d])
        if ng:
            pri[d, plan.n_loc:plan.n_loc + ng] = \
                pri_global[plan.ghost_ids[d, :ng]]
    return pri


# --------------------------------------------------------------------------
# encode + from-scratch solve
# --------------------------------------------------------------------------

def _solve_scratch(state_like, ell, osrc, odst, pri_tab, valid, boundary,
                   ghost_flat, *, n, n_loc, D, mesh, axis, C0, n_chunks,
                   impl, max_rounds, max_cap_retries):
    """Run the sharded from-scratch loop under the shared cap-doubling
    retry.  Returns ((colors_tab, r, trace, tot, ovf), C, retries)."""
    max_b = int(boundary.shape[1])
    max_g = int(ghost_flat.shape[1])
    ellj = jnp.asarray(ell).reshape(D * n_loc, -1)
    osrcj = jnp.asarray(osrc).reshape(-1)
    odstj = jnp.asarray(odst).reshape(-1)
    prij = jnp.asarray(pri_tab).reshape(-1)
    validj = jnp.asarray(valid).reshape(-1)
    boundj = jnp.asarray(boundary).reshape(-1)
    ghostj = jnp.asarray(ghost_flat).reshape(-1)

    def run(C):
        ctx = PassContext(n=n, n_pad=n_loc * D, C=C, n_chunks=n_chunks,
                          forbidden_impl=impl)
        fn = dist.build_sharded_scratch(mesh, axis, D, n_loc, max_b, max_g,
                                        ctx, max_rounds)
        return fn(ellj, osrcj, odstj, prij, validj, boundj, ghostj)

    return col._run_with_retry(run, C0, engine="sharded",
                               max_retries=max_cap_retries)


def sharded_state(g: CSRGraph, mesh, axis: str = "data", seed: int = 0,
                  n_chunks: int = 16, ell_cap: int = 512,
                  C: Optional[int] = None, ell_slack: int = 4,
                  ovf_cap: Optional[int] = None, delta_cap: int = 2048,
                  frontier_frac: float = 0.125, max_rounds: int = 1000,
                  forbidden_impl: Optional[str] = None,
                  max_cap_retries: Optional[int] = None,
                  max_ovf_growth: Optional[int] = None
                  ) -> ShardedColoringState:
    """Partition + encode ``g`` over ``mesh`` and color it from scratch
    once (one halo exchange per round).

    The RNG stream is shared between the partition shuffle and the
    priority draw in ``prepare``'s order, so a 1-shard mesh reproduces the
    single-device ``dynamic_state`` encode — and therefore its colors —
    bit-for-bit.
    """
    impl = col._resolve_impl(forbidden_impl)
    D = _mesh_size(mesh, axis)
    rng = np.random.default_rng(seed)
    with obs.phase("prepare"):
        part = part_mod.block_partition(g, D, rng=rng)       # rng draw 1
        blk = part.n_loc
        n = part.n
        n_loc = _aligned_n_loc(n, D, n_chunks)
        plan = part_mod.build_halo_mutable(
            part, n_loc=n_loc, ell_cap=ell_cap, ell_slack=ell_slack,
            ovf_cap=ovf_cap, delta_cap=delta_cap)
        pri_global = rng.permutation(n).astype(np.int32)     # rng draw 2
        pri_tab = _pri_table(pri_global, plan, n, D, blk)
        valid = _valid_mask(n, D, blk, n_loc)
        C0 = col._pick_C(part.graph, C)

    (tab, r, trace, tot, _), final_C, retries = _solve_scratch(
        None, plan.ell_local, plan.ovf_src, plan.ovf_dst, pri_tab, valid,
        plan.boundary, plan.ghost_flat, n=n, n_loc=n_loc, D=D, mesh=mesh,
        axis=axis, C0=C0, n_chunks=n_chunks, impl=impl,
        max_rounds=max_rounds, max_cap_retries=max_cap_retries)

    n_tab = n_loc + plan.max_g_cap
    hb = (1 + int(r)) * D * (plan.max_b_cap + 3) * 4
    ell, osrc, odst, pri_dev, colors_dev = _on_mesh(
        mesh, axis, plan.ell_local, plan.ovf_src, plan.ovf_dst, pri_tab,
        np.asarray(tab).reshape(D, n_tab))
    return ShardedColoringState(
        ell=ell, ovf_src=osrc, ovf_dst=odst, pri_tab=pri_dev,
        colors_tab=colors_dev,
        boundary=plan.boundary, n_boundary=plan.n_boundary,
        ghost_ids=plan.ghost_ids, ghost_flat=plan.ghost_flat,
        n_ghost=plan.n_ghost,
        n=n, blk=blk, n_loc=n_loc, n_shards=D, mesh=mesh, axis=axis,
        C=final_C, n_chunks=n_chunks,
        frontier_cap=frontier.frontier_cap(n_loc, n_chunks, frontier_frac),
        delta_cap=int(delta_cap), ell_cap=int(ell_cap),
        ell_slack=int(ell_slack),
        perm=part.perm, inv_perm=np.argsort(part.perm),
        pri_global=pri_global, row_of=_row_of(n, D, blk, n_loc),
        forbidden_impl=impl, max_rounds=int(max_rounds),
        version=0, last_rounds=int(r), last_conflicts=int(tot),
        last_gather_passes=1 + int(r), total_gather_passes=1 + int(r),
        retries=retries, ovf_grows=0, replans=0,
        last_halo_bytes=hb, total_halo_bytes=hb,
        max_cap_retries=max_cap_retries, max_ovf_growth=max_ovf_growth)


# --------------------------------------------------------------------------
# routing: undirected updates -> per-shard directed slot-space mutations
# --------------------------------------------------------------------------

class _Replan(Exception):
    """A batch outgrew the boundary/ghost slack; carries the per-shard
    capacities the re-planned halo must cover."""

    def __init__(self, need_b: int, need_g: int):
        self.need_b, self.need_g = int(need_b), int(need_g)


def _route(state: ShardedColoringState, ins_r: np.ndarray,
           dels_r: np.ndarray):
    """Route relabeled-space undirected pairs to their owning shards.

    Returns ``(batches, alloc)``: ``batches[d]`` is shard d's directed
    ``(ins, dels)`` slot-space pairs for ``delta.plan_group``, ``alloc``
    the append-only ghost/boundary slot allocations to commit.  Allocation
    is unbounded here — capacity is checked once at the end so a single
    ``_Replan`` covers the whole batch's need.

    A delete whose remote endpoint is not in the ghost table is a no-op on
    that shard (the edge cannot be present); it is routed as a (row, row)
    self-pair, which every wave kernel ignores but which still seeds the
    repair frontier — mirroring the single-device treatment of deletes of
    absent edges.
    """
    D, blk, n_loc = state.n_shards, state.blk, state.n_loc
    max_b, max_g = state.max_b_cap, state.max_g_cap
    gmap = [
        {int(v): i
         for i, v in enumerate(state.ghost_ids[d, :int(state.n_ghost[d])])}
        for d in range(D)]
    bmap = [
        {int(state.boundary[d, j]) + d * blk: j
         for j in range(int(state.n_boundary[d]))}
        for d in range(D)]
    n_b = [int(x) for x in state.n_boundary]
    n_g = [int(x) for x in state.n_ghost]
    new_bnd = [[] for _ in range(D)]       # new boundary local slots
    new_gst = [[] for _ in range(D)]       # (global id, flat pointer)
    ins_sh = [[] for _ in range(D)]
    del_sh = [[] for _ in range(D)]

    def boundary_slot(owner: int, v: int) -> int:
        j = bmap[owner].get(v)
        if j is None:
            j = n_b[owner]
            n_b[owner] += 1
            bmap[owner][v] = j
            new_bnd[owner].append(v - owner * blk)
        return j

    def ghost_slot(d: int, owner: int, v: int) -> int:
        i = gmap[d].get(v)
        if i is None:
            j = boundary_slot(owner, v)
            i = n_g[d]
            n_g[d] += 1
            gmap[d][v] = i
            new_gst[d].append((v, owner * max_b + j))
        return n_loc + i

    def shard(v: int) -> int:
        return min(v // blk, D - 1)

    for u, v in ins_r:
        u, v = int(u), int(v)
        du, dv = shard(u), shard(v)
        if u == v:
            # self-pair: dropped from insert waves, still seeds the repair
            ins_sh[du].append((u - du * blk, u - du * blk))
            continue
        tu = (v - du * blk) if dv == du else ghost_slot(du, dv, v)
        ins_sh[du].append((u - du * blk, tu))
        tv = (u - dv * blk) if du == dv else ghost_slot(dv, du, u)
        ins_sh[dv].append((v - dv * blk, tv))
    for u, v in dels_r:
        u, v = int(u), int(v)
        du, dv = shard(u), shard(v)
        if u == v:
            del_sh[du].append((u - du * blk, u - du * blk))
            continue
        gi = gmap[du].get(v) if dv != du else None
        tu = ((v - du * blk) if dv == du
              else (n_loc + gi if gi is not None else u - du * blk))
        del_sh[du].append((u - du * blk, tu))
        gj = gmap[dv].get(u) if du != dv else None
        tv = ((u - dv * blk) if du == dv
              else (n_loc + gj if gj is not None else v - dv * blk))
        del_sh[dv].append((v - dv * blk, tv))

    if max(n_b) > max_b or max(n_g) > max_g:
        raise _Replan(max(n_b), max(n_g))

    def pairs(lst):
        return (np.asarray(lst, np.int32).reshape(-1, 2) if lst
                else np.zeros((0, 2), np.int32))

    batches = [(pairs(ins_sh[d]), pairs(del_sh[d])) for d in range(D)]
    return batches, (new_bnd, new_gst, n_b, n_g)


def _commit_alloc(state: ShardedColoringState, alloc):
    """Append routed slot allocations to the host halo tables and scatter
    the new ghosts' priorities into the device table.  Returns the fields
    to replace (no-op fast path when the batch allocated nothing)."""
    new_bnd, new_gst, n_b, n_g = alloc
    if not any(new_bnd) and not any(new_gst):
        return {}
    D, n_loc = state.n_shards, state.n_loc
    boundary = state.boundary.copy()
    n_boundary = state.n_boundary.copy()
    ghost_ids = state.ghost_ids.copy()
    ghost_flat = state.ghost_flat.copy()
    n_ghost = state.n_ghost.copy()
    pri_tab = state.pri_tab
    for d in range(D):
        if new_bnd[d]:
            j0 = int(state.n_boundary[d])
            boundary[d, j0:n_b[d]] = np.asarray(new_bnd[d], np.int32)
            n_boundary[d] = n_b[d]
        if new_gst[d]:
            i0 = int(state.n_ghost[d])
            ids = np.asarray([v for v, _ in new_gst[d]], np.int64)
            flats = np.asarray([f for _, f in new_gst[d]], np.int32)
            ghost_ids[d, i0:n_g[d]] = ids
            ghost_flat[d, i0:n_g[d]] = flats
            n_ghost[d] = n_g[d]
            # new ghost slots need priorities before the next detect; their
            # colors stay -1 — the repair's up-front exchange freshens them
            pri_tab = pri_tab.at[d, n_loc + i0:n_loc + n_g[d]].set(
                jnp.asarray(state.pri_global[ids]))
    return dict(boundary=boundary, n_boundary=n_boundary,
                ghost_ids=ghost_ids, ghost_flat=ghost_flat, n_ghost=n_ghost,
                pri_tab=pri_tab)


def _replan(state: ShardedColoringState, need_b: int,
            need_g: int) -> ShardedColoringState:
    """Rebuild the halo plan of the *current* graph with doubled (and
    need-covering) boundary/ghost capacity.

    The partition geometry — perm, blk, n_loc — is preserved, so colors and
    priorities (per-vertex quantities) carry over untouched; only the
    slot-space tables are re-derived.  Re-encoding also compacts stale
    ghost/boundary slots left behind by deletes.  Not a version bump: the
    served coloring is unchanged."""
    from repro.obs import metrics as obs_metrics

    D, blk, n_loc, n = state.n_shards, state.blk, state.n_loc, state.n
    g_rel = from_edges(n, state.perm[to_edge_list(state.to_csr())
                                     .astype(np.int64)], symmetrize=False)
    part = part_mod.Partition(n=n, n_pad=blk * D, n_shards=D, n_loc=blk,
                              perm=state.perm, graph=g_rel)
    plan = part_mod.build_halo_mutable(
        part, n_loc=n_loc, ell_cap=max(state.ell_cap,
                                       int(state.ell.shape[2])),
        ell_slack=state.ell_slack,
        ovf_cap=int(state.ovf_src.shape[1]), delta_cap=state.delta_cap,
        min_b_cap=max(2 * state.max_b_cap, part_mod._slack_cap(need_b)),
        min_g_cap=max(2 * state.max_g_cap, part_mod._slack_cap(need_g)))
    n_tab = n_loc + plan.max_g_cap
    pri_tab = _pri_table(state.pri_global, plan, n, D, blk)
    colors_tab = np.full((D, n_tab), -1, np.int32)
    colors_tab[:, :n_loc] = np.asarray(state.colors_tab[:, :n_loc])
    for d in range(D):          # ghost colors: fresh from their owners
        ng = int(plan.n_ghost[d])
        if ng:
            ids = plan.ghost_ids[d, :ng]
            flat = np.asarray(state.colors_tab[:, :n_loc]).reshape(-1)
            colors_tab[d, n_loc:n_loc + ng] = flat[state.row_of[ids]]
    obs_metrics.counter("sharded.replan").inc()
    ell, osrc, odst, pri_dev, colors_dev = _on_mesh(
        state.mesh, state.axis, plan.ell_local, plan.ovf_src, plan.ovf_dst,
        pri_tab, colors_tab)
    return dataclasses.replace(
        state, ell=ell, ovf_src=osrc, ovf_dst=odst, pri_tab=pri_dev,
        colors_tab=colors_dev,
        boundary=plan.boundary, n_boundary=plan.n_boundary,
        ghost_ids=plan.ghost_ids, ghost_flat=plan.ghost_flat,
        n_ghost=plan.n_ghost, replans=state.replans + 1)


# --------------------------------------------------------------------------
# update application + repair
# --------------------------------------------------------------------------

def _grow_overflow_b(osrc_b, odst_b, factor: int = 2):
    """Uniform per-shard overflow growth (same cap math as
    ``delta.grow_overflow``, applied along axis 1 so every shard keeps the
    same buffer shape — a jit-static requirement of the stacked kernels)."""
    D, cap = osrc_b.shape
    extra = jnp.full((D, max(cap, 8) * (factor - 1)), FILL, jnp.int32)
    return (jnp.concatenate([osrc_b, extra], axis=1),
            jnp.concatenate([odst_b, extra], axis=1))


def _apply_waves(state: ShardedColoringState, batches):
    """Delete-then-insert wave application across all shards in lockstep
    (one stacked dispatch per wave), with the uniform grow-and-retry loop
    of ``delta.apply_updates``.  Returns (ell, osrc, odst, U, grows)."""
    n_tab = state.n_tab
    ovf_w, ell_w, ins_w, touched = delta.plan_group(
        batches, state.delta_cap, n_tab, directed=True)
    ell_b = state.ell
    osrc_b, odst_b = state.ovf_src, state.ovf_dst
    for j in range(ovf_w.shape[0]):
        osrc_b, odst_b = delta._mega_delete_overflow(
            osrc_b, odst_b, jnp.asarray(ovf_w[j]))
    for j in range(ell_w.shape[0]):
        ell_b = delta._mega_delete_ell_wave(ell_b, jnp.asarray(ell_w[j]))
    grows = 0
    n_ins = int(ins_w.shape[0])
    if n_ins:
        ss, ds = delta._mega_sort_overflow(osrc_b, odst_b)
    for j in range(n_ins):
        w = jnp.asarray(ins_w[j])
        while True:
            ell2, osrc2, odst2, fail = delta._mega_insert_wave(
                ell_b, osrc_b, odst_b, ss, ds, w)
            if not bool(np.asarray(fail).any()):
                ell_b, osrc_b, odst_b = ell2, osrc2, odst2
                break
            if (state.max_ovf_growth is not None
                    and grows >= state.max_ovf_growth):
                raise OvfGrowthExhausted(grows=grows,
                                         budget=state.max_ovf_growth,
                                         cap=int(osrc2.shape[1]))
            # grown buffer holds this wave's partial spills: keep it, retake
            # the presence snapshot, re-apply the same wave (idempotent)
            osrc_b, odst_b = _grow_overflow_b(osrc2, odst2)
            ell_b = ell2
            grows += 1
            ss, ds = delta._mega_sort_overflow(osrc_b, odst_b)
    return ell_b, osrc_b, odst_b, touched[:, :state.n_loc], grows


def recolor_sharded(state: ShardedColoringState, inserts=None, deletes=None,
                    max_rounds: Optional[int] = None
                    ) -> ShardedColoringState:
    """Apply an undirected edge update batch and repair the sharded
    coloring — one collective per repair round, bytes ∝ boundary.

    ``inserts`` / ``deletes`` are (k, 2) arrays of *original* vertex ids;
    deletes apply before inserts.  Returns a new state; the input state is
    untouched.  On a 1-shard mesh this is bit-identical to
    ``recolor_incremental`` on the matching single-device state.
    """
    if max_rounds is None:
        max_rounds = state.max_rounds
    ins = _check_edges(inserts if inserts is not None else [], state.n,
                       "inserts")
    dels = _check_edges(deletes if deletes is not None else [], state.n,
                        "deletes")
    if len(ins) == 0 and len(dels) == 0:
        return state
    if faults.fires("ovf.exhaust"):
        raise OvfGrowthExhausted(grows=0, budget=state.max_ovf_growth,
                                 cap=int(state.ovf_src.shape[1]),
                                 forced=True)

    ins_r = state.perm[ins] if len(ins) else ins
    dels_r = state.perm[dels] if len(dels) else dels
    try:
        batches, alloc = _route(state, ins_r, dels_r)
    except _Replan as rp:
        state = _replan(state, rp.need_b, rp.need_g)
        batches, alloc = _route(state, ins_r, dels_r)
    repl = _commit_alloc(state, alloc)
    if repl:
        state = dataclasses.replace(state, **repl)
    ell_b, osrc_b, odst_b, U, grows = _apply_waves(state, batches)

    D, n_loc = state.n_shards, state.n_loc
    max_b, max_g = state.max_b_cap, state.max_g_cap
    validj = jnp.asarray(_valid_mask(state.n, D, state.blk, n_loc)
                         ).reshape(-1)
    boundj = jnp.asarray(state.boundary).reshape(-1)
    ghostj = jnp.asarray(state.ghost_flat).reshape(-1)
    prij = state.pri_tab.reshape(-1)
    colj = state.colors_tab.reshape(-1)
    Uj = jnp.asarray(U).reshape(-1)
    ellj = ell_b.reshape(D * n_loc, -1)
    osrcj = osrc_b.reshape(-1)
    odstj = odst_b.reshape(-1)

    def run(C):
        ctx = PassContext(n=state.n, n_pad=n_loc * D, C=C,
                          n_chunks=state.n_chunks,
                          forbidden_impl=state.forbidden_impl)
        fn = dist.build_sharded_repair(state.mesh, state.axis, D, n_loc,
                                       max_b, max_g, ctx,
                                       state.frontier_cap, max_rounds)
        return fn(ellj, osrcj, odstj, prij, colj, Uj, validj, boundj,
                  ghostj)

    (tab, r, trace, tot, _), C, retries = col._run_with_retry(
        run, state.C, engine="sharded", max_retries=state.max_cap_retries)
    passes = int(r)
    # collectives: one up-front ghost refresh + one per repair round
    hb = (1 + passes) * state.halo_bytes_per_round
    return dataclasses.replace(
        state, ell=ell_b, ovf_src=osrc_b, ovf_dst=odst_b,
        colors_tab=jnp.asarray(tab).reshape(D, state.n_tab),
        C=C, version=state.version + 1, last_rounds=passes,
        last_conflicts=int(tot), last_gather_passes=passes,
        total_gather_passes=state.total_gather_passes + passes,
        retries=state.retries + retries, ovf_grows=state.ovf_grows + grows,
        last_halo_bytes=hb, total_halo_bytes=state.total_halo_bytes + hb,
        last_degrade_rung=0)


# --------------------------------------------------------------------------
# degradation-ladder rungs (dispatched from resilience/ladder.py)
# --------------------------------------------------------------------------

def scratch_sharded(state: ShardedColoringState, inserts=None,
                    deletes=None) -> ShardedColoringState:
    """Rung 1: re-encode + recolor the updated graph through the
    ``api.color`` front door on the tenant's own mesh, inheriting its
    statics and budgets.  Mirrors ``ladder.scratch_state``, including the
    rung attribution when the engine itself had to drop to the oracle."""
    from repro import api
    from repro.resilience.ladder import updated_graph

    empty = np.zeros((0, 2), np.int64)
    g2 = updated_graph(state, empty if inserts is None else inserts,
                       empty if deletes is None else deletes)
    res = api.color(
        g2, mode="incremental", backend="distributed", mesh=state.mesh,
        axis=state.axis, seed=0, n_chunks=state.n_chunks,
        ell_cap=int(state.ell.shape[2]), ell_slack=0, C=None,
        ovf_cap=int(state.ovf_src.shape[1]), delta_cap=state.delta_cap,
        max_rounds=state.max_rounds, forbidden_impl=state.forbidden_impl,
        max_cap_retries=state.max_cap_retries,
        max_ovf_growth=state.max_ovf_growth)
    st = res.state
    rung = 2 if st.last_degrade_rung == 2 else 1
    return dataclasses.replace(
        st, version=state.version + 1, last_degrade_rung=rung,
        retries=state.retries + st.retries, ovf_grows=state.ovf_grows,
        replans=state.replans,
        total_gather_passes=(state.total_gather_passes
                             + st.total_gather_passes),
        total_halo_bytes=state.total_halo_bytes + st.total_halo_bytes)


def oracle_sharded(state: ShardedColoringState, inserts=None,
                   deletes=None) -> ShardedColoringState:
    """Rung 2: serial First-Fit on the host + pure sharded encode — no
    device coloring loop, no collective, nothing left to exhaust."""
    from repro.resilience.ladder import updated_graph

    empty = np.zeros((0, 2), np.int64)
    g2 = updated_graph(state, empty if inserts is None else inserts,
                       empty if deletes is None else deletes)
    st = encode_oracle_sharded(
        g2, state.mesh, axis=state.axis, seed=0, n_chunks=state.n_chunks,
        ell_cap=int(state.ell.shape[2]), ell_slack=0,
        ovf_cap=int(state.ovf_src.shape[1]), delta_cap=state.delta_cap,
        max_rounds=state.max_rounds, forbidden_impl=state.forbidden_impl,
        max_cap_retries=state.max_cap_retries,
        max_ovf_growth=state.max_ovf_growth)
    return dataclasses.replace(
        st, version=state.version + 1, retries=state.retries,
        ovf_grows=state.ovf_grows, replans=state.replans,
        total_gather_passes=state.total_gather_passes,
        total_halo_bytes=state.total_halo_bytes)


def encode_oracle_sharded(g: CSRGraph, mesh, axis: str = "data", *,
                          seed: int = 0, n_chunks: int = 16,
                          ell_cap: int = 512, ell_slack: int = 4,
                          ovf_cap: Optional[int] = None,
                          delta_cap: int = 2048,
                          frontier_frac: float = 0.125,
                          max_rounds: int = 1000,
                          forbidden_impl: Optional[str] = None,
                          max_cap_retries: Optional[int] = None,
                          max_ovf_growth: Optional[int] = None
                          ) -> ShardedColoringState:
    """Serial-oracle colors + the standard sharded encode of ``g`` — the
    sharded counterpart of ``ladder.encode_oracle_state``.  The RNG stream
    is threaded exactly like ``sharded_state`` so the layout (and any later
    1-shard differential run) is deterministic."""
    impl = col._resolve_impl(forbidden_impl)
    D = _mesh_size(mesh, axis)
    colors = col.greedy_sequential(g)
    rng = np.random.default_rng(seed)
    part = part_mod.block_partition(g, D, rng=rng)           # rng draw 1
    blk, n = part.n_loc, part.n
    n_loc = _aligned_n_loc(n, D, n_chunks)
    plan = part_mod.build_halo_mutable(
        part, n_loc=n_loc, ell_cap=ell_cap, ell_slack=ell_slack,
        ovf_cap=ovf_cap, delta_cap=delta_cap)
    pri_global = rng.permutation(n).astype(np.int32)         # rng draw 2
    pri_tab = _pri_table(pri_global, plan, n, D, blk)
    row_of = _row_of(n, D, blk, n_loc)

    colors_rel = np.full((n,), -1, np.int32)
    colors_rel[part.perm] = colors
    n_tab = n_loc + plan.max_g_cap
    colors_tab = np.full((D, n_tab), -1, np.int32)
    for d in range(D):
        lo, hi = d * blk, min((d + 1) * blk, n)
        if hi > lo:
            colors_tab[d, :hi - lo] = colors_rel[lo:hi]
        ng = int(plan.n_ghost[d])
        if ng:
            colors_tab[d, n_loc:n_loc + ng] = \
                colors_rel[plan.ghost_ids[d, :ng]]
    n_used = int(colors.max()) + 1 if len(colors) else 1
    C = max(32, -(-n_used // 32) * 32)   # headroom for future repairs
    ell, osrc, odst, pri_dev, colors_dev = _on_mesh(
        mesh, axis, plan.ell_local, plan.ovf_src, plan.ovf_dst, pri_tab,
        colors_tab)
    return ShardedColoringState(
        ell=ell, ovf_src=osrc, ovf_dst=odst, pri_tab=pri_dev,
        colors_tab=colors_dev,
        boundary=plan.boundary, n_boundary=plan.n_boundary,
        ghost_ids=plan.ghost_ids, ghost_flat=plan.ghost_flat,
        n_ghost=plan.n_ghost,
        n=n, blk=blk, n_loc=n_loc, n_shards=D, mesh=mesh, axis=axis,
        C=C, n_chunks=n_chunks,
        frontier_cap=frontier.frontier_cap(n_loc, n_chunks, frontier_frac),
        delta_cap=int(delta_cap), ell_cap=int(ell_cap),
        ell_slack=int(ell_slack),
        perm=part.perm, inv_perm=np.argsort(part.perm),
        pri_global=pri_global, row_of=row_of,
        forbidden_impl=impl, max_rounds=int(max_rounds), version=0,
        max_cap_retries=max_cap_retries, max_ovf_growth=max_ovf_growth,
        last_degrade_rung=2)


# --------------------------------------------------------------------------
# registry adapter: (rsoc, 1, incremental, distributed) through repro.api
# --------------------------------------------------------------------------

@registry.register_engine("rsoc", distance=1, mode="incremental",
                          backend="distributed", replaces="sharded_state")
def _sharded_engine(g: CSRGraph, spec, *, mesh=None,
                    axis: str = "data") -> col.ColoringResult:
    """Encode ``g`` over the mesh and color it from scratch once; the
    ``ShardedColoringState`` rides the result's ``state`` field so the
    ``ColoringService`` keeps applying ``recolor_sharded`` batches to it.

    Like the single-device incremental engine, a from-scratch solve that
    exhausts a finite ``spec.max_cap_retries`` drops straight to the serial
    oracle encode (rung 2) instead of failing the add."""
    if mesh is None:
        raise ValueError(
            "backend='distributed' requires a device mesh: "
            "repro.api.color(g, spec, mesh=<jax.sharding.Mesh>)")
    try:
        st = sharded_state(
            g, mesh, axis=axis, seed=spec.seed, n_chunks=spec.n_chunks,
            ell_cap=spec.ell_cap, C=spec.C, ell_slack=spec.ell_slack,
            ovf_cap=spec.ovf_cap, delta_cap=spec.delta_cap,
            frontier_frac=spec.frontier_frac, max_rounds=spec.max_rounds,
            forbidden_impl=spec.forbidden_impl,
            max_cap_retries=spec.max_cap_retries,
            max_ovf_growth=spec.max_ovf_growth)
    except CapRetryExhausted:
        from repro.obs import metrics as _metrics
        _metrics.counter("resilience.degrade", rung="oracle").inc()
        st = encode_oracle_sharded(
            g, mesh, axis=axis, seed=spec.seed, n_chunks=spec.n_chunks,
            ell_cap=spec.ell_cap, ell_slack=spec.ell_slack,
            ovf_cap=spec.ovf_cap, delta_cap=spec.delta_cap,
            frontier_frac=spec.frontier_frac, max_rounds=spec.max_rounds,
            forbidden_impl=spec.forbidden_impl,
            max_cap_retries=spec.max_cap_retries,
            max_ovf_growth=spec.max_ovf_growth)
    colors = st.colors
    return col.ColoringResult(
        colors=colors, n_rounds=st.last_rounds,
        conflicts_per_round=np.array([st.last_conflicts]),
        total_conflicts=st.last_conflicts,
        n_colors=col.n_colors_used(colors),
        overflow=st.retries > 0, gather_passes=st.last_gather_passes,
        final_C=st.C, retries=st.retries, distance=1, state=st,
        degrade_rung=st.last_degrade_rung)
