"""Parallel graph coloring: serial First-Fit, Gebremedhin-Manne (GM),
Catalyurek et al. (CAT), and the paper's contribution RSOC — adapted for
lockstep SPMD execution (TPU/JAX).

Vocabulary of the TPU adaptation (DESIGN.md §2):

  * "thread concurrency" -> a *chunk*: the set of vertices (re)colored
    simultaneously in one data-parallel step.  Within a chunk execution is
    lockstep; across the ``n_chunks`` chunks of one pass execution is
    sequential and reads fresh colors — exactly a thread's sequential walk
    over its partition in the paper.  ``n_chunks`` plays the role of
    1/threads: chunk width n/n_chunks is the simulated thread count.
  * Vertices are randomly relabeled once (host-side) so a chunk is a random
    vertex sample — the paper shuffles RMAT vertex ids for the same reason.
  * CAT round = phase A: chunked re-color of the defect set U (against colors
    as of the previous detect, fresh within the pass); BARRIER; phase B:
    separate detect pass -> new U; BARRIER.  Two neighbor-gather passes,
    two materialization points per round.
  * RSOC round = ONE fused detect-and-recolor pass over U: a defect is
    repaired the moment it is seen, from the same gathered neighbor row
    ("freshest data", paper §3).  One gather pass, one materialization point.
    Repairs land a round earlier than CAT's, so rounds and conflicts drop —
    the paper's Figs. 3-6 mechanism.  A round whose U is at most half the
    rows gathers only U's rows, chunk by chunk, with the same result bit
    for bit (``_compact_chunk_pass``, DESIGN.md §2).
  * Termination under lockstep (paper §5: SIMT livelock): conflicts are broken
    *asymmetrically* by a hashed random priority — of a conflicting edge only
    the lower-priority endpoint re-colors.  Every round the highest-priority
    defective vertex becomes permanently stable => termination in <= |V|
    rounds (observed 2-8).  This is the deterministic version of the paper's
    "emulated randomness" remedy for SIMT machines.

Graph encodings: ELL (n, width) padded neighbor table (gather-friendly,
VMEM-tileable — used by the Pallas kernels too), with a COO side-channel for
overflow edges of capped-width hubs (power-law graphs).  Overflow forbidden
sets are built from the round-start snapshot, which preserves the termination
argument (the stable neighbors' colors are always avoided).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from collections.abc import Mapping
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import registry
from repro.core import bitset
from repro.core.context import (DEFAULT_FORBIDDEN_IMPL, PassContext,
                                resolve_impl)
from repro.graphs.csr import CSRGraph, FILL, from_edges, to_edge_list, to_ell
from repro import obs
from repro.resilience import faults
from repro.resilience.errors import CapRetryExhausted

MAX_ROUNDS_TRACE = 64  # fixed-size conflict trace (while_loop-friendly)

# ``jax.named_scope`` names of the static solve's device work: the neighbor
# gather and defect test, the forbidden set and mex with the write-back, the
# COO overflow snapshot and its defect test, and the two loops around them.
# Scopes set HLO ``op_name`` metadata only, never instructions or fusion, so
# they stay on with tracing off; a device profile files each op under the
# scopes on its path (DESIGN.md §12.1).
SOLVE_SCOPES = ("gather", "mex", "overflow", "round0", "repair")

# back-compat alias: the canonical definition moved to core/context.py with
# the PassContext it configures (DESIGN.md §11)
_resolve_impl = resolve_impl


# --------------------------------------------------------------------------
# result container + verification
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ColoringResult:
    colors: np.ndarray             # (n,) int32, >= 0, original vertex ids
    n_rounds: int                  # while-loop rounds (excl. round 0)
    conflicts_per_round: np.ndarray
    total_conflicts: int
    n_colors: int
    overflow: bool                 # True iff the color cap was ever exceeded
    gather_passes: int             # neighbor-gather sweeps executed (perf proxy)
    final_C: int = 0               # color cap actually used (after doublings)
    retries: int = 0               # cap-doubling re-runs (0 = first cap fit)
    distance: int = 1              # coloring distance (2 = native two-hop)
    degrade_rung: int = 0          # resilience ladder rung that produced
                                   # the colors (0 = normal path; see
                                   # resilience/ladder.RUNG_NAMES)
    # the resolved repro.api.ColoringSpec that produced this result, echoed
    # by api.color for reproducibility (None on direct engine calls); typed
    # as object because this module must not import repro.api
    spec: Optional[object] = None
    # mode="incremental" only: the DynamicColoringState behind the colors
    state: Optional[object] = None
    # True iff n_rounds exceeded the MAX_ROUNDS_TRACE device buffer, i.e.
    # conflicts_per_round is a clipped view with the tail collapsed into its
    # last slot (also warned once per process — see _trim_trace)
    trace_truncated: bool = False
    # the obs.RunTrace of this run when tracing was on (api.color attaches
    # it); typed as object because this module must not import repro.obs.*
    # artifacts at class scope
    trace: Optional[object] = None

    def summary(self) -> dict:
        return {"rounds": int(self.n_rounds),
                "conflicts": int(self.total_conflicts),
                "colors": int(self.n_colors),
                "gather_passes": int(self.gather_passes),
                "final_C": int(self.final_C),
                "retries": int(self.retries),
                "distance": int(self.distance)}


_trace_truncation_warned = False


def _trim_trace(trace, n_rounds):
    """Per-round conflict trace, clipped to the rounds that actually ran.

    The device-side trace buffer is a fixed MAX_ROUNDS_TRACE slots (the
    while-loop carry needs a static shape), and runs past it used to hand
    back a silently-clipped 64-row array.  The clipping is now explicit:
    returns ``(trimmed, truncated)`` where ``truncated`` lands on
    ``ColoringResult.trace_truncated``, plus a once-per-process warning the
    first time a run overruns the buffer.
    """
    global _trace_truncation_warned
    n_rounds = int(n_rounds)
    trimmed = np.asarray(trace).reshape(-1)[:min(n_rounds, MAX_ROUNDS_TRACE)]
    truncated = n_rounds > MAX_ROUNDS_TRACE
    if truncated and not _trace_truncation_warned:
        _trace_truncation_warned = True
        warnings.warn(
            f"conflicts_per_round truncated: {n_rounds} repair rounds "
            f"exceed the MAX_ROUNDS_TRACE={MAX_ROUNDS_TRACE} device trace "
            f"buffer, so rounds past it collapsed into the last slot "
            f"(ColoringResult.trace_truncated=True flags this run; this "
            f"warning fires once per process)", RuntimeWarning, stacklevel=3)
    return trimmed, truncated


def is_proper(g: CSRGraph, colors: np.ndarray) -> bool:
    colors = np.asarray(colors)
    e = to_edge_list(g)
    if len(e) == 0:
        return bool((colors >= 0).all())
    return bool((colors >= 0).all() and (colors[e[:, 0]] != colors[e[:, 1]]).all())


def n_colors_used(colors) -> int:
    return int(np.asarray(colors).max()) + 1


# --------------------------------------------------------------------------
# serial oracle (paper Algorithm 1)
# --------------------------------------------------------------------------

def greedy_sequential(g: CSRGraph) -> np.ndarray:
    """Sequential First-Fit. Host-side numpy oracle."""
    colors = np.full(g.n_vertices, -1, dtype=np.int32)
    scratch = np.zeros(g.max_degree + 2, dtype=np.int64)
    for v in range(g.n_vertices):
        nc = colors[g.neighbors(v)]
        nc = nc[nc >= 0]
        scratch[nc] = v + 1          # stamp trick: no re-clearing
        c = 0
        while scratch[c] == v + 1:
            c += 1
        colors[v] = c
    return colors


# --------------------------------------------------------------------------
# problem prep (host)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ColoringProblem:
    """Device-ready relabeled graph: ELL + overflow COO + priorities."""

    ell: jnp.ndarray        # (n_pad, W) int32 neighbor ids (relabeled), FILL pad
    ovf_src: jnp.ndarray    # (m_ovf,) overflow edges (relabeled)
    ovf_dst: jnp.ndarray
    pri: jnp.ndarray        # (n_pad,) int32 priority (pad rows = -1)
    n: int
    n_pad: int
    perm: np.ndarray        # old id -> new id
    C: int                  # color cap (bitmask-friendly, multiple of 32)


def _pick_C(g: CSRGraph, C: Optional[int]) -> int:
    if C is not None:
        return int(C)
    # The packed-bitset forbidden set costs 4 bytes per 32 colors per row
    # (vs 1 byte/color dense), so the default cap can afford to be generous:
    # a larger cap means fewer cap-doubling retries on high-degree graphs
    # (the paper's Figs. 3-6 regime) at 1/8th the old per-row cost.
    c = min(g.max_degree + 2, 256)
    return int(max(32, -(-c // 32) * 32))


def _relabel_rows(g: CSRGraph, perm: np.ndarray,
                  n_pad: int) -> Optional[np.ndarray]:
    """The relabeled, row-sorted ELL of ``g`` built straight from its rows,
    for a graph whose every row fits (``W = max_degree``), or None where a
    row holds a self-loop or a repeated neighbor.

    ``perm`` is a bijection, so relabeled row ``perm[u]`` is row ``u`` with
    each id mapped through ``perm`` and sorted: exactly the row that
    ``from_edges(n, perm[to_edge_list(g)], symmetrize=False)`` builds when
    there is nothing to drop or merge.  Costs O(n·W) with no global sort;
    the None cases keep that path, whose self-loop removal and dedup stay
    as they are."""
    n = g.n_vertices
    W = max(1, g.max_degree)
    deg = g.degrees
    perm32 = perm.astype(np.int32)
    # row u of g, ids mapped through perm, in CSR order; a self-loop u-u
    # reads perm[u] in row u
    mapped = np.full((n, W), FILL, np.int32)
    mapped[np.arange(W) < deg[:, None]] = perm32[g.indices]
    if (mapped == perm32[:, None]).any():
        return None
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    ell = np.empty((n_pad, W), np.int32)
    ell[n:] = FILL
    # inv is in range; "clip" lets take write into out without a buffer
    np.take(mapped, inv, axis=0, out=ell[:n], mode="clip")
    ell[:n].view(np.uint32).sort(axis=1)         # FILL sorts last
    # a sorted row's only equal neighbors are its FILL pairs, unless it
    # repeats an id
    if (np.count_nonzero(ell[:n, 1:] == ell[:n, :-1])
            != np.maximum(W - 1 - deg, 0).sum()):
        return None
    return ell


def prepare(g: CSRGraph, seed: int = 0, n_chunks: int = 16,
            ell_cap: int = 512, C: Optional[int] = None,
            relabel: bool = True) -> ColoringProblem:
    """Relabel, lay out and upload ``g``: three traced phases
    (``prepare.relabel|layout|upload``) nested in the caller's ``prepare``.
    Under a tracer the upload blocks on the copies, so its time is theirs.

    Relabel builds the relabeled, row-sorted ELL straight from ``g``'s rows
    where every row fits it and no row holds a self-loop or a repeated
    neighbor (``_relabel_rows``); otherwise it rebuilds a relabeled CSR
    through ``from_edges`` and layout splits it into ELL and COO overflow.
    Both give the same problem bit for bit; the phase's ``path`` meta says
    which ran (``rows`` or ``edges``)."""
    n = g.n_vertices
    n_pad = -(-max(n, n_chunks) // n_chunks) * n_chunks
    rng = np.random.default_rng(seed)
    ell = None
    with obs.phase("prepare.relabel") as meta:
        perm = (rng.permutation(n).astype(np.int64) if relabel
                else np.arange(n))
        if relabel:
            if g.max_degree <= ell_cap:
                ell = _relabel_rows(g, perm, n_pad)
            meta["path"] = "edges" if ell is None else "rows"
            if ell is None:
                edges = perm[to_edge_list(g).astype(np.int64)]
                g = from_edges(n, edges, symmetrize=False)
    with obs.phase("prepare.layout"):
        W = max(1, min(g.max_degree, ell_cap))
        deg = g.degrees
        if g.max_degree <= ell_cap:
            if ell is None:
                ell = to_ell(g, max_degree=W, pad_vertices_to=n_pad)
            osrc = np.zeros((0,), np.int32)
            odst = np.zeros((0,), np.int32)
        else:
            ell = np.full((n_pad, W), FILL, dtype=np.int32)
            row = np.repeat(np.arange(n), deg)
            col = np.arange(g.n_edges) - np.repeat(g.indptr[:-1], deg)
            in_ell = col < W
            ell[row[in_ell], col[in_ell]] = g.indices[in_ell]
            osrc = row[~in_ell].astype(np.int32)
            odst = g.indices[~in_ell].astype(np.int32)
        # independent random priorities (asymmetric tie-break)
        pri = np.full(n_pad, -1, np.int32)
        pri[:n] = rng.permutation(n).astype(np.int32)
        C = _pick_C(g, C)
    with obs.phase("prepare.upload"):
        dev = tuple(jnp.asarray(a) for a in (ell, osrc, odst, pri))
        if obs.current_tracer() is not None:
            jax.block_until_ready(dev)
    return ColoringProblem(*dev, n=n, n_pad=n_pad, perm=perm, C=C)


def _unpermute(colors_new: np.ndarray, perm: np.ndarray, n: int) -> np.ndarray:
    """Map colors from relabeled space back to original ids.

    ``perm`` maps old id -> new id, so colors_old[i] = colors_new[perm[i]].
    """
    return np.asarray(colors_new)[perm[:n]]


# --------------------------------------------------------------------------
# jittable primitives
# --------------------------------------------------------------------------

def _forbidden_coo(src, dst, colors, n_rows, C):
    """COO forbidden sets; FILL (-1) entries in src/dst are dead slots."""
    live = (src >= 0) & (dst >= 0)
    nbr_c = colors[jnp.clip(dst, 0, colors.shape[0] - 1)]
    ok = live & (nbr_c >= 0) & (nbr_c < C)
    forb = jnp.zeros((n_rows, C), jnp.uint8)
    return forb.at[jnp.clip(src, 0, n_rows - 1),
                   jnp.clip(nbr_c, 0, C - 1)].max(ok.astype(jnp.uint8))


def _mex(forb):
    mex = jnp.argmin(forb, axis=-1).astype(jnp.int32)
    ovf = jnp.all(forb > 0, axis=-1)
    return mex, ovf


# ---- forbidden-set representation dispatch (bitset | dense) --------------
#
# ``impl`` rides in ctx, so it is a jit-cache key like C and n_chunks;
# the passes below only ever touch forbidden tables through these four
# helpers, which keeps the two representations bit-identical by contract
# (tests/test_bitset.py enforces it).

def _forbidden(nbrc, C, impl):
    """(rows, W) gathered neighbor colors -> forbidden table (inline pack)."""
    if impl == "dense":
        return _forbidden_from_nbrc(nbrc, C)
    return bitset.pack_from_nbrc(nbrc, C)


def _mex_of(forb, C, impl):
    """Smallest free color + overflow flag per row of a forbidden table."""
    if impl == "dense":
        return _mex(forb)
    return bitset.mex_words(forb, C)


def _merge_forbidden(a, b, impl):
    """Union of two forbidden tables (gathered row ∪ COO snapshot slice)."""
    if impl == "dense":
        return jnp.maximum(a, b)
    return a | b


def _snapshot_coo(src, dst, colors, n_rows, C, impl):
    """Pass-start COO snapshot table: scatter dense, then (bitset) pack —
    jnp scatters have no bitwise-or mode, so the packed path routes the
    one-off scatter through a transient dense table and retains only the
    packed words (see bitset.pack_dense)."""
    dense = _forbidden_coo(src, dst, colors, n_rows, C)
    if impl == "dense":
        return dense
    return bitset.pack_dense(dense, C)


def _ovf_conflict(osrc, odst, colors, pri, n_rows):
    """Per-row defect flags from overflow edges (FILL slots are dead)."""
    live = (osrc >= 0) & (odst >= 0)
    s = jnp.clip(osrc, 0, colors.shape[0] - 1)
    d = jnp.clip(odst, 0, colors.shape[0] - 1)
    conf = live & (colors[s] == colors[d]) & (colors[s] >= 0) & (pri[d] > pri[s])
    return jnp.zeros((n_rows,), jnp.uint8).at[jnp.clip(osrc, 0, n_rows - 1)].max(
        conf.astype(jnp.uint8)).astype(bool)


def _gather_nbr(ell_k, colors, pri):
    """Neighbor colors + priorities for a block of ELL rows."""
    safe = jnp.clip(ell_k, 0, colors.shape[0] - 1)
    m = ell_k >= 0
    return jnp.where(m, colors[safe], -1), jnp.where(m, pri[safe], -1)


def _forbidden_from_nbrc(nbrc, C):
    rows = nbrc.shape[0]
    ok = (nbrc >= 0) & (nbrc < C)
    forb = jnp.zeros((rows, C), jnp.uint8)
    r = jnp.arange(rows)[:, None]
    return forb.at[r, jnp.clip(nbrc, 0, C - 1)].max(ok.astype(jnp.uint8))


def _pass_snapshot(ctx, osrc, odst, pri, colors, detect):
    """Pass-start COO overflow tables ``(snap_forb, ovf_defect)`` over all
    ``n_pad`` rows, each None where there is no overflow (``ovf_defect``
    also without ``detect``)."""
    if osrc.shape[0] == 0:
        return None, None
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    with jax.named_scope("overflow"):
        snap_forb = _snapshot_coo(osrc, odst, colors, n_pad, C, impl)
        # overflow-edge conflicts, evaluated once on the pass-start
        # snapshot.  (Conflicts only ever arise between two vertices
        # recolored in the same earlier pass, so the snapshot view is
        # sufficient for detection; see module docstring termination
        # argument.)
        ovf_defect = (_ovf_conflict(osrc, odst, colors, pri, n_pad)
                      if detect else None)
    return snap_forb, ovf_defect


def _chunked_pass(ctx, ell, osrc, odst, pri, colors, U, force, *,
                  detect: bool, valid=None):
    """One sequential sweep over n_chunks chunks.

    detect=False (CAT phase A): re-color every vertex in U | force.
    detect=True  (RSOC fused) : re-color a vertex in U only if it is
                                defective right now (fresh check), or forced.
    ``valid`` overrides the default prefix validity mask (length
    ``ctx.n_pad``) — the sharded engine's per-shard row layout is not a
    prefix of the global vertex range.  ``colors``/``pri`` may be longer
    than ``ctx.n_pad`` (a sharded color table with a ghost tail): only the
    first ``n_pad`` rows are swept, but gathers read the full table.
    Returns (colors, recolored_mask, n_defects, overflowed).
    """
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    cs = n_pad // n_chunks
    valid_row = jnp.arange(n_pad) < n if valid is None else valid
    has_ovf = osrc.shape[0] > 0
    snap_forb, ovf_defect = _pass_snapshot(ctx, osrc, odst, pri, colors,
                                           detect)

    def chunk_body(k, carry):
        colors, recolored, n_def, ovf = carry
        lo = k * cs
        ell_k = jax.lax.dynamic_slice_in_dim(ell, lo, cs, 0)
        U_k = jax.lax.dynamic_slice_in_dim(U, lo, cs, 0)
        force_k = jax.lax.dynamic_slice_in_dim(force, lo, cs, 0)
        valid_k = jax.lax.dynamic_slice_in_dim(valid_row, lo, cs, 0)
        c_k = jax.lax.dynamic_slice_in_dim(colors, lo, cs, 0)
        pri_k = jax.lax.dynamic_slice_in_dim(pri, lo, cs, 0)
        with jax.named_scope("gather"):
            nbrc, nbrp = _gather_nbr(ell_k, colors, pri)      # FRESH colors
            if detect:
                defect = ((nbrc == c_k[:, None]) & (c_k[:, None] >= 0)
                          & (nbrp > pri_k[:, None])).any(axis=1)
                if ovf_defect is not None:
                    defect = defect | jax.lax.dynamic_slice_in_dim(
                        ovf_defect, lo, cs, 0)
                work = valid_k & ((U_k & defect) | force_k)
                n_def = n_def + (valid_k & U_k & defect).sum(dtype=jnp.int32)
            else:
                work = valid_k & (U_k | force_k)
        with jax.named_scope("mex"):
            forb = _forbidden(nbrc, C, impl)
            if has_ovf:
                sf_k = jax.lax.dynamic_slice_in_dim(snap_forb, lo, cs, 0)
                forb = _merge_forbidden(forb, sf_k, impl)
            mex, ovf_k = _mex_of(forb, C, impl)
            newc = jnp.where(work, mex, c_k)
            colors = jax.lax.dynamic_update_slice_in_dim(colors, newc, lo, 0)
            recolored = jax.lax.dynamic_update_slice_in_dim(recolored, work,
                                                            lo, 0)
        return colors, recolored, n_def, ovf | (ovf_k & work).any()

    init = (colors, jnp.zeros((n_pad,), bool), jnp.int32(0), jnp.bool_(False))
    return jax.lax.fori_loop(0, n_chunks, chunk_body, init)


# The compacted repair pass gathers ELL rows in blocks of B rows, B the
# power of two with B * W in [2^15, 2^16) elements (W the ELL width), capped
# at the chunk: a few hundred KB of gathered colors and priorities a block,
# whatever the graph's degree.
_BLOCK_ELEMS = 1 << 15


def _repair_block(W: int, cs: int) -> int:
    """Rows per block of ``_compact_chunk_pass`` for ELL width ``W`` and
    chunk size ``cs``."""
    return min(1 << (-(-_BLOCK_ELEMS // W) - 1).bit_length(), cs)


def _compact_cap(n_pad: int) -> int:
    """The largest frontier a repair round gathers compacted; a larger one
    takes the full-width pass.  With every row in the frontier, on one
    v5e chip, the compacted pass took about 0.50 s on RMAT-B 2^16 against
    0.393 s at full width, and about 0.70 s on RMAT-ER 2^20 against
    1.158 s (PERF.md §6): which wins depends on the graph, not on the
    count, and the full-width pass keeps the round that holds most rows."""
    return n_pad // 2


def _compact_chunk_pass(ctx, ell, osrc, odst, pri, colors, U, force):
    """``_chunked_pass(detect=True)`` that gathers only the rows of ``U``.

    Same schedule, same result bit for bit: chunk k still covers rows
    [k*cs, (k+1)*cs), the chunks run in order, and every frontier row of
    chunk k is judged against the colors as they stood at the start of
    chunk k.  A row outside ``U`` can never change there (``force`` lies in
    ``U``), so it is not gathered.  Chunk k visits its own frontier rows in
    ``ceil(count_k / B)`` blocks of B rows (``_repair_block``), none when
    it has none; each block reads the chunk-start colors and writes a
    chunk-local buffer that is committed when the chunk ends.  The overflow
    tables are the full pass's pass-start snapshot, read by row.  Returns
    (colors, recolored_mask, n_defects, overflowed, rows): ``rows`` is the
    ELL rows the blocks gathered, ``B`` times their count (dead code unless
    a traced loop keeps it).
    """
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    cs = n_pad // n_chunks
    B = _repair_block(ell.shape[1], cs)
    snap_forb, ovf_defect = _pass_snapshot(ctx, osrc, odst, pri, colors,
                                           True)
    counts = U.reshape(n_chunks, cs).sum(axis=1, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    # the rows of U in ascending order, so chunk k's are
    # idx[starts[k]:starts[k] + counts[k]]; B tail slots keep every block's
    # slice in range
    idx = jnp.nonzero(U, size=n_pad + B, fill_value=n_pad)[0].astype(
        jnp.int32)

    def chunk_body(k, carry):
        colors_k0, recolored, n_def, ovf = carry
        lo = k * cs

        def block_body(b, bcarry):
            buf, recolored, n_def, ovf = bcarry
            ids = jax.lax.dynamic_slice_in_dim(idx, starts[k] + b * B, B, 0)
            # slots past the chunk's count hold n_pad: the scatters drop them
            ids = jnp.where(b * B + jnp.arange(B) < counts[k], ids, n_pad)
            rows = jnp.minimum(ids, n_pad - 1)
            with jax.named_scope("gather"):
                c_b = colors_k0[rows]
                nbrc, nbrp = _gather_nbr(ell[rows], colors_k0, pri)
                defect = ((nbrc == c_b[:, None]) & (c_b[:, None] >= 0)
                          & (nbrp > pri[rows][:, None])).any(axis=1)
            if ovf_defect is not None:
                with jax.named_scope("overflow"):
                    defect = defect | ovf_defect[rows]
                    sf_b = snap_forb[rows]
            with jax.named_scope("gather"):
                valid_b = ids < n
                work = valid_b & (defect | force[rows])
                n_def = n_def + (valid_b & defect).sum(dtype=jnp.int32)
            with jax.named_scope("mex"):
                forb = _forbidden(nbrc, C, impl)
                if ovf_defect is not None:
                    forb = _merge_forbidden(forb, sf_b, impl)
                mex, ovf_b = _mex_of(forb, C, impl)
                buf = buf.at[ids - lo].set(jnp.where(work, mex, c_b),
                                           mode="drop")
                recolored = recolored.at[ids].set(work, mode="drop")
            return buf, recolored, n_def, ovf | (ovf_b & work).any()

        n_blocks = (counts[k] + B - 1) // B
        buf0 = jax.lax.dynamic_slice_in_dim(colors_k0, lo, cs, 0)
        buf, recolored, n_def, ovf = jax.lax.fori_loop(
            0, n_blocks, block_body, (buf0, recolored, n_def, ovf))
        colors = jax.lax.dynamic_update_slice_in_dim(colors_k0, buf, lo, 0)
        return colors, recolored, n_def, ovf

    init = (colors, jnp.zeros((n_pad,), bool), jnp.int32(0), jnp.bool_(False))
    out = jax.lax.fori_loop(0, n_chunks, chunk_body, init)
    return out + (((counts + B - 1) // B * B).sum(),)


def _detect_pass(ctx, ell, osrc, odst, pri, colors, U):
    """CAT phase B: standalone defect detection over U (full gather pass)."""
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    valid_row = jnp.arange(n_pad) < n
    with jax.named_scope("gather"):
        nbrc, nbrp = _gather_nbr(ell, colors, pri)
        defect = ((nbrc == colors[:, None]) & (colors[:, None] >= 0)
                  & (nbrp > pri[:, None])).any(axis=1)
    if osrc.shape[0] > 0:
        with jax.named_scope("overflow"):
            defect = defect | _ovf_conflict(osrc, odst, colors, pri, n_pad)
    return defect & U & valid_row


# --------------------------------------------------------------------------
# algorithm loops
# --------------------------------------------------------------------------

def _fused_repair(ctx, ell, osrc, odst, pri, colors, U, max_rounds,
                  ovf0=False):
    """Fused detect-and-recolor rounds from an arbitrary (colors, U) start.

    This is the RSOC inner loop factored out of the from-scratch driver so a
    caller (incremental recoloring, distributed shards) can supply its own
    seed set U and partial coloring.  Vertices in U are re-colored only when
    defective *right now*; uncolored seeds (colors < 0) are force-colored on
    their first pass.  A round whose frontier is at most half the rows
    gathers only the frontier's rows (``_compact_chunk_pass``), any other
    the full width (``_chunked_pass``): the two give the same colors bit for
    bit.  Returns (colors, n_rounds, trace, total_defects, ovf) — one
    neighbor-gather pass per round — or, under the static ``ctx.trace``
    flag, (colors, n_rounds, trace, ftrace, total_defects, ovf) with a
    per-round (2, MAX_ROUNDS_TRACE) trace, |U| and the ELL rows gathered,
    spliced in BEFORE the trailing pair so the retry contract (overflow
    flag last) survives.  ``ctx.trace`` is a jit-cache key: the False
    program carries neither.
    """
    n, n_pad, C, n_chunks, impl = ctx.unpack()

    def cond(s):
        # terminate when a full fused pass detected zero defects: colors were
        # untouched during that pass, so its detection was complete.
        # (state tail is fixed at (..., r, tot, last_def, ovf) whether or
        # not the optional ftrace rides along)
        return (s[-2] > 0) & (s[-4] < max_rounds)

    def body(s):
        if ctx.trace:
            colors, U, trace, ftrace, r, tot, last_def, ovf = s
        else:
            colors, U, trace, r, tot, last_def, ovf = s
        force = U & (colors < 0)
        # ONE fused detect-and-recolor pass; ``rows``: what it gathered
        args = (ctx, ell, osrc, odst, pri, colors, U, force)
        colors2, recolored, n_def, ovf2, rows = jax.lax.cond(
            U.sum(dtype=jnp.int32) > _compact_cap(n_pad),
            lambda: _chunked_pass(*args, detect=True) + (jnp.int32(n_pad),),
            lambda: _compact_chunk_pass(*args))
        if ctx.trace:
            ftrace = ftrace.at[:, jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(
                jnp.stack([U.sum(dtype=jnp.int32), rows]))
        trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(n_def)
        # forced vertices were colored speculatively, not verified: keep the
        # loop alive so the next pass checks them (two adjacent uncolored
        # seeds can pick the same color from one snapshot)
        n_work = n_def + force.sum(dtype=jnp.int32)
        head = ((colors2, recolored, trace, ftrace) if ctx.trace
                else (colors2, recolored, trace))
        return head + (r + 1, tot + n_def, n_work, ovf | ovf2)

    trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
    head = ((colors, U, trace, jnp.zeros((2, MAX_ROUNDS_TRACE), jnp.int32))
            if ctx.trace else (colors, U, trace))
    state = head + (jnp.int32(0), jnp.int32(0), jnp.int32(1),
                    jnp.bool_(ovf0))
    with jax.named_scope("repair"):
        out = jax.lax.while_loop(cond, body, state)
    if ctx.trace:
        colors, U, trace, ftrace, r, tot, _, ovf = out
        return colors, r, trace, ftrace, tot, ovf
    colors, U, trace, r, tot, _, ovf = out
    return colors, r, trace, tot, ovf


@functools.partial(jax.jit, static_argnames=("ctx", "max_rounds"))
def _rsoc_loop(ell, osrc, odst, pri, ctx, max_rounds):
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    colors0 = jnp.full((n_pad,), -1, jnp.int32)
    valid = jnp.arange(n_pad) < n
    zeros = jnp.zeros((n_pad,), bool)

    # round 0: tentative coloring of the whole graph (chunked, fresh)
    with jax.named_scope("round0"):
        colors1, U, _, ovf0 = _chunked_pass(
            ctx, ell, osrc, odst, pri, colors0, zeros, valid, detect=False)
    out = _fused_repair(
        ctx, ell, osrc, odst, pri, colors1, U, max_rounds, ovf0)
    return (out[0][:n],) + out[1:]


@functools.partial(jax.jit, static_argnames=("ctx", "max_rounds"))
def _rsoc_repair_loop(ell, osrc, odst, pri, colors, U, ctx, max_rounds):
    """Externally-seeded fused repair (no round 0)."""
    return _fused_repair(ctx, ell, osrc, odst, pri, colors, U, max_rounds)


@functools.partial(jax.jit, static_argnames=("ctx", "max_rounds"))
def _cat_loop(ell, osrc, odst, pri, ctx, max_rounds):
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    colors0 = jnp.full((n_pad,), -1, jnp.int32)
    valid = jnp.arange(n_pad) < n
    zeros = jnp.zeros((n_pad,), bool)

    with jax.named_scope("round0"):
        # round 0 phase A: color everything (chunked, fresh within pass)
        colors1, _, _, ovf0 = _chunked_pass(
            ctx, ell, osrc, odst, pri, colors0, zeros, valid, detect=False)
        # round 0 phase B: detect                               (pass 2)
        U1 = _detect_pass(ctx, ell, osrc, odst, pri, colors1, valid)

    def cond(s):
        return s[1].any() & (s[3] < max_rounds)

    def body(s):
        colors, U, trace, r, tot, ovf = s
        n_def = U.sum(dtype=jnp.int32)
        trace = trace.at[jnp.minimum(r, MAX_ROUNDS_TRACE - 1)].set(n_def)
        # phase A: re-color the defect set                      (pass 1)
        colors2, _, _, ovf2 = _chunked_pass(
            ctx, ell, osrc, odst, pri, colors, U, zeros, detect=False)
        # phase B: separate detect pass                         (pass 2)
        U2 = _detect_pass(ctx, ell, osrc, odst, pri, colors2, U)
        return colors2, U2, trace, r + 1, tot + n_def, ovf | ovf2

    trace = jnp.zeros((MAX_ROUNDS_TRACE,), jnp.int32)
    state = (colors1, U1, trace, jnp.int32(0), jnp.int32(0), ovf0)
    with jax.named_scope("repair"):
        colors, U, trace, r, tot, ovf = jax.lax.while_loop(cond, body, state)
    return colors[:n], r, trace, tot, ovf


@functools.partial(jax.jit, static_argnames=("ctx",))
def _gm_round0(ell, osrc, odst, pri, ctx):
    n, n_pad, C, n_chunks, impl = ctx.unpack()
    colors0 = jnp.full((n_pad,), -1, jnp.int32)
    valid = jnp.arange(n_pad) < n
    zeros = jnp.zeros((n_pad,), bool)
    colors1, _, _, ovf = _chunked_pass(
        ctx, ell, osrc, odst, pri, colors0, zeros, valid, detect=False)
    defect = _detect_pass(ctx, ell, osrc, odst, pri, colors1, valid)
    return colors1, defect, ovf


@functools.partial(jax.jit, static_argnames=("n", "C", "max_rounds", "impl"))
def _jp_loop(src, dst, pri, n, C, max_rounds, impl=DEFAULT_FORBIDDEN_IMPL):
    colors0 = jnp.full((n,), -1, jnp.int32)

    def cond(s):
        return (s[0] < 0).any() & (s[1] < max_rounds)

    def body(s):
        colors, r, ovf = s
        uncolored = colors < 0
        nbr_pri = jnp.where(uncolored[dst], pri[dst], -1)
        best = jnp.full((n,), -1, jnp.int32).at[src].max(nbr_pri)
        elig = uncolored & (pri > best)
        forb = _snapshot_coo(src, dst, colors, n, C, impl)
        mex, o = _mex_of(forb, C, impl)
        colors = jnp.where(elig, mex, colors)
        return colors, r + 1, ovf | (o & elig).any()

    colors, r, ovf = jax.lax.while_loop(
        cond, body, (colors0, jnp.int32(0), jnp.bool_(False)))
    return colors, r, ovf


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def _run_with_retry(run, C: int, *, engine: str = "",
                    max_retries: Optional[int] = None):
    """Run ``run(C)``, doubling the color cap until it fits.

    ``run`` returns any tuple whose LAST element is the boolean overflow
    flag.  This is the single cap-doubling loop shared by every engine
    (from-scratch, frontier-compacted, JP, native distance-2, incremental)
    — they differ only in the closure they pass.  Returns
    (run output, final C, number of cap-doubling retries).

    ``max_retries`` bounds the doublings (``ColoringSpec.max_cap_retries``):
    a pathological graph/cap pair raises ``CapRetryExhausted`` instead of
    spinning, and the dynamic stack degrades through its ladder (DESIGN.md
    §14.2).  ``None`` keeps the legacy unbounded loop bit-exactly.  The
    ``cap.exhaust`` fault site rides here too — host-side, before the
    dispatch, so faults-off runs compile byte-identical programs.

    Observability rides here precisely because every engine funnels through:
    each attempt is a ``solve`` phase on the current tracer (blocking on the
    outputs so the wall time is real), and each doubling bumps the
    ``engine.cap_retry{engine=...}`` counter.  With no tracer and no armed
    faults the only addition over the pre-obs loop is two None checks per
    attempt.
    """
    retries = 0
    while True:
        if faults.fires("cap.exhaust", engine=engine):
            raise CapRetryExhausted(engine=engine, C=C, retries=retries,
                                    budget=max_retries, forced=True)
        tracer = obs.current_tracer()
        if tracer is None:
            out = run(C)
        else:
            with tracer.phase("solve", C=int(C), attempt=retries):
                out = jax.block_until_ready(run(C))
        if not bool(out[-1]):
            return out, C, retries
        if max_retries is not None and retries >= max_retries:
            raise CapRetryExhausted(engine=engine, C=C, retries=retries,
                                    budget=max_retries)
        C *= 2  # rare: color cap exceeded -> retry with doubled cap
        retries += 1
        obs.metrics.counter("engine.cap_retry",
                            engine=engine or "unknown").inc()


def _prob_runner(loop, prob: ColoringProblem, n_chunks: int, max_rounds: int,
                 impl: str, trace: bool = False):
    """Adapt the standard from-scratch loop signature to ``_run_with_retry``."""
    def run(C):
        ctx = PassContext.for_problem(prob, n_chunks=n_chunks, C=C,
                                      forbidden_impl=impl, trace=trace)
        return loop(prob.ell, prob.ovf_src, prob.ovf_dst, prob.pri,
                    ctx, max_rounds)
    return run


def _loop_outputs(out, traced: bool):
    """Split a retry-loop output tuple into (colors, r, trace, ftrace, tot).

    The traced program returns six elements (frontier trace spliced before
    the trailing (tot, ovf) pair), the plain program five; ftrace is None
    when the loop did not collect one.
    """
    if traced:
        colors, r, trace, ftrace, tot, _ = out
        return colors, r, trace, ftrace, tot
    colors, r, trace, tot, _ = out
    return colors, r, trace, None, tot


def _report_frontier(tracer, ftrace, r, cap=None, n_pad=None):
    """Hand a loop-carried frontier trace to the tracer, clipped like the
    conflict trace is.  A 2-row trace (``_fused_repair``'s) holds |U| and
    the ELL rows gathered per round, out of ``n_pad``."""
    if tracer is not None and ftrace is not None:
        trimmed = np.asarray(ftrace)[..., :min(int(r), MAX_ROUNDS_TRACE)]
        if trimmed.ndim == 2:
            tracer.set_frontier_trace(trimmed[0], cap=cap, rows=trimmed[1],
                                      n_pad=n_pad)
        else:
            tracer.set_frontier_trace(trimmed, cap=cap)


# --------------------------------------------------------------------------
# registered engines (the implementations behind repro.api.color)
# --------------------------------------------------------------------------

@registry.register_engine("rsoc", distance=1, mode="static",
                          replaces="color_rsoc")
def _rsoc_engine(g: CSRGraph, spec) -> ColoringResult:
    """RSOC (paper Alg. 3): fused detect-and-recolor, one pass per round."""
    impl = resolve_impl(spec.forbidden_impl)
    tracer = obs.current_tracer()
    with obs.phase("prepare"):
        prob = prepare(g, spec.seed, spec.n_chunks, spec.ell_cap, spec.C,
                       spec.relabel)
    out, final_C, retries = _run_with_retry(
        _prob_runner(_rsoc_loop, prob, spec.n_chunks, spec.max_rounds, impl,
                     trace=tracer is not None),
        prob.C, engine="rsoc", max_retries=spec.max_cap_retries)
    with obs.phase("finish"):
        colors, r, trace, ftrace, tot = _loop_outputs(out, tracer is not None)
        _report_frontier(tracer, ftrace, r, cap=_compact_cap(prob.n_pad),
                         n_pad=prob.n_pad)
        conf, truncated = _trim_trace(trace, r)
        colors = _unpermute(colors, prob.perm, prob.n)
        n_colors = n_colors_used(colors)
    return ColoringResult(colors=colors, n_rounds=int(r),
                          conflicts_per_round=conf,
                          total_conflicts=int(tot),
                          n_colors=n_colors,
                          overflow=retries > 0,
                          gather_passes=1 + int(r),
                          final_C=final_C, retries=retries,
                          trace_truncated=truncated)


@registry.register_engine("cat", distance=1, mode="static",
                          replaces="color_cat")
def _cat_engine(g: CSRGraph, spec) -> ColoringResult:
    """Catalyurek et al. (paper Alg. 2): two-phase rounds."""
    impl = resolve_impl(spec.forbidden_impl)
    tracer = obs.current_tracer()
    with obs.phase("prepare"):
        prob = prepare(g, spec.seed, spec.n_chunks, spec.ell_cap, spec.C,
                       spec.relabel)
    (colors, r, trace, tot, _), final_C, retries = _run_with_retry(
        _prob_runner(_cat_loop, prob, spec.n_chunks, spec.max_rounds, impl),
        prob.C, engine="cat", max_retries=spec.max_cap_retries)
    with obs.phase("finish"):
        conf, truncated = _trim_trace(trace, r)
        # CAT's frontier IS its conflict count: a round re-colors exactly
        # the defect set U detected by the previous phase B, so no extra
        # device collection is needed (the traced and untraced programs are
        # identical).
        _report_frontier(tracer, conf, r)
        colors = _unpermute(colors, prob.perm, prob.n)
        n_colors = n_colors_used(colors)
    return ColoringResult(colors=colors, n_rounds=int(r),
                          conflicts_per_round=conf,
                          total_conflicts=int(tot),
                          n_colors=n_colors,
                          overflow=retries > 0,
                          gather_passes=2 * (1 + int(r)),
                          final_C=final_C, retries=retries,
                          trace_truncated=truncated)


@registry.register_engine("gm", distance=1, mode="static",
                          replaces="color_gm")
def _gm_engine(g: CSRGraph, spec) -> ColoringResult:
    """Gebremedhin-Manne: speculate, detect, serial repair (one round —
    ``spec.max_rounds`` is inert for this engine)."""
    impl = resolve_impl(spec.forbidden_impl)
    with obs.phase("prepare"):
        prob = prepare(g, spec.seed, spec.n_chunks, spec.ell_cap, spec.C,
                       spec.relabel)
    ctx = PassContext.for_problem(prob, n_chunks=spec.n_chunks,
                                  forbidden_impl=impl)
    with obs.phase("solve", C=prob.C):
        colors, defect, ovf = jax.block_until_ready(
            _gm_round0(prob.ell, prob.ovf_src, prob.ovf_dst, prob.pri, ctx))
    colors_np = np.asarray(colors[:prob.n]).copy()
    defect_np = np.asarray(defect[:prob.n])
    # serial repair in the *relabeled* space: rebuild neighbor lists from ELL
    # plus the COO overflow side-channel (capped-width hub rows spill there —
    # skipping it produced improper repairs on power-law graphs).
    with obs.phase("serial_repair",
                         n_defects=int(defect_np.sum())):
        ell_np = np.asarray(prob.ell)
        osrc_np = np.asarray(prob.ovf_src)
        odst_np = np.asarray(prob.ovf_dst)
        order = np.argsort(osrc_np, kind="stable")
        osrc_sorted, odst_sorted = osrc_np[order], odst_np[order]
        for v in np.nonzero(defect_np)[0]:
            nb = ell_np[v]
            nb = nb[(nb >= 0) & (nb < prob.n)]
            if len(osrc_sorted):
                lo, hi = np.searchsorted(osrc_sorted, [v, v + 1])
                nb = np.concatenate([nb, odst_sorted[lo:hi]])
            nc = colors_np[nb]
            used = set(int(x) for x in nc if x >= 0)
            c = 0
            while c in used:
                c += 1
            colors_np[v] = c
    tot = int(defect_np.sum())
    colors_out = _unpermute(colors_np, prob.perm, prob.n)
    return ColoringResult(colors=colors_out, n_rounds=1,
                          conflicts_per_round=np.array([tot]),
                          total_conflicts=tot,
                          n_colors=n_colors_used(colors_out),
                          overflow=bool(ovf),
                          gather_passes=2, final_C=prob.C, retries=0)


@registry.register_engine("jp", distance=1, mode="static",
                          replaces="color_jp")
def _jp_engine(g: CSRGraph, spec) -> ColoringResult:
    """Jones-Plassmann priority-MIS baseline (COO formulation; the ELL/chunk
    fields of the spec — n_chunks, ell_cap, relabel — are inert here)."""
    impl = resolve_impl(spec.forbidden_impl)
    n = g.n_vertices
    with obs.phase("prepare"):
        e = to_edge_list(g)
        src = jnp.asarray(e[:, 0], jnp.int32)
        dst = jnp.asarray(e[:, 1], jnp.int32)
        pri = jnp.asarray(np.random.default_rng(spec.seed).permutation(n)
                          .astype(np.int32))
    (colors, r, _), Cv, retries = _run_with_retry(
        lambda Cv: _jp_loop(src, dst, pri, n, Cv, spec.max_rounds, impl),
        _pick_C(g, spec.C), engine="jp",
        max_retries=spec.max_cap_retries)
    colors = np.asarray(colors)
    if (colors < 0).any():
        # never silent: a JP round bound that is too small used to return a
        # partial coloring with -1 entries (the legacy color_jp default was
        # max_rounds=10000 vs the spec's 1000, so the spec path hits it
        # earlier on adversarial priority chains)
        raise RuntimeError(
            f"JP left {int((colors < 0).sum())} vertices uncolored after "
            f"max_rounds={spec.max_rounds}; raise ColoringSpec.max_rounds "
            f"(JP needs one round per step of its longest decreasing "
            f"priority path)")
    return ColoringResult(colors=colors, n_rounds=int(r),
                          conflicts_per_round=np.zeros(1),
                          total_conflicts=0,
                          n_colors=n_colors_used(colors),
                          overflow=retries > 0,
                          gather_passes=int(r),
                          final_C=Cv, retries=retries)


# --------------------------------------------------------------------------
# legacy entry points: thin deprecation shims over repro.api.color
# --------------------------------------------------------------------------

def color_rsoc(g: CSRGraph, seed: int = 0, C: Optional[int] = None,
               n_chunks: int = 16, max_rounds: int = 1000,
               ell_cap: int = 512, relabel: bool = True,
               forbidden_impl: Optional[str] = None) -> ColoringResult:
    """Deprecated: use ``repro.api.color(g, algorithm="rsoc", ...)``."""
    return registry.legacy_entry(
        "color_rsoc", "algorithm='rsoc'", g, algorithm="rsoc", seed=seed,
        C=C, n_chunks=n_chunks, max_rounds=max_rounds, ell_cap=ell_cap,
        relabel=relabel, forbidden_impl=forbidden_impl)


def color_cat(g: CSRGraph, seed: int = 0, C: Optional[int] = None,
              n_chunks: int = 16, max_rounds: int = 1000,
              ell_cap: int = 512, relabel: bool = True,
              forbidden_impl: Optional[str] = None) -> ColoringResult:
    """Deprecated: use ``repro.api.color(g, algorithm="cat", ...)``."""
    return registry.legacy_entry(
        "color_cat", "algorithm='cat'", g, algorithm="cat", seed=seed,
        C=C, n_chunks=n_chunks, max_rounds=max_rounds, ell_cap=ell_cap,
        relabel=relabel, forbidden_impl=forbidden_impl)


def color_gm(g: CSRGraph, seed: int = 0, C: Optional[int] = None,
             n_chunks: int = 16, ell_cap: int = 512,
             relabel: bool = True,
             forbidden_impl: Optional[str] = None) -> ColoringResult:
    """Deprecated: use ``repro.api.color(g, algorithm="gm", ...)``."""
    return registry.legacy_entry(
        "color_gm", "algorithm='gm'", g, algorithm="gm", seed=seed,
        C=C, n_chunks=n_chunks, ell_cap=ell_cap, relabel=relabel,
        forbidden_impl=forbidden_impl)


def color_jp(g: CSRGraph, seed: int = 0, C: Optional[int] = None,
             max_rounds: int = 10000,
             forbidden_impl: Optional[str] = None) -> ColoringResult:
    """Deprecated: use ``repro.api.color(g, algorithm="jp", ...)``."""
    return registry.legacy_entry(
        "color_jp", "algorithm='jp'", g, algorithm="jp", seed=seed, C=C,
        max_rounds=max_rounds, forbidden_impl=forbidden_impl)


class _AlgorithmsView(Mapping):
    """``ALGORITHMS`` as a live registry view (DESIGN.md §11).

    Keys are the algorithm names registered for the classic combo
    (distance=1, mode="static", backend="local"); values are callables
    ``fn(g, **spec_overrides) -> ColoringResult`` that route through
    ``repro.api.color`` — the supported bulk interface, so unlike the
    ``color_*`` shims it does not emit deprecation warnings.
    """

    def _names(self) -> list[str]:
        from repro import api
        return api.algorithms()   # the (1, "static", "local") slice

    def __getitem__(self, name: str):
        if name not in self._names():
            raise KeyError(name)

        def run(g, **overrides):
            from repro import api
            return api.color(g, algorithm=name, **overrides)

        run.__name__ = f"color_via_registry[{name}]"
        return run

    def __iter__(self):
        return iter(self._names())

    def __len__(self) -> int:
        return len(self._names())

    def __repr__(self) -> str:
        return f"ALGORITHMS({', '.join(self._names())})"


ALGORITHMS = _AlgorithmsView()
