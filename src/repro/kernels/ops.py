"""Jit'd dispatch wrappers for the Pallas kernels.

``backend='auto'`` uses the Pallas kernel on TPU when the TPU compiler
(Mosaic) accepts it, and the jnp reference path otherwise — on every other
platform, and on TPU for the kernels listed in ``MOSAIC_UNSUPPORTED``.  An
explicit ``backend='pallas'`` for a listed kernel raises
``NotImplementedError`` with the compiler's reason instead of silently
running the reference.  ``backend='pallas_interpret'`` forces the kernel body
through the Pallas interpreter (CPU-executable, bit-faithful to kernel
semantics); that is how the test suite validates every kernel.

The coloring dispatchers take ``impl`` ("bitset" | "dense"), forwarded to
the jnp refs; the Pallas kernels are the packed-bitset expression by
construction (DESIGN.md §10) and ignore it — every (backend, impl) corner
must agree bit-for-bit (tests/test_kernels.py).

**VMEM accounting** (DESIGN.md §8.3): every dispatcher shares one honest
estimator, ``vmem_bytes(kernel, ...)``, that counts what a kernel program
actually keeps resident — double-buffered (×2) for grid-varying blocks
(the Pallas pipeline prefetches the next block while the current one
computes), single-buffered for grid-invariant blocks like the color and
priority vectors, plus accumulators/scratch.  A kernel only falls back to
the jnp reference when that estimate busts ``VMEM_BUDGET_BYTES`` —
post-paging this is the *degenerate-shape* predicate (e.g. the un-pageable
(n,) vectors alone exceeding the budget), not a cliff at table size: the
two-hop kernel pages its hop-2 table through VMEM (kernels/twohop.py), so
arbitrarily large ELL tables stay on the Pallas path.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.kernels import ref
from repro.kernels import twohop as _twohop_mod
from repro.kernels.firstfit import firstfit as _firstfit_pallas
from repro.kernels.detect_recolor import detect_recolor as _dr_pallas
from repro.kernels.twohop import twohop_detect_recolor as _twohop_pallas
from repro.kernels.ell_spmm import ell_spmm as _spmm_pallas
from repro.kernels.flash_attention import flash_attention as _fa_pallas
from repro.obs import metrics as obs_metrics
from repro.resilience import faults

# Per-invocation VMEM residency budget (conservative: real cores have
# ~16 MB; half is left to XLA temporaries and the pipeline itself).
VMEM_BUDGET_BYTES = 8 * 2**20


# Kernels the TPU compiler refuses, with its reason (compiled for a described
# v5e chip; tests/test_tpu_compile.py pins both directions of this table).
# The four coloring/aggregation kernels gather per row from a VMEM-resident
# (n,) vector, and Mosaic lowers a gather only within one vreg; the column
# read ``ell[:, j]`` inside a fori_loop is a dynamic_slice it does not lower
# either.  Making them lower is a redesign (gather outside the kernel), not a
# lowering fix, so on TPU ``auto`` runs the jnp reference for them.
_GATHER_REASON = ("Mosaic: 'Unimplemented primitive in Pallas TPU lowering: "
                  "dynamic_slice' (column read ell[:, j] in a fori_loop), "
                  "then 'Only 2D gather is supported' (per-row gather from "
                  "the VMEM-resident (n,) vector)")
MOSAIC_UNSUPPORTED = {
    "firstfit": _GATHER_REASON,
    "detect_recolor": _GATHER_REASON,
    "twohop": _GATHER_REASON,
    "ell_aggregate": _GATHER_REASON,
}


def default_backend(kernel: str) -> str:
    """What ``backend='auto'`` runs for ``kernel`` on this process's
    platform."""
    if jax.default_backend() == "tpu" and kernel not in MOSAIC_UNSUPPORTED:
        return "pallas"
    return "jnp"


def _resolve(kernel: str, backend: str) -> str:
    return default_backend(kernel) if backend == "auto" else backend


def _require_mosaic(kernel: str, b: str) -> None:
    """An explicit ``pallas`` request for a kernel Mosaic refuses raises —
    it never turns into the reference path without saying so."""
    if b == "pallas" and kernel in MOSAIC_UNSUPPORTED:
        raise NotImplementedError(
            f"{kernel}: the Pallas kernel does not lower for TPU — "
            f"{MOSAIC_UNSUPPORTED[kernel]}; use backend='jnp' or 'auto' "
            f"(the jnp reference) or 'pallas_interpret'")


def _forced_fallback(kernel: str, b: str) -> str:
    """``kernel.fallback`` fault site (DESIGN.md §14.4): force the jnp
    reference path — bit-identical output by the parity contract, so chaos
    runs exercise the fallback plumbing without changing results.  With
    faults off this is one module-global None check."""
    if b != "jnp" and faults.fires("kernel.fallback", kernel=kernel):
        obs_metrics.counter("kernels.fallback", kernel=kernel,
                            reason="forced").inc()
        return "jnp"
    return b


def _dispatched(kernel: str, backend: str) -> None:
    """Count every dispatch decision: ``kernels.dispatch{kernel=,backend=}``
    tells a perf report which path actually ran (DESIGN.md §12)."""
    obs_metrics.counter("kernels.dispatch", kernel=kernel,
                        backend=backend).inc()


_fallback_warned: set = set()


def _vmem_fallback(kernel: str, detail: str) -> None:
    """A requested Pallas kernel fell back to the jnp reference because its
    working set would not stay VMEM-resident.  Used to be silent — now it
    warns once per process per kernel (naming the overflowing shape) and
    counts every occurrence in ``kernels.fallback{kernel=,reason=vmem}``."""
    obs_metrics.counter("kernels.fallback", kernel=kernel,
                        reason="vmem").inc()
    if kernel not in _fallback_warned:
        _fallback_warned.add(kernel)
        warnings.warn(
            f"{kernel}: Pallas kernel fell back to the jnp reference — "
            f"{detail}. Counted in obs.metrics "
            f"'kernels.fallback{{kernel={kernel},reason=vmem}}'; this "
            f"warning fires once per process per kernel.",
            RuntimeWarning, stacklevel=3)


# --------------------------------------------------------------------------
# honest per-kernel VMEM estimators (unit-pinned by tests/test_kernels.py)
# --------------------------------------------------------------------------

def firstfit_vmem_bytes(R: int, W: int, n: int, C: int,
                        block_rows: int = 256) -> int:
    """Resident bytes of one firstfit program: double-buffered (BV, W) ELL
    tile, the full (n,) color vector, the packed forbidden accumulator, and
    double-buffered (BV,) outputs (mex int32 + ovf bool)."""
    BV = min(block_rows, R)
    return (2 * BV * W * 4            # ELL tile (pipelined)
            + n * 4                   # colors (grid-invariant)
            + BV * bitset.n_words(C) * 4
            + 2 * BV * (4 + 1))       # outputs


def detect_recolor_vmem_bytes(R: int, W: int, n: int, C: int,
                              block_rows: int = 256) -> int:
    """firstfit's account plus the (n,) priority vector, the per-block
    U/rowc/rowp inputs, and the recolored/overflow outputs."""
    BV = min(block_rows, R)
    return (2 * BV * W * 4                  # ELL tile
            + 2 * n * 4                     # colors + priorities
            + 2 * BV * (1 + 4 + 4)          # U, rowc, rowp
            + BV * bitset.n_words(C) * 4    # forbidden words
            + BV * 4                        # defect flags
            + 2 * BV * (4 + 1 + 1))         # newc, rec, ovf


def twohop_vmem_bytes(R: int, W: int, n: int, C: int,
                      block_rows: int = 128,
                      page_rows: int | None = None,
                      n_all: int | None = None) -> int:
    """Resident bytes of one paged two-hop program: detect_recolor's account
    plus TWO (page_rows, W) hop-2 table pages (compute + DMA prefetch), the
    (BV, W) hop-2 gather panel, the rowid block, and the accumulator
    scratch.  This replaces the old predicate, which counted only the
    *whole-table* ``n_all*W*4`` bytes and ignored every vector — wrong in
    both directions once the table is paged."""
    BV = min(block_rows, R)
    if page_rows is None:
        page_rows = _twohop_mod.default_page_rows(n_all if n_all else n, W)
    return (2 * BV * W * 4                  # row tile
            + 2 * page_rows * W * 4         # hop-2 pages (double-buffered)
            + 2 * n * 4                     # colors + priorities
            + 2 * BV * (1 + 4 + 4 + 4)      # U, rowc, rowp, rowid
            + BV * W * 4                    # per-neighbor hop-2 gather panel
            + BV * bitset.n_words(C) * 4    # forbidden word scratch
            + BV * 4                        # defect scratch
            + 2 * BV * (4 + 1 + 1))         # newc, rec, ovf


def ell_aggregate_vmem_bytes(R: int, W: int, n: int, d: int,
                             itemsize: int = 4, block_rows: int = 128,
                             block_feats: int = 128) -> int:
    """Resident bytes of one ELL-aggregation program: the feature panel is
    (n, min(block_feats, d)) — the *real* width, not a hardcoded 128-wide
    panel — double-buffered only when the feature axis actually pages
    (d > block_feats)."""
    br = min(block_rows, R)
    bf = min(block_feats, d)
    panel_bufs = 2 if d > bf else 1
    return (2 * br * W * 4                  # ELL tile
            + panel_bufs * n * bf * itemsize
            + br * bf * itemsize            # accumulator
            + 2 * br * bf * itemsize)       # output tile


_VMEM_ESTIMATORS = {
    "firstfit": firstfit_vmem_bytes,
    "detect_recolor": detect_recolor_vmem_bytes,
    "twohop": twohop_vmem_bytes,
    "ell_aggregate": ell_aggregate_vmem_bytes,
}


def vmem_bytes(kernel: str, **shape) -> int:
    """Honest resident-bytes estimate for ``kernel`` — the single fallback
    predicate shared by every dispatcher (and the bench working-set
    accountant)."""
    try:
        est = _VMEM_ESTIMATORS[kernel]
    except KeyError:
        raise ValueError(f"unknown kernel {kernel!r}; "
                         f"known: {sorted(_VMEM_ESTIMATORS)}") from None
    return est(**shape)


def _mb(b: int) -> str:
    return f"{b / 2**20:.1f} MB"


# --------------------------------------------------------------------------
# dispatchers
# --------------------------------------------------------------------------

def firstfit(ell, colors, C: int = 64, backend: str = "auto",
             impl: str = "bitset", **kw):
    b = _resolve("firstfit", backend)
    R, W = ell.shape
    n = colors.shape[0]
    if b != "jnp":
        need = firstfit_vmem_bytes(R, W, n, C,
                                   kw.get("block_rows", 256))
        if min(R, W) == 0 or need > VMEM_BUDGET_BYTES:
            _vmem_fallback(
                "firstfit",
                f"resident set for ELL {R}x{W}, n={n}, C={C} is "
                f"{_mb(need)} > {_mb(VMEM_BUDGET_BYTES)} budget "
                f"(the (n,) color vector is not pageable)")
            b = "jnp"
    b = _forced_fallback("firstfit", b)
    _require_mosaic("firstfit", b)
    _dispatched("firstfit", b)
    if b == "jnp":
        return ref.firstfit_ref(ell, colors, C, impl=impl)
    interp = b == "pallas_interpret"
    mex, ovf = _firstfit_pallas(ell, colors, C=C, interpret=interp, **kw)
    return mex, ovf


def detect_recolor(ell, colors, pri, U_rows, row_start: int, C: int = 64,
                   backend: str = "auto", impl: str = "bitset", **kw):
    b = _resolve("detect_recolor", backend)
    R, W = ell.shape
    n = colors.shape[0]
    if b != "jnp":
        need = detect_recolor_vmem_bytes(R, W, n, C,
                                         kw.get("block_rows", 256))
        if min(R, W) == 0 or need > VMEM_BUDGET_BYTES:
            _vmem_fallback(
                "detect_recolor",
                f"resident set for ELL {R}x{W}, n={n}, C={C} is "
                f"{_mb(need)} > {_mb(VMEM_BUDGET_BYTES)} budget "
                f"(the (n,) color/priority vectors are not pageable)")
            b = "jnp"
    b = _forced_fallback("detect_recolor", b)
    _require_mosaic("detect_recolor", b)
    _dispatched("detect_recolor", b)
    if b == "jnp":
        return ref.detect_recolor_ref(ell, colors, pri, row_start, U_rows, C,
                                      impl=impl)
    interp = b == "pallas_interpret"
    return _dr_pallas(ell, colors, pri, U_rows, row_start=row_start, C=C,
                      interpret=interp, **kw)


def twohop(ell_rows, ell_all, colors, pri, U_rows, row_start: int,
           C: int = 64, backend: str = "auto", impl: str = "bitset",
           page_rows: int | None = None, **kw):
    """Fused two-hop (distance-2) detect-and-recolor for rows
    [row_start, row_start + R).  The hop-2 table is paged through VMEM
    (``page_rows`` rows per page, None -> ~2 MB pages), so table size no
    longer forces a fallback; only degenerate shapes — empty tiles, or the
    un-pageable (n,) color/priority vectors busting the budget — take the
    jnp reference path."""
    b = _resolve("twohop", backend)
    R, W = ell_rows.shape
    n = colors.shape[0]
    n_all = ell_all.shape[0]
    if b != "jnp":
        block_rows = kw.get("block_rows", 128)
        pr = (page_rows if page_rows is not None
              else _twohop_mod.default_page_rows(n_all, W))
        need = twohop_vmem_bytes(R, W, n, C, block_rows, pr, n_all=n_all)
        if min(R, W, n_all) == 0 or need > VMEM_BUDGET_BYTES:
            _vmem_fallback(
                "twohop",
                f"paged resident set for rows {R}x{W}, table {n_all}x{W}, "
                f"n={n}, C={C}, page_rows={pr} is {_mb(need)} > "
                f"{_mb(VMEM_BUDGET_BYTES)} budget — the (n,) color/priority "
                f"vectors are not pageable (degenerate shape)")
            b = "jnp"
    b = _forced_fallback("twohop", b)
    _require_mosaic("twohop", b)
    _dispatched("twohop", b)
    if b == "jnp":
        return ref.twohop_ref(ell_rows, ell_all, colors, pri, row_start,
                              U_rows, C, impl=impl)
    interp = b == "pallas_interpret"
    return _twohop_pallas(ell_rows, ell_all, colors, pri, U_rows,
                          row_start=row_start, C=C, page_rows=page_rows,
                          interpret=interp, **kw)


def ell_aggregate(ell, feats, op: str = "sum", backend: str = "auto", **kw):
    """GNN neighbor aggregation.  Falls back to jnp when the honest resident
    set (feature panel at its REAL width min(block_feats, d), not a
    hardcoded 128 lanes) busts the VMEM budget."""
    b = _resolve("ell_aggregate", backend)
    R, W = ell.shape
    n, d = feats.shape
    if b != "jnp":
        need = ell_aggregate_vmem_bytes(
            R, W, n, d, feats.dtype.itemsize,
            kw.get("block_rows", 128), kw.get("block_feats", 128))
        if min(R, W, d) == 0 or need > VMEM_BUDGET_BYTES:
            _vmem_fallback(
                "ell_aggregate",
                f"resident set for ELL {R}x{W}, feature panel {n}x"
                f"{min(kw.get('block_feats', 128), d)} ({feats.dtype}) is "
                f"{_mb(need)} > {_mb(VMEM_BUDGET_BYTES)} budget")
            b = "jnp"
    b = _forced_fallback("ell_aggregate", b)
    _require_mosaic("ell_aggregate", b)
    _dispatched("ell_aggregate", b)
    if b == "jnp":
        return ref.ell_spmm_ref(ell, feats, op)
    interp = b == "pallas_interpret"
    return _spmm_pallas(ell, feats, op=op, interpret=interp, **kw)


def attention(q, k, v, *, causal: bool = True, backend: str = "auto", **kw):
    b = _resolve("attention", backend)
    _dispatched("attention", b)
    if b == "jnp":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    interp = b == "pallas_interpret"
    return _fa_pallas(q, k, v, causal=causal, interpret=interp, **kw)
