"""Per-kernel parity tests: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes.

The coloring refs carry a forbidden-set ``impl`` switch ("bitset" packed
words vs "dense" one-hot, DESIGN.md §10); parity is asserted against BOTH,
so each test cross-checks three corners (kernel, bitset ref, dense ref).
``REPRO_KERNEL_BACKEND`` selects the ops-dispatch backend the agreement
tests pit against jnp — CI runs the module once per backend.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref, ops
from repro.kernels.firstfit import firstfit
from repro.kernels.detect_recolor import detect_recolor
from repro.kernels.ell_spmm import ell_spmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.twohop import twohop_detect_recolor


# ops-dispatch backend under test (CI runs both: pallas_interpret and jnp)
DISPATCH_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "pallas_interpret")
REF_IMPLS = ("bitset", "dense")


def _rand_ell(rng, R, W, n, frac_fill=0.3):
    ell = rng.integers(0, n, size=(R, W)).astype(np.int32)
    ell[rng.random((R, W)) < frac_fill] = -1
    return ell


@pytest.mark.parametrize("impl", REF_IMPLS)
@pytest.mark.parametrize("R,W,n,C", [
    (256, 8, 1024, 32), (512, 32, 512, 64), (256, 1, 64, 32), (1024, 16, 4096, 128),
])
def test_firstfit_matches_ref(R, W, n, C, impl):
    rng = np.random.default_rng(R + W)
    ell = _rand_ell(rng, R, W, n)
    colors = rng.integers(-1, C - 1, size=(n,)).astype(np.int32)
    got_mex, got_ovf = firstfit(jnp.asarray(ell), jnp.asarray(colors), C=C,
                                interpret=True)
    want_mex, want_ovf = ref.firstfit_ref(jnp.asarray(ell),
                                          jnp.asarray(colors), C, impl=impl)
    np.testing.assert_array_equal(got_mex, want_mex)
    np.testing.assert_array_equal(got_ovf, want_ovf)


@pytest.mark.parametrize("impl", REF_IMPLS)
@pytest.mark.parametrize("R,W,n,C,row_start", [
    (256, 8, 1024, 32, 0), (256, 16, 1024, 64, 256), (512, 4, 2048, 32, 1024),
])
def test_detect_recolor_matches_ref(R, W, n, C, row_start, impl):
    rng = np.random.default_rng(R * W)
    ell = _rand_ell(rng, R, W, n)
    colors = rng.integers(0, C // 2, size=(n,)).astype(np.int32)
    pri = rng.permutation(n).astype(np.int32)
    U = rng.random(R) < 0.7
    args = (jnp.asarray(ell), jnp.asarray(colors), jnp.asarray(pri),
            jnp.asarray(U))
    got = detect_recolor(*args, row_start=row_start, C=C, interpret=True)
    want = ref.detect_recolor_ref(args[0], args[1], args[2], row_start,
                                  args[3], C, impl=impl)
    for g, w, name in zip(got, want, ("newc", "recolored", "ovf")):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("impl", REF_IMPLS)
@pytest.mark.parametrize("R,W,n,C,row_start", [
    (128, 4, 512, 32, 0), (128, 8, 512, 64, 128), (256, 2, 1024, 32, 256),
    (128, 6, 128, 32, 0),        # rows == whole table (self-heavy)
])
def test_twohop_matches_ref(R, W, n, C, row_start, impl):
    """Fused two-hop kernel vs jnp oracle, bit-for-bit."""
    rng = np.random.default_rng(R * W + C)
    ell_all = _rand_ell(rng, n, W, n)
    ell_rows = ell_all[row_start:row_start + R]
    colors = rng.integers(0, C // 2, size=(n,)).astype(np.int32)
    pri = rng.permutation(n).astype(np.int32)
    U = rng.random(R) < 0.7
    args = (jnp.asarray(ell_rows), jnp.asarray(ell_all), jnp.asarray(colors),
            jnp.asarray(pri), jnp.asarray(U))
    got = twohop_detect_recolor(*args, row_start=row_start, C=C,
                                interpret=True)
    want = ref.twohop_ref(args[0], args[1], args[2], args[3], row_start,
                          args[4], C, impl=impl)
    for g, w, name in zip(got, want, ("newc", "recolored", "ovf")):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("impl", REF_IMPLS)
@pytest.mark.parametrize("kernel", ["firstfit", "detect_recolor", "twohop"])
def test_kernel_backends_agree_under_saturation(kernel, impl):
    """The env-selected dispatch backend vs the jnp oracle (in both
    forbidden impls) agree bit-for-bit through the ops dispatch layer, on
    inputs dense enough that the forbidden set saturates C on some rows —
    the overflow (ovf) flags must match too, and fire.  Note C=4 is NOT a
    multiple of 32: the packed path's tail-masking is load-bearing here."""
    if DISPATCH_BACKEND == "jnp" and impl == "bitset":
        pytest.skip("backend=jnp with impl=bitset is the identical "
                    "invocation on both sides — nothing to compare")
    rng = np.random.default_rng(
        {"firstfit": 11, "detect_recolor": 22, "twohop": 33}[kernel])
    n, W, R, C = 512, 16, 256, 4
    ell_all = _rand_ell(rng, n, W, n, frac_fill=0.05)
    colors = rng.integers(0, C, size=(n,)).astype(np.int32)
    pri = rng.permutation(n).astype(np.int32)
    U = np.ones(R, bool)
    if kernel == "firstfit":
        a = ops.firstfit(jnp.asarray(ell_all[:R]), jnp.asarray(colors), C=C,
                         backend="jnp", impl=impl)
        b = ops.firstfit(jnp.asarray(ell_all[:R]), jnp.asarray(colors), C=C,
                         backend=DISPATCH_BACKEND)
        ovf = a[1]
    elif kernel == "detect_recolor":
        args = (jnp.asarray(ell_all[:R]), jnp.asarray(colors),
                jnp.asarray(pri), jnp.asarray(U))
        a = ops.detect_recolor(*args, row_start=0, C=C, backend="jnp",
                               impl=impl)
        b = ops.detect_recolor(*args, row_start=0, C=C,
                               backend=DISPATCH_BACKEND)
        ovf = a[2]
    else:
        args = (jnp.asarray(ell_all[:R]), jnp.asarray(ell_all),
                jnp.asarray(colors), jnp.asarray(pri), jnp.asarray(U))
        a = ops.twohop(*args, row_start=0, C=C, backend="jnp", impl=impl)
        b = ops.twohop(*args, row_start=0, C=C, backend=DISPATCH_BACKEND)
        ovf = a[2]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert np.asarray(ovf).any(), "saturation case must trip ovf flags"


@pytest.mark.parametrize("op", ["sum", "mean", "max"])
@pytest.mark.parametrize("R,W,n,d,dtype", [
    (128, 8, 256, 128, np.float32),
    (256, 16, 1024, 256, np.float32),
    (128, 4, 512, 128, jnp.bfloat16),
])
def test_ell_spmm_matches_ref(op, R, W, n, d, dtype):
    rng = np.random.default_rng(R + d)
    ell = _rand_ell(rng, R, W, n)
    feats = rng.standard_normal((n, d)).astype(np.float32)
    feats = jnp.asarray(feats).astype(dtype)
    got = ell_spmm(jnp.asarray(ell), feats, op=op, interpret=True)
    want = ref.ell_spmm_ref(jnp.asarray(ell), feats, op)
    rtol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol,
                               atol=1e-5 if dtype == np.float32 else 1e-1)


def test_ell_spmm_isolated_vertex():
    """All-FILL rows aggregate to zero (no NaN from empty max)."""
    ell = jnp.full((128, 4), -1, jnp.int32)
    feats = jnp.ones((64, 128), jnp.float32)
    for op in ("sum", "mean", "max"):
        out = ell_spmm(ell, feats, op=op, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,Hq,Hkv,Lq,Lk,D", [
    (1, 4, 4, 128, 128, 64),
    (2, 8, 2, 128, 256, 64),    # GQA + decode-style Lk > Lq
    (1, 2, 1, 256, 256, 128),   # MQA
])
def test_flash_attention_matches_ref(causal, B, Hq, Hkv, Lq, Lk, D):
    rng = np.random.default_rng(Lq + D)
    q = jnp.asarray(rng.standard_normal((B, Hq, Lq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, Lk, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, Lk, D)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_ops_dispatch_jnp_cpu():
    """On CPU auto-dispatch uses the jnp path and agrees with pallas_interpret."""
    rng = np.random.default_rng(0)
    ell = jnp.asarray(_rand_ell(rng, 256, 8, 512))
    colors = jnp.asarray(rng.integers(-1, 16, size=(512,)).astype(np.int32))
    a = ops.firstfit(ell, colors, C=32, backend="auto")
    b = ops.firstfit(ell, colors, C=32, backend="pallas_interpret")
    np.testing.assert_array_equal(a[0], b[0])


@pytest.mark.parametrize("kernel", ["firstfit", "detect_recolor", "twohop",
                                    "ell_aggregate", "attention"])
def test_auto_on_tpu_runs_mosaic_only_where_it_lowers(kernel, monkeypatch):
    """On TPU ``auto`` picks Pallas only for kernels outside
    ``MOSAIC_UNSUPPORTED``; a listed kernel runs the jnp reference and the
    dispatch counter says so."""
    from repro.obs import metrics
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    lowers = kernel not in ops.MOSAIC_UNSUPPORTED
    assert ops.default_backend(kernel) == ("pallas" if lowers else "jnp")
    if kernel != "firstfit":
        return
    before = metrics.counter_value("kernels.dispatch", kernel=kernel,
                                   backend="jnp")
    ell = jnp.asarray(_rand_ell(np.random.default_rng(1), 64, 8, 128))
    colors = jnp.zeros((128,), jnp.int32)
    a = ops.firstfit(ell, colors, C=32, backend="auto")
    np.testing.assert_array_equal(a[0], ref.firstfit_ref(ell, colors, 32)[0])
    assert metrics.counter_value("kernels.dispatch", kernel=kernel,
                                 backend="jnp") == before + 1


# --------------------------------------------------------------------------
# VMEM paging boundary (DESIGN.md §8.3): table sizes straddling the old 8 MB
# residency bound stay on the Pallas path — zero kernels.fallback — and match
# the oracle bit-for-bit.  Before paging, the 'above' shape silently fell
# back to jnp.
# --------------------------------------------------------------------------

from repro.obs import metrics as obs_metrics

_W16_8MB_ROWS = ops.VMEM_BUDGET_BYTES // (16 * 4)   # table rows at the bound


@pytest.mark.parametrize("n_all", [_W16_8MB_ROWS - 1024, _W16_8MB_ROWS,
                                   _W16_8MB_ROWS + 1024])
def test_twohop_paged_parity_at_vmem_boundary(n_all):
    W, R, C = 16, 256, 32
    table_mb = n_all * W * 4 / 2**20
    rng = np.random.default_rng(n_all)
    ell_all = _rand_ell(rng, n_all, W, n_all)
    colors = rng.integers(0, C // 2, size=(n_all,)).astype(np.int32)
    pri = rng.permutation(n_all).astype(np.int32)
    U = rng.random(R) < 0.7
    args = (jnp.asarray(ell_all[:R]), jnp.asarray(ell_all),
            jnp.asarray(colors), jnp.asarray(pri), jnp.asarray(U))
    fb0 = obs_metrics.total_matching("kernels.fallback")
    got = ops.twohop(*args, row_start=0, C=C, backend="pallas_interpret")
    assert obs_metrics.total_matching("kernels.fallback") == fb0, \
        f"{table_mb:.2f}MB table is pageable and must not fall back"
    want = ref.twohop_ref(args[0], args[1], args[2], args[3], 0, args[4], C)
    for g, w, name in zip(got, want, ("newc", "recolored", "ovf")):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("page_rows,row_start", [(96, 0), (100, 128),
                                                 (256, 256)])
def test_twohop_ragged_pages_parity(page_rows, row_start):
    """Explicit page sizes that do NOT divide the table (ragged last page,
    -1-padded) and offset row windows, vs the oracle."""
    n, W, R, C = 1000, 8, 128, 32
    rng = np.random.default_rng(page_rows + row_start)
    ell_all = _rand_ell(rng, n, W, n)
    colors = rng.integers(0, C // 2, size=(n,)).astype(np.int32)
    pri = rng.permutation(n).astype(np.int32)
    U = rng.random(R) < 0.7
    args = (jnp.asarray(ell_all[row_start:row_start + R]),
            jnp.asarray(ell_all), jnp.asarray(colors), jnp.asarray(pri),
            jnp.asarray(U))
    got = twohop_detect_recolor(*args, row_start=row_start, C=C,
                                page_rows=page_rows, interpret=True)
    want = ref.twohop_ref(args[0], args[1], args[2], args[3], row_start,
                          args[4], C)
    for g, w, name in zip(got, want, ("newc", "recolored", "ovf")):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("kernel,n,expect_fallback", [
    # firstfit resident set ≈ n*4 + 69 KB: 2.0M is just under the 8 MB
    # budget (Pallas path), 2.2M just over (counted jnp fallback)
    ("firstfit", 2_000_000, False),
    ("firstfit", 2_200_000, True),
    # detect_recolor carries colors AND priorities (2n*4 + ~75 KB)
    ("detect_recolor", 1_000_000, False),
    ("detect_recolor", 1_100_000, True),
])
def test_vector_bound_dispatch_and_parity(kernel, n, expect_fallback):
    """The un-pageable (n,) vectors are the only remaining size cliff: just
    under the budget dispatches Pallas, just over counts a vmem fallback —
    and both sides stay bit-identical to the oracle."""
    R, W, C = 512, 32, 32
    rng = np.random.default_rng(n % 9973)
    ell = _rand_ell(rng, R, W, n)
    colors = rng.integers(0, C // 2, size=(n,)).astype(np.int32)
    fb0 = obs_metrics.total_matching("kernels.fallback")
    if kernel == "firstfit":
        args = (jnp.asarray(ell), jnp.asarray(colors))
        got = ops.firstfit(*args, C=C, backend="pallas_interpret")
        want = ref.firstfit_ref(*args, C)
    else:
        pri = rng.permutation(n).astype(np.int32)
        U = rng.random(R) < 0.7
        args = (jnp.asarray(ell), jnp.asarray(colors), jnp.asarray(pri),
                jnp.asarray(U))
        got = ops.detect_recolor(*args, row_start=0, C=C,
                                 backend="pallas_interpret")
        want = ref.detect_recolor_ref(args[0], args[1], args[2], 0, args[3],
                                      C)
    fb = obs_metrics.total_matching("kernels.fallback") - fb0
    assert fb == (1 if expect_fallback else 0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_ell_aggregate_real_width_no_false_fallback():
    """Narrow features stay on the Pallas path: the honest estimator charges
    the real min(block_feats, d) panel width, where the old hardcoded
    128-lane estimate (n*128*4 = 16 MB here) forced a silent jnp fallback."""
    R, W, n, d = 256, 4, 32768, 16
    assert n * 128 * 4 > ops.VMEM_BUDGET_BYTES          # the old estimate
    assert ops.vmem_bytes("ell_aggregate", R=R, W=W, n=n,
                          d=d) < ops.VMEM_BUDGET_BYTES  # the honest one
    rng = np.random.default_rng(5)
    ell = jnp.asarray(_rand_ell(rng, R, W, n))
    feats = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    fb0 = obs_metrics.total_matching("kernels.fallback")
    got = ops.ell_aggregate(ell, feats, backend="pallas_interpret")
    assert obs_metrics.total_matching("kernels.fallback") == fb0
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ell_spmm_ref(ell, feats,
                                                           "sum")),
                               rtol=1e-5, atol=1e-5)


def test_ell_aggregate_wide_panel_falls_back():
    """A genuinely over-budget double-buffered panel (d > block_feats) is
    caught BEFORE any compile and counted as a vmem fallback."""
    R, W, n, d = 128, 4, 16384, 256
    assert ops.vmem_bytes("ell_aggregate", R=R, W=W, n=n,
                          d=d) > ops.VMEM_BUDGET_BYTES
    rng = np.random.default_rng(6)
    ell = jnp.asarray(_rand_ell(rng, R, W, n))
    feats = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    fb0 = obs_metrics.total_matching("kernels.fallback")
    got = ops.ell_aggregate(ell, feats, backend="pallas")   # safe: falls back
    assert obs_metrics.total_matching("kernels.fallback") == fb0 + 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ell_spmm_ref(ell, feats,
                                                           "sum")),
                               rtol=1e-5, atol=1e-5)


def test_vmem_bytes_accounting_pinned():
    """Pin the estimators term-by-term so a silent accounting change (the
    bug class this PR fixes) breaks a unit test, not a benchmark."""
    from repro.core import bitset

    # firstfit, BV capped by block_rows=256: 2×ELL tile + colors + packed
    # forbidden (C=32 -> 1 word) + 2×(mex+ovf)
    assert ops.vmem_bytes("firstfit", R=1024, W=8, n=4096, C=32) == (
        2 * 256 * 8 * 4 + 4096 * 4 + 256 * 4 + 2 * 256 * (4 + 1))
    # BV capped by R when the tile is short
    assert ops.vmem_bytes("firstfit", R=64, W=8, n=256, C=32) == (
        2 * 64 * 8 * 4 + 256 * 4 + 64 * 4 + 2 * 64 * (4 + 1))
    # detect_recolor adds priorities + U/rowc/rowp + defect + rec outputs
    assert ops.vmem_bytes("detect_recolor", R=512, W=16, n=2048, C=64) == (
        2 * 256 * 16 * 4 + 2 * 2048 * 4 + 2 * 256 * (1 + 4 + 4)
        + 256 * bitset.n_words(64) * 4 + 256 * 4 + 2 * 256 * (4 + 1 + 1))
    # twohop with an explicit page size: 2 pages resident, never the table
    assert ops.vmem_bytes("twohop", R=256, W=8, n=10_000, C=32,
                          block_rows=128, page_rows=512) == (
        2 * 128 * 8 * 4 + 2 * 512 * 8 * 4 + 2 * 10_000 * 4
        + 2 * 128 * (1 + 4 + 4 + 4) + 128 * 8 * 4 + 128 * 4 + 128 * 4
        + 2 * 128 * (4 + 1 + 1))
    # the twohop estimate is page_rows-resident, not n_all-resident: growing
    # the table 100x must not change the estimate
    small = ops.vmem_bytes("twohop", R=256, W=8, n=10_000, C=32,
                           page_rows=512, n_all=10_000)
    big = ops.vmem_bytes("twohop", R=256, W=8, n=10_000, C=32,
                         page_rows=512, n_all=1_000_000)
    assert small == big
    # ell_aggregate: single-buffered panel at the REAL width when d fits
    assert ops.vmem_bytes("ell_aggregate", R=256, W=4, n=1024, d=16) == (
        2 * 128 * 4 * 4 + 1 * 1024 * 16 * 4 + 128 * 16 * 4
        + 2 * 128 * 16 * 4)
    # ...double-buffered at block_feats when the feature axis pages
    assert ops.vmem_bytes("ell_aggregate", R=256, W=4, n=1024, d=256) == (
        2 * 128 * 4 * 4 + 2 * 1024 * 128 * 4 + 128 * 128 * 4
        + 2 * 128 * 128 * 4)
    with pytest.raises(ValueError, match="unknown kernel"):
        ops.vmem_bytes("attention", R=1, W=1, n=1, C=1)


def test_ref_impls_agree_cross():
    """bitset ref == dense ref on identical inputs (the unit-level corner
    of the differential square; the engine level lives in test_bitset.py)."""
    rng = np.random.default_rng(42)
    for C in (32, 64, 96, 256):
        ell = jnp.asarray(_rand_ell(rng, 128, 12, 256))
        colors = jnp.asarray(
            rng.integers(-1, C + 8, size=(256,)).astype(np.int32))
        a = ref.firstfit_ref(ell, colors, C, impl="bitset")
        b = ref.firstfit_ref(ell, colors, C, impl="dense")
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
