"""Compile the main path for a described (not attached) TPU v5e chip.

What the TPU compiler refuses costs no chip time here: the static loops at
paper scale, the megabatched service step at the chip smoke's tenant size,
and the distributed rsoc program on a 4-chip mesh all compile for v5e, and
the Pallas dispatch table in ``kernels/ops.py`` is pinned against what Mosaic
actually lowers.  Nothing runs, so nothing here says anything about results
or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import coloring as col
from repro.core import distributed as dist
from repro.core import frontier
from repro.core.context import PassContext
from repro.dynamic import megabatch
from repro.kernels import ops
from repro.kernels.detect_recolor import detect_recolor
from repro.kernels.ell_spmm import ell_spmm
from repro.kernels.firstfit import firstfit
from repro.kernels.flash_attention import flash_attention
from repro.kernels.twohop import twohop_detect_recolor

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding):
    return lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("algorithm", ["rsoc", "cat"])
def test_static_loop_compiles_at_paper_scale(one_chip, algorithm):
    """The loops behind ``api.color`` at RMAT-ER 2^22 width (W=64)."""
    S = _sds(one_chip)
    n, W = 2**22, 64
    ctx = PassContext(n=n, n_pad=n, C=64, n_chunks=16)
    loop = {"rsoc": col._rsoc_loop, "cat": col._cat_loop}[algorithm]
    compiled = loop.lower(S((n, W)), S((0,)), S((0,)), S((n,)), ctx=ctx,
                          max_rounds=1000).compile()
    _fits(compiled)


def test_megabatch_step_compiles_at_service_size(one_chip):
    """``_mega_step`` for two RMAT-ER 2^20 tenants and one batch round of
    2048 inserts and 512 deletes (the chip smoke's service phase)."""
    S = _sds(one_chip)
    k, n, W, dc, ovf = 2, 2**20, 48, 2048, 1024
    ctx = PassContext(n=n, n_pad=n, C=64, n_chunks=16)
    compiled = megabatch._mega_step.lower(
        S((k, n, W)), S((k, ovf)), S((k, ovf)), S((k, n)), S((k, n)),
        S((1, k, n), jnp.bool_), S((1, 1, k, dc, 2)), S((1, 1, k, dc, 2)),
        S((1, 2, k, dc, 2)), ctx=ctx, cap=frontier.frontier_cap(n, 16),
        max_rounds=1000).compile()
    _fits(compiled)


def test_distributed_rsoc_compiles_on_four_chips(topo):
    """The shard_map rsoc program on a 4-device mesh.  Its per-round
    all_gather stays a collective (the TPU compiler may lower it as an
    all-reduce)."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, W = 2**20, 48
    ctx = PassContext(n=n, n_pad=n, C=64, n_chunks=16)
    fn = dist.build_rsoc_distributed(mesh, "data", ctx, 1000)
    compiled = fn.lower(
        jax.ShapeDtypeStruct((n, W), jnp.int32,
                             sharding=NamedSharding(mesh, P("data", None))),
        jax.ShapeDtypeStruct((n,), jnp.int32,
                             sharding=NamedSharding(mesh, P()))).compile()
    _fits(compiled)
    assert re.search(r"all-(gather|reduce)", compiled.as_text())


def _kernel_case(name, S):
    """(raw Pallas call for ``name``, its abstract args, a small-shape
    ``ops`` dispatch with backend='pallas')."""
    i32, b = jnp.int32, jnp.bool_
    ell = jnp.zeros((256, 8), i32)
    vec = jnp.zeros((256,), i32)
    U = jnp.ones((256,), b)
    feats = jnp.zeros((256, 128), jnp.float32)
    q = jnp.zeros((1, 2, 128, 128), jnp.float32)
    return {
        "firstfit": (
            lambda e, c: firstfit(e, c, C=64),
            (S((1024, 64)), S((4096,))),
            lambda: ops.firstfit(ell, vec, C=64, backend="pallas")),
        "detect_recolor": (
            lambda e, c, p, u: detect_recolor(e, c, p, u, row_start=0, C=64),
            (S((1024, 64)), S((4096,)), S((4096,)), S((1024,), b)),
            lambda: ops.detect_recolor(ell, vec, vec, U, 0, C=64,
                                       backend="pallas")),
        "twohop": (
            lambda e, a, c, p, u: twohop_detect_recolor(
                e, a, c, p, u, row_start=0, C=64),
            (S((1024, 16)), S((4096, 16)), S((4096,)), S((4096,)),
             S((1024,), b)),
            lambda: ops.twohop(ell, ell, vec, vec, U, 0, C=64,
                               backend="pallas")),
        "ell_aggregate": (
            lambda e, f: ell_spmm(e, f),
            (S((1024, 16)), S((4096, 128), jnp.float32)),
            lambda: ops.ell_aggregate(ell, feats, backend="pallas")),
        "attention": (
            lambda q_, k_, v_: flash_attention(q_, k_, v_),
            (S((1, 4, 256, 128), jnp.bfloat16),
             S((1, 2, 256, 128), jnp.bfloat16),
             S((1, 2, 256, 128), jnp.bfloat16)),
            lambda: ops.attention(q, q, q, backend="pallas")),
    }[name]


@pytest.mark.parametrize("kernel", ["firstfit", "detect_recolor", "twohop",
                                    "ell_aggregate", "attention"])
def test_mosaic_table_matches_the_compiler(one_chip, kernel):
    """A kernel outside ``ops.MOSAIC_UNSUPPORTED`` compiles for v5e; a
    listed one is refused by the compiler (so the table is not stale) and
    an explicit ``backend='pallas'`` for it raises instead of quietly
    running the reference."""
    raw, args, dispatch = _kernel_case(kernel, _sds(one_chip))
    if kernel not in ops.MOSAIC_UNSUPPORTED:
        compiled = jax.jit(raw).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return
    with pytest.raises(Exception, match="dynamic_slice|2D gather"):
        jax.jit(raw).lower(*args).compile()
    with pytest.raises(NotImplementedError, match="does not lower for TPU"):
        dispatch()
