#!/usr/bin/env python3
"""The controls and planted faults that the check of ``correct`` must fail.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--seconds s]

runs the cell's control on the chip at the cell's own size, once per seed,
in one process, and prints one JSON line per seed with every number the
check compared.  The benchmark's own runs never run it.

The control: the configurations state no precision, so it breaks a
guarantee they state.  It is the program's own early stop,
``ColoringSpec.max_rounds=1``, which ends the speculative repair after one
round and leaves conflicts.

The fault (``bench/tests/test_bench_control.py`` plants it under a tiny run
on the CPU and sees ``correct`` come out false): ``altered``, one vertex's
color changed to a neighbor's where the answer is produced.  A static cell
keeps no state from step to step, leaves no batch to halve, and on one chip
has no exchange between chips.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def control(traffic: dict) -> dict:
    """The traffic to run with the cell's control switched on."""
    return dict(traffic, spec=dict(traffic.get("spec", {}), max_rounds=1))


def _alter(colors_dev, ell):
    """Give relabeled vertex r the color of its first ELL neighbor."""
    import jax.numpy as jnp
    nb = np.asarray(ell)
    r = int(np.flatnonzero((nb >= 0).any(axis=1))[0])
    j = int(nb[r][nb[r] >= 0][0])
    return jnp.asarray(colors_dev).at[r].set(jnp.asarray(colors_dev)[j])


@contextlib.contextmanager
def altered(monkeypatch):
    """Plant the ``altered`` fault under a static run (``monkeypatch`` is
    pytest's, or any object with its ``setattr``)."""
    from repro.core import coloring as col
    real_prepare, real_unpermute = col.prepare, col._unpermute
    ell = []

    def prepare_seen(*a, **kw):
        prob = real_prepare(*a, **kw)
        ell[:] = [prob.ell]
        return prob

    def unpermute_altered(colors_new, perm, n):
        return real_unpermute(_alter(colors_new, ell[0]), perm, n)
    monkeypatch.setattr(col, "prepare", prepare_seen)
    monkeypatch.setattr(col, "_unpermute", unpermute_altered)
    yield


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                         "src")]
    from bench import run
    man = run.manifest()
    cell, config, traffic = run.resolve(man, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.JAX_CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", run.JAX_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = run.require_chip(jax, cell["chips"])
    peaks = run.peaks_for(device["kind"])
    seconds = args.seconds or man["run_seconds"]
    tr = control(traffic)
    for seed in args.seeds:
        out = run.measure(cell, config, tr, seed, seconds, False, man,
                          device, peaks)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
