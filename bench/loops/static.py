"""Closed loop of static colorings through ``repro.api.color``.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``algorithm``   the engine, as ``ColoringSpec.algorithm`` names it;
* ``call_seeds``  the ``ColoringSpec.seed`` (relabel and priorities) of each
                  call, cycled through in an order drawn from the run's seed;
* ``warm_seed``   the seed of the warm-up call, which the window never uses;
* ``spec``        further ``ColoringSpec`` fields, may be empty.

One caller colors the configuration's graph again and again; the next call
starts when the last one returned.  A call's seed sets its work (its repair
rounds), so every run makes the same calls, in another order: calls with
seeds drawn from the run's seed made ``static_s`` swing by a seventh from
seed to seed on RMAT-B.  The window closes at the end of the first whole
cycle of ``call_seeds`` that ends after ``seconds``, so each seed counts
equally however many calls fit.  Every call's answer is kept and judged by
``bench/reference.py`` once the window has closed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import reference


class Loop:
    def __init__(self, graph, traffic: dict, seed: int, seconds: float,
                 traced: bool):
        from repro import api
        self.indptr, self.indices = graph
        self.n = len(self.indptr) - 1
        self.traced = traced
        from bench.graphs import to_program
        self.g = to_program(self.indptr, self.indices)
        self.spec = api.ColoringSpec(algorithm=traffic["algorithm"],
                                     **traffic.get("spec", {}))
        self.order = np.random.default_rng([seed, 1]).permutation(
            traffic["call_seeds"]).tolist()
        self.colors = []
        self._call(traffic["warm_seed"])       # warm-up: compiles or loads

    def _call(self, seed: int):
        from repro import api, obs
        spec = dataclasses.replace(self.spec, seed=seed)
        if not self.traced:
            return api.color(self.g, spec), None
        with obs.trace() as tc:
            res = api.color(self.g, spec)
        return res, tc.traces[-1]

    def window(self, seconds: float) -> dict:
        walls, n_colors, passes, traces = [], [], [], []
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end or len(walls) % len(self.order):
            seed = self.order[len(walls) % len(self.order)]
            with TraceAnnotation("bench.call"):
                c0 = time.perf_counter()
                res, rt = self._call(seed)
                c1 = time.perf_counter()
            walls.append(c1 - c0)
            colors = np.asarray(res.colors)
            n_colors.append(reference.colors_used(colors))
            passes.append(res.gather_passes)
            traces.append(rt)
            self.colors.append(colors)
        return {"wall_s": walls, "n_colors": n_colors,
                "gather_passes": passes,
                "run_traces": [t for t in traces if t is not None],
                "calls": len(walls),
                "overrun_s": time.perf_counter() - end}

    def end_to_end(self, s: dict) -> dict:
        return {"static_s": sum(s["wall_s"]) / s["calls"],
                "n_colors": float(np.mean(s["n_colors"]))}

    def release(self) -> None:
        self.g = None

    def check(self):
        """``(checks, attempted, failed)``: totals of broken guarantees over
        every call of the window, each with its limit."""
        tot = {"conflicts": 0, "uncolored": 0, "over_degree": 0}
        failed = 0
        for colors in self.colors:
            f = reference.coloring_faults(self.indptr, self.indices, colors)
            failed += any(f.values())
            for k in tot:
                tot[k] += f[k]
        checks = {k: {"value": v, "limit": 0} for k, v in tot.items()}
        return checks, len(self.colors), failed
