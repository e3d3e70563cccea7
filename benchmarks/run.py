"""Benchmark orchestrator: one section per paper table/figure.

  table1           paper Table 1 + Figs 1-2 (time, speedup, passes)
  conflicts        paper Figs 3-4 + 5-6 (conflicts, rounds vs parallelism)
  colors           color-quality vs serial greedy
  forbidden        forbidden-table micro: packed bitset vs dense (§10)
  distance2        paper §6 outlook (G^2 density; native vs materialized)
  colored_scatter  the technique applied to GNN aggregation
  incremental      dynamic-graph incremental recoloring vs from-scratch
  service          multi-tenant ColoringService: megabatched vs loop step
  sharded          sharded incremental: step latency + halo bytes vs scale
  lm_step          measured smoke-scale LM train-step wall time

Usage: PYTHONPATH=src python -m benchmarks.run [--scale=NAME] [--json]
                                               [section ...]

``--json`` additionally writes BENCH_<section>.json per section (schema:
{"section", "scale", "rows": [{... every CSV column, plus the normalized
keys the section's SECTION_KEYS schema declares}]}) so the perf trajectory
is machine-trackable across PRs; CI uploads these as artifacts (tiny AND
small scale).  Normalized keys a section does not declare are omitted, not
null-backfilled — non-coloring sections (lm_step, colored_scatter) carry
no graph/algo/ms/spec keys at all.  ``spec``/``spec_key`` echo the
resolved ``repro.api.ColoringSpec`` of the row's coloring call (DESIGN.md
§11), so trajectories key on the exact task, not just the column values.

Unknown section names abort *before* anything runs — a typo must not
silently skip a benchmark after minutes of earlier sections.
"""
from __future__ import annotations

import json
import sys
import time


SECTIONS = ["table1", "conflicts", "colors", "forbidden", "distance2",
            "colored_scatter", "incremental", "service", "sharded",
            "lm_step"]
SCALES = ["tiny", "small", "medium"]
# (SECTION_KEYS below must stay exhaustive over SECTIONS — checked at
# import so a new section cannot silently ship schema-less)

# Normalized keys are declared PER SECTION: a BENCH_<section>.json row
# carries a normalized key only when the section's schema declares it (plus
# every raw CSV column it emitted).  Sections that never invoke a coloring
# engine (lm_step, colored_scatter) therefore no longer emit garbage rows
# full of null graph/algo/ms/spec keys — and lm_step's model-parameter
# footprint is its own ``params_mb`` column, never misattributed to the
# coloring sections' forbidden-working-set ``ws_mb``.
# spec/spec_key are the resolved repro.api.ColoringSpec of the row's
# coloring call; n_rounds/retries come from the row's ColoringResult and
# kernel_fallbacks is the kernels.fallback counter delta attributed to the
# row (DESIGN.md §12) — tracked for every section, kernels dispatch
# everywhere.
_COLORING_KEYS = ("graph", "algo", "ms", "ws_mb", "colors", "gather_passes",
                  "spec_key", "spec", "n_rounds", "retries",
                  "kernel_fallbacks")
SECTION_KEYS = {
    "table1": _COLORING_KEYS,
    "conflicts": ("graph", "algo", "ws_mb", "colors", "spec_key", "spec",
                  "n_rounds", "retries", "kernel_fallbacks"),
    "colors": ("graph", "algo", "ws_mb", "colors", "spec_key", "spec",
               "n_rounds", "retries", "kernel_fallbacks"),
    "forbidden": ("graph", "algo", "ms", "ws_mb", "kernel_fallbacks"),
    "distance2": _COLORING_KEYS + ("bytes_moved", "kernel"),
    "colored_scatter": ("ms", "ws_mb", "kernel_fallbacks"),
    "incremental": ("graph", "ws_mb", "spec_key", "spec", "n_rounds",
                    "retries", "kernel_fallbacks"),
    "service": ("ms", "kernel_fallbacks"),
    # sharded rows carry no spec echo: each row aggregates a whole update
    # stream over one mesh
    "sharded": ("graph", "colors", "kernel_fallbacks"),
    "lm_step": ("params_mb", "kernel_fallbacks"),
}
assert set(SECTION_KEYS) == set(SECTIONS), \
    (sorted(set(SECTION_KEYS) ^ set(SECTIONS)))


def lm_step(scale: str = "small") -> None:
    """Wall-time of the real jitted train step at smoke scale (sanity that
    the training path is healthy; full-scale numbers live in §Roofline).
    ``scale='tiny'`` drops to a single architecture so bench-smoke stays
    fast; the smoke model configs themselves are already minimal."""
    import functools
    import jax
    import jax.numpy as jnp
    from benchmarks.common import Csv, time_fn
    from repro import configs
    from repro.data.pipeline import TokenStream
    from repro.models import transformer as TF
    from repro.training.optimizer import OptimizerConfig, init_opt_state
    from repro.training.train_loop import make_train_step

    archs = ("qwen3-1.7b",) if scale == "tiny" else \
        ("qwen3-1.7b", "phi3.5-moe-42b-a6.6b")
    # params_mb, NOT ws_mb: this is the model-parameter footprint, a
    # different quantity from the coloring sections' forbidden-table
    # working set — the shared name used to misattribute it in the JSON
    csv = Csv(["arch", "ms_per_step", "tokens_per_s", "loss0", "loss_end",
               "params_mb"])
    for arch in archs:
        cfg = configs.get(arch).make_smoke()
        params = TF.init_params(jax.random.PRNGKey(0), cfg)
        params_mb = sum(x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(params)) / 2**20
        stream = TokenStream(batch=8, seq_len=64, vocab=cfg.vocab)
        step = make_train_step(lambda p, b: TF.train_step_loss(p, cfg, b),
                               OptimizerConfig(warmup_steps=2,
                                               total_steps=20), 1,
                               donate=False)
        opt = init_opt_state(params)
        batch = jax.tree.map(jnp.asarray, next(stream))
        params, opt, m0 = step(params, opt, batch)      # compile + step
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            batch = jax.tree.map(jnp.asarray, next(stream))
            params, opt, m = step(params, opt, batch)
        jax.block_until_ready(params)
        dt = (time.perf_counter() - t0) / n
        csv.row(arch, dt * 1e3, 8 * 64 / dt, float(m0["loss"]),
                float(m["loss"]), params_mb)


def _section(name: str):
    if name == "table1":
        from benchmarks import bench_table1 as b
    elif name == "forbidden":
        from benchmarks import bench_forbidden as b
    elif name == "conflicts":
        from benchmarks import bench_conflicts as b
    elif name == "colors":
        from benchmarks import bench_colors as b
    elif name == "distance2":
        from benchmarks import bench_distance2 as b
    elif name == "colored_scatter":
        from benchmarks import bench_colored_scatter as b
    elif name == "incremental":
        from benchmarks import bench_incremental as b
    elif name == "service":
        from benchmarks import bench_service as b
    elif name == "sharded":
        from benchmarks import bench_sharded as b
    elif name == "lm_step":
        return lm_step
    else:
        raise AssertionError(name)
    return b.main


def _write_json(name: str, scale: str, rows: list, elapsed_s: float) -> str:
    keys = SECTION_KEYS[name]
    # declared-but-absent keys surface as explicit nulls (within-section row
    # variance, e.g. distance2's engine vs kernel rows); undeclared keys are
    # OMITTED, never null-backfilled — consumers key on presence
    out = {"section": name, "scale": scale, "elapsed_s": elapsed_s,
           "rows": [{**{k: r.get(k) for k in keys}, **r} for r in rows]}
    path = f"BENCH_{name}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return path


def main(argv=None) -> None:
    args = list(argv if argv is not None else sys.argv[1:])
    scale = "small"
    emit_json = False
    names = []
    for a in args:
        if a.startswith("--scale="):
            scale = a.split("=", 1)[1]
        elif a == "--scale":
            raise SystemExit("use --scale=NAME")
        elif a == "--json":
            emit_json = True
        else:
            names.append(a)
    names = names or SECTIONS
    # validate everything up front: fail loudly before running any section
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        raise SystemExit(f"unknown section(s) {unknown}; known: {SECTIONS}")
    if scale not in SCALES:
        raise SystemExit(f"unknown scale {scale!r}; known: {SCALES}")
    from repro import compile_cache
    compile_cache.enable()
    for name in names:
        print(f"\n===== bench: {name} (scale={scale}) =====", flush=True)
        t0 = time.perf_counter()
        import contextlib
        tc_ctx = contextlib.nullcontext()
        if emit_json:
            from benchmarks import common
            from repro import obs
            common.start_json_capture()
            tc_ctx = obs.trace()       # collect a RunTrace per api.color call
        try:
            with tc_ctx as tc:
                _section(name)(scale=scale)
        finally:
            elapsed = time.perf_counter() - t0
            if emit_json:
                from benchmarks import common
                from repro.obs import export
                path = _write_json(name, scale, common.end_json_capture(),
                                   elapsed)
                print(f"# wrote {path}", flush=True)
                n = export.write_jsonl(tc.traces, f"TRACE_{name}.jsonl")
                print(f"# wrote TRACE_{name}.jsonl ({n} traces)", flush=True)
        print(f"===== {name} done in {elapsed:.1f}s =====", flush=True)


if __name__ == "__main__":
    main()
