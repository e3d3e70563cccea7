#!/usr/bin/env python3
"""The chip benchmark of the coloring system.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip it is started on: set-up
(graph from ``bench/.cache/`` or sampled, the program's objects, warm-up of
every shape the window uses), then ``--seconds`` of the cell's traffic, then
the check of every answer against ``bench/reference.py``.  The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``,
and last ``checks``: each number compared, beside its limit.  The same
numbers close standard error.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the window and reports its per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

Everything is found by name: the cell's configuration file, its traffic
mix ``bench/traffic/<mix>.json``, whose ``loop`` names the general loop
``bench/loops/<loop>.py``, and the readers of its per-layer metrics.  The
run refuses to measure (exit 3, no result) unless JAX finds a TPU with as
many chips as the cell asks for, and the chip's kind is in
``bench/peaks.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
JAX_CACHE = os.path.join(HERE, ".compile_cache")
TRACE_DIR = os.path.join(HERE, ".cache", "trace")


class Refused(Exception):
    """The run cannot measure here; nothing is printed on stdout."""


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def resolve(man: dict, workload: str):
    """``(cell, config, traffic)`` of a cell, each as a dict."""
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; "
                      f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    config = _json(conf["file"])
    traffic = _json(os.path.join("bench", "traffic",
                                 cell["traffic"] + ".json"))
    return cell, config, traffic


def declared(metrics: list, cell: str) -> list:
    """The metrics of ``metrics`` that ``cell`` reports."""
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_file(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(jax, chips: int) -> dict:
    """The device block of the result; raises ``Refused`` off the chip."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r}); "
                      f"this benchmark measures only on the chip")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX has "
                      f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in "
                      f"bench/peaks.json")
    return table[kind]


def _peak_bytes(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def measure(cell: dict, config: dict, traffic: dict, seed: int,
            seconds: float, trace: bool, man: dict, device: dict,
            peaks: dict) -> dict:
    """One run of a cell; returns the result object."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import graphs, trace_reduce
    from repro import compile_cache

    compile_cache.watch()
    t_init = time.perf_counter()
    graph = graphs.load(config)
    t_graph = time.perf_counter()
    loop_mod = importlib.import_module("bench.loops." + traffic["loop"])
    loop = loop_mod.Loop(graph, traffic, seed, seconds, trace)
    setup_s = time.perf_counter() - T_START
    c_setup = compile_cache.counts()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    c0 = compile_cache.counts()
    with TraceAnnotation("bench.window"):
        samples = loop.window(seconds)
    c1 = compile_cache.counts()
    if trace:
        jax.profiler.stop_trace()
    e2e = dict(loop.end_to_end(samples), setup_s=setup_s)
    device = dict(device, memory_peak_bytes=_peak_bytes(jax,
                                                        device["count"]))
    loop.release()
    gc.collect()
    checks, attempted, failed = loop.check()

    reduced = None
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR))
        reduced = trace_reduce.reduce(tr, *trace_reduce.window_of(tr))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    metrics = {}
    if not trace:
        for m in declared(man["end_to_end"], cell["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        indptr, _ = graph
        run = types.SimpleNamespace(
            samples=samples, trace=reduced, peaks=peaks, cell=cell,
            config=config, n=len(indptr) - 1, nnz=int(indptr[-1]))
        for m in declared(man["per_layer"], cell["name"]):
            reader = load_file(os.path.join(HERE, "metrics",
                                            m["name"] + ".py"))
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    info = {"setup": {"start_s": t_init - T_START, "graph_s": t_graph - t_init,
                      "loop_s": T_START + setup_s - t_graph,
                      "compile_s": c_setup["compile_s"],
                      "cache_hits": c_setup["hits"],
                      "cache_misses": c_setup["misses"]},
            "compiles_in_window": c1["misses"] - c0["misses"],
            "compile_s_in_window": c1["compile_s"] - c0["compile_s"],
            "overrun_s": samples["overrun_s"],
            "calls": samples["calls"], "call_s": samples["wall_s"],
            "call_passes": samples["gather_passes"], "e2e": e2e}
    print("bench: " + json.dumps(info, default=float), file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        man = manifest()
        cell, config, traffic = resolve(man, args.workload)
        # the persistent compilation cache lives at a fixed path inside the
        # checkout, and the program takes the one given here
        os.makedirs(JAX_CACHE, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE
        # the TPU runtime's logs stay inside the checkout too
        os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache",
                                                          "tpu_logs"))
        if HERE in sys.path:
            sys.path.remove(HERE)
        sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
        import jax
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        device = require_chip(jax, cell["chips"])
        peaks = peaks_for(device["kind"])
        from repro import compile_cache
        compile_cache.watch()
    except (Refused, OSError, ImportError, KeyError) as exc:
        print(f"bench: refused: {exc}", file=sys.stderr)
        return 3
    out = measure(cell, config, traffic, args.seed % (1 << 63),
                  args.seconds, bool(args.trace), man, device, peaks)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
