"""Every cell's traffic, driven end to end on the CPU at a tiny size.

The device check is bypassed here only: ``run.measure`` is called with the
device block a chip would give.  A traced run on the CPU has no TPU plane,
so the trace load is wrapped to add one device op per harness span.  The
command itself still refuses to measure off the chip.
"""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from bench import graphs, run, trace_reduce

TINY_SCALE = 10
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
MAN = run.manifest()


def tiny(cell_name):
    cell, config, traffic = run.resolve(MAN, cell_name)
    return cell, dict(config, scale=TINY_SCALE), traffic


@pytest.fixture(autouse=True)
def scratch_dirs(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE_DIR", str(tmp_path / "graphs"))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    real_load = trace_reduce.load

    def load_with_device(path):
        t = real_load(path)
        ops = [(n, s, e) for n, s, e in t.spans if n == "bench.call"]
        return trace_reduce.Trace(ops={"/device:TPU:0": ops}, spans=t.spans)

    monkeypatch.setattr(trace_reduce, "load", load_with_device)


def _finite(out, names):
    assert sorted(out["metrics"]) == sorted(names)
    for name, m in out["metrics"].items():
        assert math.isfinite(m["value"]), name
        assert m["unit"]


@pytest.mark.parametrize("cell_name", [w["name"] for w in MAN["workloads"]])
def test_cell_rehearsal(cell_name):
    cell, config, traffic = tiny(cell_name)
    peaks = run.peaks_for(DEVICE["kind"])
    for trace, kind in ((0, "end_to_end"), (1, "per_layer")):
        out = run.measure(cell, config, traffic, 2**33 + 7, 1.5, bool(trace),
                          MAN, DEVICE, peaks)
        assert out["correct"] is True, out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert out["attempted"] % len(traffic["call_seeds"]) == 0
        _finite(out, [m["name"] for m in run.declared(MAN[kind], cell_name)])
        assert list(out)[-1] == "checks"
        assert all(c["value"] == 0 and c["limit"] == 0
                   for c in out["checks"].values())
        assert out["device"]["memory_peak_bytes"] >= 0
        if trace:
            assert out["device"]["busy_s"] > 0
            assert out["device"]["window_s"] > 0
            assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        json.dumps(out)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat_er.static",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_off_the_chip():
    p = _run_cli(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".compile_cache",
                                                  "__pycache__"))
    p = _run_cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Refused):
        run.peaks_for("TPU v99")

