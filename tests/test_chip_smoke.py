"""Rehearse ``chip_smoke.py`` on the CPU at a tiny size.

The script itself has no CPU mode; these tests call its phase functions
directly to exercise control flow and the guarded-counter check, and show
that ``main`` refuses a platform that is not a TPU without printing the
``"ok": true`` line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro import compile_cache
from repro.obs import metrics

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()


def _no_ok_line(text: str) -> bool:
    return '"ok": true' not in text


def test_one_chip_phases_rehearse_on_cpu():
    compile_cache.watch()
    baseline = cs.guarded_counts()
    batched0 = metrics.counter_value("service.mega", outcome="batched")
    cs.one_chip(er_scale=10, b_scale=10, service_scale=12, n_batches=2,
                n_ins=64, n_del=16)
    cs.check_counters(baseline)
    assert metrics.counter_value("service.mega", outcome="batched") \
        == batched0 + 2 * 2


_FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
baseline = cs.guarded_counts()
cs.four_chips(er_scale=10, side=24, n_batches=2, n_ins=64, n_del=16)
cs.check_counters(baseline)
print("FOUR_OK")
"""


def test_four_chip_phases_rehearse_on_host_devices():
    """The mesh phase on four forced host devices, in a child process
    (the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _FOUR,
                        str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "FOUR_OK" in p.stdout
    steps = [json.loads(line.split(": ", 1)[1]) for line in
             p.stdout.splitlines() if '"phase": "sharded_step"' in line]
    assert len(steps) == 2
    assert all(s["proper"] and s["one_shard_identical"]
               and s["on_distinct_devices"] for s in steps)


@pytest.mark.parametrize("name,outcome", [
    ("kernels.fallback", None), ("resilience.rollback", None),
    ("resilience.degrade", None), ("resilience.quarantine", None),
    ("service.mega", "group_fail")])
def test_counter_check_refuses_a_moved_counter(name, outcome):
    assert (name, outcome) in cs.GUARDED
    baseline = cs.guarded_counts()
    labels = ({"outcome": outcome} if outcome is not None
              else {"reason": "chip-smoke-test"})
    metrics.counter(name, **labels).inc()
    try:
        with pytest.raises(cs.SmokeFailure, match="guarded counters moved"):
            cs.check_counters(baseline)
    finally:
        if outcome is None:
            metrics.remove(name, **labels)
        else:
            metrics.counter(name, **labels).inc(-1)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_platform_that_is_not_tpu(argv, capsys):
    assert cs.main(argv) == 2
    out, err = capsys.readouterr()
    assert "found no TPU" in err
    assert _no_ok_line(out)


def test_script_alone_fails(tmp_path):
    """Without the rest of the repository the script cannot start."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert _no_ok_line(p.stdout)
