"""The check of ``correct`` fails each cell's control and every planted
fault, on a tiny run driven end to end on the CPU (device check bypassed
here only)."""
import pytest

from bench import control, graphs, run

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
MAN = run.manifest()
# the control's one repair round leaves conflicts from 2^12 vertices up
SCALE = 12


@pytest.fixture(autouse=True)
def graph_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE_DIR", str(tmp_path))


def tiny(cell_name):
    cell, config, traffic = run.resolve(MAN, cell_name)
    return cell, dict(config, scale=SCALE), traffic


def measure(cell, config, traffic, seconds=1.5):
    return run.measure(cell, config, traffic, 97, seconds, False, MAN,
                       DEVICE, run.peaks_for(DEVICE["kind"]))


def _fails(out):
    bad = {k: c for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert out["correct"] is False and bad, out["checks"]
    return bad


CELLS = [w["name"] for w in MAN["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    cell, config, traffic = tiny(cell_name)
    assert "conflicts" in _fails(measure(cell, config,
                                         control.control(traffic)))


@pytest.mark.parametrize("cell_name", CELLS)
def test_planted_fault_is_not_correct(cell_name, monkeypatch):
    cell, config, traffic = tiny(cell_name)
    with control.altered(monkeypatch):
        assert "conflicts" in _fails(measure(cell, config, traffic))
