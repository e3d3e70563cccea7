"""``BENCHMARK.json`` holds to the benchmark's contract, and every name in
it resolves to a file under ``bench/``."""
import json
import os
import re

import pytest

from bench import run

MAN = run.manifest()
ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CELLS = [w["name"] for w in MAN["workloads"]]


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(MAN) == KEYS
    assert 1 <= len(MAN["command"]) <= 32 and all(map(_text, MAN["command"]))
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in MAN["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_run_seconds_fits_a_full_check():
    s = MAN["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names)), group
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in MAN["workloads"]:
        assert NAME.match(w["traffic"]) and _text(w["why"])
        assert w["chips"] in (1, 4)
    for c in MAN["configs"]:
        assert _text(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MAN["per_layer"]:
        assert _text(m["layer"])


def test_entry_keys():
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}
    for group, keys in allowed.items():
        for e in MAN[group]:
            assert set(e) <= keys, (group, e["name"])
            assert set(e) >= keys - {"workloads"}, (group, e["name"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    w, config, traffic = run.resolve(MAN, cell)
    conf = {c["name"]: c for c in MAN["configs"]}[w["config"]]
    assert conf["file"].startswith("bench/configs/")
    assert config["name"] == conf["name"]
    assert set(conf["reduced"]) <= set(config["reduced"])
    loop = os.path.join(run.HERE, "loops", traffic["loop"] + ".py")
    assert os.path.isfile(loop)
    for m in run.declared(MAN["per_layer"], cell):
        assert os.path.isfile(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")
    setup = {m["name"]: m for m in MAN["end_to_end"]}["setup_s"]
    assert setup["bound"] <= 0.25 and "workloads" not in setup
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = [m["name"] for m in run.declared(MAN["end_to_end"], cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.declared(MAN["per_layer"], cell)


def test_per_layer_moves_an_end_to_end_metric_of_each_of_its_cells():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    layers = {}
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_peaks_cover_the_chip():
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5 lite" in peaks
    for kind, p in peaks.items():
        assert p["hbm_bytes_per_s"] > 0 and p["source"]
        assert run.peaks_for(kind) == p
