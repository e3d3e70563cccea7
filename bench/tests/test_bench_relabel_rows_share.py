"""The reader of ``relabel_rows_share`` on hand-built traces."""
import os
import types

import pytest

from bench import run
from repro.obs.trace import PhaseEvent

READER = run.load_file(os.path.join(run.HERE, "metrics",
                                    "relabel_rows_share.py"))


def _run(paths):
    """A window of calls, one per entry: the ``path`` meta of the call's
    ``prepare.relabel`` phase, or None for a phase without one."""
    traces = [types.SimpleNamespace(phases=(
        PhaseEvent("prepare", 1.0),
        PhaseEvent("prepare.relabel", 0.5,
                   {} if p is None else {"path": p}),
        PhaseEvent("solve", 2.0, {"attempt": 0})))
        for p in paths]
    return types.SimpleNamespace(samples={"run_traces": traces,
                                          "calls": len(traces)})


@pytest.mark.parametrize("paths,want", [
    (["rows"] * 3, 100.0),
    (["edges"] * 3, 0.0),
    (["rows", "edges", "rows", "rows"], 75.0),
    (["rows", None], 50.0),
])
def test_share_of_calls_on_the_row_path(paths, want):
    assert READER.read(_run(paths)) == pytest.approx(want)


@pytest.mark.parametrize("paths", [[None, None], []])
def test_none_where_no_phase_carries_a_path(paths):
    assert READER.read(_run(paths)) is None
    assert READER.read(types.SimpleNamespace(samples={})) is None
