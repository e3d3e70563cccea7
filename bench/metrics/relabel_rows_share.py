"""Calls whose host relabel built the ELL straight from the graph's rows, as
a share of the window's traced calls: the ``path`` meta of each call's
``prepare.relabel`` phase, ``rows`` against ``edges``.  None where no phase
carries a ``path`` (a program that records none)."""


def read(run):
    traces = run.samples.get("run_traces") or ()
    paths = [[p.meta.get("path") for p in t.phases
              if p.name == "prepare.relabel"] for t in traces]
    if not any(x is not None for ps in paths for x in ps):
        return None
    return 100 * sum("rows" in ps for ps in paths) / len(paths)
