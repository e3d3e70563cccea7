"""Share of the HBM roofline reached by the device while the window's
``api.color`` calls ran.

The bytes are the least any distance-1 coloring of the graph must move,
whatever implements it: each directed adjacency entry and the neighbor's
color read once (4 + 4 bytes), and each vertex's color written once (4
bytes), so ``8 * nnz + 4 * n`` per call.  They never use the program's own
pass count, so no change of passes or kernels can push the share past 100%.
The time is the device's busy time inside the harness's ``bench.call``
spans, from the trace.
"""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_within_s"].get("bench.call", 0.0)
    calls = run.samples.get("calls", 0)
    if busy <= 0 or not calls:
        return None
    need = calls * (8 * run.nnz + 4 * run.n)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy
