"""Host prepare (relabel, CSR to ELL) per call: the ``prepare`` phase of the
program's ``RunTrace``, mean over the window's calls."""


def read(run):
    traces = run.samples.get("run_traces")
    if not traces:
        return None
    return sum(t.phase_wall_s("prepare") for t in traces) / len(traces)
