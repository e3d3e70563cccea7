"""Device solve per call: the ``solve`` phases of the program's ``RunTrace``
(every color-cap attempt), mean over the window's calls."""


def read(run):
    traces = run.samples.get("run_traces")
    if not traces:
        return None
    return sum(t.phase_wall_s("solve") for t in traces) / len(traces)
