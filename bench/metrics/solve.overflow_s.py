"""Device time per call of the solve's COO overflow (the pass-start
snapshot of the hubs' forbidden sets and their defect test): ops in the
named scope ``overflow``, from the trace."""
from bench import spans


def read(run):
    return spans.scope_per_call(run, "overflow")
