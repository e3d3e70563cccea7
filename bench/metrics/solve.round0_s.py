"""Device time per call of the solve's first round, which colors every
vertex once: ops in the named scope ``round0``, from the trace."""
from bench import spans


def read(run):
    return spans.scope_per_call(run, "round0")
