"""Device time per call of the solve's forbidden sets, mex and color
write-back: ops in the named scope ``mex``, from the trace."""
from bench import spans


def read(run):
    return spans.scope_per_call(run, "mex")
