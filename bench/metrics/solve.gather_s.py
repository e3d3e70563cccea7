"""Device time per call of the solve's neighbor-color gather and defect
test: ops in the named scope ``gather``, from the trace."""
from bench import spans


def read(run):
    return spans.scope_per_call(run, "gather")
