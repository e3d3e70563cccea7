"""Device time per call of the solve's repair rounds, which recolor the
vertices of conflicting edges until none is left: ops in the named scope
``repair``, from the trace."""
from bench import spans


def read(run):
    return spans.scope_per_call(run, "repair")
