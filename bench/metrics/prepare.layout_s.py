"""Host layout per call (the ELL and its COO overflow, the priorities, the
color cap): the ``prepare.layout`` phase of the program's ``RunTrace``,
mean over the window's calls."""
from bench import spans


def read(run):
    return spans.phase_per_call(run, "prepare.layout")
