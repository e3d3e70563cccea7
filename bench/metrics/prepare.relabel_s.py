"""Host relabel per call (the permutation, the edge list, the dedup and sort
of the relabeled CSR): the ``prepare.relabel`` phase of the program's
``RunTrace``, mean over the window's calls."""
from bench import spans


def read(run):
    return spans.phase_per_call(run, "prepare.relabel")
