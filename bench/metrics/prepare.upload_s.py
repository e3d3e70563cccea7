"""Host-to-device copy per call (ELL, overflow, priorities): the
``prepare.upload`` phase of the program's ``RunTrace``, which a traced call
ends on the copies; mean over the window's calls."""
from bench import spans


def read(run):
    return spans.phase_per_call(run, "prepare.upload")
