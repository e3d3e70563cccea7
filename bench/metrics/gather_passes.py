"""Full-width neighbor-gather passes per call (``ColoringResult``), mean
over the window's calls."""


def read(run):
    passes = run.samples.get("gather_passes")
    if not passes:
        return None
    return sum(passes) / len(passes)
