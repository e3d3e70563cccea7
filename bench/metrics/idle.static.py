"""Device idle share of the traced window, in a static cell: 100 * (1 -
busy / window)."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
