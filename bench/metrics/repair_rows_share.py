"""ELL rows the repair passes of a call gathered, as a share of what as
many full-width passes gather (repair passes x ``n_pad``), mean over the
window's calls: the ``rows`` of each round event of the program's
``RunTrace`` over its ``n_pad``.  None where the trace carries no such
count."""


def read(run):
    shares = []
    for t in run.samples.get("run_traces") or ():
        n_pad = getattr(t, "n_pad", -1)
        rows = [getattr(e, "rows", -1) for e in t.rounds]
        if n_pad <= 0 or any(r < 0 for r in rows):
            return None
        if rows:
            shares.append(100 * sum(rows) / (len(rows) * n_pad))
    if not shares:
        return None
    return sum(shares) / len(shares)
