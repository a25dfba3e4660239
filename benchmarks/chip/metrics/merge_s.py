"""Wall time per merge: the window's batches, each from its first submit to
its last commit, summed and divided by the jobs they committed."""


def read(run):
    jobs = sum(len(b["jobs"]) for b in run["batches"])
    return sum(b["wall_s"] for b in run["batches"]) / jobs if jobs else None
