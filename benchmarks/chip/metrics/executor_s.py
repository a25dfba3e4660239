"""Executor seconds per merge (core.executor run stats ``seconds``: prefetch,
compute windows, write-behind, commit), averaged over the window's jobs."""


def read(run):
    secs = [j["seconds"] for b in run["batches"] for j in b["jobs"]]
    return sum(secs) / len(secs) if secs else None
