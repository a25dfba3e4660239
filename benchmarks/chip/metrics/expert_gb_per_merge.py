"""Expert bytes read in the window (IOStats.total_expert_bytes, every tier;
physical, so a read shared by several jobs counts once) per committed
merge, in GB (1e9 bytes)."""


def read(run):
    jobs = sum(len(b["jobs"]) for b in run["batches"])
    return sum(b["expert_bytes"] for b in run["batches"]) / jobs / 1e9 if jobs else None
