"""Bytes read and written in the window, in every IOStats category, per
committed merge, in GB (1e9 bytes)."""


def read(run):
    jobs = sum(len(b["jobs"]) for b in run["batches"])
    return sum(b["io_bytes"] for b in run["batches"]) / jobs / 1e9 if jobs else None
