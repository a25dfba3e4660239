"""Tensor files opened for reading per merge (the program's ``fd_opens``
counter: each open of a tensor file by a store reader while a job's span
is open on the reading thread), averaged over the window's jobs.  Nothing
to read where no job counted an open: a program that keeps every tensor
file open from its first read to the reader's close counts none."""
from program_trace import counter_mean, job_traces


def read(run):
    traces = job_traces(run)
    if not traces or not any("fd_opens" in t["counters"] for t in traces):
        return None
    return counter_mean(run, "fd_opens")
