"""Seconds per merge the write-behind thread spent beginning and finishing
output tensors (``mergepipe.bg.tensor_sync``: each tensor file's create
and close, and the journal's tensor and finish records, which fsync),
averaged over the window's jobs.  Nothing to read where no job has the
span."""
from program_trace import job_traces, span_seconds

SPAN = "mergepipe.bg.tensor_sync"


def read(run):
    traces = job_traces(run)
    if not traces or not any(SPAN in t["spans"] for t in traces):
        return None
    return span_seconds(run, SPAN)
