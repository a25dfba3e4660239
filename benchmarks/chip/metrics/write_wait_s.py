"""Seconds per merge the executor's compute spent handing its output to the
write-behind queue (``mergepipe.exec.wait_write``: the hand-off, blocked
while the queue is full, and the last flush), averaged over the window's
jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.exec.wait_write")
