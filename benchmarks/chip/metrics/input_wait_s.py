"""Seconds per merge the executor's compute waited for staged windows
(``mergepipe.exec.wait_input``: prefetch, store reads, verify-on-read and
delta pull behind it), averaged over the window's jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.exec.wait_input")
