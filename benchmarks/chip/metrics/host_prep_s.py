"""Seconds per merge of the compute thread's host work around the device
calls (``mergepipe.exec.host_prep``: the window's bookkeeping, stacking, the
f32 upcast of the base, the cast back to the stored dtype), averaged over
the window's jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.exec.host_prep")
