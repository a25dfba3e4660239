"""Seconds per merge in the synchronous device calls
(``mergepipe.exec.device``: host-to-device copies, the kernels,
device-to-host copies), averaged over the window's jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.exec.device")
