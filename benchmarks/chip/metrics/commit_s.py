"""Seconds per merge in commit (``mergepipe.commit``: the re-read and re-hash
of the output, publish, catalog lineage, journal removal), averaged over
the window's jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.commit")
