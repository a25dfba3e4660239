"""Planning seconds per merge: the ``mergepipe.plan`` span (the level's
``plan_batch``, in equal parts per job of the level), averaged over the
window's jobs."""
from program_trace import span_seconds


def read(run):
    return span_seconds(run, "mergepipe.plan")
