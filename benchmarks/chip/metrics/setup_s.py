"""Set-up time: from process start to the window's start (JAX start, fleet
generation, register, ANALYZE, the warm-up batch with its compiles)."""


def read(run):
    return run["setup_s"]
