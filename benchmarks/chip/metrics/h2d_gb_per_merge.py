"""Bytes handed to the device kernels per merge (the program's ``h2d_bytes``
counter: the operands of every ``kernels.ops.merge_blocks`` call), in GB
(1e9 bytes), averaged over the window's jobs."""
from program_trace import counter_mean


def read(run):
    n = counter_mean(run, "h2d_bytes")
    return None if n is None else n / 1e9
