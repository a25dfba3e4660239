"""Peaks of each chip and the bytes a merge needs.

``PEAKS`` is keyed by JAX's ``device_kind``.  A kind that is not in the
table is an error: no share of a peak is ever taken against a guess.
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9, "source": "cloud.google.com TPU v5e"},
}


def peak(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no peaks recorded for device kind %r; add them to "
                       "PEAKS with their source" % device_kind) from None


def merge_bytes(selection: Mapping[str, Mapping[str, Sequence[int]]],
                tensor_nbytes: Mapping[str, int], block_size: int) -> int:
    """Bytes one merge has to move through the device, in the stored
    dtype: for every merged block (one with at least one selected
    expert), one base block, its ``k_sel`` expert blocks and one output
    block.  Blocks no expert was selected for pass through on the host
    and are not counted.

    ``selection`` is ``{expert: {tensor: [block, ...]}}``, as a merge plan
    records it."""
    k_sel: Dict[Tuple[str, int], int] = {}
    for per_tensor in selection.values():
        for tensor, blocks in per_tensor.items():
            for b in blocks:
                k_sel[(tensor, b)] = k_sel.get((tensor, b), 0) + 1
    total = 0
    for (tensor, b), k in k_sel.items():
        size = min(block_size, tensor_nbytes[tensor] - b * block_size)
        if size <= 0:
            raise ValueError("block %d is outside tensor %r" % (b, tensor))
        total += (2 + k) * size
    return total
