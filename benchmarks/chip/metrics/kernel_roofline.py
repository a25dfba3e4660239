"""Share of the HBM roofline reached by all device work of the traced batch:
the bytes its merges need in the stored dtype (roofline.merge_bytes: per
merged block one base block, its k_sel expert blocks and one output block)
over the chip's peak HBM bandwidth, divided by the device's busy time.
Nothing to read without a trace or where the device was never busy."""


def read(run):
    tr, needed = run["trace"], run["traced_merge_bytes"]
    if not tr or not needed or tr["busy_s"] <= 0:
        return None
    return 100.0 * needed / run["peak"]["hbm_bytes_per_s"] / tr["busy_s"]
