"""The flash kernels' share of their roofline: the least time the chip
could take for the causal attention the traced steps need (``flops.py:
flash_train``: 2 matmuls forward and 5 backward at the exact causal half,
each operand moved once) over the time the kernels took.  At GPT-2's
shapes (T = 1,024, head 64) the FLOPs bound it, not the bytes.  The
kernels here run more than that (whole blocks, and S and dP twice in the
backward), which is what the share is there to show."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("flash_roofline", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "flash")
    if seconds is None or sources.get("peak") is None:
        return None
    return 100.0 * kernel_share.least_seconds(sources, "flash") / seconds
