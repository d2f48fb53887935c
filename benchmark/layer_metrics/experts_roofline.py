"""The grouped matmuls' share of their roofline: the least time the chip
could take for the routed experts of the traced steps
(``flops_laguna.py:experts_train``: 3 matmuls forward and 6 backward over
the rows expected to land on the experts held here, their matrices read
forward and backward and their gradients written once) over the time
``moe_gmm`` and ``moe_tgmm`` took.  Rows padded to whole tiles, an
uneven load and, under ``remat``, the forward run twice all lower it."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("experts_roofline", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "experts")
    if seconds is None or sources.get("peak") is None:
        return None
    return 100.0 * kernel_share.least_seconds(sources, "experts") / seconds
