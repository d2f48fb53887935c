"""The sliding-window kernels' share of their roofline: the least time the
chip could take for the band's (query, key) pairs of the traced steps
(``flops_laguna.py:window_flash_train``: 2 matmuls forward and 5 backward
over min(i + 1, window) keys a query, each operand moved once) over the
time the kernels took.  The kernels run whole 512-blocks (two a query
block where the band is 512 wide), S and dP twice in the backward, and
under ``remat`` the forward twice: the share shows all of that."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("window_flash_roofline", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "window_flash")
    if seconds is None or sources.get("peak") is None:
        return None
    return 100.0 * kernel_share.least_seconds(sources, "window_flash") \
        / seconds
