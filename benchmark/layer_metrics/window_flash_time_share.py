"""Share of device 0's busy time inside the traced window that the
sliding-window attention kernels (``window_attn_fwd``, ``_bwd_dq``,
``_bwd_dkv``: the flash kernels walking the band's blocks alone) take."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("window_flash_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "window_flash")
    if seconds is None:
        return None
    return 100.0 * seconds / sources["trace"]["busy_s"]
