"""Share of device 0's busy time inside the traced window that the Pallas
flash-attention calls (forward, dQ, dK/dV) take."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("flash_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "flash")
    if seconds is None:
        return None
    return 100.0 * seconds / sources["trace"]["busy_s"]
