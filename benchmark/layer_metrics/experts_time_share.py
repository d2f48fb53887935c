"""Share of device 0's busy time inside the traced window that the routed
experts' grouped matmuls (``moe_gmm``, ``moe_tgmm``:
``ops/pallas_moe.py``) take."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("experts_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "experts")
    if seconds is None:
        return None
    return 100.0 * seconds / sources["trace"]["busy_s"]
