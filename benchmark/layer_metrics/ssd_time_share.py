"""Share of device 0's busy time inside the traced window that the
Mamba-2 mixers' chunked scan kernels (``ssd_chunk_fwd``,
``ssd_chunk_bwd``: ``ops/ssm.py``) take."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("ssd_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "ssd")
    if seconds is None:
        return None
    return 100.0 * seconds / sources["trace"]["busy_s"]
