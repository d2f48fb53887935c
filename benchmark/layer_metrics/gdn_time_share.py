"""Share of device 0's busy time inside the traced window that the Gated
DeltaNet mixers' chunked delta rule kernels (``gdn_chunk_fwd``,
``gdn_chunk_bwd``: ``ops/gated_delta.py``) take."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("gdn_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "gdn")
    if seconds is None:
        return None
    return 100.0 * seconds / sources["trace"]["busy_s"]
