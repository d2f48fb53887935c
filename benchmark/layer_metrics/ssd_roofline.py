"""The chunked scan kernels' share of their roofline: the least time the
chip could take for the Mamba-2 scans of the traced steps (the
configuration's ``kernels.ssd.work``, ``flops_nemotron_h.py:ssd_train``:
each product of the chunked form once forward and twice backward, x, B,
C, dt and the gradients moved once a pass) over the time
``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` took.  The bytes bound it at the
published widths, not the FLOPs.  What the kernels do beyond the needed
work (the chunks' states written and read, everything recomputed in the
backward) lowers it, so it cannot pass 100."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("ssd_roofline", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "ssd")
    if seconds is None or sources.get("peak") is None:
        return None
    return 100.0 * kernel_share.least_seconds(sources, "ssd") / seconds
