"""The chunked delta rule kernels' share of their roofline: the least time
the chip could take for the Gated DeltaNet rules of the traced steps (the
configuration's ``kernels.gdn.work``, ``flops_olmo_hybrid.py:gdn_train``:
each product of the chunked form once forward and twice backward, q, k,
v, log alpha, beta and the gradients moved once a pass) over the time
``gdn_chunk_fwd`` and ``gdn_chunk_bwd`` took.  What the kernels do beyond
the needed work (the chunks' states written and read, everything
recomputed in the backward, a triangular inverse by doubling) lowers it,
so it cannot pass 100."""

import kernel_share

NAME, UNIT, LAYER, MOVES = ("gdn_roofline", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    seconds = kernel_share.kernel_seconds(sources, "gdn")
    if seconds is None or sources.get("peak") is None:
        return None
    return 100.0 * kernel_share.least_seconds(sources, "gdn") / seconds
