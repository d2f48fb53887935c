"""Share of device 0's busy time inside the traced window that the
flash-attention backward's dQ kernel (``flash_bwd_dq``: S, dP and dQ)
takes: one of the three parts of ``flash_time_share``."""

from tracing_fields import kernel_time_share

NAME, UNIT, LAYER, MOVES = ("flash_bwd_dq_time_share", "%", "kernels",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    return kernel_time_share(sources, "flash_bwd_dq")
