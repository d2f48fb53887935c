"""Operations and bytes of ``zoo.decoder_lm`` with ``total_ut_steps`` > 1
(the ``ouro`` family) from a configuration's ``sizes``: ``flops_laguna``'s
count of one pass through the layers, once for EVERY pass.  A looped
parameter is one set of weights and ``total_ut_steps`` matmuls a row; the
head reads every pass, and so does the exit gate (D x 1); the embedding
is a gather and costs nothing, once or four times.  As ``flops.py``
counts: a multiply-add is two, training = 3 x forward for every matmul,
nothing recomputed is counted.
"""

import flops_laguna


def forward_per_row(sizes: dict) -> dict:
    """Forward FLOPs of one row (``seq_len`` tokens), by part, over all
    the passes."""
    steps = sizes.get("total_ut_steps", 1)
    parts = {part: steps * flops for part, flops in
             flops_laguna.forward_per_row(sizes).items()}
    parts["exit_gate"] = steps * sizes["seq_len"] * 2 * sizes["hidden_size"]
    return parts


def train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row."""
    return float(3 * sum(forward_per_row(sizes).values()))


def flash_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's attention: every layer's
    kernels run once a pass, forward and backward, on operands of their
    own (a pass's q, k, v, o and their gradients are that pass's)."""
    flops, bytes_ = flops_laguna.full_flash_train(sizes, batch)
    steps = sizes.get("total_ut_steps", 1)
    return steps * flops, steps * bytes_
