"""Operations and bytes of ``zoo.decoder_lm`` with ``linear_attention``
layers (the ``olmo_hybrid`` family) from a configuration's ``sizes``: what
the algorithm needs, as ``flops.py`` counts (a multiply-add is two;
training = 3 x forward for every matmul; nothing recomputed is counted,
so a step under ``remat`` reads lower).

A ``full_attention`` layer is ``flops_laguna``'s (projections, causal
attention at the pairs it needs); a ``linear_attention`` layer is its
projections (W_in of 2·H·Dk + 2·H·Dv + 2·H columns, W_out) and the gated
delta rule in its chunked form at the configured chunk C: a chunk of a
head costs K K^T, W = T (beta gamma K), Q K^T (2 C C Dk each), T (beta V)
and P U (2 C C Dv each), W S^T, Q S^T and U^T K (2 C Dk Dv each), and the
triangular inverse T (2 C^3 / 3, forward substitution's count); every
layer has a SwiGLU of ``intermediate_size``.
"""

import flops_laguna

BF16, F32 = 2, 4


def kinds(sizes: dict) -> list:
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def rule_flops_per_token(sizes: dict) -> float:
    """Forward FLOPs a token of one mixer's chunked delta rule, all its
    heads."""
    h, dk, dv, c = (sizes["linear_num_key_heads"],
                    sizes["linear_key_head_dim"],
                    sizes["linear_value_head_dim"],
                    sizes["linear_chunk_size"])
    return float(h * (3 * 2 * c * dk + 2 * 2 * c * dv + 3 * 2 * dk * dv
                      + 2 * c * c / 3))


def forward_per_row(sizes: dict) -> dict:
    """Forward FLOPs of one row (``seq_len`` tokens), by part."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    linear = kinds(sizes).count("linear_attention")
    h = sizes["linear_num_key_heads"]
    qk, vz = h * sizes["linear_key_head_dim"], h * sizes[
        "linear_value_head_dim"]
    full = [layer for layer in flops_laguna.layers(sizes)
            if layer[0] != "linear_attention"]
    attention = flops_laguna.forward_per_row(dict(
        sizes, num_hidden_layers=len(full),
        **dict(zip(("layer_types", "num_attention_heads_per_layer",
                    "mlp_layer_types"), map(list, zip(*full))))))
    return {
        "linear_projections": linear * t * 2 * d * (2 * qk + 2 * vz + 2 * h
                                                     + vz),
        "delta_rule": linear * t * rule_flops_per_token(sizes),
        "attention_projections": attention["projections"],
        "attention": attention["full_attention"],
        "mlp": len(kinds(sizes)) * t * 3 * 2 * d * sizes["intermediate_size"],
        "head": attention["head"],
    }


def train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row."""
    return float(3 * sum(forward_per_row(sizes).values()))


def flash_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's full attention:
    ``flops_laguna.full_flash_train``."""
    return flops_laguna.full_flash_train(sizes, batch)


def gdn_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's chunked delta rules: each
    product once forward and twice backward; q, k, v read forward and
    backward, o written, dO read and dq, dk, dv written, in bf16; log
    alpha and beta read twice and their gradients written, in float32.
    The chunks' states, which THIS repo's kernels keep for their
    backward, are no part of the needed work."""
    h, dk, dv = (sizes["linear_num_key_heads"], sizes["linear_key_head_dim"],
                 sizes["linear_value_head_dim"])
    tokens = kinds(sizes).count("linear_attention") * batch \
        * sizes["seq_len"]
    flops = 3 * tokens * rule_flops_per_token(sizes)
    bytes_ = tokens * h * ((3 * (2 * dk + dv) + 2 * dv) * BF16 + 6 * F32)
    return float(flops), float(bytes_)
