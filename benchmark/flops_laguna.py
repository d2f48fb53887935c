"""Operations and bytes of ``zoo.decoder_lm`` from a configuration's
``sizes``: what the algorithm needs, as ``flops.py`` counts (a
multiply-add is two; training = 3 x forward for every matmul; nothing
recomputed is counted, so a step under ``remat`` reads lower).

Causal attention at the pairs it needs: a query at position i sees i + 1
keys, or min(i + 1, window) on a sliding layer.  A share's experts at
their EXPECTED load: a token's ``num_experts_per_tok`` choices fall on
the ``experts_held`` of ``num_experts`` held here in that proportion
(1 / 8 at 32 of 256); the router, the shared expert and everything else
see every token.
"""

BF16 = 2

#: matmuls over the (query, key) pairs that attention needs: S = QK^T and
#: PV forward; S again, dP, dV, dK, dQ backward (``flops.FLASH_MATMULS``)
ATTENTION_MATMULS = 2 + 5

#: matmuls of a SwiGLU over its rows: gate, up, down forward; each one's
#: two gradients backward
SWIGLU_MATMULS = 3 + 6


def layers(sizes: dict) -> list:
    """(attention kind, query heads, FF kind) of each layer that is run."""
    return list(zip(sizes["layer_types"],
                    sizes["num_attention_heads_per_layer"],
                    sizes["mlp_layer_types"]))[:sizes["num_hidden_layers"]]


def attended_pairs(sizes: dict, kind: str) -> float:
    """(query, key) pairs of one head over one row."""
    t = sizes["seq_len"]
    w = min(sizes["sliding_window"], t) if kind == "sliding_attention" else t
    return w * (w + 1) / 2 + (t - w) * w


def held_share(sizes: dict) -> float:
    return sizes.get("experts_held", sizes["num_experts"]) \
        / sizes["num_experts"]


def forward_per_row(sizes: dict) -> dict:
    """Forward FLOPs of one row (``seq_len`` tokens), by part."""
    d, dh, t = sizes["hidden_size"], sizes["head_dim"], sizes["seq_len"]
    kv = sizes["num_key_value_heads"]
    parts = dict.fromkeys(("projections", "full_attention",
                           "sliding_attention", "dense_ff", "router",
                           "shared_expert", "routed_experts", "head"), 0.0)
    for kind, heads, mlp in layers(sizes):
        parts["projections"] += t * 2 * d * (
            (heads + 2 * kv) * dh + heads * dh
            + (heads if sizes.get("gating") else 0))
        parts[kind] += 2 * 2 * dh * heads * attended_pairs(sizes, kind)
        if mlp == "dense":
            parts["dense_ff"] += t * 3 * 2 * d * sizes["intermediate_size"]
        else:
            f = sizes["moe_intermediate_size"]
            parts["router"] += t * 2 * d * sizes["num_experts"]
            parts["shared_expert"] += t * 3 * 2 * d * sizes.get(
                "shared_expert_intermediate_size", 0)
            parts["routed_experts"] += t * sizes["num_experts_per_tok"] \
                * held_share(sizes) * 3 * 2 * d * f
    parts["head"] = t * 2 * d * sizes["vocab_size"]
    return parts


def train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row."""
    return float(3 * sum(forward_per_row(sizes).values()))


def _flash_train(sizes: dict, batch: int, kind: str) -> tuple:
    """(FLOPs, bytes) of one training step's attention of ``kind``: 7
    matmuls over the pairs it needs; each operand once in bf16 — q, o,
    dO, dq at the query heads (q and o read twice: forward and
    backward), k, v, dk, dv at the K/V heads (k and v read twice)."""
    dh, t = sizes["head_dim"], sizes["seq_len"]
    kv = sizes["num_key_value_heads"]
    flops = bytes_ = 0.0
    for layer_kind, heads, _ in layers(sizes):
        if layer_kind == kind:
            flops += batch * heads * ATTENTION_MATMULS * 2 * dh \
                * attended_pairs(sizes, kind)
            bytes_ += batch * (6 * heads + 6 * kv) * t * dh * BF16
    return float(flops), float(bytes_)


def full_flash_train(sizes: dict, batch: int) -> tuple:
    return _flash_train(sizes, batch, "full_attention")


def window_flash_train(sizes: dict, batch: int) -> tuple:
    return _flash_train(sizes, batch, "sliding_attention")


def experts_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's grouped matmuls: 9 matmuls
    of (D x F) over the expected rows landing here; the held experts'
    matrices read forward, read backward and their gradients written,
    the rows' activations in and out of each pass, in bf16."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = sizes.get("experts_held", sizes["num_experts"])
    sparse = sum(mlp == "sparse" for _, _, mlp in layers(sizes))
    rows = batch * sizes["seq_len"] * sizes["num_experts_per_tok"] \
        * held_share(sizes)
    flops = sparse * rows * SWIGLU_MATMULS * 2 * d * f
    bytes_ = sparse * (3 * held * 3 * d * f + 5 * rows * d) * BF16
    return float(flops), float(bytes_)
