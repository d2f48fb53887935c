"""Operations and bytes of ``zoo.hybrid_lm`` from a configuration's
``sizes``: what the algorithm needs, as ``flops.py`` counts (a
multiply-add is two; training = 3 x forward for every matmul; nothing
recomputed is counted, so a step under ``remat`` reads lower).

One mixer a layer, by ``hybrid_override_pattern``.  Causal attention at
the pairs it needs.  A Mamba-2 mixer's scan in its chunked form at the
published chunk: ``C B^T`` once a group, and a head's three products of a
chunk (mask times x, the chunk's own state, the incoming state times C).
A share's experts at their EXPECTED load: a token's
``num_experts_per_tok`` choices fall on the ``experts_held`` of
``n_routed_experts`` held here in that proportion (1 / 16 at 8 of 128:
384 tokens an expert a row of 8,192); the router, the shared expert and
everything else see every token.
"""

BF16 = 2

#: matmuls over the (query, key) pairs that attention needs: S = QK^T and
#: PV forward; S again, dP, dV, dK, dQ backward (``flops.FLASH_MATMULS``)
ATTENTION_MATMULS = 2 + 5

#: matmuls of an ungated relu² expert over its rows: up and down forward;
#: each one's two gradients backward
RELU2_MATMULS = 2 + 4


def pattern(sizes: dict) -> str:
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def held_share(sizes: dict) -> float:
    return sizes.get("experts_held", sizes["n_routed_experts"]) \
        / sizes["n_routed_experts"]


def scan_flops_per_token(sizes: dict) -> float:
    """Forward FLOPs a token of one mixer's chunked scan: a chunk of L
    positions costs 2 L L N a group (``C B^T``) and 2 L L P + 4 L P N a
    head."""
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    n, g, chunk = (sizes["ssm_state_size"], sizes["n_groups"],
                   sizes["chunk_size"])
    return float(g * 2 * chunk * n + h * (2 * chunk * p + 4 * p * n))


def forward_per_row(sizes: dict) -> dict:
    """Forward FLOPs of one row (``seq_len`` tokens), by part."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    kinds = pattern(sizes)
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    bc = sizes["n_groups"] * sizes["ssm_state_size"]
    heads, kv, dh = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    f = sizes["moe_intermediate_size"]
    return {
        "mamba_projections": kinds.count("M") * t * 2 * d * (
            2 * inner + 2 * bc + sizes["mamba_num_heads"] + inner),
        "mamba_scan": kinds.count("M") * t * scan_flops_per_token(sizes),
        "attention_projections": kinds.count("*") * t * 2 * d * (
            (heads + 2 * kv) * dh + heads * dh),
        "attention": kinds.count("*") * 2 * 2 * dh * heads
        * t * (t + 1) / 2,
        "router": kinds.count("E") * t * 2 * d * sizes["n_routed_experts"],
        "shared_expert": kinds.count("E") * t * 2 * 2 * d * sizes.get(
            "moe_shared_expert_intermediate_size", 0),
        "routed_experts": kinds.count("E") * t
        * sizes["num_experts_per_tok"] * held_share(sizes) * 2 * 2 * d * f,
        "dense_mlp": kinds.count("-") * t * 2 * 2 * d
        * sizes.get("intermediate_size", 0),
        "head": t * 2 * d * sizes["vocab_size"],
    }


def train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row."""
    return float(3 * sum(forward_per_row(sizes).values()))


def flash_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's attention: 7 matmuls over the
    causal pairs; each operand once in bf16 — q, o, dO, dq at the query
    heads (q and o read twice: forward and backward), k, v, dk, dv at the
    K/V heads (k and v read twice)."""
    heads, kv, dh, t = (sizes["num_attention_heads"],
                        sizes["num_key_value_heads"], sizes["head_dim"],
                        sizes["seq_len"])
    layers = pattern(sizes).count("*")
    flops = layers * batch * heads * ATTENTION_MATMULS * 2 * dh \
        * t * (t + 1) / 2
    bytes_ = layers * batch * (6 * heads + 6 * kv) * t * dh * BF16
    return float(flops), float(bytes_)


def experts_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's grouped matmuls: 6 matmuls
    of (D x F) over the expected rows landing here (384 x 8 a layer a
    row); the held experts' matrices read forward, read backward and
    their gradients written, the rows' activations in and out of each
    pass, in bf16."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = sizes.get("experts_held", sizes["n_routed_experts"])
    sparse = pattern(sizes).count("E")
    rows = batch * sizes["seq_len"] * sizes["num_experts_per_tok"] \
        * held_share(sizes)
    flops = sparse * rows * RELU2_MATMULS * 2 * d * f
    bytes_ = sparse * (3 * held * 2 * d * f + 5 * rows * d) * BF16
    return float(flops), float(bytes_)


def ssd_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of one training step's chunked scans: each product
    once forward and twice backward; x read forward and backward, y
    written, dy read and dx written (5 x H P a token), B and C read
    twice and their gradients written (6 x G N), in bf16; dt read twice
    and its gradient written in float32.  The chunks' states, which THIS
    repo's kernels keep for their backward, are no part of the needed
    work."""
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    bc = sizes["n_groups"] * sizes["ssm_state_size"]
    tokens = pattern(sizes).count("M") * batch * sizes["seq_len"]
    flops = 3 * tokens * scan_flops_per_token(sizes)
    bytes_ = tokens * ((5 * inner + 6 * bc) * BF16
                       + 3 * sizes["mamba_num_heads"] * 4)
    return float(flops), float(bytes_)
