"""Plain reference for the ``laguna-*`` configurations.

The forward pass of poolside's Laguna decoders as their public
``config.json`` describes them (``model_type: laguna``), in
straightforward float32 ``jax.numpy``: no kernel, no sort, no grouped
matmul, no cache.  It reads the variables tree ``zoo.decoder_lm`` makes
and the configuration's ``sizes``, and nothing else of the program.

Block l: ``h = x + Attn_l(norm(x))``, ``y = h + FF_l(norm(h))``, with
``norm(x) = x / sqrt(mean(x^2) + eps) * w``; a final norm and an untied
head without bias.

* ``Attn_l``: H_l = ``num_attention_heads_per_layer[l]`` query heads of
  ``head_dim`` over ``num_key_value_heads`` K/V heads (query head j reads
  K/V head j // (H_l / KV)); rotary with half-split pairing over the
  first ``partial_rotary_factor`` of each head, plain or YaRN
  frequencies, as ``rope_parameters[layer_types[l]]`` says; scores
  q.k / sqrt(head_dim), a key seen where key <= query and, on
  ``sliding_attention`` layers, query - key < ``sliding_window``;
  softmax; head j's output times sigmoid(norm(x) W_g)_j; concat, W_o.
  One score matrix a block of ``QUERY_BLOCK`` queries, so that long rows
  fit.
* dense ``FF``: (silu(u W_gate) * u W_up) W_down.
* sparse ``FF``: p = softmax(u W_r) over all ``num_experts``; S = the
  ``num_experts_per_tok`` largest (``lax.top_k``); w_e = scale * p_e /
  sum_S p; y = sum_{e in S, e held here} w_e E_e(u) + E_shared(u): a
  loop over the held experts, each applied to every token and masked by
  its weight (zero where it was not chosen).  ``experts_held`` / ``first_expert`` in ``sizes`` name the
  share; left out, every expert is held and this is the whole layer.
* ties: the choice of S is not continuous.  Where a token's last chosen
  and first unchosen probabilities lie nearer than float32 can tell
  (their logits are dot products of 2,048 float32 terms, and come out
  EQUAL about once in 20 layers of 16,384 tokens), two correct float32
  programs may choose differently, and the token's output then differs
  by a whole expert.  ``forward_choices`` therefore gives every
  routing's relative gap and whether the choice bears on the experts
  held here, and takes ``swap``: the routings at which to take the other
  of the two, honoured only where the gap it meets is under ``tie``.
  ``runners/train_routed.py`` compares with these (``passes``).

Set by the family's convention where the config is silent (the
configuration file lists them under ``assumed``): silu; the gate is
per head, from the block's normed input, sigmoid; softmax router scores;
the routed scale on the routed sum only; the shared expert ungated; no
q/k norm.

The program keeps gate and up side by side in one (D, 2F) matrix, q, k
and v in one (D, (H + 2 KV) Dh) matrix: read apart here.

On a TPU a float32 matmul rounds its inputs to bfloat16 unless told
otherwise, so ``forward`` sets ``jax.default_matmul_precision("highest")``
itself.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def rms_norm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def rotary_tables(positions, head_dim, rope):
    """(cos, sin), each (T, n): n rotated pairs a head."""
    dim = int(round(head_dim * rope.get("partial_rotary_factor", 1.0)))
    theta = rope.get("rope_theta", 10000.0)
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / dim)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        orig = rope["original_max_position_embeddings"]

        def pair(beta):
            return dim * math.log(orig / (2 * math.pi * beta)) \
                / (2 * math.log(theta))

        lo = max(math.floor(pair(rope["beta_fast"])), 0)
        hi = min(math.ceil(pair(rope["beta_slow"])), dim - 1)
        ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
        freq = freq / rope["factor"] * ramp + freq * (1.0 - ramp)
        factor = rope["attention_factor"]
    ang = positions[:, None].astype(jnp.float32) \
        * jnp.asarray(freq, jnp.float32)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """x (B, T, H, Dh): pair i with i + n inside the first 2n dims."""
    n = cos.shape[-1]
    a, b, rest = x[..., :n], x[..., n:2 * n], x[..., 2 * n:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def attention(p, u, heads, kind, sizes):
    b, t, _ = u.shape
    kv, dh = sizes["num_key_value_heads"], sizes["head_dim"]
    w = p["qkv"]
    q = (u @ w[:, :heads * dh]).reshape(b, t, heads, dh)
    k = (u @ w[:, heads * dh:(heads + kv) * dh]).reshape(b, t, kv, dh)
    v = (u @ w[:, (heads + kv) * dh:]).reshape(b, t, kv, dh)
    cos, sin = rotary_tables(jnp.arange(t), dh,
                             sizes.get("rope_parameters", {}).get(kind, {}))
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    group = heads // kv
    window = sizes.get("sliding_window") if kind == "sliding_attention" \
        else None
    key = jnp.arange(t)[None, :]
    block = min(QUERY_BLOCK, t)

    def one_block(start):  # the scores of `block` queries against every key
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qb = qb.reshape(b, block, kv, group, dh)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, k) / math.sqrt(dh)
        query = start + jnp.arange(block)[:, None]
        seen = key <= query
        if window is not None:
            seen = seen & (query - key < window)
        s = jnp.where(seen, s, -jnp.inf)
        o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(b, block, heads, dh)

    o = jax.lax.map(one_block, jnp.arange(0, t, block))    # (T/Q, B, Q, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads, dh)
    if "gate" in p:
        o = o * jax.nn.sigmoid(u @ p["gate"])[..., None]
    return o.reshape(b, t, heads * dh) @ p["out"]


def swiglu(gate_up, down, u):
    f = gate_up.shape[-1] // 2
    return (jax.nn.silu(u @ gate_up[:, :f]) * (u @ gate_up[:, f:])) @ down


def routing(p, u, sizes, swap=False, tie=0.0):
    """(weights (..., E) with zeros off the chosen experts, each token's
    relative gap between its last chosen and first unchosen probability,
    those two experts (..., 2)).  Where ``swap`` is set and the gap is
    under ``tie``, the first unchosen is taken for the last chosen."""
    k = sizes["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ p["router"]["kernel"], axis=-1)
    top, idx = jax.lax.top_k(probs, k + 1)
    gap = (top[..., k - 1] - top[..., k]) / top[..., k - 1]
    edge = idx[..., k - 1:]
    taken = jnp.where(swap & (gap < tie), k, k - 1)[..., None]
    top, idx = (jnp.concatenate(
        [a[..., :k - 1], jnp.take_along_axis(a, taken, axis=-1)], axis=-1)
        for a in (top, idx))
    if sizes.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * sizes.get("moe_routed_scaling_factor", 1.0)
    chosen = jax.nn.one_hot(idx, probs.shape[-1], dtype=top.dtype)
    return jnp.einsum("...k,...ke->...e", top, chosen), gap, edge


def sparse_ff(p, u, sizes, swap=False, tie=0.0):
    """(the layer's output, each token's gap, whether its last chosen or
    first unchosen expert is held here: whether the choice between the
    two changes this share's output)."""
    weights, gap, edge = routing(p, u, sizes, swap, tie)
    first = sizes.get("first_expert", 0)
    held = p["experts"]["gate_up"].shape[0]
    bears = jnp.any((edge >= first) & (edge < first + held), axis=-1)
    mine = jax.lax.dynamic_slice_in_dim(weights, first, held, axis=-1)

    def add_expert(y, expert):  # every token through it, times its weight
        gate_up, down, w = expert
        return y + w[..., None] * swiglu(gate_up, down, u), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        p["experts"]["gate_up"], p["experts"]["down"],
        jnp.moveaxis(mine, -1, 0)))
    if "shared" in p:
        y = y + swiglu(p["shared"]["gate_up"], p["shared"]["down"], u)
    return y, gap, bears


def logits_and_gaps(params, tokens, sizes, swap=None, tie=0.0):
    """(logits (B, T, V); by sparse layer (L, B, T): each token's relative
    gap between its last chosen and first unchosen expert, and whether
    that choice bears on the experts held here).  ``swap`` (L, B, T) and
    ``tie``: see ``routing``."""
    embed, *blocks, norm_f, head = params
    eps = sizes.get("rms_norm_eps", 1e-6)
    gaps, bears = [], []
    x = embed["table"][tokens]
    for layer, (attn, ff) in enumerate(zip(blocks[0::2], blocks[1::2])):
        norm1, mha = attn["inner"]
        x = x + attention(
            mha, rms_norm(norm1, x, eps),
            sizes["num_attention_heads_per_layer"][layer],
            sizes["layer_types"][layer], sizes)
        norm2, mlp = ff["inner"]
        u = rms_norm(norm2, x, eps)
        if sizes["mlp_layer_types"][layer] == "dense":
            x = x + swiglu(mlp["gate_up"], mlp["down"], u)
        else:
            y, gap, bear = sparse_ff(
                mlp, u, sizes, False if swap is None else swap[len(gaps)],
                tie)
            x = x + y
            gaps.append(gap)
            bears.append(bear)
    none = jnp.zeros((0,) + tokens.shape)
    return (rms_norm(norm_f, x, eps) @ head["kernel"],
            jnp.stack(gaps) if gaps else none,
            jnp.stack(bears) if bears else none.astype(bool))


def float32(variables):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])


def passes(sizes, precision="highest"):
    """``run(variables, tokens, swap=None, tie=0.0)`` -> ``(logits, gaps,
    bears)`` as ``logits_and_gaps`` gives them, for int tokens (B, T).

    The whole pass is ONE jitted program (the loops over query blocks and
    experts are ``lax.map`` and ``lax.scan``): run op by op, each float32
    matmul shape compiled on its own, 100 s where this takes 30 (my chip
    runs, PR 29).  ``run`` holds it, so a second pass compiles nothing,
    and the program goes when ``run`` does.

    ``swap``, boolean (L, B, T), names routings at which to take the
    first unchosen expert for the last chosen; it is honoured only where
    the gap met there is under ``tie``, so no pass ever chooses an expert
    that float32 can tell is not among the k best.  ``precision`` is
    there to read what a lower one gives (``"bfloat16"``: the tolerance
    has to fail it); the comparison runs at ``"highest"``, which a TPU
    needs said (its default float32 matmul rounds the inputs to
    bfloat16)."""
    program = jax.jit(lambda params, ids, swap, tie: logits_and_gaps(
        params, ids, sizes, swap, tie))

    def run(variables, tokens, swap=None, tie=0.0):
        tokens = jnp.asarray(tokens)
        if swap is None:  # embedding, two entries a block, norm, head
            blocks = (len(variables["params"]) - 3) // 2
            swap = np.zeros(
                (sizes["mlp_layer_types"][:blocks].count("sparse"),)
                + tokens.shape, bool)
        with jax.default_matmul_precision(precision):
            return program(float32(variables), tokens, jnp.asarray(swap),
                           jnp.float32(tie))

    return run


def forward_choices(variables, tokens, sizes, swap=None, tie=0.0,
                    precision="highest"):
    """One pass of ``passes``: ``(logits, gaps, bears)``."""
    return passes(sizes, precision)(variables, tokens, swap, tie)


def forward(variables, tokens, sizes, precision="highest"):
    """Logits (B, T, V) in float32 for int tokens (B, T): every token's k
    best experts as this pass's own float32 finds them."""
    return forward_choices(variables, tokens, sizes, precision=precision)[0]


def loss_and_grads(variables, tokens, labels, sizes):
    """Mean next-token cross-entropy and its gradient by every parameter
    (for the CPU tests; the chip's comparison is of logits)."""
    def loss(params):
        logits = logits_and_gaps(params, jnp.asarray(tokens), sizes)[0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                     axis=-1)
        return -jnp.mean(picked)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(float32(variables))
