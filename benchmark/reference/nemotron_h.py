"""Plain reference for the ``nemotron3-*`` configurations.

The forward pass of NVIDIA's Nemotron-H decoders as their public
``config.json`` describes them (``model_type: nemotron_h``), in
straightforward float32 ``jax.numpy``: no kernel, no chunking, no sort, no
grouped matmul, no cache.  It reads the variables tree ``zoo.hybrid_lm``
makes and the configuration's ``sizes``, and nothing else of the program.

Layer l is ``x + mixer_l(norm(x))`` with ``norm(x) = x / sqrt(mean(x^2) +
eps) * w`` and ONE mixer, named by ``hybrid_override_pattern[l]``; a final
norm and an untied head without bias.  No linear layer has a bias.

* ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
  state N = ``ssm_state_size``, G = ``n_groups``, K = ``conv_kernel``):
  ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))``, a causal
  depthwise convolution of K taps with bias; ``xBC -> x | B | C``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; for head h of group h //
  (H / G) the recurrence itself, one position after another
  (``lax.scan`` over t with the whole (H, P, N) state):
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
  D_h x_t``; then ``y * silu(z)``, an RMS norm over each group of H P / G
  channels with an H·P-wide weight, and ``W_out``.
* ``E``, mixture of experts (DeepSeek-V3's router): ``s = sigmoid(u W_r)``
  over all ``n_routed_experts``; S = the ``num_experts_per_tok`` largest
  of ``s + b`` (``b`` bears on the choice alone); ``w_e = scale * s_e /
  (sum_S s + 1e-20)``; ``y = sum_{e in S, e held here} w_e E_e(u) +
  E_shared(u)`` with ``E(u) = W_down relu(W_up u)^2``, no gate: a loop over
  the held experts, each applied to every token and masked by its weight.
  ``experts_held`` / ``first_expert`` in ``sizes`` name the share; left
  out, every expert is held and this is the whole layer.
* ``*``, attention: ``num_attention_heads`` query heads of ``head_dim``
  over ``num_key_value_heads`` K/V heads (query head j reads K/V head j //
  (H / KV)), causal, scores q.k / sqrt(head_dim), softmax, concat, W_o.
  No rotary and no other positional term (Nemotron-H report,
  arXiv:2504.03624, section 2).  One score matrix a block of
  ``QUERY_BLOCK`` queries, so that long rows fit.
* ``-``, a dense MLP: ``W_down relu(W_up u)^2``.
* ties: the choice of S is not continuous.  ``passes`` therefore gives
  every routing's relative gap between the last chosen and the first
  unchosen of ``s + b`` and whether that choice bears on the experts held
  here, and takes ``swap``: the routings at which to take the other of
  the two, honoured only where the gap it meets is under ``tie``
  (``runners/train_routed.py`` compares with these).

The program keeps q, k and v in one (D, (H + 2 KV) Dh) matrix: read apart
here.  On a TPU a float32 matmul rounds its inputs to bfloat16 unless told
otherwise, so every pass sets ``jax.default_matmul_precision`` itself.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def rms_norm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def relu2_mlp(up, down, u):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def mamba(p, u, sizes):
    b, t, _ = u.shape
    h, hp = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    n, g, taps = (sizes["ssm_state_size"], sizes["n_groups"],
                  sizes["conv_kernel"])
    inner, bc = h * hp, g * n
    w = p["in_proj"]
    z = u @ w[:, :inner]
    xbc = u @ w[:, inner:2 * inner + 2 * bc]
    dt = jax.nn.softplus(u @ w[:, 2 * inner + 2 * bc:] + p["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv"]["bias"] + sum(
        p["conv"]["kernel"][k] * padded[:, k:k + t] for k in range(taps)))
    x = xbc[..., :inner].reshape(b, t, g, h // g, hp)
    bm = xbc[..., inner:inner + bc].reshape(b, t, g, n)
    cm = xbc[..., inner + bc:].reshape(b, t, g, n)
    dt = dt.reshape(b, t, g, h // g)
    a = -jnp.exp(p["A_log"]).reshape(g, h // g)

    def step(state, now):  # state (B, G, H / G, P, N)
        x_t, dt_t, b_t, c_t = now
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        return state, jnp.einsum("bgkpn,bgn->bgkp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((b, g, h // g, hp, n), u.dtype),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + p["D"].reshape(g, h // g, 1) * x
    y = y.reshape(b, t, g, inner // g) \
        * jax.nn.silu(z).reshape(b, t, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + sizes.get("layer_norm_epsilon", 1e-5))
    return (y.reshape(b, t, inner) * p["norm"]["scale"]) @ p["out_proj"]


def attention(p, u, sizes):
    b, t, _ = u.shape
    heads, kv, dh = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    w = p["qkv"]
    q = (u @ w[:, :heads * dh]).reshape(b, t, heads, dh)
    k = (u @ w[:, heads * dh:(heads + kv) * dh]).reshape(b, t, kv, dh)
    v = (u @ w[:, (heads + kv) * dh:]).reshape(b, t, kv, dh)
    group = heads // kv
    key = jnp.arange(t)[None, :]
    block = min(QUERY_BLOCK, t)

    def one_block(start):  # the scores of `block` queries against every key
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qb = qb.reshape(b, block, kv, group, dh)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, k) / math.sqrt(dh)
        s = jnp.where(key <= start + jnp.arange(block)[:, None], s, -jnp.inf)
        o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(b, block, heads, dh)

    o = jax.lax.map(one_block, jnp.arange(0, t, block))    # (T/Q, B, Q, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads * dh)
    return o @ p["out"]


def routing(p, u, sizes, swap=False, tie=0.0):
    """(weights (..., E) with zeros off the chosen experts, each token's
    relative gap between the last chosen and the first unchosen of
    ``s + b``, those two experts (..., 2)).  Where ``swap`` is set and
    the gap is under ``tie``, the first unchosen is taken for the last
    chosen."""
    k = sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    top, idx = jax.lax.top_k(scores + p["router"]["bias"], k + 1)
    gap = (top[..., k - 1] - top[..., k]) / jnp.abs(top[..., k - 1])
    edge = idx[..., k - 1:]
    taken = jnp.where(swap & (gap < tie), k, k - 1)[..., None]
    idx = jnp.concatenate(
        [idx[..., :k - 1], jnp.take_along_axis(idx, taken, axis=-1)],
        axis=-1)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if sizes.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * sizes.get("routed_scaling_factor", 1.0)
    chosen = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("...k,...ke->...e", w, chosen), gap, edge


def sparse_ff(p, u, sizes, swap=False, tie=0.0):
    """(the layer's output, each token's gap, whether its last chosen or
    first unchosen expert is held here: whether the choice between the
    two changes this share's output)."""
    weights, gap, edge = routing(p, u, sizes, swap, tie)
    first = sizes.get("first_expert", 0)
    held = p["experts"]["up"].shape[0]
    bears = jnp.any((edge >= first) & (edge < first + held), axis=-1)
    mine = jax.lax.dynamic_slice_in_dim(weights, first, held, axis=-1)

    def add_expert(y, expert):  # every token through it, times its weight
        up, down, w = expert
        return y + w[..., None] * relu2_mlp(up, down, u), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        p["experts"]["up"], p["experts"]["down"],
        jnp.moveaxis(mine, -1, 0)))
    if "shared" in p:
        y = y + relu2_mlp(p["shared"]["up"], p["shared"]["down"], u)
    return y, gap, bears


def pattern(sizes) -> str:
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def logits_and_gaps(params, tokens, sizes, swap=None, tie=0.0):
    """(logits (B, T, V); by ``E`` layer (L, B, T): each token's relative
    gap between its last chosen and first unchosen expert, and whether
    that choice bears on the experts held here).  ``swap`` (L, B, T) and
    ``tie``: see ``routing``."""
    embed, *layers, norm_f, head = params
    eps = sizes.get("layer_norm_epsilon", 1e-5)
    gaps, bears = [], []
    x = embed["table"][tokens]
    for kind, layer in zip(pattern(sizes), layers, strict=True):
        norm, mixer = layer["inner"]
        u = rms_norm(norm, x, eps)
        if kind == "M":
            y = mamba(mixer, u, sizes)
        elif kind == "*":
            y = attention(mixer, u, sizes)
        elif kind == "-":
            y = relu2_mlp(mixer[0]["kernel"], mixer[1]["kernel"], u)
        else:
            y, gap, bear = sparse_ff(
                mixer, u, sizes, False if swap is None else swap[len(gaps)],
                tie)
            gaps.append(gap)
            bears.append(bear)
        x = x + y
    none = jnp.zeros((0,) + tokens.shape)
    return (rms_norm(norm_f, x, eps) @ head["kernel"],
            jnp.stack(gaps) if gaps else none,
            jnp.stack(bears) if bears else none.astype(bool))


def float32(variables):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])


def passes(sizes, precision="highest"):
    """``run(variables, tokens, swap=None, tie=0.0)`` -> ``(logits, gaps,
    bears)`` as ``logits_and_gaps`` gives them, for int tokens (B, T):
    ``runners/train_routed.py``'s contract.

    The whole pass is ONE jitted program (the loops over positions, query
    blocks and experts are ``lax.scan`` and ``lax.map``), held by ``run``,
    so a second pass compiles nothing.  ``swap``, boolean (L, B, T), names
    routings at which to take the first unchosen expert for the last
    chosen; it is honoured only where the gap met there is under ``tie``.
    ``precision`` is there to read what a lower one gives (``"bfloat16"``:
    the tolerance has to fail it); the comparison runs at ``"highest"``."""
    program = jax.jit(lambda params, ids, swap, tie: logits_and_gaps(
        params, ids, sizes, swap, tie))

    def run(variables, tokens, swap=None, tie=0.0):
        tokens = jnp.asarray(tokens)
        if swap is None:
            swap = np.zeros((pattern(sizes).count("E"),) + tokens.shape,
                            bool)
        with jax.default_matmul_precision(precision):
            return program(float32(variables), tokens, jnp.asarray(swap),
                           jnp.float32(tie))

    return run


def forward(variables, tokens, sizes, precision="highest"):
    """Logits (B, T, V) in float32 for int tokens (B, T): every token's k
    best experts as this pass's own float32 finds them."""
    return passes(sizes, precision)(variables, tokens)[0]


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
