"""Plain reference for the ``ouro-*`` configurations.

The forward pass, the exit distribution and the first-stage loss of
ByteDance's Ouro looped language models as their public ``config.json``
(``model_type: ouro``) and the paper (arXiv:2510.25741) describe them, in
straightforward float32 ``jax.numpy``: no kernel, no checkpoint, no cache.
It reads the variables tree ``zoo.decoder_lm(total_ut_steps=4,
sandwich_norm=True)`` makes and the configuration's ``sizes``, and nothing
else of the program.

* A layer has four RMSNorms: ``h = x + N2(Attn(N1(x)))``, ``y = h +
  N4(SwiGLU(N3(h)))``, with ``N(x) = x / sqrt(mean(x^2) + eps) * w``.
  ``Attn``: ``num_attention_heads_per_layer[l]`` query heads of
  ``head_dim`` over ``num_key_value_heads`` K/V heads (equal here), rotary
  with half-split pairing over the whole head at ``rope_theta``, scores
  q.k / sqrt(head_dim), a key seen where key <= query, softmax, concat,
  W_o; no bias anywhere.  One score matrix a block of ``QUERY_BLOCK``
  queries, so that long rows fit.  ``SwiGLU(u) = (silu(u W_g) * u W_u)
  W_d``.
* The loop: ``h_0 = Embed(tokens)``; for pass t = 1 .. ``total_ut_steps``:
  ``h_t = N_f(Layers(h_{t-1}))``, every layer in order, the SAME
  parameters in every pass, then the one final norm, whose output is both
  the pass's output and the next pass's input.  Positions are the same in
  every pass.  ``logits_t = h_t W_head``; ``g_t = h_t w_e + b_e``.
* The exit distribution, per token: ``lambda_t = sigmoid(g_t)``; ``p_t =
  lambda_t prod_{j<t} (1 - lambda_j)`` for t below the last, and the last
  pass takes what is left, ``prod_{j<last} (1 - lambda_j)``.
* The loss (Stage I, uniform prior): mean over tokens of ``sum_t p_t
  nll_t - beta H(p)``, ``H(p) = -sum_t p_t log p_t``, ``beta`` 0.1 unless
  ``sizes`` says otherwise (the configuration file lists it under
  ``assumed``).

The program keeps q, k and v in one (D, (H + 2 KV) Dh) matrix and gate and
up side by side in one (D, 2F) matrix: read apart here.

On a TPU a float32 matmul rounds its inputs to bfloat16 unless told
otherwise, so ``forward`` sets ``jax.default_matmul_precision("highest")``
itself; ``precision`` is there to read what a lower one gives.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
BETA = 0.1


def rms_norm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def rotate(x, theta):
    """x (B, T, H, Dh): dimension i paired with i + Dh / 2, turned by
    position * theta ** (-2 i / Dh)."""
    t, dh = x.shape[1], x.shape[-1]
    freq = theta ** (-2.0 * np.arange(dh // 2, dtype=np.float64) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def attention(p, u, heads, sizes):
    b, t, _ = u.shape
    kv, dh = sizes["num_key_value_heads"], sizes["head_dim"]
    theta = sizes.get("rope_parameters", {}).get("full_attention", {}).get(
        "rope_theta", 10000.0)
    w = p["qkv"]
    q = (u @ w[:, :heads * dh]).reshape(b, t, heads, dh)
    k = (u @ w[:, heads * dh:(heads + kv) * dh]).reshape(b, t, kv, dh)
    v = (u @ w[:, (heads + kv) * dh:]).reshape(b, t, kv, dh)
    q, k = rotate(q, theta), rotate(k, theta)
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


def swiglu(p, u):
    f = p["gate_up"].shape[-1] // 2
    return (jax.nn.silu(u @ p["gate_up"][:, :f]) * (u @ p["gate_up"][:, f:])) \
        @ p["down"]


def one_pass(loop, x, sizes):
    """``N_f(Layers(x))``."""
    eps = sizes.get("rms_norm_eps", 1e-6)
    blocks = loop["body"]
    for layer, (attn, ff) in enumerate(zip(blocks[0::2], blocks[1::2])):
        n1, mha, n2 = attn["inner"]
        x = x + rms_norm(n2, attention(
            mha, rms_norm(n1, x, eps),
            sizes["num_attention_heads_per_layer"][layer], sizes), eps)
        n3, mlp, n4 = ff["inner"]
        x = x + rms_norm(n4, swiglu(mlp, rms_norm(n3, x, eps)), eps)
    return rms_norm(loop["closing"], x, eps)


def exit_distribution(gates):
    """``p`` (..., passes) from the gate's values (..., passes)."""
    lam = jax.nn.sigmoid(gates)
    left = jnp.cumprod(1.0 - lam, axis=-1)     # prod_{j<=t} (1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(left[..., :1]), left[..., :-1]],
                             axis=-1)
    return jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]],
                           axis=-1)


def logits_and_p(params, tokens, sizes):
    """(logits (passes, B, T, V), p (B, T, passes))."""
    embed, loop, heads = params
    x = embed["table"][tokens]
    logits, gates = [], []
    for _ in range(sizes["total_ut_steps"]):
        x = one_pass(loop, x, sizes)
        logits.append(x @ heads["head"]["kernel"])
        gates.append(x @ heads["exit_gate"]["kernel"]
                     + heads["exit_gate"]["bias"])
    return jnp.stack(logits), exit_distribution(
        jnp.concatenate(gates, axis=-1))


def float32(variables):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])


def forward(variables, tokens, sizes, precision="highest"):
    """(every pass's logits (passes, B, T, V), the exit distribution p
    (B, T, passes)) in float32 for int tokens (B, T); one jitted
    program."""
    with jax.default_matmul_precision(precision):
        return jax.jit(lambda params, ids: logits_and_p(params, ids, sizes))(
            float32(variables), jnp.asarray(tokens))


def loss_of(params, tokens, labels, sizes):
    logits, p = logits_and_p(params, tokens, sizes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[None, ..., None], axis=-1)[..., 0]
    expected = jnp.sum(p * jnp.moveaxis(nll, 0, -1), axis=-1)
    entropy = -jnp.sum(p * jnp.log(p), axis=-1)
    return jnp.mean(expected - sizes.get("beta", BETA) * entropy)


def loss_and_grads(variables, tokens, labels, sizes):
    """The loss above and its gradient by every parameter (for the CPU
    tests; the chip's comparison is of logits and p)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda params: loss_of(params, jnp.asarray(tokens),
                                   jnp.asarray(labels), sizes)))(
            float32(variables))
