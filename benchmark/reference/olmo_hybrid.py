"""Plain reference for the ``olmo-hybrid-*`` configurations.

The forward pass of AI2's Olmo Hybrid decoders as their public
``config.json`` describes them (``model_type: olmo_hybrid``), in
straightforward float32 ``jax.numpy``: no kernel, no chunking, no cache.
It reads the variables tree ``zoo.decoder_lm`` makes and the
configuration's ``sizes``, and nothing else of the program.

Layer l is ``h = x + norm(mixer_l(x))``, ``y = h + norm(SwiGLU(h))``: the
Olmo 2 family's placement of the norms (arXiv:2501.00656), ``norm(x) = x
/ sqrt(mean(x^2) + eps) * w``; a final norm and an untied head without
bias follow.  No linear layer has a bias.  ``layer_types[l]`` names the
mixer:

* ``linear_attention``, Gated DeltaNet (Yang et al., arXiv:2412.06464)
  with H = ``linear_num_key_heads`` heads, keys of Dk =
  ``linear_key_head_dim``, values of Dv = ``linear_value_head_dim``:
  q, k, v = silu(conv(x W_q, x W_k, x W_v)), a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps without bias;
  ``q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(Dk)``, ``k^ = k / sqrt(|k|^2 +
  1e-6)``, per head; ``beta = 2 sigmoid(x W_b)`` where
  ``linear_allow_neg_eigval`` (Grazzi et al., arXiv:2411.12537), else
  ``sigmoid(x W_b)``; ``alpha = exp(-exp(A_log) softplus(x W_a +
  dt_bias))``; for each head the recurrence itself, one position after
  another (``lax.scan`` over t with the whole (H, Dv, Dk) state):
  ``S_t = alpha_t S_{t-1} (I - beta_t k^_t k^_t^T) + beta_t v_t
  k^_t^T``, ``o_t = S_t q^_t``; then an RMS norm over each head's Dv with
  one Dv-wide weight, times ``silu(x W_z)``, and ``W_out``.
* ``full_attention``: ``num_attention_heads_per_layer[l]`` query heads of
  ``head_dim`` over ``num_key_value_heads`` K/V heads (query head j reads
  K/V head j // (H / KV)); with ``qk_norm`` q and k are normed over all
  their columns before the heads are split; causal, scores q.k /
  sqrt(head_dim), softmax, concat, W_o.  No rotary and no other
  positional term: ``rope_theta`` is null.  One score matrix a block of
  ``QUERY_BLOCK`` queries, so that long rows fit.

Departures, each a layout of the same function: the program keeps W_q,
W_k, W_v, W_z, W_b, W_a in one matrix (columns in that order) and q, k, v
of attention in another, read apart here.  On a TPU a float32 matmul
rounds its inputs to bfloat16 unless told otherwise, so every pass sets
``jax.default_matmul_precision`` itself.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
L2_EPS = 1e-6


def rms_norm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def l2_normalised(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def swiglu(p, u):
    h = u @ p["gate_up"]
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ p["down"]


def delta_rule(q, k, v, g, beta):
    """``o_t = S_t q_t`` with ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t
    k_t^T) + beta_t v_t k_t^T``, one position after another.  ``q``,
    ``k`` (B, T, H, Dk); ``v`` (B, T, H, Dv); ``g``, ``beta`` (B, T, H).
    Returns (B, T, H, Dv)."""
    b, _, h, dk = k.shape

    def step(state, now):  # state (B, H, Dv, Dk)
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None, None] * state
        state = state - b_t[..., None, None] * jnp.einsum(
            "bhv,bhd->bhvd", jnp.einsum("bhvd,bhd->bhv", state, k_t), k_t)
        state = state + b_t[..., None, None] * jnp.einsum(
            "bhv,bhd->bhvd", v_t, k_t)
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, v.shape[-1], dk), v.dtype),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def gated_deltanet(p, u, sizes):
    b, t, _ = u.shape
    h, dk, dv = (sizes["linear_num_key_heads"], sizes["linear_key_head_dim"],
                 sizes["linear_value_head_dim"])
    taps = sizes.get("linear_conv_kernel_dim", 4)
    qk, vz = h * dk, h * dv
    w = p["in_proj"]
    qkv = u @ w[:, :2 * qk + vz]
    z = u @ w[:, 2 * qk + vz:2 * qk + 2 * vz]
    beta = jax.nn.sigmoid(u @ w[:, 2 * qk + 2 * vz:2 * qk + 2 * vz + h])
    if sizes.get("linear_allow_neg_eigval", False):
        beta = 2.0 * beta
    a = u @ w[:, 2 * qk + 2 * vz + h:]
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv"]["kernel"][j] * padded[:, j:j + t]
                          for j in range(taps)))
    q = l2_normalised(qkv[..., :qk].reshape(b, t, h, dk)) / math.sqrt(dk)
    k = l2_normalised(qkv[..., qk:2 * qk].reshape(b, t, h, dk))
    v = qkv[..., 2 * qk:].reshape(b, t, h, dv)
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(p["norm"]["scale"], o, sizes["rms_norm_eps"]) \
        * jax.nn.silu(z).reshape(b, t, h, dv)
    return o.reshape(b, t, vz) @ p["out_proj"]


def attention(p, u, heads, sizes):
    b, t, _ = u.shape
    kv, dh, eps = (sizes["num_key_value_heads"], sizes["head_dim"],
                   sizes["rms_norm_eps"])
    w = p["qkv"]
    q = u @ w[:, :heads * dh]
    k = u @ w[:, heads * dh:(heads + kv) * dh]
    v = (u @ w[:, (heads + kv) * dh:]).reshape(b, t, kv, dh)
    if sizes.get("qk_norm"):
        q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)
    q, k = q.reshape(b, t, heads, dh), k.reshape(b, t, kv, dh)
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


def logits(params, tokens, sizes):
    """Logits (B, T, V) of int tokens (B, T)."""
    embed, *blocks, norm_f, head = params
    eps = sizes["rms_norm_eps"]
    kinds = list(zip(sizes["layer_types"],
                     sizes["num_attention_heads_per_layer"]))[
        :sizes["num_hidden_layers"]]
    x = embed["table"][tokens]
    for (kind, heads), mixing, ff in zip(kinds, blocks[0::2], blocks[1::2],
                                         strict=True):
        mixer, norm = mixing["inner"]
        y = gated_deltanet(mixer, x, sizes) if kind == "linear_attention" \
            else attention(mixer, x, heads, sizes)
        x = x + rms_norm(norm["scale"], y, eps)
        mlp, norm = ff["inner"]
        x = x + rms_norm(norm["scale"], swiglu(mlp, x), eps)
    return rms_norm(norm_f["scale"], x, eps) @ head["kernel"]


def float32(variables):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])


def forward(variables, tokens, sizes, precision="highest"):
    """Logits (B, T, V) in float32 for int tokens (B, T), as ONE jitted
    program (the loops over positions and query blocks are ``lax.scan``
    and ``lax.map``).  ``precision`` is there to read what a lower one
    gives (``"bfloat16"``: the tolerance has to fail it); the comparison
    runs at ``"highest"``."""
    with jax.default_matmul_precision(precision):
        return jax.jit(lambda p, ids: logits(p, ids, sizes))(
            float32(variables), jnp.asarray(tokens))


def loss_and_grads(variables, tokens, labels, sizes):
    """Mean next-token cross-entropy and its gradient by every parameter
    (for the CPU tests; the chip's comparison is of logits)."""
    def loss(params):
        logp = jax.nn.log_softmax(logits(params, jnp.asarray(tokens), sizes),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                     axis=-1)
        return -jnp.mean(picked)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(float32(variables))
