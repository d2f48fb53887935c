"""Plain reference for the ``gpt2-*`` configurations.

The forward pass of GPT-2 (Radford et al. 2019, "Language Models are
Unsupervised Multitask Learners"; blocks as in Radford et al. 2018 with
the layer norm moved to each sub-block's input and one more after the
last block) in straightforward float32 ``jax.numpy``: no kernel, no
cache, no batching tricks, one T x T score matrix a head.  It reads the
variables tree ``zoo.gpt_lm`` makes and nothing else of the program.

Departures from the paper, all ``zoo.gpt_lm``'s and followed here so the
two compute the same function: the output head is a separate d x V
matrix with a bias, NOT the transposed embedding (163 M parameters
against the paper's 124 M); the fused qkv and the attention output
projections carry no bias; gelu is the tanh approximation (as in the
released GPT-2 code).

On a TPU a float32 matmul rounds its inputs to bfloat16 unless told
otherwise, so ``forward`` sets ``jax.default_matmul_precision("highest")``
itself.
"""

import math

import jax
import jax.numpy as jnp


def layer_norm(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(p, x, num_heads):
    b, t, d = x.shape
    dh = d // num_heads
    q, k, v = jnp.split(x @ p["qkv"], 3, axis=-1)

    def heads(a):
        return a.reshape(b, t, num_heads, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]
    scores = jnp.where(future, -jnp.inf, scores)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, t, d) @ p["out"]


def forward(variables, tokens, sizes):
    """Logits (B, T, V) in float32 for int tokens (B, T)."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), variables["params"])
    embed, positions, *blocks, ln_f, head = params
    t = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        x = embed["table"][tokens] + positions["table"][:t]
        for attn, ff in zip(blocks[0::2], blocks[1::2]):
            ln1, mha = attn["inner"]
            x = x + attention(mha, layer_norm(ln1, x), sizes["num_heads"])
            ln2, up, down = ff["inner"]
            h = gelu(layer_norm(ln2, x) @ up["kernel"] + up["bias"])
            x = x + h @ down["kernel"] + down["bias"]
        return layer_norm(ln_f, x) @ head["kernel"] + head["bias"]
