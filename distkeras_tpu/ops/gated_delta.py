"""Gated DeltaNet mixer: a delta rule with a decay, on a chunked form.

The layer (Yang et al. 2024, "Gated Delta Networks", arXiv:2412.06464,
with the negative eigenvalues of Grazzi et al. 2024, arXiv:2411.12537;
the ``olmo_hybrid`` family's ``linear_attention`` layers) maps (T, D) to
(T, D) with a (Dv x Dk) state a head, carried along the sequence:

    [q | k | v | z | b | a] = x W_in             widths H·Dk, H·Dk, H·Dv,
                                                  H·Dv, H, H
    q, k, v = silu(conv(q, k, v))                 causal, depthwise, K taps
    q^ = q / |q| · Dk^-1/2,  k^ = k / |k|         a head's own norms
    beta = 2 sigmoid(b)    (sigmoid(b) without negative eigenvalues)
    log alpha = -exp(A_log) softplus(a + dt_bias)                  float32
    S_t = alpha_t S_{t-1} (I - beta_t k^_t k^_t^T) + beta_t v_t k^_t^T
    o_t = S_t q^_t
    y = RMSNorm over each head's Dv of o, times silu(z);  out = y W_out

The recurrence runs in the chunked form (section 3 of the paper).  In a
chunk of C positions with gamma_t the product of the alphas up to t,
Gamma[t, r] = gamma_t / gamma_r, and S_0 the state the chunk receives:

    A = tril_-1(diag(beta) K K^T . Gamma),   T = (I + A)^-1
    W = T diag(beta gamma) K,   U = T diag(beta) V - W S_0^T
    O = diag(gamma) Q S_0^T + (Q K^T . Gamma . tril) U
    S_C = gamma_C S_0 + U^T diag(gamma_C / gamma) K

so all but T and the carry is matmuls of C x C, C x Dk and C x Dv.  Decays,
T and the carried state are float32; with beta up to 2 the state's
eigenvalues lie in (-1, 1) and T's entries do not shrink.  A length that
is no multiple of the chunk is padded at the end with k = 0, beta = 0 and
alpha = 1: those rows change no state and are cut off the output.

``impl="chunked"``: the form above as ``jnp`` einsums (T by a triangular
solve), gradients by autodiff: the CPU path and the kernels' oracle.
``impl="pallas"``: ``ops.pallas_gdn``'s ``gdn_chunk_fwd`` /
``gdn_chunk_bwd`` (a custom VJP, the carry in the kernel), imported at
first use as ``ops.ssm`` imports its kernels.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..models.layers import Layer, glorot_uniform, register
from ..models.remat import sizing
from ..obs.registry import default_registry
from .ssm import _NEG, causal_conv

#: added to a head's squared norm before the root (the ``fla`` library's
#: ``l2norm``)
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# the delta rule
# ---------------------------------------------------------------------------

def gated_delta_rule(q, k, v, g, beta, *, chunk: int,
                     impl: str = "chunked"):
    """``o_t = S_t q_t`` of the recurrence in the module's docstring.

    ``q``, ``k`` (B, T, H, Dk): q^ and k^, normalised (and q scaled);
    ``v`` (B, T, H, Dv); ``g`` (B, T, H) float32, log alpha (<= 0);
    ``beta`` (B, T, H) float32.  Returns (B, T, H, Dv) in ``v``'s
    dtype."""
    bsz, t, h, _ = q.shape
    pad = -t % chunk
    if pad:  # k = 0, beta = 0, alpha = 1: the state passes through
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    heads_first = lambda a: jnp.moveaxis(a, 2, 1)  # noqa: E731

    def rows(a):  # (B, T, H) -> (B, H, chunks, 1, C) float32
        return heads_first(a.astype(jnp.float32)).reshape(
            bsz, h, nc, 1, chunk)

    # the running sums of log alpha inside a chunk, as a product with a
    # triangle of ones (``ops.ssm.ssd`` does the same: XLA's cumsum is a
    # reduce-window)
    gc = jnp.einsum("...r,rt->...t", rows(g),
                    jnp.triu(jnp.ones((chunk, chunk), jnp.float32)),
                    precision=lax.Precision.HIGHEST)
    if not sizing():  # the recompute plan's own trace of a child
        default_registry().counter("gdn.chunks").inc(nc)
    if impl == "pallas":
        from .pallas_gdn import gdn_chunks as run
    else:
        run = _gdn_chunked
    o = run(heads_first(q), heads_first(k), heads_first(v), gc, rows(beta))
    return jnp.moveaxis(o, 1, 2)[:, :t]


def _gdn_chunked(q, k, v, gc, beta):
    """The chunked form in einsums.  ``q``, ``k`` (B, H, T, Dk), ``v``
    (B, H, T, Dv); ``gc`` (the running sums of log alpha in a chunk) and
    ``beta`` (B, H, T / C, 1, C) float32."""
    bsz, h, nc, _, c = gc.shape
    f32 = jnp.float32
    by_chunk = lambda a: a.reshape(bsz, h, nc, c, a.shape[-1]) \
        .astype(f32)  # noqa: E731
    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    g, b = gc[..., 0, :], beta[..., 0, :]                  # (B, H, nc, C)
    gamma = jnp.exp(jnp.where(jnp.tril(jnp.ones((c, c), bool)),
                              g[..., :, None] - g[..., None, :], _NEG))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(strict, b[..., :, None] * gamma
                  * jnp.einsum("...td,...rd->...tr", kc, kc), 0.0)
    eye = jnp.eye(c, dtype=f32)
    t_inv = jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)
    w = t_inv @ ((b * jnp.exp(g))[..., None] * kc)
    u_own = t_inv @ (b[..., None] * vc)
    p = jnp.einsum("...td,...rd->...tr", qc, kc) * gamma
    qg = jnp.exp(g)[..., None] * qc
    kd = jnp.exp(g[..., -1:] - g)[..., None] * kc
    moved = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731

    def carry(state, now):  # state (B, H, Dv, Dk): what the chunk receives
        w_c, u_c, p_c, qg_c, kd_c, end = now
        u = u_c - jnp.einsum("bhtd,bhvd->bhtv", w_c, state)
        o = jnp.einsum("bhtd,bhvd->bhtv", qg_c, state) + p_c @ u
        state = end[..., None, None] * state \
            + jnp.einsum("bhtv,bhtd->bhvd", u, kd_c)
        return state, o

    _, o = lax.scan(carry, jnp.zeros((bsz, h, vc.shape[-1], kc.shape[-1]),
                                     f32),
                    tuple(map(moved, (w, u_own, p, qg, kd,
                                      jnp.exp(g[..., -1])))))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape).astype(v.dtype)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def l2_normalised(x):
    """``x / sqrt(sum(x^2) + L2_EPS)`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS)


@register
class GatedDeltaNet(Layer):
    """The Gated DeltaNet mixer of the module's docstring over (T, D)
    inputs: ``num_heads`` heads with keys and queries of ``key_head_dim``
    and values of ``value_head_dim``, a causal depthwise convolution of
    ``conv_kernel`` taps without bias on q, k and v, beta in (0, 2) where
    ``allow_neg_eigval`` (else (0, 1)), the rule in chunks of
    ``chunk_size`` (``impl``: ``"chunked"`` einsums or the ``"pallas"``
    kernels), an RMSNorm of each head's output with ``norm_eps`` and one
    weight of ``value_head_dim`` shared by the heads, gated by silu(z).
    No bias on either projection.

    Parameters: ``in_proj`` (D, 2·H·Dk + 2·H·Dv + 2·H), columns [q | k |
    v | z | b | a]; ``conv.kernel`` (K, 2·H·Dk + H·Dv); ``A_log``,
    ``dt_bias`` (H,), float32 in a bf16 step too
    (``parallel.sync.FLOAT32_KEYS``); ``norm.scale`` (Dv,); ``out_proj``
    (H·Dv, D).  Initialised as Mamba-2's are: A uniform in [1, 16], dt
    log-uniform in [0.001, 0.1] through the inverse softplus."""

    time_mixing = True  # no decode state yet: generate by full recompute

    def __init__(self, num_heads: int, key_head_dim: int,
                 value_head_dim: int, conv_kernel: int = 4,
                 chunk_size: int = 64, norm_eps: float = 1e-6,
                 allow_neg_eigval: bool = True, impl: str = "chunked"):
        if impl not in ("chunked", "pallas"):
            raise ValueError(f"impl must be 'chunked' or 'pallas', got "
                             f"{impl!r}")
        self.num_heads, self.key_head_dim = int(num_heads), int(key_head_dim)
        self.value_head_dim = int(value_head_dim)
        self.conv_kernel, self.chunk_size = int(conv_kernel), int(chunk_size)
        self.norm_eps = float(norm_eps)
        self.allow_neg_eigval, self.impl = bool(allow_neg_eigval), impl

    @property
    def _widths(self):
        """(one of q / k = H·Dk, one of v / z = H·Dv)."""
        return (self.num_heads * self.key_head_dim,
                self.num_heads * self.value_head_dim)

    def init(self, rng, in_shape):
        d, h = in_shape[-1], self.num_heads
        qk, vz = self._widths
        k_in, k_conv, k_a, k_dt, k_out = jax.random.split(rng, 5)
        dt = jnp.exp(jax.random.uniform(
            k_dt, (h,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "in_proj": glorot_uniform(k_in, (d, 2 * qk + 2 * vz + 2 * h)),
            "conv": {"kernel": jax.random.uniform(
                k_conv, (self.conv_kernel, 2 * qk + vz), jnp.float32,
                -bound, bound)},
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": {"scale": jnp.ones((self.value_head_dim,))},
            "out_proj": glorot_uniform(k_out, (vz, d)),
        }, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        bsz, t, _ = x.shape
        h, dk, dv = self.num_heads, self.key_head_dim, self.value_head_dim
        qk, vz = self._widths
        f32 = jnp.float32
        with jax.named_scope("in_proj"):
            proj = x @ params["in_proj"].astype(x.dtype)
            qkv, z = proj[..., :2 * qk + vz], proj[..., 2 * qk + vz:2 * qk
                                                   + 2 * vz]
            b, a = (proj[..., 2 * qk + 2 * vz:].astype(f32)
                    .reshape(bsz, t, 2, h)[:, :, i] for i in (0, 1))
            beta = jax.nn.sigmoid(b) * (2.0 if self.allow_neg_eigval
                                        else 1.0)
            g = -jnp.exp(params["A_log"].astype(f32)) \
                * jax.nn.softplus(a + params["dt_bias"].astype(f32))
        with jax.named_scope("conv"):
            qkv = jax.nn.silu(causal_conv(qkv, params["conv"]["kernel"]))
            q = l2_normalised(qkv[..., :qk].reshape(bsz, t, h, dk)) \
                * (dk ** -0.5)
            k = l2_normalised(qkv[..., qk:2 * qk].reshape(bsz, t, h, dk))
            v = qkv[..., 2 * qk:].reshape(bsz, t, h, dv)
        with jax.named_scope("delta_rule"):
            o = gated_delta_rule(q.astype(x.dtype), k.astype(x.dtype),
                                 v.astype(x.dtype), g, beta,
                                 chunk=self.chunk_size, impl=self.impl)
        with jax.named_scope("gated_norm"):
            o = o.astype(f32)
            o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.norm_eps)
            o = (o * params["norm"]["scale"].astype(f32)).reshape(
                bsz, t, vz) * jax.nn.silu(z.astype(f32))
        with jax.named_scope("out_proj"):
            return o.astype(x.dtype) @ params["out_proj"].astype(x.dtype), \
                state

    def get_config(self):
        return {"num_heads": self.num_heads,
                "key_head_dim": self.key_head_dim,
                "value_head_dim": self.value_head_dim,
                "conv_kernel": self.conv_kernel,
                "chunk_size": self.chunk_size, "norm_eps": self.norm_eps,
                "allow_neg_eigval": self.allow_neg_eigval,
                "impl": self.impl}
