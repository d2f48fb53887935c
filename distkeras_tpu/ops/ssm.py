"""Mamba-2 mixer: a selective state-space layer on a chunked scan.

The layer (Dao & Gu 2024, "Transformers are SSMs"; the ``nemotron_h``
family's ``M`` blocks) maps (T, D) to (T, D) with a state a head that is
carried along the sequence and never grows with it:

    [z | xBC | dt] = u W_in                   widths H·P | H·P + 2·G·N | H
    xBC = silu(conv(xBC))                     causal, depthwise, K taps, bias
    xBC -> x (H heads of P) | B (G groups of N) | C (G groups of N)
    dt  = softplus(dt + dt_bias),  A = -exp(A_log)            float32, (H,)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t            S in R^{P x N}
    y_t = S_t C_t + D_h x_t                   head h reads group h // (H/G)
    y   = RMSNorm over each group of H·P/G channels of (y · silu(z))
    out = y W_out

The recurrence is run in the chunked (SSD) form.  With ``a_t = dt_t A_h``
and ``s_t`` its running sum inside a chunk of L positions:

    Y_diag[t] = sum_{r <= t} exp(s_t - s_r) (C_t . B_r) dt_r x_r
    S_c       = sum_r exp(s_end - s_r) dt_r x_r (x) B_r       the chunk's own
    S_in,c+1  = exp(s_end,c) S_in,c + S_c                     the carry
    Y_off[t]  = exp(s_t) S_in,c C_t
    y         = Y_diag + Y_off + D x

so all but the carry is matmuls of L x L, L x N and L x P.  Decays, sums
and the carried state are float32; matmul operands are in the activations'
dtype.  A length that is no multiple of the chunk is padded at the end
with rows of ``dt = 0``: they decay nothing, add nothing to any state and
are cut off the output.

``impl="chunked"``: the form above as ``jnp`` einsums, gradients by
autodiff; the CPU path and the kernels' oracle.  The L x L decay mask of
every head and chunk goes through HBM (T·H·L·4 bytes a pass).

``impl="pallas"``: ``ops.pallas_ssm``'s ``ssd_chunk_fwd`` /
``ssd_chunk_bwd`` (the mask built in VMEM, the carry in the kernel, a
custom VJP), imported at first use as ``ops.moe`` imports its kernels:
Pallas costs a second or two at start-up, which every ``import
distkeras_tpu`` would pay.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..models.layers import Layer, glorot_uniform, register
from ..models.remat import sizing
from ..obs.registry import default_registry

_NEG = -1e30  # exp() of it is 0.0: the mask's fill, never an inf - inf


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

def _by_chunk(v, chunk: int, groups: int):
    """(B, T, H) float32 -> (B, T / chunk, G, H / G, chunk): a head's
    ``chunk`` values of one chunk side by side."""
    b, t, h = v.shape
    return v.reshape(b, t // chunk, chunk, groups, h // groups) \
        .transpose(0, 1, 3, 4, 2)


def ssd(x, dt, a, b, c, *, chunk: int, impl: str = "chunked"):
    """``y_t = S_t C_t`` of the recurrence in the module's docstring,
    without the ``D x`` term.

    ``x`` (B, T, H, P); ``dt`` (B, T, H) float32, after its softplus;
    ``a`` (H,) float32, negative; ``b``, ``c`` (B, T, G, N).  Returns
    (B, T, H, P) in ``x``'s dtype."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    pad = -t % chunk
    if pad:  # dt = 0 rows: no decay, nothing added to any state
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    dt5 = _by_chunk(dt.astype(jnp.float32), chunk, g)
    # the running sums inside a chunk, as a product with a triangle of
    # ones: XLA's cumsum is a reduce-window, 0.75 ms a mixer a pass on a
    # v5e for these 0.5 M numbers (11 a step: 8.3 ms of 351; PR 34)
    s5 = jnp.einsum(
        "...r,rt->...t", dt5 * a.astype(jnp.float32).reshape(g, h // g, 1),
        jnp.triu(jnp.ones((chunk, chunk), jnp.float32)),
        precision=lax.Precision.HIGHEST)
    if not sizing():  # the recompute plan's own trace of a child
        default_registry().counter("ssm.chunks").inc((t + pad) // chunk)
    if impl == "pallas":
        from .pallas_ssm import ssd_chunks as run
    else:
        run = _ssd_chunked
    y = run(x.reshape(bsz, t + pad, h * p), b.reshape(bsz, t + pad, g * n),
            c.reshape(bsz, t + pad, g * n), dt5, s5)
    return y.reshape(bsz, t + pad, h, p)[:, :t]


def _ssd_chunked(x, b, c, dt, s):
    """The chunked form in einsums.  ``x`` (B, T, H·P), ``b`` / ``c``
    (B, T, G·N), ``dt`` / ``s`` (B, T / L, G, H / G, L)."""
    bsz, nc, g, hg, L = dt.shape
    p, n = x.shape[-1] // (g * hg), b.shape[-1] // g
    cd = x.dtype
    xr = x.reshape(bsz, nc, L, g, hg, p)
    br, cr = (v.reshape(bsz, nc, L, g, n) for v in (b, c))
    rows = lambda v: v.transpose(0, 1, 4, 2, 3)[..., None]  # noqa: E731

    tri = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(tri, s[..., :, None] - s[..., None, :], _NEG))
    cb = jnp.einsum("bctgn,bcrgn->bcgtr", cr, br,
                    preferred_element_type=jnp.float32)
    m = cb[:, :, :, None] * decay * dt[..., None, :]
    y = jnp.einsum("bcgktr,bcrgkp->bctgkp", m.astype(cd), xr,
                   preferred_element_type=jnp.float32)

    v = jnp.exp(s[..., -1:] - s) * dt
    own = jnp.einsum("bcrgkp,bcrgn->bcgkpn",
                     (xr.astype(jnp.float32) * rows(v)).astype(cd), br,
                     preferred_element_type=jnp.float32)

    def carry(state, chunk):
        decay_end, own_c = chunk
        return decay_end[..., None, None] * state + own_c, state

    _, s_in = lax.scan(carry, jnp.zeros_like(own[:, 0]), (
        jnp.moveaxis(jnp.exp(s[..., -1]), 1, 0), jnp.moveaxis(own, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                  # (B, nc, G, Hg, P, N)
    y = y + rows(jnp.exp(s)) * jnp.einsum(
        "bctgn,bcgkpn->bctgkp", cr, s_in.astype(cd),
        preferred_element_type=jnp.float32)
    return y.astype(cd).reshape(x.shape)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution over time: ``out[t] = bias + sum_k
    kernel[k] x[t - (K - 1) + k]`` (zeros before the start), summed in
    float32.  ``x`` (B, T, C); ``kernel`` (K, C); ``bias`` (C,) or
    None."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        term = kernel[k].astype(jnp.float32) \
            * padded[:, k:k + t].astype(jnp.float32)
        out = term if out is None else out + term
    return out


@register
class Mamba2Mixer(Layer):
    """The Mamba-2 mixer of the module's docstring over (T, D) inputs:
    ``num_heads`` heads of ``head_dim`` with a state of ``state_size`` a
    channel, B and C shared by the heads of each of ``n_groups`` groups,
    a causal depthwise convolution of ``conv_kernel`` taps, the scan in
    chunks of ``chunk_size`` (``impl``: ``"chunked"`` einsums or the
    ``"pallas"`` kernels), a gated RMSNorm a group with ``norm_eps``.  No
    bias on either projection.

    Parameters: ``in_proj`` (D, 2·H·P + 2·G·N + H), columns [z | x | B |
    C | dt]; ``conv.kernel`` (K, H·P + 2·G·N) and ``conv.bias``;
    ``A_log``, ``dt_bias``, ``D`` (H,), float32 in a bf16 step too
    (``parallel.sync.FLOAT32_KEYS``); ``norm.scale`` (H·P,); ``out_proj``
    (H·P, D).  Initialised as the published code does: ``A = -(1..H)``,
    ``D = 1``, ``dt`` log-uniform in [0.001, 0.1] through the inverse
    softplus."""

    time_mixing = True  # no decode cache yet: generate by full recompute

    def __init__(self, num_heads: int, head_dim: int, state_size: int,
                 n_groups: int = 1, conv_kernel: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5,
                 impl: str = "chunked"):
        if impl not in ("chunked", "pallas"):
            raise ValueError(f"impl must be 'chunked' or 'pallas', got "
                             f"{impl!r}")
        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads do not divide into "
                             f"{n_groups} groups")
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.state_size, self.n_groups = int(state_size), int(n_groups)
        self.conv_kernel, self.chunk_size = int(conv_kernel), int(chunk_size)
        self.norm_eps, self.impl = float(norm_eps), impl

    @property
    def _widths(self):
        """(inner = H·P, one of B / C = G·N)."""
        return (self.num_heads * self.head_dim,
                self.n_groups * self.state_size)

    def init(self, rng, in_shape):
        d, h = in_shape[-1], self.num_heads
        inner, bc = self._widths
        k_in, k_conv, k_dt, k_out = jax.random.split(rng, 4)
        dt = jnp.exp(jax.random.uniform(
            k_dt, (h,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "in_proj": glorot_uniform(k_in, (d, 2 * inner + 2 * bc + h)),
            "conv": {"kernel": jax.random.uniform(
                k_conv, (self.conv_kernel, inner + 2 * bc), jnp.float32,
                -bound, bound), "bias": jnp.zeros((inner + 2 * bc,))},
            "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((h,), jnp.float32),
            "norm": {"scale": jnp.ones((inner,))},
            "out_proj": glorot_uniform(k_out, (inner, d)),
        }, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        bsz, t, _ = x.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = self._widths
        f32 = jnp.float32
        with jax.named_scope("in_proj"):
            zxbcdt = x @ params["in_proj"].astype(x.dtype)
            z = zxbcdt[..., :inner]
            xbc = zxbcdt[..., inner:2 * inner + 2 * bc]
            dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * bc:].astype(f32)
                                 + params["dt_bias"].astype(f32))
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(
                xbc, params["conv"]["kernel"], params["conv"]["bias"])
            ).astype(x.dtype)
            xs = xbc[..., :inner].reshape(bsz, t, h, p)
        with jax.named_scope("ssd"):
            y = ssd(xs, dt, -jnp.exp(params["A_log"].astype(f32)),
                    xbc[..., inner:inner + bc].reshape(bsz, t, g, n),
                    xbc[..., inner + bc:].reshape(bsz, t, g, n),
                    chunk=self.chunk_size, impl=self.impl)
        with jax.named_scope("gated_norm"):
            y = y.astype(f32) + params["D"].astype(f32)[:, None] \
                * xs.astype(f32)
            y = (y.reshape(bsz, t, inner) * jax.nn.silu(z.astype(f32))) \
                .reshape(bsz, t, g, inner // g)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.norm_eps)
            y = (y.reshape(bsz, t, inner)
                 * params["norm"]["scale"].astype(f32)).astype(x.dtype)
        with jax.named_scope("out_proj"):
            return y @ params["out_proj"].astype(x.dtype), state

    def get_config(self):
        return {"num_heads": self.num_heads, "head_dim": self.head_dim,
                "state_size": self.state_size, "n_groups": self.n_groups,
                "conv_kernel": self.conv_kernel,
                "chunk_size": self.chunk_size, "norm_eps": self.norm_eps,
                "impl": self.impl}
