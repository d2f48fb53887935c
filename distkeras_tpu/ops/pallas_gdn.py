"""The gated delta rule's chunks — Pallas TPU kernels.

``ops.gated_delta`` has the layer, the chunked form's equations and its
``jnp`` oracle; here are ``gdn_chunk_fwd`` / ``gdn_chunk_bwd``.  A grid
step is one chunk (C positions) of one head, on a grid (batch, head,
chunk):

* q, k and v come heads first, (B, H, T, D): a head's Dk = 96 or Dv = 192
  is a whole block's lanes, which no share of the projected (T, H·D)
  layout gives (30 heads of 96 are 22.5 blocks of 128);
* the C x C matrices (K K^T, the decays Gamma, A and T) are built in VMEM
  and never leave it;
* T = (I + A)^-1 by doubling the blocks of a block-diagonal inverse:
  with D the inverse of (I + A)'s diagonal blocks of size s, D - D A' D
  is that of its blocks of size 2s (A' the part of A below the diagonal
  blocks of size s, inside those of size 2s).  log2(C) steps of two
  matmuls, every entry an entry of a diagonal block's own inverse: with
  beta near 2 and aligned keys the product (I - A)(I + A^2)(I + A^4)...
  cancels terms of 1e9 and more, this does not (tests/test_gated_delta.py);
* the chunks of a sequence are walked in order (the backward: last to
  first) and the carry, S in the forward and dS in the backward, lives in
  a float32 VMEM scratch;
* the forward writes each chunk's incoming state (float32, (B, H, chunks,
  Dv, Dk)) and tags it and the output with ``remat.name_kernel_outputs``,
  so a recomputed layer reads them as kept and runs no forward kernel
  again; the backward computes everything else again in VMEM.

A head's running sums of log alpha and its betas arrive as rows (1, C);
where a column is needed it is read off the diagonal of a broadcast (a
select and a sum: exact).  Precision: T, A, the decays and the carried
state are float32 always, and products with T are float32 at HIGHEST;
every other product takes its operands in the inputs' dtype (float32 at
HIGHEST, bf16 at the MXU's rate into float32), as ``ops.pallas_ssm``'s.
No ``cost_estimate`` (PERF.md §3).  Off the TPU the kernels run in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.remat import name_kernel_outputs
from .pallas_attention import _NEG, _interpret

_F32 = jnp.float32


def _mm(a, b, dtype, *, ta: bool = False, tb: bool = False):
    """``a @ b`` (``ta`` / ``tb``: that operand transposed) with both
    operands in ``dtype``: float32 at HIGHEST, bf16 at the MXU's rate,
    into float32."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    if dtype == _F32:
        return lax.dot_general(a.astype(_F32), b.astype(_F32), dims,
                               precision=lax.Precision.HIGHEST)
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=_F32)


def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular (C, C) float32 ``a``,
    by doubling the blocks of a block-diagonal inverse (the module's
    docstring); any C."""
    c = a.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    r = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    d = (t == r).astype(_F32)
    shift = 0
    while (1 << shift) < c:
        below = ((t >> (shift + 1)) == (r >> (shift + 1))) \
            & (((t >> shift) & 1) == 1) & (((r >> shift) & 1) == 0)
        d = d - _mm(_mm(d, jnp.where(below, a, 0.0), _F32), d, _F32)
        shift += 1
    return d


def _rowdot(x, y):
    """Each row's dot product: (C, D) x (C, D) -> (C, 1)."""
    return jnp.sum(x * y, axis=1, keepdims=True)


class _Chunk:
    """What both kernels compute of one chunk of one head from its k, its
    running sums of log alpha and its betas: the masks, the decays, A and
    T."""

    def __init__(self, k, g_row, b_row, cd):
        c = k.shape[0]
        t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        r = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.incl, self.strict = t == r, r <= t, r < t
        self.last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
        self.g, self.b = self.col(g_row), self.col(b_row)       # (C, 1)
        g_end = jnp.sum(jnp.where(self.last, g_row, 0.0), axis=1,
                        keepdims=True)                           # (1, 1)
        self.gamma = jnp.exp(self.g)                             # gamma_t
        self.gamma_end = jnp.exp(g_end)                          # gamma_C
        self.to_end = jnp.exp(g_end - self.g)                    # gamma_C/gamma_t
        self.decay = jnp.exp(jnp.where(self.incl, self.g - g_row, _NEG))
        self.kk = _mm(k, k, cd, tb=True)
        self.a = jnp.where(self.strict, self.b * self.kk * self.decay, 0.0)
        self.t = unit_lower_inverse(self.a)

    def col(self, row):
        """(1, C) -> (C, 1) through the diagonal (adds zeros: exact)."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, sin_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    cd = q.dtype
    ch = _Chunk(k, g_ref[...], b_ref[...], cd)
    s0 = state[...]                                              # (Dv, Dk)
    sin_ref[...] = s0
    kf = k.astype(_F32)
    w = _mm(ch.t, ch.b * ch.gamma * kf, _F32)                    # (C, Dk)
    u = _mm(ch.t, ch.b * v.astype(_F32), _F32) - _mm(w, s0, cd, tb=True)
    p = _mm(q, k, cd, tb=True) * ch.decay
    o = ch.gamma * _mm(q, s0, cd, tb=True) + _mm(p, u, cd)
    o_ref[...] = o.astype(o_ref.dtype)
    state[...] = ch.gamma_end * s0 + _mm(u, ch.to_end * kf, cd, ta=True)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sin_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
    """One chunk's gradients; ``dstate`` holds the gradient by the state
    the chunk hands on, from the chunks after it (walked last to first).
    The forward's steps are taken back in reverse order."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    cd = q.dtype
    ch = _Chunk(k, g_ref[...], b_ref[...], cd)
    s0, ds = sin_ref[...], dstate[...]
    kf, vf = k.astype(_F32), v.astype(_F32)
    kb, vb = ch.b * ch.gamma * kf, ch.b * vf
    w = _mm(ch.t, kb, _F32)
    u = _mm(ch.t, vb, _F32) - _mm(w, s0, cd, tb=True)
    p = _mm(q, k, cd, tb=True) * ch.decay
    kd = ch.to_end * kf

    # S_C = gamma_C S_0 + U^T Kd
    ds0 = ch.gamma_end * ds
    dg_end = ch.gamma_end * jnp.sum(jnp.sum(s0 * ds, axis=1, keepdims=True),
                                    axis=0, keepdims=True)
    du = _mm(kd, ds, cd, tb=True)                                # (C, Dv)
    dkd = _mm(u, ds, cd)                                         # (C, Dk)
    dk = ch.to_end * dkd
    de = ch.to_end * _rowdot(kf, dkd)
    dg = -de
    dg_end = dg_end + jnp.sum(de, axis=0, keepdims=True)
    # O = diag(gamma) Q S_0^T + P U
    dqs = ch.gamma * do.astype(_F32)
    dq = _mm(dqs, s0, cd)
    ds0 = ds0 + _mm(dqs, q, cd, ta=True)
    dg = dg + _rowdot(_mm(q, s0, cd, tb=True), dqs)
    dp = _mm(do, u, cd, tb=True)                                 # (C, C)
    du = du + _mm(p, do, cd, ta=True)
    dpp = dp * p
    dg_row = -jnp.sum(dpp, axis=0, keepdims=True)
    dg = dg + jnp.sum(dpp, axis=1, keepdims=True)
    dqk = dp * ch.decay
    dq = dq + _mm(dqk, k, cd)
    dk = dk + _mm(dqk, q, cd, ta=True)
    # U = T Vb - W S_0^T, W = T Kb
    dw = -_mm(du, s0, cd)
    ds0 = ds0 - _mm(du, w, cd, ta=True)
    dt = _mm(dw, kb, _F32, tb=True) + _mm(du, vb, _F32, tb=True)
    dkb = _mm(ch.t, dw, _F32, ta=True)
    dvb = _mm(ch.t, du, _F32, ta=True)
    dk = dk + ch.b * ch.gamma * dkb
    kdkb = ch.gamma * _rowdot(kf, dkb)            # by beta_t of Kb's row t
    db = kdkb + _rowdot(vf, dvb)
    dg = dg + ch.b * kdkb
    dv = ch.b * dvb
    # T = (I + A)^-1, A = tril_-1(diag(beta) K K^T . Gamma)
    da = jnp.where(ch.strict, -_mm(_mm(ch.t, dt, _F32, ta=True), ch.t, _F32,
                                   tb=True), 0.0)
    daa = da * ch.a
    db = db + jnp.sum(da * ch.kk * ch.decay, axis=1, keepdims=True)
    dg = dg + jnp.sum(daa, axis=1, keepdims=True)
    dg_row = dg_row - jnp.sum(daa, axis=0, keepdims=True)
    dkk = da * ch.b * ch.decay
    dk = dk + _mm(dkk, k, cd) + _mm(dkk, k, cd, ta=True)

    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = ch.row(dg) + dg_row + jnp.where(ch.last, dg_end, 0.0)
    db_ref[...] = ch.row(db)
    dstate[...] = ds0


def _specs(q, v, g, reverse: bool):
    """Block specs of (a (B, H, T, Dk) array, a (B, H, T, Dv) array, a
    (B, H, chunks, 1, C) row, the (B, H, chunks, Dv, Dk) states) on the
    grid (batch, head, chunk); ``reverse``: the chunks last to first."""
    nc, c = g.shape[2], g.shape[4]
    dk, dv = q.shape[-1], v.shape[-1]
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    return (
        pl.BlockSpec((None, None, c, dk), lambda b, h, ci: (b, h, at(ci), 0)),
        pl.BlockSpec((None, None, c, dv), lambda b, h, ci: (b, h, at(ci), 0)),
        pl.BlockSpec((None, None, None, 1, c),
                     lambda b, h, ci: (b, h, at(ci), 0, 0)),
        pl.BlockSpec((None, None, None, dv, dk),
                     lambda b, h, ci: (b, h, at(ci), 0, 0)))


def _call(kernel, name, q, v, g, in_specs, out_specs, out_shape, interpret):
    bsz, h, nc = g.shape[:3]
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=(bsz, h, nc), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((v.shape[-1], q.shape[-1]), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_fwd(q, k, v, g, beta, *, interpret):
    qk, wide, row, states = _specs(q, v, g, reverse=False)
    bsz, h, nc = g.shape[:3]
    return _call(
        _fwd_kernel, "gdn_chunk_fwd", q, v, g, [qk, qk, wide, row, row],
        [wide, states],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((bsz, h, nc, v.shape[-1], q.shape[-1]), _F32)],
        interpret)(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_bwd(q, k, v, g, beta, s_in, do, *, interpret):
    qk, wide, row, states = _specs(q, v, g, reverse=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return _call(
        _bwd_kernel, "gdn_chunk_bwd", q, v, g,
        [qk, qk, wide, row, row, states, wide], [qk, qk, wide, row, row],
        [like(q), like(k), like(v), like(g), like(beta)], interpret)(
            q, k, v, g, beta, s_in, do)


@jax.custom_vjp
def gdn_chunks(q, k, v, g, beta):
    """``ops.gated_delta._gdn_chunked``'s contract: ``q``, ``k`` (B, H,
    T, Dk), ``v`` (B, H, T, Dv), ``g`` (the running sums of log alpha in
    a chunk) and ``beta`` (B, H, T / C, 1, C) float32 -> o (B, H, T,
    Dv)."""
    return _gdn_pallas_fwd(q, k, v, g, beta)[0]


def _gdn_pallas_fwd(q, k, v, g, beta):
    o, s_in = _gdn_fwd(q, k, v, g, beta, interpret=_interpret())
    # what a checkpoint around the layer keeps (``models.remat``): the
    # recomputed forward then needs no kernel; outside one, nothing
    o, s_in = name_kernel_outputs(o, s_in, kernel="gdn")
    return o, (q, k, v, g, beta, s_in)


def _gdn_pallas_bwd(res, do):
    return _gdn_bwd(*res, do.astype(res[2].dtype), interpret=_interpret())


gdn_chunks.defvjp(_gdn_pallas_fwd, _gdn_pallas_bwd)
