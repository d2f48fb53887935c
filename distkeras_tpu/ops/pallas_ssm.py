"""The Mamba-2 scan's chunks — Pallas TPU kernels.

``ops.ssm`` has the layer, the chunked form's equations and its ``jnp``
oracle; here are ``ssd_chunk_fwd`` / ``ssd_chunk_bwd``.  A grid step is one
chunk (L positions) of one group's heads, on a grid (batch, group, chunk):

* the L x L decay mask ``exp(s_t - s_r)`` is built in VMEM from the
  chunk's L running sums and never leaves it (as einsums it goes through
  HBM: T·H·L·4 bytes a pass, 268 MB a mixer at the published sizes);
* ``C B^T`` is computed once for the group's heads, and B's and C's
  gradients come out summed over them;
* heads narrower than 128 lanes are taken ``128 // P`` at a time, told
  apart by lane masks, so every block and every matmul is 128 lanes wide
  and no slice cuts a vreg;
* the chunks of a sequence are walked in order (the backward: last to
  first) and the carry ``S_in,c+1 = exp(s_end,c) S_in,c + S_c`` lives in a
  float32 VMEM scratch.  A kernel that takes ``S_in`` cannot also give the
  ``S_c`` that ``S_in`` is made from, so a carry left to XLA would cost two
  kernels a pass and the states' round trip through HBM;
* the forward writes each chunk's ``S_in`` (what the backward needs beside
  the inputs: float32, (B, chunks, H, P, N)) and tags it and ``y`` with
  ``remat.name_kernel_outputs``, so a recomputed layer reads them as kept
  and runs no forward kernel again; everything else the backward needs it
  computes again in VMEM.

A head's sums and steps arrive as rows (Hg, L), lane-dense; where a column
is needed it is read off the diagonal of a broadcast (a select and a sum:
exact).  Precision follows ``pallas_attention._dot``: float32 operands
multiply at HIGHEST, bf16 at the MXU's rate into float32; decays, sums and
the carried state are float32 always.  No ``cost_estimate`` (PERF.md §3).
Off the TPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.remat import name_kernel_outputs
from .pallas_attention import _NEG, _dot, _dot_t, _interpret
from .pallas_moe import _dot_tn


def _pack(heads: int, head_dim: int) -> int:
    """Heads of one group taken side by side in a block's lanes: as many
    as fit 128 lanes, a divisor of the group's ``heads``."""
    return max(k for k in range(1, heads + 1)
               if heads % k == 0 and k * head_dim <= max(128, head_dim))


def _total(v):
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


class _Chunk:
    """What both kernels read of one chunk of one group: its B and C,
    ``C B^T``, the masks, and a head's row of sums and steps."""

    def __init__(self, b_ref, c_ref, dt_ref, s_ref, pack, head_dim):
        self.b, self.c = b_ref[...], c_ref[...]
        self.cb = _dot_t(self.c, self.b)                      # (L, L)
        L, self.width = self.b.shape[0], pack * head_dim
        self.L, self.head_dim = L, head_dim
        self.dt_ref, self.s_ref = dt_ref, s_ref
        t = lax.broadcasted_iota(jnp.int32, (L, L), 0)
        r = lax.broadcasted_iota(jnp.int32, (L, L), 1)
        self.tri, self.eye = r <= t, r == t
        self.last = lax.broadcasted_iota(jnp.int32, (1, L), 1) == L - 1
        self.lane = lax.broadcasted_iota(jnp.int32, (L, self.width), 1)
        self.row = lax.broadcasted_iota(
            jnp.int32, (self.width, self.b.shape[1]), 0)

    def col(self, row):
        """(1, L) -> (L, 1) through the diagonal (adds zeros: exact)."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def as_row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def of_head(self, iota, j):
        return (iota >= j * self.head_dim) & (iota < (j + 1) * self.head_dim)

    def head(self, h):
        """A head's (s row, s column, dt row, dt column, s_end (1, 1),
        decay mask (L, L))."""
        s_r, dt_r = self.s_ref[h:h + 1, :], self.dt_ref[h:h + 1, :]
        s_c = self.col(s_r)
        s_end = jnp.sum(jnp.where(self.last, s_r, 0.0), axis=1,
                        keepdims=True)
        decay = jnp.exp(jnp.where(self.tri, s_c - s_r, _NEG))
        return s_r, s_c, dt_r, self.col(dt_r), s_end, decay


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, s_ref, y_ref, sin_ref, state,
                *, heads: int, head_dim: int, pack: int):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    k = _Chunk(b_ref, c_ref, dt_ref, s_ref, pack, head_dim)
    cd, f32 = x_ref.dtype, jnp.float32
    sin_ref[...] = state[...]
    for lo in range(0, heads * head_dim, k.width):
        cols = slice(lo, lo + k.width)
        x = x_ref[:, cols]
        s_in = state[cols, :]                                  # (W, N)
        y = jnp.zeros((k.L, k.width), f32)
        e_t = jnp.zeros((k.L, k.width), f32)   # exp(s_t), by head's lanes
        v = jnp.zeros((k.L, k.width), f32)     # exp(s_end - s_r) dt_r
        keep = jnp.zeros(s_in.shape, f32)      # exp(s_end), by head's rows
        for j in range(pack):
            _, s_c, dt_r, dt_c, s_end, decay = k.head(
                lo // head_dim + j)
            mine = k.of_head(k.lane, j)
            m = (k.cb * decay * dt_r).astype(cd)
            y = jnp.where(mine, _dot(m, x), y)
            e_t = jnp.where(mine, jnp.exp(s_c), e_t)
            v = jnp.where(mine, jnp.exp(s_end - s_c) * dt_c, v)
            keep = jnp.where(k.of_head(k.row, j), jnp.exp(s_end), keep)
        y = y + e_t * _dot_t(k.c, s_in.astype(cd))
        y_ref[:, cols] = y.astype(y_ref.dtype)
        state[cols, :] = keep * s_in + _dot_tn(
            (x.astype(f32) * v).astype(cd), k.b)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, s_ref, sin_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, ds_ref, dstate, *,
                heads: int, head_dim: int, pack: int):
    """One chunk's gradients; ``dstate`` holds the gradient by the state
    the chunk hands on, from the chunks after it (walked last to first)."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    k = _Chunk(b_ref, c_ref, dt_ref, s_ref, pack, head_dim)
    cd, f32 = x_ref.dtype, jnp.float32
    head_row = lax.broadcasted_iota(jnp.int32, (heads, k.L), 0)
    dcb = jnp.zeros((k.L, k.L), f32)
    db = jnp.zeros(k.b.shape, f32)
    dc = jnp.zeros(k.b.shape, f32)
    ddt = jnp.zeros((heads, k.L), f32)
    ds = jnp.zeros((heads, k.L), f32)
    for lo in range(0, heads * head_dim, k.width):
        cols = slice(lo, lo + k.width)
        x, dy = x_ref[:, cols], dy_ref[:, cols]
        s_in, d_out = sin_ref[cols, :], dstate[cols, :]        # (W, N)
        z = _dot_t(k.c, s_in.astype(cd))           # S_in C_t, by head
        w = _dot_t(k.b, d_out.astype(cd))          # dS B_r, by head
        xw = x.astype(f32) * w
        dyz = dy.astype(f32) * z
        held = d_out * s_in
        dx = jnp.zeros((k.L, k.width), f32)
        e_t = jnp.zeros((k.L, k.width), f32)
        v = jnp.zeros((k.L, k.width), f32)
        keep = jnp.zeros(s_in.shape, f32)
        for j in range(pack):
            h = lo // head_dim + j
            _, s_c, dt_r, dt_c, s_end, decay = k.head(h)
            mine = k.of_head(k.lane, j)
            cbd = k.cb * decay
            m = cbd * dt_r
            dm = _dot_t(jnp.where(mine, dy, jnp.zeros_like(dy)), x)
            dx = jnp.where(mine, _dot_tn(m.astype(cd), dy), dx)
            q = dm * m
            dcb = dcb + dm * decay * dt_r
            e_s, e_v = jnp.exp(s_c), jnp.exp(s_end - s_c)
            v_c = e_v * dt_c
            dv = jnp.sum(jnp.where(mine, xw, 0.0), axis=1, keepdims=True)
            ds_c = jnp.sum(q, axis=1, keepdims=True) - dv * v_c + e_s \
                * jnp.sum(jnp.where(mine, dyz, 0.0), axis=1, keepdims=True)
            ds_end = _total(dv * v_c) + jnp.exp(s_end) * _total(
                jnp.where(k.of_head(k.row, j), held, 0.0))
            ds_r = k.as_row(ds_c) - jnp.sum(q, axis=0, keepdims=True) \
                + jnp.where(k.last, ds_end, 0.0)
            ddt_r = jnp.sum(dm * cbd, axis=0, keepdims=True) \
                + k.as_row(dv * e_v)
            ds = jnp.where(head_row == h, ds_r, ds)
            ddt = jnp.where(head_row == h, ddt_r, ddt)
            e_t = jnp.where(mine, e_s, e_t)
            v = jnp.where(mine, v_c, v)
            keep = jnp.where(k.of_head(k.row, j), jnp.exp(s_end), keep)
        dz = (e_t * dy.astype(f32)).astype(cd)
        dc = dc + _dot(dz, s_in.astype(cd))
        db = db + _dot((x.astype(f32) * v).astype(cd), d_out.astype(cd))
        dx_ref[:, cols] = (dx + v * w).astype(dx_ref.dtype)
        dstate[cols, :] = keep * d_out + _dot_tn(dz, k.c)
    dcb = dcb.astype(cd)
    dc_ref[...] = (dc + _dot(dcb, k.b)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot_tn(dcb, k.c)).astype(db_ref.dtype)
    ddt_ref[...] = ddt
    ds_ref[...] = ds


def _specs(x, b, dt, reverse: bool):
    """Block specs of (a (B, T, H·P) array, a (B, T, G·N) array, a
    (B, nc, G, Hg, L) array, the (B, nc, G, Hg·P, N) states) on the grid
    (batch, group, chunk); ``reverse``: the chunks last to first."""
    bsz, nc, g, hg, L = dt.shape
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    wide, n = x.shape[-1] // g, b.shape[-1] // g
    return (
        pl.BlockSpec((None, L, wide), lambda bi, gi, ci: (bi, at(ci), gi)),
        pl.BlockSpec((None, L, n), lambda bi, gi, ci: (bi, at(ci), gi)),
        pl.BlockSpec((None, None, None, hg, L),
                     lambda bi, gi, ci: (bi, at(ci), gi, 0, 0)),
        pl.BlockSpec((None, None, None, wide, n),
                     lambda bi, gi, ci: (bi, at(ci), gi, 0, 0)))


def _call(kernel, name, x, b, dt, in_specs, out_specs, out_shape, interpret):
    bsz, nc, g, hg, L = dt.shape
    wide, n = x.shape[-1] // g, b.shape[-1] // g
    head_dim = wide // hg
    return pl.pallas_call(
        functools.partial(kernel, heads=hg, head_dim=head_dim,
                          pack=_pack(hg, head_dim)),
        out_shape=out_shape, grid=(bsz, g, nc), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((wide, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)


def _states_shape(x, b, dt):
    bsz, nc, g = dt.shape[:3]
    return jax.ShapeDtypeStruct(
        (bsz, nc, g, x.shape[-1] // g, b.shape[-1] // g), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_fwd(x, b, c, dt, s, *, interpret):
    wide, bc, row, states = _specs(x, b, dt, reverse=False)
    return _call(
        _fwd_kernel, "ssd_chunk_fwd", x, b, dt, [wide, bc, bc, row, row],
        [wide, states], [jax.ShapeDtypeStruct(x.shape, x.dtype),
                         _states_shape(x, b, dt)], interpret)(x, b, c, dt, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_bwd(x, b, c, dt, s, s_in, dy, *, interpret):
    wide, bc, row, states = _specs(x, b, dt, reverse=True)
    like = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    return _call(
        _bwd_kernel, "ssd_chunk_bwd", x, b, dt,
        [wide, bc, bc, row, row, states, wide], [wide, bc, bc, row, row],
        [like(x), like(b), like(c), like(dt), like(s)], interpret)(
            x, b, c, dt, s, s_in, dy)


@jax.custom_vjp
def ssd_chunks(x, b, c, dt, s):
    """``ops.ssm._ssd_chunked``'s contract: ``x`` (B, T, H·P), ``b`` /
    ``c`` (B, T, G·N), ``dt`` / ``s`` (B, T / L, G, H / G, L) float32 ->
    y (B, T, H·P)."""
    return _ssd_pallas_fwd(x, b, c, dt, s)[0]


def _ssd_pallas_fwd(x, b, c, dt, s):
    y, s_in = _ssd_fwd(x, b, c, dt, s, interpret=_interpret())
    # what a checkpoint around the layer keeps (``models.remat``): the
    # recomputed forward then needs no kernel; outside one, nothing
    y, s_in = name_kernel_outputs(y, s_in, kernel="ssd")
    return y, (x, b, c, dt, s, s_in)


def _ssd_pallas_bwd(res, dy):
    return _ssd_bwd(*res, dy.astype(res[0].dtype), interpret=_interpret())


ssd_chunks.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)
