"""Grouped matmuls for dropless expert routing — Pallas TPU kernels.

A routed layer sorts its (token, expert) assignments by expert and then
multiplies each expert's rows by that expert's matrix.  How many rows an
expert gets is only known on the device, so the rows live in a buffer of
static size, laid out by ``ops.moe.dispatch_plan``: every expert held
here owns a stretch of whole ``tile_rows``-row tiles (its last tile
padded with zero rows, at least one tile even with no row), the
stretches follow each other, and the tiles past the last stretch are
unused.  A tile therefore belongs to ONE expert, named by the
scalar-prefetched ``tile_expert``; ``num_tiles`` says where the used
tiles end.

* ``grouped_matmul(lhs (R, C), rhs (E, C, N)) -> (R, N)``: tile i times
  ``rhs[tile_expert[i]]``.  Grid (N tiles, row tiles), rows innermost:
  consecutive tiles of one expert keep its block of ``rhs`` in VMEM, so
  every expert's matrix is read once a column tile.  A tile past
  ``num_tiles`` is not computed: its step writes zeros and moves no
  operand (the index maps hold the last used blocks).
* its backward: the same kernel with ``rhs`` transposed for the rows'
  gradient, and ``_tgmm`` (``lhs^T @ dy`` summed over each expert's tiles
  into a float32 accumulator) for the matrices'.

Precision follows ``pallas_attention._dot``: float32 operands multiply at
HIGHEST, bf16 at the MXU's rate into float32.  Off the TPU the kernels
run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _dot, _dot_t, _interpret

#: rows of a tile: what one MXU pass takes on its long side
TILE_ROWS = 128

#: VMEM one double-buffered operand block (or the float32 accumulator)
#: may take; v5e's scoped default is 16 MiB for a whole kernel
_BLOCK_BUDGET = 4 * 2**20


def _col_tile(n: int, c: int, itemsize: int) -> int:
    """Columns of a block that is ``c`` deep: the widest multiple of 128
    dividing ``n`` whose two buffers fit ``_BLOCK_BUDGET``, or all of
    ``n`` where 128 does not divide it (a block equal to the array's
    dimension needs no alignment: the CPU tests' sizes)."""
    if n % 128:
        return n
    for tn in (1024, 512, 256, 128):
        if n % tn == 0 and 2 * c * tn * itemsize <= _BLOCK_BUDGET:
            return tn
    return 128


def _dot_tn(a, b):
    """a^T @ b (contracting the rows of both), ``_dot``'s precision."""
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST)
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _gmm_kernel(tile_expert, num_tiles, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool):
    del tile_expert  # read by the index maps
    i = pl.program_id(1)

    @pl.when(i < num_tiles[0])
    def _run():
        dot = _dot_t if transpose_rhs else _dot
        out_ref[...] = dot(lhs_ref[...], rhs_ref[...]).astype(out_ref.dtype)

    @pl.when(i >= num_tiles[0])
    def _unused():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(tile_expert, num_tiles, lhs_ref, dy_ref, out_ref, acc_ref,
                 *, n_tiles: int):
    i = pl.program_id(1)
    last = num_tiles[0] - 1
    expert = tile_expert[i]

    @pl.when(i <= last)
    def _run():
        @pl.when((i == 0) | (tile_expert[jnp.maximum(i - 1, 0)] != expert))
        def _first_of_expert():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += _dot_tn(lhs_ref[...], dy_ref[...])

        @pl.when((i == last)
                 | (tile_expert[jnp.minimum(i + 1, n_tiles - 1)] != expert))
        def _last_of_expert():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _used(i, num_tiles):
    """Row tile ``i``, held at the last used one past ``num_tiles``."""
    return jnp.minimum(i, num_tiles[0] - 1)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tile_rows",
                                             "interpret"))
def _gmm(lhs, rhs, tile_expert, num_tiles, *, transpose_rhs, tile_rows,
         interpret):
    rows, c = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _col_tile(n, c, lhs.dtype.itemsize)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, c), lambda j, i, te, nt: (te[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, c, tn), lambda j, i, te, nt: (te[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_rows),
            in_specs=[pl.BlockSpec((tile_rows, c),
                                   lambda j, i, te, nt: (_used(i, nt), 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tile_rows, tn),
                                   lambda j, i, te, nt: (i, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, num_tiles, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("num_experts", "tile_rows",
                                             "interpret"))
def _tgmm(lhs, dy, tile_expert, num_tiles, *, num_experts, tile_rows,
          interpret):
    """(E, C, N): for every expert ``lhs_e^T @ dy_e`` over its tiles.
    Every expert owns at least one tile, so every block is written."""
    rows, c = lhs.shape
    n = dy.shape[1]
    tn = _col_tile(n, c, 4)  # the accumulator is float32
    n_tiles = rows // tile_rows
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_tiles=n_tiles),
        out_shape=jax.ShapeDtypeStruct((num_experts, c, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, n_tiles),
            in_specs=[pl.BlockSpec((tile_rows, c),
                                   lambda j, i, te, nt: (_used(i, nt), 0)),
                      pl.BlockSpec((tile_rows, tn),
                                   lambda j, i, te, nt: (_used(i, nt), j))],
            out_specs=pl.BlockSpec((None, c, tn),
                                   lambda j, i, te, nt: (te[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((c, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_tgmm",
    )(tile_expert, num_tiles, lhs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, rhs, tile_expert, num_tiles,
                   tile_rows: int = TILE_ROWS):
    """``out[tile i] = lhs[tile i] @ rhs[tile_expert[i]]`` for the first
    ``num_tiles[0]`` tiles of ``tile_rows`` rows, zeros after them.

    ``lhs`` (R, C); ``rhs`` (E, C, N); ``tile_expert`` (R / tile_rows,)
    int32, non-decreasing over the used tiles, every expert present, and
    past them equal to its last used entry; ``num_tiles`` (1,) int32."""
    return _gmm(lhs, rhs, tile_expert, num_tiles, transpose_rhs=False,
                tile_rows=tile_rows, interpret=_interpret())


def _grouped_fwd(lhs, rhs, tile_expert, num_tiles, tile_rows):
    out = _gmm(lhs, rhs, tile_expert, num_tiles, transpose_rhs=False,
               tile_rows=tile_rows, interpret=_interpret())
    return out, (lhs, rhs, tile_expert, num_tiles)


def _grouped_bwd(tile_rows, res, g):
    lhs, rhs, tile_expert, num_tiles = res
    g = g.astype(lhs.dtype)
    d_lhs = _gmm(g, rhs, tile_expert, num_tiles, transpose_rhs=True,
                 tile_rows=tile_rows, interpret=_interpret())
    d_rhs = _tgmm(lhs, g, tile_expert, num_tiles, num_experts=rhs.shape[0],
                  tile_rows=tile_rows, interpret=_interpret())
    return d_lhs, d_rhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
