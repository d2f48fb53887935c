"""Grouped matmuls for dropless expert routing — Pallas TPU kernels.

A routed layer sorts its (token, expert) assignments by expert and then
multiplies each expert's rows by that expert's matrix.  How many rows an
expert gets is only known on the device, so ``ops.moe.dispatch_plan`` lays
them out in whole ``tile_rows``-row tiles: every expert held here owns a
stretch of tiles (its last tile padded with zero rows, at least one tile
even with no row) and the stretches follow each other.  A tile therefore
belongs to ONE expert.  The kernels never see the whole layout: the layer
walks it in ROUNDS of ``T_c`` tiles (``ops.moe.routed_experts``), a size
read from the shapes that holds a step's expected rows twice over, and
hands a kernel one round's rows with its ``tile_expert`` (scalar-
prefetched) and ``num_tiles``, the round's used tiles: all ``T_c`` but in
the last round, where the tiles after them are unused.  An expert's
stretch may begin in the round before or end in the next, and an expert
may have no tile in a round.

* ``grouped_matmul(lhs (R, C), rhs (E, C, N)) -> (R, N)``: tile i times
  ``rhs[tile_expert[i]]``.  Grid (N tiles, row tiles), rows innermost:
  consecutive tiles of one expert keep its block of ``rhs`` in VMEM, so
  every expert's matrix is read once a column tile.  A tile past
  ``num_tiles`` is not computed: its step writes zeros and moves no
  operand (the index maps hold the last used blocks).  Where even the
  narrowest block over the whole depth C does not fit (a width like
  1,856 that 128 does not divide is one block, and (2,688, 1,856) is
  10 MB in bf16), the depth is tiled too: a third, innermost grid axis of
  contraction steps into a float32 accumulator.
* its backward: the same kernel with ``rhs`` transposed for the rows'
  gradient, and ``_tgmm`` (``lhs^T @ dy`` summed over each expert's tiles
  into a float32 accumulator, a block of ``lhs``'s columns at a time
  where a (C, N tile) accumulator does not fit) for the matrices': this
  round's part of them, undefined for an expert with no tile in it (the
  layer merges the rounds' parts by the experts present in each).
* ``rows_to_tokens(rows (R, D), scale, token) -> (N, D)`` float32, kernel
  ``moe_rows_to_tokens``: every token the sum of its weighted rows in the
  round.  The layer's combine and, in the backward, the tokens' gradient.

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

#: ... and what the operand block of a depth-tiled call may take: such a
#: call holds little else (row blocks and the accumulator, 128 rows
#: each), and every contraction step is a grid step that the idle tiles
#: past the used ones pay too
_DEPTH_BUDGET = 8 * 2**20


def _tile_of(n: int, fits) -> int:
    """The widest multiple of 128 dividing ``n``, at most 1,024 wide, that
    ``fits``; 128 where none does; all of ``n`` where 128 does not divide
    it (a block equal to the array's dimension needs no alignment: the
    CPU tests' sizes, and a published width like 1,856)."""
    if n % 128:
        return n
    return next((tn for tn in range(min(n, 1024), 127, -128)
                 if n % tn == 0 and fits(tn)), 128)


def _col_tile(n: int, c: int, itemsize: int) -> int:
    """Columns of a block that is ``c`` deep: the widest whose two buffers
    fit ``_BLOCK_BUDGET`` (2,688 takes 896 or 384 columns, not 128)."""
    return _tile_of(n, lambda tn: 2 * c * tn * itemsize <= _BLOCK_BUDGET)


def _depth_tile(c: int, row_bytes: int, budget: int) -> int:
    """Rows of a block whose every row takes ``row_bytes`` (over its
    buffers): all ``c`` where that fits ``_BLOCK_BUDGET`` or 128 does not
    divide ``c``, else the deepest multiple of 128 dividing ``c`` that
    fits ``budget``."""
    if c * row_bytes <= _BLOCK_BUDGET:
        return c
    return _tile_of(c, lambda tc: tc * row_bytes <= budget)


def _dot_tn(a, b):
    """a^T @ b (contracting the rows of both), ``_dot``'s precision."""
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST)
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _gmm_kernel(tile_expert, num_tiles, lhs_ref, rhs_ref, out_ref, *scratch,
                transpose_rhs: bool, depth_steps: int):
    del tile_expert  # read by the index maps
    i = pl.program_id(1)
    # the grid has a third axis where the depth takes more than one step
    k = pl.program_id(2) if depth_steps > 1 else 0
    dot = _dot_t if transpose_rhs else _dot

    @pl.when(i < num_tiles[0])
    def _run():
        if depth_steps == 1:
            out_ref[...] = dot(lhs_ref[...], rhs_ref[...]).astype(
                out_ref.dtype)
            return
        acc_ref, = scratch

        @pl.when(k == 0)
        def _first_step():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += dot(lhs_ref[...], rhs_ref[...])

        @pl.when(k == depth_steps - 1)
        def _last_step():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @pl.when(i >= num_tiles[0])
    def _unused():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(tile_expert, num_tiles, lhs_ref, dy_ref, out_ref, acc_ref,
                 *, n_tiles: int, row_axis: int):
    i = pl.program_id(row_axis)
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
    tc = _depth_tile(c, 2 * tn * lhs.dtype.itemsize, _DEPTH_BUDGET)
    steps = c // tc
    # grid (N tiles, row tiles[, contraction steps]); an index map takes
    # (j, i, k, tile_expert, num_tiles), k = 0 where the depth is whole
    one = lambda fn: fn if steps > 1 else (  # noqa: E731
        lambda j, i, te, nt: fn(j, i, 0, te, nt))

    def depth(i, k, nt):
        """Contraction step ``k``, held at the last one past the used
        tiles: an unused tile moves no operand."""
        return jnp.where(i < nt[0], k, steps - 1) if steps > 1 else 0

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, tc), one(
            lambda j, i, k, te, nt: (te[i], j, depth(i, k, nt))))
    else:
        rhs_spec = pl.BlockSpec((None, tc, tn), one(
            lambda j, i, k, te, nt: (te[i], depth(i, k, nt), j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs,
                          depth_steps=steps),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_rows) + ((steps,) if steps > 1
                                                 else ()),
            in_specs=[pl.BlockSpec((tile_rows, tc), one(
                lambda j, i, k, te, nt: (_used(i, nt), depth(i, k, nt)))),
                rhs_spec],
            out_specs=pl.BlockSpec((tile_rows, tn), one(
                lambda j, i, k, te, nt: (i, j))),
            scratch_shapes=[pltpu.VMEM((tile_rows, tn), jnp.float32)]
            if steps > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
            + (("arbitrary",) if steps > 1 else ())),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, num_tiles, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("num_experts", "tile_rows",
                                             "interpret"))
def _tgmm(lhs, dy, tile_expert, num_tiles, *, num_experts, tile_rows,
          interpret):
    """(E, C, N): for every expert with a used tile ``lhs_e^T @ dy_e`` over
    its used tiles.  An expert with none (a round may hold no tile of
    it) has its block never written: ``ops.moe._merge_by_expert`` takes a
    round's blocks for the experts present in it alone."""
    rows, c = lhs.shape
    n = dy.shape[1]
    tn = _col_tile(n, c, 4)  # the accumulator is float32
    tc = _depth_tile(c, tn * 4, _BLOCK_BUDGET)  # ... in one buffer
    n_tiles = rows // tile_rows
    # grid ([C tiles,] N tiles, row tiles); an index map takes
    # (m, j, i, tile_expert, num_tiles), m = 0 where the depth is whole
    one = lambda fn: fn if c > tc else (  # noqa: E731
        lambda j, i, te, nt: fn(0, j, i, te, nt))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_tiles=n_tiles,
                          row_axis=2 if c > tc else 1),
        out_shape=jax.ShapeDtypeStruct((num_experts, c, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((c // tc,) if c > tc else ()) + (n // tn, n_tiles),
            in_specs=[pl.BlockSpec((tile_rows, tc), one(
                lambda m, j, i, te, nt: (_used(i, nt), m))),
                pl.BlockSpec((tile_rows, tn), one(
                    lambda m, j, i, te, nt: (_used(i, nt), j)))],
            out_specs=pl.BlockSpec((None, tc, tn), one(
                lambda m, j, i, te, nt: (te[i], m, j))),
            scratch_shapes=[pltpu.VMEM((tc, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(("parallel",) if c > tc else ())
            + ("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_tgmm",
    )(tile_expert, num_tiles, lhs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, rhs, tile_expert, num_tiles,
                   tile_rows: int = TILE_ROWS):
    """``out[tile i] = lhs[tile i] @ rhs[tile_expert[i]]`` for the first
    ``num_tiles[0]`` tiles of ``tile_rows`` rows, zeros after them.

    ``lhs`` (R, C); ``rhs`` (E, C, N); ``tile_expert`` (R / tile_rows,)
    int32, non-decreasing over the used tiles and past them equal to its
    last used entry; ``num_tiles`` (1,) int32, at least 1.  The tiles may
    be a round of a longer layout (``ops.moe.Round``): an expert's tiles
    may be few of its stretch, or none, and its matrix's gradient is then
    its part from these tiles, or UNDEFINED (an expert with no used tile:
    the block is never written; select it away, do not multiply)."""
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


# ---------------------------------------------------------------------------
# rows -> tokens: each token the weighted sum of its rows
# ---------------------------------------------------------------------------

#: what the (N, column block) float32 sum, twice buffered, may take of
#: VMEM; v5e has 128 MiB, and the call raises its scoped limit to fit
_SUM_BUDGET = 48 * 2**20


def rows_to_tokens_block(n: int, d: int):
    """Columns of :func:`rows_to_tokens`' resident sum over ``n`` tokens:
    ``_tile_of``'s widest block of ``d`` whose two float32 buffers fit
    ``_SUM_BUDGET``, or None where none does (the caller keeps XLA's
    scatter-add)."""
    fits = lambda cb: 2 * n * cb * 4 <= _SUM_BUDGET  # noqa: E731
    block = _tile_of(d, fits)
    return block if fits(block) else None


def _to_tokens_kernel(token, num_tiles, rows_ref, scale_ref, out_ref,
                      scaled_ref, *, tile_rows: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < num_tiles[0])
    def _run():
        scaled_ref[...] = rows_ref[...].astype(jnp.float32) * scale_ref[...]

        def add_rows(group, carry):  # 8 rows a step, unrolled by hand
            for r in range(8):
                r = group * 8 + r
                t = token[i * tile_rows + r]
                out_ref[pl.ds(t, 1), :] += scaled_ref[pl.ds(r, 1), :]
            return carry

        lax.fori_loop(0, tile_rows // 8, add_rows, 0)


@functools.partial(jax.jit, static_argnames=("n", "block", "tile_rows",
                                             "interpret"))
def rows_to_tokens(rows, scale, token, num_tiles, *, n: int, block: int,
                   tile_rows: int = TILE_ROWS, interpret: bool = False):
    """(N, D) float32: ``out[token[i]] += scale[i] * rows[i]`` over the
    rows of the first ``num_tiles[0]`` tiles, in row order, float32
    products and sums.

    ``rows`` (R, D); ``scale`` (R,) float32, 0 where a row holds nothing;
    ``token`` (R,) int32 in [0, N), scalar-prefetched.  Grid (column
    blocks, row tiles), rows innermost: a block of ``block`` columns of
    the WHOLE sum stays in VMEM while the tiles' rows are added to it one
    by one (a row's token is only known on the device, so its place is a
    dynamic sublane), and is written once.  A tile past ``num_tiles``
    moves nothing."""
    r, d = rows.shape
    return pl.pallas_call(
        functools.partial(_to_tokens_kernel, tile_rows=tile_rows),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d // block, r // tile_rows),
            in_specs=[pl.BlockSpec((tile_rows, block),
                                   lambda j, i, tok, nt: (_used(i, nt), j)),
                      pl.BlockSpec((tile_rows, 1),
                                   lambda j, i, tok, nt: (_used(i, nt), 0))],
            out_specs=pl.BlockSpec((n, block), lambda j, i, tok, nt: (0, j)),
            scratch_shapes=[pltpu.VMEM((tile_rows, block), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_SUM_BUDGET + 16 * 2**20),
        interpret=interpret,
        name="moe_rows_to_tokens",
    )(token, num_tiles, rows, scale.reshape(r, 1))
