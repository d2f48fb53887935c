"""Flash attention as Pallas TPU kernels — fused forward AND backward.

The one hot op where hand-scheduling beats XLA's fusion: dense attention
materializes the (T×T) score matrix in HBM; these kernels keep the scores
in VMEM, so HBM traffic is O(T·D) instead of O(T²) and sequence length is
limited by HBM, not by the score matrix (verified: T=16k+ on one v5e chip
where the dense path's scores alone would need tens of GB).

Two walks over the (q tile, k tile) pairs, chosen by ``_blocks`` from what
it can see (``causal``, the two lengths, T, Dh, the dtype):

* the GRID walk: one block pair a grid step, K/V blocks streaming through
  VMEM, the online-softmax running statistics in VMEM scratch that
  persists across a row's steps.  Blocks come from ``_auto_block``: as
  large as VMEM allows.  One set of kernel bodies on three grids
  (``_walk`` builds them, ``_step`` tells a body where it is):
  - a causal call — explicit ``block_q``/``block_k``, or a length whose
    whole-sequence operands would not fit ``_CAUSAL_VMEM_BUDGET``
    (T = 4,096 and up at bf16) — runs a (batch·head, step) grid over
    ``_walk_table``: a static table of the block pairs with work,
    scalar-prefetched, that every index map reads; a pair past the
    diagonal is no step and no DMA, and the mask is built only in the
    blocks the diagonal crosses.  At T = 8,192 in 512-blocks 136 of 256
    pairs are steps, 16 of them masked;
  - a sliding window runs (batch·head, query block, band): the key
    blocks the band touches alone (2 of 16), by arithmetic index maps —
    with one idle step in 32 the table's dearer step does not pay;
  - a non-causal call (every rectangular one: the zigzag ring's hops)
    runs the dense (batch·head, block, block) grid: it has no pair to
    skip.
* the IN-KERNEL causal walk (default blocks, causal, Tq == Tk, 256 <= T
  within the budget): one grid step a batch·head with q, k, v (and dO)
  resident as whole-sequence blocks; the kernel body walks the static
  ``_causal_schedule`` — per tile ONE matmul over the tiles at or before
  the diagonal, the mask built only on the tile the diagonal crosses,
  no grid step and no DMA for a tile past it.  At T = 1,024 (256 tiles)
  10 of 16 tile pairs run, 4 of them masked.

Grouped queries: K and V come at their own head count, (B, T, KV, Dh)
with KV dividing H, and the grid walk never makes them H heads wide.
Query head ``kv·G + g`` reads K/V head ``kv``: the forward's and dQ's K/V
blocks are indexed ``head // G``; dK/dV runs over the K/V heads, a key
block's row walking its query blocks once for each of the group's G
heads (a ``g`` column of the table, or one more grid axis) into one
float32 accumulator, written once.  G is read from the shapes; G = 1
builds the programs it always built.  (The in-kernel walk takes equal
head counts; ``flash_attention`` repeats K/V for it alone.)

Backward is the standard flash recurrence (Dao 2022): the forward saves
only O and the per-row logsumexp L; dQ and dK/dV are each one fused kernel
re-computing P = exp(S − L) tile by tile, so training memory is O(T·D) too.

Math follows the same blockwise recurrence as
``parallel.ring.ring_attention`` (intra-chip instead of inter-chip); both
are tested equal to ``ops.attention.dot_product_attention``, gradients
included.  On non-TPU backends the kernels run in Pallas interpret mode
(slow but exact) so tests stay hermetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.remat import name_kernel_outputs, sizing
from ..obs.registry import default_registry

_NEG = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b):
    """MXU matmul, f32 result.  Precision policy: f32 inputs use HIGHEST
    (multi-pass, exact — the 2.4e-6-vs-f64 configuration BASELINE.md
    records); sub-f32 inputs (bf16 training) run the MXU at full native
    rate with f32 ACCUMULATION — the standard flash-attention trade, and
    the same input precision XLA's dense path uses in bf16 training."""
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST)
    # explicit DEFAULT: a global jax_default_matmul_precision=highest
    # override would otherwise request fp32 contract precision on bf16
    # operands, which Mosaic rejects ("Bad lhs type")
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _dot_t(a, b):  # a @ b.T, same precision policy as _dot
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST)
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


#: a step's flags in the causal grid walk's table: the row's first and
#: last step, and whether the block needs a mask
_FIRST, _LAST, _MASKED = 1, 2, 4


def _walk_table(tq: int, tk: int, bq: int, bk: int, by_keys: bool = False,
                group: int = 1):
    """The causal grid walk's schedule, one entry a grid step:
    ``(query block, key block, first, last, masked)``.

    Only block pairs with work are steps: those with a key at or before
    a query (``kb·bk <= qi·bq + bq − 1``).  A ROW of the grid is a query
    block and its key blocks in ascending order (the forward and dQ: the
    diagonal block last), or ``by_keys`` a key block and its query
    blocks (dK/dV: the diagonal block first); a row's steps are
    contiguous, ``first`` / ``last`` mark its ends (init / finalize),
    and the row's output block stays put over them.  ``masked`` is true
    where the diagonal passes through the block (its last key is after
    its first query); in every other block every pair attends and no
    mask is built.  136 steps, 16 of them masked, at T = 8,192 with
    512-blocks (256 pairs in all).

    ``group`` > 1 (``by_keys`` alone: dK/dV of a K/V head that ``group``
    query heads share): a key block's row walks its query blocks once
    for each of the group's heads in turn, the entry gains the head
    ``g`` as a sixth field, and ``first`` / ``last`` are the row's ends
    over the whole group, so one accumulator sums dK / dV over it
    (136 × 6 = 816 steps a K/V head in Laguna's full layers)."""
    def needed(qi, kb):
        return kb * bk <= qi * bq + bq - 1

    n_q, n_k = tq // bq, tk // bk
    if by_keys:
        rows = [[(qi, kb) for qi in range(n_q) if needed(qi, kb)]
                for kb in range(n_k)]
    else:
        rows = [[(qi, kb) for kb in range(n_k) if needed(qi, kb)]
                for qi in range(n_q)]
    if group > 1:
        rows = [[(qi, kb, g) for g in range(group) for qi, kb in row]
                for row in rows]
    return [(qi, kb, j == 0, j == len(row) - 1, kb * bk + bk - 1 > qi * bq,
             *g) for row in rows for j, (qi, kb, *g) in enumerate(row)]


def _band_blocks(window: int, block: int) -> int:
    """Key blocks a query block of a sliding-window call can see: its
    own and those the ``window - 1`` keys before its first query reach
    into (2 at window = block = 512)."""
    return 1 + -(-(window - 1) // block)


def _step(refs, walk, block_q: int, block_k: int):
    """Where this grid step is, for each of the three grids ``_walk``
    builds: ``(offset, first, last, runs, operand refs)``.  ``offset``
    is the block's first query position less its first key position
    (what a mask needs); ``first`` / ``last`` the ends of the row (init
    / finalize); ``runs`` the ``(condition, with_mask)`` bodies the step
    may take, a condition of None meaning always.

    * dense (``walk`` None): every pair, no mask anywhere.
    * a window's band, ``("band", by_keys, n_q)``: the minor axis is the
      band, step j of query block i is key block i - (band - 1) + j (of
      key block i: query block i + j); a step that falls off the
      sequence does nothing, every other builds the mask.
    * the causal table, ``("table", plain, masked, columns)``: the first
      three of its ``columns`` scalar-prefetched columns of
      ``_walk_table`` say where the step is and whether its block is
      masked; one body for each kind the table holds.
    * ``("group", dense or band)``: dK/dV of a K/V head that several
      query heads share, one grid axis more between the row and its
      steps; the row begins at the first head's first step and ends at
      the last head's last.  (On the table the group is in the rows.)"""
    if walk is not None and walk[0] == "table":
        (qi_ref, kb_ref, flag_ref), refs = refs[:3], refs[walk[3]:]
        s = pl.program_id(1)
        flags = flag_ref[s]
        masked = (flags & _MASKED) != 0
        runs = [(jnp.logical_not(masked), False), (masked, True)]
        if not (walk[1] and walk[2]):  # one kind alone: no branch
            runs = [(None, walk[2])]
        return (qi_ref[s] * block_q - kb_ref[s] * block_k,
                (flags & _FIRST) != 0, (flags & _LAST) != 0, runs, refs)
    grouped = walk is not None and walk[0] == "group"
    if grouped:
        walk = walk[1]
    minor = 3 if grouped else 2
    row, j, n = pl.program_id(1), pl.program_id(minor), pl.num_programs(minor)

    def ends():
        if not grouped:
            return j == 0, j == n - 1
        g, last_g = pl.program_id(2), pl.num_programs(2) - 1
        return (g == 0) & (j == 0), (g == last_g) & (j == n - 1)

    if walk is None:
        return None, *ends(), [(None, False)], refs
    _, by_keys, n_q = walk
    if by_keys:
        qi, kb = row + j, row
    else:
        qi, kb = row, row - (n - 1) + j
    inside = qi < n_q if by_keys else kb >= 0
    return (qi * block_q - kb * block_k, *ends(), [(inside, True)], refs)


def _run(runs, compute) -> None:
    for condition, with_mask in runs:
        body = functools.partial(compute, with_mask)
        body() if condition is None else pl.when(condition)(body)


def _pair_mask(offset, shape, window, keys_first: bool = False):
    """Mask of a (queries, keys) block — (keys, queries) if
    ``keys_first`` — whose first query is ``offset`` positions after its
    first key: key <= query, and with a ``window`` also query - key <
    window."""
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1)
    ahead = rows - cols if keys_first else cols - rows  # key - query
    mask = ahead <= offset
    if window is not None:
        mask &= ahead > offset - window
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _lanes(x, n: int):
    """A (rows, 128) lane-replicated statistic as (rows, n): whole
    128-lane tiles repeated, or the first ``n`` lanes of one."""
    if n % 128:
        return x[:, :n] if n < 128 else jnp.broadcast_to(
            x[:, :1], (x.shape[0], n))
    return x if n == 128 else pltpu.repeat(x, n // 128, axis=1)


def _fwd_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                walk):
    """One (q block, K/V block) pair a grid step; the accumulators
    persist across a row's steps (TPU executes the grid sequentially,
    minor-most last).  Grid (bh, q blocks, k blocks) for a non-causal
    call; (bh, steps of ``_walk_table``) for a causal one.  The running
    max and sum are kept REPLICATED over the 128 lanes of their scratch:
    a lane reduction leaves its result that way, so a step loads,
    updates and stores them as whole vregs; kept as one column they cost
    a lane gather and a rotate a vreg a step (451 + 448 in the lowered
    body), a third of the forward's time at 512² blocks (v5e)."""
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc = refs

    @pl.when(first)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    def _compute(with_mask):
        # matmuls in the input dtype (f32 → HIGHEST, bf16 → full MXU
        # rate with f32 accumulation); softmax statistics always f32
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        if with_mask:
            mask = _pair_mask(offset, s.shape, window)
            s = jnp.where(mask, s, _NEG)
        m_prev = m_acc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        if with_mask:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[:] = l_acc[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[:] = o_acc[:] * _lanes(corr, o_acc.shape[1]) + _dot(
            p.astype(v_ref.dtype), v_ref[0])
        m_acc[:] = m_new

    _run(runs, _compute)

    @pl.when(last)
    def _finalize():
        l = l_acc[:]
        o_ref[0] = (o_acc[:] / _lanes(l, o_acc.shape[1])).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_acc[:] + jnp.log(l))[:, 0]


# ---------------------------------------------------------------------------
# backward (Dao 2022 recurrence; P recomputed blockwise from L)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                   walk):
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref, dq_acc = refs

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(with_mask):
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if with_mask:
            p = jnp.where(_pair_mask(offset, s.shape, window), p, 0.0)
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - dvec_ref[0, 0][:, None]) * scale
        dq_acc[:] = dq_acc[:] + _dot(ds.astype(k_ref.dtype), k_ref[0])

    _run(runs, _compute)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                    walk):
    """A row is a K/V block and its query blocks: every one on the dense
    grid, from the diagonal's on down the causal walk.  S and dP are
    built TRANSPOSED (keys on sublanes), as the in-kernel walk builds
    them: P^T and dS^T feed the dV and dK matmuls as they are (no 512²
    transpose a step), and ``lse`` / ``dvec`` broadcast from the (1, bq)
    lane layout they arrive in."""
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    (k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref, dk_ref, dv_ref, dk_acc,
     dv_acc) = refs

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(with_mask):
        st = _dot_t(k_ref[0], q_ref[0]) * scale       # (BK, BQ)
        pt = jnp.exp(st - lse_ref[0])
        if with_mask:
            pt = jnp.where(_pair_mask(offset, st.shape, window,
                                      keys_first=True), pt, 0.0)
        # dV += P^T dO ; dS = P∘(dO V^T − D) ; dK += dS^T Q
        dv_acc[:] = dv_acc[:] + _dot(pt.astype(do_ref.dtype), do_ref[0])
        dpt = _dot_t(v_ref[0], do_ref[0])
        dst = pt * (dpt - dvec_ref[0]) * scale
        dk_acc[:] = dk_acc[:] + _dot(dst.astype(q_ref.dtype), q_ref[0])

    _run(runs, _compute)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# causal self-attention: the K/V walk inside the kernel
# ---------------------------------------------------------------------------

#: VMEM the whole-sequence operands of one grid step may take, double
#: buffers included (the dK/dV kernel holds six); past it a causal call
#: keeps the grid walk.  v5e's scoped default is 16 MiB.
_CAUSAL_VMEM_BUDGET = 8 * 2**20


def _causal_tile(t: int, dh: int, itemsize: int):
    """Tile of the in-kernel causal walk for a (T, Dh) head, or None
    where the call keeps the grid walk: a sequence of one tile has
    nothing to skip, and six double-buffered whole-sequence operands
    (lanes padded to 128) have to fit ``_CAUSAL_VMEM_BUDGET``."""
    if t % 128 or t < 256:
        return None
    if 12 * t * max(dh, 128) * itemsize > _CAUSAL_VMEM_BUDGET:
        return None
    return 256 if t % 256 == 0 and t > 256 else 128


def _diag_mask(shape, keys_first: bool = False):
    """Causal mask of the square tile on the diagonal: key <= query, the
    queries on axis 0, or on axis 1 (``keys_first``, the transposed S)."""
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows <= cols if keys_first else cols <= rows


def _causal_schedule(t: int, tile: int, by_keys: bool = False):
    """What a causal kernel executes: for every q tile (k tile if
    ``by_keys``: the dK/dV walk) ``(start, lo, hi)`` — the tile's start
    and the stretch [lo, hi) of the other operand it meets, whole tiles
    in ONE matmul.  The tile at ``start`` is the only one the diagonal
    crosses and the only one masked; before it (after it, by keys) every
    tile is wholly inside the triangle; past it none is in the walk."""
    return [(s, s, t) if by_keys else (s, 0, s + tile)
            for s in range(0, t, tile)]


def _mask_diag(x, at: int, tile: int, fill: float, keys_first=False):
    """``x`` with the ``tile`` columns from ``at`` (the tile the diagonal
    crosses) masked to ``fill``; the other columns are not touched."""
    diag = x[:, at:at + tile]
    parts = [x[:, :at], jnp.where(_diag_mask(diag.shape, keys_first), diag,
                                  fill), x[:, at + tile:]]
    return jnp.concatenate([p for p in parts if p.shape[1]], axis=1)


def _to_row(col):
    """(n, 1) column -> (1, n) row, n a multiple of 128, through the
    diagonal of each 128-row block: a select and a sum over sublanes
    (adding zeros: exact).  Mosaic's own relayout of ``col[:, 0]`` took
    0.82 of the forward's 3.48 us a batch·head at T = 1,024 (v5e)."""
    eye = (lax.broadcasted_iota(jnp.int32, (128, 128), 0)
           == lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col[i:i + 128], 0.0), axis=0, keepdims=True)
         for i in range(0, col.shape[0], 128)], axis=1)


def _fwd_causal_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                       tile: int):
    """All q tiles of one batch·head; a q tile's row of S is whole in
    VMEM, so its softmax is plain: one max, one sum a row."""
    for qs, lo, hi in _causal_schedule(q_ref.shape[1], tile):
        s = _dot_t(q_ref[0, qs:qs + tile, :], k_ref[0, lo:hi, :]) * scale
        s = _mask_diag(s, qs - lo, tile, _NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = _dot(p.astype(v_ref.dtype), v_ref[0, lo:hi, :])
        o_ref[0, qs:qs + tile, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, :, qs:qs + tile] = _to_row(m + jnp.log(l))


def _bwd_dq_causal_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                          dq_ref, *, scale: float, tile: int):
    for qs, lo, hi in _causal_schedule(q_ref.shape[1], tile):
        k = k_ref[0, lo:hi, :]
        s = _dot_t(q_ref[0, qs:qs + tile, :], k) * scale
        p = jnp.exp(s - lse_ref[0, 0, qs:qs + tile][:, None])
        p = _mask_diag(p, qs - lo, tile, 0.0)
        dp = _dot_t(do_ref[0, qs:qs + tile, :], v_ref[0, lo:hi, :])
        ds = p * (dp - dvec_ref[0, 0, qs:qs + tile][:, None]) * scale
        dq_ref[0, qs:qs + tile, :] = _dot(ds.astype(k.dtype),
                                          k).astype(dq_ref.dtype)


def _bwd_dkv_causal_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref,
                           dk_ref, dv_ref, *, scale: float, tile: int):
    """All k tiles of one batch·head, S and dP built TRANSPOSED (keys on
    sublanes): P^T and dS^T feed the dV and dK matmuls as they are, and
    the row statistics broadcast from their (1, T) lane layout."""
    for ks, lo, hi in _causal_schedule(q_ref.shape[1], tile, by_keys=True):
        q = q_ref[0, lo:hi, :]
        do = do_ref[0, lo:hi, :]
        st = _dot_t(k_ref[0, ks:ks + tile, :], q) * scale
        pt = jnp.exp(st - lse_ref[0, :, lo:hi])
        pt = _mask_diag(pt, ks - lo, tile, 0.0, keys_first=True)
        dv_ref[0, ks:ks + tile, :] = _dot(pt.astype(do.dtype),
                                          do).astype(dv_ref.dtype)
        dpt = _dot_t(v_ref[0, ks:ks + tile, :], do)
        dst = pt * (dpt - dvec_ref[0, :, lo:hi]) * scale
        dk_ref[0, ks:ks + tile, :] = _dot(dst.astype(q.dtype),
                                          q).astype(dk_ref.dtype)


# ---------------------------------------------------------------------------
# launchers
# ---------------------------------------------------------------------------

def _count_tiles(causal, tq, tk, bq, bk, tile, kernels: int,
                 window=None, group: int = 1) -> None:
    """The schedule is static, so it is counted where it is built: a
    causal call adds, once for each of the ``kernels`` it puts into the
    program, to the default registry's ``flash.causal_tiles_executed`` /
    ``flash.causal_tiles_total`` the tile pairs its kernels execute and
    the tile pairs in all (10 and 16 a kernel at T = 1,024; 136 and 256
    at T = 8,192, 512-blocks), to ``flash.causal_grid_steps`` the pairs
    its walk takes a step for (the same number on both walks: neither
    steps where there is no work) and to ``flash.causal_tiles_masked``
    those it builds a mask in (4 and 16: the diagonal's); a
    sliding-window call to ``flash.window_tiles_executed`` /
    ``flash.window_tiles_total`` instead (31 of 256 at T = 8,192,
    window 512).  A call whose ``group`` > 1 query heads share a K/V
    head, causal or not, adds its kernels to ``flash.kv_native_kernels``
    where they read K and V at their own head count (the grid walk), to
    ``flash.kv_expanded_kernels`` where they run on K and V repeated to
    the query heads (the in-kernel walk, ``tile``)."""
    if sizing():  # the recompute plan's own trace of a child
        return
    registry = default_registry()
    if group > 1:
        registry.counter("flash.kv_native_kernels" if tile is None
                         else "flash.kv_expanded_kernels").inc(kernels)
    if not causal:
        return
    if window is not None:
        band, n = _band_blocks(window, bq), tq // bq
        registry.counter("flash.window_tiles_executed").inc(
            kernels * sum(min(band, qi + 1) for qi in range(n)))
        registry.counter("flash.window_tiles_total").inc(kernels * n * n)
        return
    if tile is not None:
        schedule = _causal_schedule(tq, tile)
        executed = sum((hi - lo) // tile for _, lo, hi in schedule)
        masked = len(schedule)
        total = (tq // tile) ** 2
    else:
        table = _walk_table(tq, tk, bq, bk)
        executed = len(table)
        masked = sum(masked for *_, masked in table)
        total = (tq // bq) * (tk // bk)
    registry.counter("flash.causal_tiles_executed").inc(kernels * executed)
    registry.counter("flash.causal_tiles_total").inc(kernels * total)
    registry.counter("flash.causal_grid_steps").inc(kernels * executed)
    registry.counter("flash.causal_tiles_masked").inc(kernels * masked)


def _whole(t, dh):
    return pl.BlockSpec((1, t, dh), lambda b: (b, 0, 0))


def _whole_row(t):
    return pl.BlockSpec((1, 1, t), lambda b: (b, 0, 0))


def _walk(causal, tq, tk, bq, bk, window, by_keys=False, group=1):
    """The grid a grid-walk kernel runs on, from what the launcher can
    see: ``(walk, grid, at, prefetch)`` — what ``_step`` reads in the
    kernel, the grid's axes after its leading one, the index maps of a
    block on the query side (``"q"``), on the key side (``"k"``) and of
    a query block of ``lse`` / ``dvec`` on lanes (``"row"``), and the
    scalar-prefetched operands.  A row of the grid is a query block, or
    ``by_keys`` (the dK/dV kernel) a key block.

    * a causal call: (steps,) over ``_walk_table``, whose columns are
      prefetched and read by every index map;
    * a sliding window: (rows, band) — the band's blocks alone, the
      index held at the sequence's end where a step falls off it (the
      kernel skips the step; the block is already there);
    * any other call: the dense (rows, blocks).

    ``group`` query heads share a K/V head (``head = kv·group + g``, so
    row ``bh`` of the (B·H, T, Dh) arrays reads row ``bh // group`` of
    the (B·KV, T, Dh) ones).  The leading axis of a query-row grid is
    batch·head and its key-side maps read ``b // group``; of a
    ``by_keys`` grid it is batch·K/V head, a key block's row walks the
    group's heads in turn (the table's ``g`` column, or one more axis
    between the rows and their steps) and the query-side maps read head
    ``b·group + g``: the key block and its accumulators stay put over
    the group.  ``group`` = 1 builds what it built before there was
    one: no division, no axis, no column."""
    n_q, n_k = tq // bq, tk // bk
    grouped = by_keys and group > 1

    def key_head(b):  # the K/V head of a grid's leading index
        return b if by_keys or group == 1 else lax.div(b, group)

    if causal and window is None:
        table = _walk_table(tq, tk, bq, bk, by_keys, group if by_keys else 1)
        kinds = {step[4] for step in table}

        def q_head(b, s, g):  # ``g``: the table's fourth column, if any
            return b * group + g[0][s] if grouped else b

        at = {"q": lambda b, s, qi, kb, flags, *g: (q_head(b, s, g), qi[s],
                                                    0),
              "k": lambda b, s, qi, kb, flags, *g: (key_head(b), kb[s], 0),
              "row": lambda b, s, qi, kb, flags, *g: (q_head(b, s, g), 0,
                                                      qi[s])}
        prefetch = tuple(
            jnp.asarray(column, jnp.int32) for column in zip(*(
                (qi, kb, first * _FIRST | last * _LAST | masked * _MASKED,
                 *g) for qi, kb, first, last, masked, *g in table)))
        return (("table", False in kinds, True in kinds, len(prefetch)),
                (len(table),), at, prefetch)
    walk, cols = None, n_q if by_keys else n_k
    if window is not None:
        walk, cols = ("band", by_keys, n_q), _band_blocks(window, bk)

    def walked(i, j):  # the block of the other side that step j meets
        if window is None:
            return j
        return jnp.minimum(i + j, n_q - 1) if by_keys else jnp.maximum(
            i - (cols - 1) + j, 0)

    def q_of(i, j): return walked(i, j) if by_keys else i
    def k_of(i, j): return i if by_keys else walked(i, j)
    if grouped:
        at = {"q": lambda b, i, g, j: (b * group + g, q_of(i, j), 0),
              "k": lambda b, i, g, j: (b, k_of(i, j), 0),
              "row": lambda b, i, g, j: (b * group + g, 0, q_of(i, j))}
        return ("group", walk), (n_k, group, cols), at, ()
    at = {"q": lambda b, i, j: (b, q_of(i, j), 0),
          "k": lambda b, i, j: (key_head(b), k_of(i, j), 0),
          "row": lambda b, i, j: (b, 0, q_of(i, j))}
    return walk, (n_k if by_keys else n_q, cols), at, ()


def _grid_walk(body, kernel, args, operands, outputs, out_shape, scratch, *,
               causal, bq, bk, scale, window, interpret, by_keys=False):
    """One grid-walk kernel, ``flash_<kernel>`` (``window_attn_<kernel>``
    with a window: the names a trace row reads), on the grid ``_walk``
    gives it.  ``operands`` and ``outputs`` name the kind of each block
    of ``args`` and of the results: ``"q"`` / ``"k"`` a (block, Dh) tile
    on the query / key side, ``"row"`` a query block of ``lse`` or
    ``dvec`` on lanes.  The query side's leading dimension is batch·head
    and the key side's batch·K/V head; their ratio is the group that
    shares a K/V head, and a ``by_keys`` grid leads with the key
    side's."""
    sides = dict(zip(operands, args))
    (bh, tq, dh), (bkv, tk, _) = sides["q"].shape, sides["k"].shape
    walk, grid, at, prefetch = _walk(causal, tq, tk, bq, bk, window, by_keys,
                                     bh // bkv)
    shape = {"q": (1, bq, dh), "k": (1, bk, dh), "row": (1, 1, bq)}
    in_specs, out_specs = ([pl.BlockSpec(shape[x], at[x]) for x in kinds]
                           for kinds in (operands, outputs))
    return pl.pallas_call(
        functools.partial(body, scale=scale, block_q=bq, block_k=bk,
                          window=window, walk=walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(bkv if by_keys else bh, *grid),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
        name=f"flash_{kernel}" if window is None else f"window_attn_{kernel}",
    )(*prefetch, *args)


#: the launchers are jitted so that equal calls (a model's blocks) share
#: ONE trace and ONE lowering of each kernel body: the step program of
#: GPT-2 small holds 36 kernels of 3 kinds, and on the chip's host the
#: unrolled causal bodies cost 0.1 s apiece to trace and lower.
#: ``interpret`` is an argument because a cached trace would otherwise
#: keep the backend it was first traced for.
#: The calls carry NO ``cost_estimate``: given one, XLA overlaps more of
#: its own prefetches with the kernels (13 more ``copy-done``, 60 more
#: ``slice-done`` in a 4-block GPT-2-medium step) and the matmul fusions
#: beside them slow down: 48.28 against 49.88 samples/s on
#: ``gpt2m-train``, 126.13 against 126.32 on ``gpt2s-train`` (v5e, PR 27)
_LAUNCHER_STATICS = ("causal", "bq", "bk", "scale", "tile", "interpret",
                     "window")


@functools.partial(jax.jit, static_argnames=_LAUNCHER_STATICS)
def _flash_fwd_raw(qr, kr, vr, *, causal, bq, bk, scale, tile, interpret,
                   window=None):
    """(BH, Tq, D) + (B·KV, Tk, D) in → (out (BH,Tq,D), lse (BH,Tq)) via
    the fused kernel.  Rectangular Tq ≠ Tk is the ring's half-block hop
    shape (zigzag schedule); causal requires Tq == Tk (diagonal
    alignment).  ``tile`` (``_causal_tile``) selects the in-kernel causal
    walk, which takes equal head counts."""
    bh, tq, dh = qr.shape
    tk = kr.shape[1]
    if causal and tq != tk:
        raise ValueError(f"causal flash needs equal q/k lengths, got "
                         f"{tq} vs {tk}")
    out_shape = [
        jax.ShapeDtypeStruct((bh, tq, dh), qr.dtype),
        # (bh, 1, t) layout so the block's last-two dims satisfy the
        # TPU (8, 128) tiling rule (second-to-last == array dim == 1)
        jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
    ]
    if tile is not None:
        return pl.pallas_call(
            functools.partial(_fwd_causal_kernel, scale=scale, tile=tile),
            grid=(bh,),
            in_specs=[_whole(tq, dh)] * 3,
            out_specs=[_whole(tq, dh), _whole_row(tq)],
            out_shape=out_shape,
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    return _grid_walk(
        _fwd_kernel, "fwd", (qr, kr, vr), ["q", "k", "k"], ["q", "row"],
        out_shape,
        [pltpu.VMEM((bq, dh), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32)],
        causal=causal, bq=bq, bk=bk, scale=scale, window=window,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=_LAUNCHER_STATICS)
def _flash_bwd_raw(qr, kr, vr, do, lse, dvec, *, causal, bq, bk, scale,
                   tile, interpret, window=None):
    bh, tq, dh = qr.shape
    dq_shape = jax.ShapeDtypeStruct((bh, tq, dh), qr.dtype)
    dkv_shape = [jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                 jax.ShapeDtypeStruct(vr.shape, vr.dtype)]
    operands = (qr, kr, vr, do, lse, dvec)

    if tile is not None:
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_causal_kernel, scale=scale, tile=tile),
            grid=(bh,),
            in_specs=[_whole(tq, dh)] * 4 + [_whole_row(tq)] * 2,
            out_specs=_whole(tq, dh),
            out_shape=dq_shape,
            interpret=interpret,
            name="flash_bwd_dq",
        )(*operands)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_causal_kernel, scale=scale,
                              tile=tile),
            grid=(bh,),
            in_specs=[_whole(tq, dh)] * 4 + [_whole_row(tq)] * 2,
            out_specs=[_whole(tq, dh)] * 2,
            out_shape=dkv_shape,
            interpret=interpret,
            name="flash_bwd_dkv",
        )(kr, vr, qr, do, lse, dvec)
        return dq, dk, dv

    statics = dict(causal=causal, bq=bq, bk=bk, scale=scale, window=window,
                   interpret=interpret)
    dq, = _grid_walk(
        _bwd_dq_kernel, "bwd_dq", operands,
        ["q", "k", "k", "q", "row", "row"], ["q"], [dq_shape],
        [pltpu.VMEM((bq, dh), jnp.float32)], **statics)
    dk, dv = _grid_walk(
        _bwd_dkv_kernel, "bwd_dkv", (kr, vr, qr, do, lse, dvec),
        ["k", "k", "q", "q", "row", "row"], ["k", "k"], dkv_shape,
        [pltpu.VMEM((bk, dh), jnp.float32),
         pltpu.VMEM((bk, dh), jnp.float32)], by_keys=True, **statics)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _to_bh(x):
    b, t, h, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)


def _from_bh(x, b, h):
    bh, t, dh = x.shape
    return x.reshape(b, h, t, dh).transpose(0, 2, 1, 3)


def _kv_to_bh(k, v, group: int, tile):
    """K and V as the kernels take them: (B·KV, T, Dh), their own head
    count, for the grid walk; repeated to the ``group`` query heads of
    each for the in-kernel causal walk (``tile``), whose whole-sequence
    kernels take equal head counts."""
    kr, vr = _to_bh(k), _to_bh(v)
    if group > 1 and tile is not None:
        kr, vr = jnp.repeat(kr, group, axis=0), jnp.repeat(vr, group, axis=0)
    return kr, vr


def _kv_from_bh(x, b: int, kv: int):
    """dK or dV (rows, T, Dh) → (B, T, KV, Dh).  Rows at the query head
    count (the in-kernel walk ran on repeated heads) are first summed
    over each group, in float32: the repeat's transpose."""
    rows, t, dh = x.shape
    if rows != b * kv:
        x = x.reshape(b * kv, rows // (b * kv), t, dh).astype(
            jnp.float32).sum(axis=1)
    return _from_bh(x, b, kv)


def _tileable(t: int) -> bool:
    """Whether a sequence length has a block Mosaic can tile: the
    ``lse``/``dvec`` specs map the block onto lanes, so it is a multiple
    of 128 — or the whole sequence, since a block equal to the array
    dimension needs no alignment (kept to T <= 128: one block).  Interpret
    mode accepts anything; T=200 -> 100 and T=544 -> 68 passed every CPU
    test and were refused on the chip."""
    return t % 128 == 0 or t <= 128


def _auto_block(t: int, dh: int) -> int:
    """Default block size of the GRID walk: as LARGE as VMEM allows
    (measured r4 at T=8192/dh=64: 1024² blocks run the fused bwd 3.4×
    faster than the old 128² default and 2.4× faster than XLA dense — the
    per-grid-step overhead and small-K matmuls dominated at 128).  The
    score block is b²·4 bytes of VMEM (f32), with 2-3 alive in the
    backward, so the cap shrinks as the head dim's tiles grow.  A causal
    call of one length does not come here for its tiles where
    ``_causal_tile`` engages the in-kernel walk: one 1,024² block at
    T = 1,024 has no block to skip, which is why that walk exists.
    Where it does come here (T = 8,192: 512-blocks at head 128), the
    block is also the grain the causal grid walk skips and masks at:
    ``_walk_table`` takes a step for 136 of the 256 pairs.

    Only blocks Mosaic can tile come back (see :func:`_tileable`); any
    other T is refused — ``ops.attention`` pads such causal lengths to a
    multiple of 128 before they get here."""
    if not _tileable(t):
        raise ValueError(
            f"flash attention has no tileable block for sequence length "
            f"{t}: it is neither a multiple of 128 nor at most 128 (one "
            f"block); pad it to a multiple of 128")
    cap = 1024 if dh <= 64 else 512 if dh <= 128 else 256
    for b in (1024, 512, 256, 128):
        if b <= cap and t % b == 0:
            return b
    return t


def _blocks(q, k, causal, block_q, block_k, window=None):
    """(block_q, block_k, tile) for (B, T, H, Dh) operands: the grid
    walk's blocks, and the in-kernel causal walk's tile where it engages
    (default blocks, causal, one length, no window; see
    ``_causal_tile``) — else None and the blocks decide: ``_walk``
    builds the grid from them, ``_walk_table``'s steps for a causal call
    (the block pairs at or below the diagonal), the band's blocks for a
    sliding window, the dense grid for any other."""
    tq, tk, dh = q.shape[1], k.shape[1], q.shape[3]
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"the query heads ({q.shape[2]}) must be a whole multiple of "
            f"the K/V heads ({k.shape[2]})")
    if window is not None and not (causal and tq == tk and window >= 1):
        raise ValueError(
            f"a sliding window needs causal self-attention and window >= "
            f"1, got causal={causal}, lengths ({tq}, {tk}), window="
            f"{window}")
    tile = None
    if causal and block_q is None and block_k is None and tq == tk \
            and window is None:
        tile = _causal_tile(tq, dh, q.dtype.itemsize)
    if block_q is None:
        block_q = _auto_block(tq, dh)
    if block_k is None:
        block_k = _auto_block(tk, dh)
    bq, bk = min(block_q, tq), min(block_k, tk)
    if tq % bq or tk % bk:
        raise ValueError(f"sequence lengths ({tq}, {tk}) must divide "
                         f"block sizes ({bq}, {bk})")
    if window is not None and bq != bk:
        raise ValueError(f"a sliding window walks square blocks, got "
                         f"({bq}, {bk})")
    return bq, bk, tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q=None,
                    block_k=None, window=None):
    """Pallas flash attention; q (B, T, H, Dh), k/v (B, T, KV, Dh) →
    (B, T, H, Dh).

    KV divides H: query head ``kv·G + g`` reads K/V head ``kv`` (G = H /
    KV, read from the shapes; ``MultiHeadAttention._expand_kv``'s order,
    which the weights and the decode cache assume).  The grid walk's
    kernels read K and V at their own head count — a K/V block's index
    map is ``head // G``, and dK/dV runs over the K/V heads, a key
    block's row walking its query blocks once for each head of the
    group into one float32 accumulator — so nothing on the K/V side is
    ever H heads wide: not the transposes around the kernels, not dK /
    dV, not the K and V a checkpoint keeps for the backward.  The
    in-kernel causal walk (whole-sequence operands) takes equal head
    counts: for it alone K and V are repeated here, after the transpose,
    and dK / dV summed over the group after the kernel
    (``flash.kv_expanded_kernels`` counts those, ``flash.kv_native_kernels``
    the others).  G = 1 is the program it always was.

    Numerically equal to ``dot_product_attention`` (tested, gradients
    included); O(T·D) HBM traffic on BOTH forward and backward (the
    backward kernels recompute P blockwise from the saved logsumexp).
    Precision follows the input dtype (see ``_dot``): f32 inputs are
    exact (multi-pass HIGHEST); bf16 inputs run the MXU at full rate
    with f32 accumulation and f32 online-softmax statistics.
    ``block_q``/``block_k`` given: the grid walk with those blocks.
    Left to default: causal self-attention of 256 <= T within the VMEM
    budget takes the in-kernel causal walk (only the tiles at or before
    the diagonal run, ``_causal_tile``); every other call the grid walk
    with ``_auto_block``'s blocks, the largest VMEM-fitting block
    dividing T — large blocks are where that walk beats XLA dense (see
    BASELINE.md flash-vs-dense ladder).  On the grid walk a causal call
    takes a grid step only for the block pairs with a key at or before
    a query (136 of 256 at T = 8,192, 512-blocks) and masks only those
    the diagonal crosses (16), whatever the two blocks' sizes.
    Interpret mode is selected automatically off TPU.
    ``window`` (static, causal self-attention only): a query sees the
    keys at most ``window - 1`` positions before it and itself.  The
    grid then walks only the key blocks that band touches (2 of 16 at
    T = 8,192, window 512, 512-blocks), forward, dQ and dK/dV alike,
    in kernels named ``window_attn_*``.
    """
    out, _ = _vjp_fwd(q, k, v, causal, block_q, block_k, window)
    return out


def _vjp_fwd(q, k, v, causal, block_q, block_k, window=None):
    b, t, h, dh = q.shape
    bq, bk, tile = _blocks(q, k, causal, block_q, block_k, window)
    group = h // k.shape[2]
    _count_tiles(causal, t, k.shape[1], bq, bk, tile, kernels=1,
                 window=window, group=group)
    scale = 1.0 / math.sqrt(dh)
    # "layout": the (B, T, H, Dh) <-> (BH, T, Dh) transposes around the
    # kernels, named so a trace can charge their copies to attention
    with jax.named_scope("layout"):
        qr, (kr, vr) = _to_bh(q), _kv_to_bh(k, v, group, tile)
    out, lse = _flash_fwd_raw(qr, kr, vr, causal=causal, bq=bq, bk=bk,
                              scale=scale, tile=tile,
                              interpret=_interpret(), window=window)
    # what a checkpoint around this call keeps (``models.remat``): the
    # recomputed forward then needs no kernel; outside one, nothing
    out, lse = name_kernel_outputs(out, lse)
    with jax.named_scope("layout"):
        out_bthd = _from_bh(out, b, h)
    return out_bthd, (q, k, v, out, lse)


def _bwd_impl(causal, block_q, block_k, res, g_out, g_lse=None,
              window=None):
    """Shared backward: ``g_lse`` (the lse cotangent, (B, H, T)) folds
    into the softmax-grad correction term — ∂lse_i/∂s_ij = P_ij lands
    exactly where D_i enters dS = P∘(dP − D), so ``dvec − g_lse`` covers
    it with the kernels unchanged."""
    q, k, v, out_bh, lse = res
    b, t, h, dh = q.shape
    kv = k.shape[2]
    bq, bk, tile = _blocks(q, k, causal, block_q, block_k, window)
    _count_tiles(causal, t, k.shape[1], bq, bk, tile, kernels=2,
                 window=window, group=h // kv)
    scale = 1.0 / math.sqrt(dh)
    with jax.named_scope("layout"):
        do = _to_bh(g_out.astype(q.dtype))
    # D_i = rowsum(dO_i ∘ O_i) — the softmax-grad correction term (f32)
    dvec = jnp.sum(do.astype(jnp.float32) * out_bh.astype(jnp.float32),
                   axis=-1)[:, None, :]
    if g_lse is not None:
        dvec = dvec - g_lse.astype(jnp.float32).reshape(b * h, 1, t)
    with jax.named_scope("layout"):
        qr, (kr, vr) = _to_bh(q), _kv_to_bh(k, v, h // kv, tile)
    dq, dk, dv = _flash_bwd_raw(qr, kr, vr, do, lse, dvec, causal=causal,
                                bq=bq, bk=bk, scale=scale, tile=tile,
                                interpret=_interpret(), window=window)
    with jax.named_scope("layout"):
        return (_from_bh(dq, b, h).astype(q.dtype),
                _kv_from_bh(dk, b, kv).astype(k.dtype),
                _kv_from_bh(dv, b, kv).astype(v.dtype))


def _vjp_bwd(causal, block_q, block_k, window, res, g):
    return _bwd_impl(causal, block_q, block_k, res, g, window=window)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# flash attention WITH the logsumexp exposed (ring / cross-block merging)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_lse(q, k, v, causal: bool = False, block_q=None,
                        block_k=None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp ``lse`` (B, H, T) in f32 — the statistic that lets callers
    merge attention over key/value BLOCKS exactly:

        lse_tot = logaddexp(lse_a, lse_b)
        out_tot = out_a·exp(lse_a − lse_tot) + out_b·exp(lse_b − lse_tot)

    (``parallel.ring`` uses this to run the fused kernel per ring hop.)
    Differentiable in BOTH outputs: an ``lse`` cotangent folds into the
    backward as ``dvec − g_lse`` — since ∂lse_i/∂s_ij = P_ij, the extra
    term lands exactly where the softmax-grad correction D_i already
    enters dS = P∘(dP − D), so the kernels are reused unchanged.
    """
    (out, lse), _ = _vjp_lse_fwd(q, k, v, causal, block_q, block_k)
    return out, lse


def _vjp_lse_fwd(q, k, v, causal, block_q, block_k):
    out, res = _vjp_fwd(q, k, v, causal, block_q, block_k)
    b, t, h, dh = q.shape
    lse = res[4].reshape(b, h, t)  # (BH, 1, T) -> (B, H, T), f32
    return (out, lse), res


def _vjp_lse_bwd(causal, block_q, block_k, res, cts):
    g_out, g_lse = cts
    return _bwd_impl(causal, block_q, block_k, res, g_out, g_lse)


flash_attention_lse.defvjp(_vjp_lse_fwd, _vjp_lse_bwd)
