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
    (T = 4,096 and up at bf16) — runs a (batch, head, step) grid over
    ``_walk_table``: a static table of the block pairs with work,
    scalar-prefetched, that every index map reads; a pair past the
    diagonal is no step and no DMA, and the mask is built only in the
    blocks the diagonal crosses.  At T = 8,192 in 512-blocks 136 of 256
    pairs are steps, 16 of them masked;
  - a sliding window runs (batch, head, query block, band): the key
    blocks the band touches alone (2 of 16), by arithmetic index maps —
    with one idle step in 32 the table's dearer step does not pay;
  - a non-causal call (every rectangular one: the zigzag ring's hops)
    runs the dense (batch, head, block, block) grid: it has no pair to
    skip.
  ("head" is a block of heads of the layout below.)
* the IN-KERNEL causal walk (default blocks, causal, Tq == Tk, 256 <= T
  within the budget): one grid step a (batch, head) with q, k, v (and dO)
  resident as whole-sequence blocks; the kernel body walks the static
  ``_causal_schedule`` — per tile ONE matmul over the tiles at or before
  the diagonal, the mask built only on the tile the diagonal crosses,
  no grid step and no DMA for a tile past it.  At T = 1,024 (256 tiles)
  10 of 16 tile pairs run, 4 of them masked.

The layout: heads of 64 (``_pack``, from the shapes: as many K/V heads
as query heads, an even count of them) are read and written as the
projections make them, (B, T, heads·64), with no transpose around any
kernel.  A block is (rows, 128) columns of that array, two heads told
apart by lane masks, its index map picks the head pair's column block,
and every grid leads with (batch, head pair).  S of head j is q masked
to j's lanes against K (the same MXU passes as a 64-wide contraction),
and P_j V and dS_j K keep j's lanes alone, so the two heads' results
add exactly.  The per-row statistics ``lse`` / ``dvec`` are (B, heads ÷
2, 2, T), a block's heads as rows on lanes.  Every other shape takes the
same kernels on transposed operands, (B·heads, T, Dh), one head a block
and a (batch·head, 1) lead: the counters
``flash.layout_native_kernels`` / ``flash.layout_transposed_kernels``
say which a program built.

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
      the last head's last.  (On the table the group is in the rows.)

    Every grid leads with two axes, (batch, head block), that no body
    reads."""
    if walk is not None and walk[0] == "table":
        (qi_ref, kb_ref, flag_ref), refs = refs[:3], refs[walk[3]:]
        s = pl.program_id(2)
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
    minor = 4 if grouped else 3
    row, j, n = pl.program_id(2), pl.program_id(minor), pl.num_programs(minor)

    def ends():
        if not grouped:
            return j == 0, j == n - 1
        g, last_g = pl.program_id(3), pl.num_programs(3) - 1
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


def _heads(x, pack: int):
    """A block of ``pack`` heads side by side in its lanes as ``pack``
    blocks, each one head's lanes with the others zero (one head: the
    block itself)."""
    if pack == 1:
        return [x]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    width = x.shape[1] // pack
    return [jnp.where((lane >= j * width) & (lane < (j + 1) * width), x,
                      jnp.zeros_like(x)) for j in range(pack)]


def _joined(parts):
    """One block from one a head: head j's lanes from ``parts[j]``."""
    if len(parts) == 1:
        return parts[0]
    lane = lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
    width = parts[0].shape[1] // len(parts)
    out = parts[-1]
    for j in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (j + 1) * width, parts[j], out)
    return out


def _fwd_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                walk, pack: int):
    """One (q block, K/V block) pair a grid step; the accumulators
    persist across a row's steps (TPU executes the grid sequentially,
    minor-most last).  Grid (batch, head, q blocks, k blocks) for a
    non-causal call; (batch, head, steps of ``_walk_table``) for a causal
    one.  The running max and sum, one (rows, 128) scratch a head of
    the block, are kept REPLICATED over its lanes: a lane reduction
    leaves its result that way, so a step loads, updates and stores them
    as whole vregs; kept as one column they cost a lane gather and a
    rotate a vreg a step (451 + 448 in the lowered body), a third of the
    forward's time at 512² blocks (v5e)."""
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc = refs
    width = o_acc.shape[1]

    @pl.when(first)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    def _compute(with_mask):
        # matmuls in the input dtype (f32 → HIGHEST, bf16 → full MXU
        # rate with f32 accumulation); softmax statistics always f32
        k, v = k_ref[0], v_ref[0]
        if with_mask:
            mask = _pair_mask(offset, (block_q, block_k), window)
        corrs, pvs = [], []
        for j, q in enumerate(_heads(q_ref[0], pack)):
            s = _dot_t(q, k) * scale
            if with_mask:
                s = jnp.where(mask, s, _NEG)
            m_prev = m_acc[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))
            if with_mask:
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_acc[j] = l_acc[j] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_acc[j] = m_new
            corrs.append(_lanes(corr, width))
            pvs.append(_dot(p.astype(v.dtype), v))
        o_acc[:] = o_acc[:] * _joined(corrs) + _joined(pvs)

    _run(runs, _compute)

    @pl.when(last)
    def _finalize():
        l = [l_acc[j] for j in range(pack)]
        o_ref[0] = (o_acc[:] / _joined([_lanes(x, width) for x in l])
                    ).astype(o_ref.dtype)
        for j in range(pack):
            lse_ref[0, 0, j] = (m_acc[j] + jnp.log(l[j]))[:, 0]


# ---------------------------------------------------------------------------
# backward (Dao 2022 recurrence; P recomputed blockwise from L)
# ---------------------------------------------------------------------------

def _rowsums(do, o, pack: int, g=None):
    """D = rowsum(dO ∘ O) of each head of a block, the softmax-grad
    correction term, less ``g`` (the ``lse`` cotangent's block, (pack,
    rows) on lanes) where there is one: ∂lse_i/∂s_ij = P_ij lands where
    D_i enters dS = P∘(dP − D).  For each head a float32 (rows, 128)
    block of D replicated over its lanes, and D as a (1, rows) row.

    The sums run on the MXU, against ones, giving the row and the
    replicated column directly, where a lane reduction and the relayout
    of its column to a row would load the vector units (PERF.md §6).  A
    product of two bf16 numbers has 16 significant bits, so it is two
    bf16 parts that sum to it exactly, each multiplied at full rate into
    float32; float32 operands multiply at HIGHEST (``_dot``)."""
    o = o.astype(jnp.float32)
    width = o.shape[1]
    sums = []
    for j, do_j in enumerate(_heads(do, pack)):
        x = do_j.astype(jnp.float32) * o       # head j's lanes alone
        parts = [x]
        if do.dtype != jnp.float32:
            high = x.astype(do.dtype)
            parts = [high, (x - high.astype(jnp.float32)).astype(do.dtype)]
        col = sum(_dot(part, jnp.ones((width, 128), part.dtype))
                  for part in parts)
        row = sum(_dot_t(jnp.ones((8, width), part.dtype), part)
                  for part in parts)[:1]
        if g is not None:
            col, row = col - g[j][:, None], row - g[j:j + 1]
        sums.append((col, row))
    return sums


def _bwd_dq_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                   walk, pack: int, corrected: bool):
    """dQ of a query block over its key blocks; at a row's first step
    the block's D (``_rowsums``) is computed once, kept lane-replicated
    in scratch for the row's steps and written out for dK/dV."""
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *refs = refs
    g_ref = refs.pop(0) if corrected else None
    dq_ref, dvec_ref, dq_acc, dvec_acc = refs

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        for j, (col, row) in enumerate(_rowsums(
                do_ref[0], o_ref[0], pack,
                None if g_ref is None else g_ref[0, 0])):
            dvec_acc[j] = col
            dvec_ref[0, 0, j:j + 1] = row

    def _compute(with_mask):
        k, v = k_ref[0], v_ref[0]
        if with_mask:
            mask = _pair_mask(offset, (block_q, block_k), window)
        parts = []
        for j, (q, do) in enumerate(zip(_heads(q_ref[0], pack),
                                        _heads(do_ref[0], pack))):
            s = _dot_t(q, k) * scale
            p = jnp.exp(s - lse_ref[0, 0, j][:, None])
            if with_mask:
                p = jnp.where(mask, p, 0.0)
            dp = _dot_t(do, v)
            ds = p * (dp - _lanes(dvec_acc[j], block_k)) * scale
            parts.append(_dot(ds.astype(k.dtype), k))
        dq_acc[:] = dq_acc[:] + _joined(parts)

    _run(runs, _compute)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, block_q: int, block_k: int, window,
                    walk, pack: int):
    """A row is a K/V block and its query blocks: every one on the dense
    grid, from the diagonal's on down the causal walk.  S and dP are
    built TRANSPOSED (keys on sublanes), as the in-kernel walk builds
    them: P^T and dS^T feed the dV and dK matmuls as they are (no 512²
    transpose a step), and ``lse`` / ``dvec`` broadcast from the (1, bq)
    lane layout they arrive in.  Of a block of heads, K and V are the
    side masked to a head's lanes."""
    offset, first, last, runs, refs = _step(refs, walk, block_q, block_k)
    (k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref, dk_ref, dv_ref, dk_acc,
     dv_acc) = refs

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(with_mask):
        q, do = q_ref[0], do_ref[0]
        if with_mask:
            mask = _pair_mask(offset, (block_k, block_q), window,
                              keys_first=True)
        dks, dvs = [], []
        for j, (k, v) in enumerate(zip(_heads(k_ref[0], pack),
                                       _heads(v_ref[0], pack))):
            st = _dot_t(k, q) * scale                     # (BK, BQ)
            pt = jnp.exp(st - lse_ref[0, 0, j:j + 1])
            if with_mask:
                pt = jnp.where(mask, pt, 0.0)
            # dV += P^T dO ; dS = P∘(dO V^T − D) ; dK += dS^T Q
            dvs.append(_dot(pt.astype(do.dtype), do))
            dpt = _dot_t(v, do)
            dst = pt * (dpt - dvec_ref[0, 0, j:j + 1]) * scale
            dks.append(_dot(dst.astype(q.dtype), q))
        dv_acc[:] = dv_acc[:] + _joined(dvs)
        dk_acc[:] = dk_acc[:] + _joined(dks)

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
                       tile: int, pack: int):
    """All q tiles of one (batch, head block); a q tile's row of S is
    whole in VMEM, so its softmax is plain: one max, one sum a row."""
    for qs, lo, hi in _causal_schedule(q_ref.shape[1], tile):
        k, v = k_ref[0, lo:hi, :], v_ref[0, lo:hi, :]
        outs = []
        for j, q in enumerate(_heads(q_ref[0, qs:qs + tile, :], pack)):
            s = _mask_diag(_dot_t(q, k) * scale, qs - lo, tile, _NEG)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            outs.append(_dot(p.astype(v.dtype), v) / l)
            lse_ref[0, 0, j:j + 1, qs:qs + tile] = _to_row(m + jnp.log(l))
        o_ref[0, qs:qs + tile, :] = _joined(outs).astype(o_ref.dtype)


def _bwd_dq_causal_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *refs,
                          scale: float, tile: int, pack: int,
                          corrected: bool):
    """dQ of all q tiles of one (batch, head block), and their D
    (``_rowsums``), written out for dK/dV."""
    g_ref = refs[0] if corrected else None
    dq_ref, dvec_ref = refs[-2:]
    for qs, lo, hi in _causal_schedule(q_ref.shape[1], tile):
        k, v = k_ref[0, lo:hi, :], v_ref[0, lo:hi, :]
        rows = slice(qs, qs + tile)
        dvecs = _rowsums(do_ref[0, rows, :], o_ref[0, rows, :], pack,
                         None if g_ref is None else g_ref[0, 0, :, rows])
        parts = []
        for j, (q, do) in enumerate(zip(_heads(q_ref[0, rows, :], pack),
                                        _heads(do_ref[0, rows, :], pack))):
            s = _dot_t(q, k) * scale
            p = jnp.exp(s - lse_ref[0, 0, j, rows][:, None])
            p = _mask_diag(p, qs - lo, tile, 0.0)
            dp = _dot_t(do, v)
            ds = p * (dp - _lanes(dvecs[j][0], hi - lo)) * scale
            parts.append(_dot(ds.astype(k.dtype), k))
            dvec_ref[0, 0, j:j + 1, rows] = dvecs[j][1]
        dq_ref[0, rows, :] = _joined(parts).astype(dq_ref.dtype)


def _bwd_dkv_causal_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref,
                           dk_ref, dv_ref, *, scale: float, tile: int,
                           pack: int):
    """All k tiles of one (batch, head block), S and dP built TRANSPOSED
    (keys on sublanes): P^T and dS^T feed the dV and dK matmuls as they
    are, and the row statistics broadcast from their (1, T) lane
    layout."""
    for ks, lo, hi in _causal_schedule(q_ref.shape[1], tile, by_keys=True):
        q, do = q_ref[0, lo:hi, :], do_ref[0, lo:hi, :]
        dks, dvs = [], []
        for j, (k, v) in enumerate(zip(
                _heads(k_ref[0, ks:ks + tile, :], pack),
                _heads(v_ref[0, ks:ks + tile, :], pack))):
            st = _dot_t(k, q) * scale
            pt = jnp.exp(st - lse_ref[0, 0, j:j + 1, lo:hi])
            pt = _mask_diag(pt, ks - lo, tile, 0.0, keys_first=True)
            dvs.append(_dot(pt.astype(do.dtype), do))
            dpt = _dot_t(v, do)
            dst = pt * (dpt - dvec_ref[0, 0, j:j + 1, lo:hi]) * scale
            dks.append(_dot(dst.astype(q.dtype), q))
        dv_ref[0, ks:ks + tile, :] = _joined(dvs).astype(dv_ref.dtype)
        dk_ref[0, ks:ks + tile, :] = _joined(dks).astype(dk_ref.dtype)


# ---------------------------------------------------------------------------
# launchers
# ---------------------------------------------------------------------------

def _count_tiles(causal, tq, tk, bq, bk, tile, kernels: int,
                 window=None, group: int = 1, pack=None) -> None:
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
    the query heads (the in-kernel walk, ``tile``).  Every call adds its
    kernels to ``flash.layout_native_kernels`` where they read and write
    the projected layout in place (``pack``, ``_pack``), to
    ``flash.layout_transposed_kernels`` where they run on transposed
    operands (``pack`` None)."""
    if sizing():  # the recompute plan's own trace of a child
        return
    registry = default_registry()
    registry.counter("flash.layout_transposed_kernels" if pack is None
                     else "flash.layout_native_kernels").inc(kernels)
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


def _whole(t, width):
    return pl.BlockSpec((1, t, width), lambda n, c: (n, 0, c))


def _whole_row(t, pack):
    return pl.BlockSpec((1, 1, pack, t), lambda n, c: (n, c, 0, 0))


def _walk(causal, tq, tk, bq, bk, window, by_keys=False, group=1):
    """The grid a grid-walk kernel runs on, from what the launcher can
    see: ``(walk, grid, at, prefetch)`` — what ``_step`` reads in the
    kernel, the grid's axes after its leading two (batch, head block),
    the index maps of a block on the query side (``"q"``), on the key
    side (``"k"``) and of a query block of ``lse`` / ``dvec`` on lanes
    (``"row"``), and the scalar-prefetched operands.  A row of the grid
    is a query block, or ``by_keys`` (the dK/dV kernel) a key block.

    * a causal call: (steps,) over ``_walk_table``, whose columns are
      prefetched and read by every index map;
    * a sliding window: (rows, band) — the band's blocks alone, the
      index held at the sequence's end where a step falls off it (the
      kernel skips the step; the block is already there);
    * any other call: the dense (rows, blocks).

    A block's position is (row ``n``, column block ``c``) of the
    leading two axes.  ``group`` query heads share a K/V head (``head =
    kv·group + g``; transposed operands alone, so row ``n`` of the
    (B·H, T, Dh) arrays reads row ``n // group`` of the (B·KV, T, Dh)
    ones).  The leading axes of a query-row grid are the query side's;
    of a ``by_keys`` grid the K/V side's, a key block's row walks the
    group's heads in turn (the table's ``g`` column, or one more axis
    between the rows and their steps) and the query-side maps read row
    ``n·group + g``: the key block and its accumulators stay put over
    the group.  ``group`` = 1 builds what it built before there was one:
    no division, no axis, no column."""
    n_q, n_k = tq // bq, tk // bk
    grouped = by_keys and group > 1

    def query_head(n, g):  # of the grid's row n and a group's head g
        return n * group + g if grouped else n

    def key_head(n):
        return n if by_keys or group == 1 else lax.div(n, group)

    def maps(position):  # the grid's minor indices -> (qi, kb, g)
        def q(n, c, *minor):
            qi, _, g = position(*minor)
            return query_head(n, g), qi, c

        def k(n, c, *minor):
            return key_head(n), position(*minor)[1], c

        def row(n, c, *minor):
            qi, _, g = position(*minor)
            return query_head(n, g), c, 0, qi

        return {"q": q, "k": k, "row": row}

    if causal and window is None:
        table = _walk_table(tq, tk, bq, bk, by_keys, group if by_keys else 1)
        kinds = {step[4] for step in table}
        prefetch = tuple(
            jnp.asarray(column, jnp.int32) for column in zip(*(
                (qi, kb, first * _FIRST | last * _LAST | masked * _MASKED,
                 *g) for qi, kb, first, last, masked, *g in table)))
        at = maps(lambda s, qi, kb, flags, *g: (qi[s], kb[s],
                                                g[0][s] if g else 0))
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

    def position(i, j, g=0):
        return ((walked(i, j), i, g) if by_keys else (i, walked(i, j), g))

    if grouped:
        at = maps(lambda i, g, j: position(i, j, g))
        return ("group", walk), (n_k, group, cols), at, ()
    return walk, (n_k if by_keys else n_q, cols), maps(position), ()


def _grid_walk(body, kernel, args, operands, outputs, out_shape, scratch, *,
               causal, bq, bk, scale, window, interpret, pack, dh,
               by_keys=False):
    """One grid-walk kernel, ``flash_<kernel>`` (``window_attn_<kernel>``
    with a window: the names a trace row reads), on the grid ``_walk``
    gives it.  ``operands`` and ``outputs`` name the kind of each block
    of ``args`` and of the results: ``"q"`` / ``"k"`` a (block, pack·Dh)
    tile on the query / key side, ``"row"`` a query block of ``lse`` or
    ``dvec`` on lanes, (pack, block).  The query side's rows and the key
    side's differ by the group that shares a K/V head (transposed
    operands); a ``by_keys`` grid leads with the key side's."""
    sides = dict(zip(operands, args))
    width = pack * dh
    (nq, tq, cq), (nk, tk, ck) = sides["q"].shape, sides["k"].shape
    walk, grid, at, prefetch = _walk(causal, tq, tk, bq, bk, window, by_keys,
                                     nq // nk)
    shape = {"q": (1, bq, width), "k": (1, bk, width),
             "row": (1, 1, pack, bq)}
    in_specs, out_specs = ([pl.BlockSpec(shape[x], at[x]) for x in kinds]
                           for kinds in (operands, outputs))
    lead = (nk, ck // width) if by_keys else (nq, cq // width)
    return pl.pallas_call(
        functools.partial(body, scale=scale, block_q=bq, block_k=bk,
                          window=window, walk=walk, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(*lead, *grid),
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
                     "window", "pack", "dh")


@functools.partial(jax.jit, static_argnames=_LAUNCHER_STATICS)
def _flash_fwd_raw(qr, kr, vr, *, causal, bq, bk, scale, tile, interpret,
                   pack, dh, window=None):
    """(N, Tq, C) + (N', Tk, C') in → (out (N, Tq, C), lse (N, C ÷
    (pack·Dh), pack, Tq)) via the fused kernel: the projected layout, N
    = B, C = H·Dh, ``pack`` heads a block, or transposed operands, N =
    B·H (B·KV for K and V), C = Dh, ``pack`` 1.  Rectangular Tq ≠ Tk is the ring's half-block hop
    shape (zigzag schedule); causal requires Tq == Tk (diagonal
    alignment).  ``tile`` (``_causal_tile``) selects the in-kernel causal
    walk, which takes equal head counts."""
    n, tq, c = qr.shape
    tk = kr.shape[1]
    width = pack * dh
    if causal and tq != tk:
        raise ValueError(f"causal flash needs equal q/k lengths, got "
                         f"{tq} vs {tk}")
    out_shape = [
        jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        # rows on lanes: a block's second-to-last dim is the array's
        # (``pack``), which satisfies the TPU (8, 128) tiling rule
        jax.ShapeDtypeStruct((n, c // width, pack, tq), jnp.float32),
    ]
    if tile is not None:
        return pl.pallas_call(
            functools.partial(_fwd_causal_kernel, scale=scale, tile=tile,
                              pack=pack),
            grid=(n, c // width),
            in_specs=[_whole(tq, width)] * 3,
            out_specs=[_whole(tq, width), _whole_row(tq, pack)],
            out_shape=out_shape,
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    return _grid_walk(
        _fwd_kernel, "fwd", (qr, kr, vr), ["q", "k", "k"], ["q", "row"],
        out_shape,
        [pltpu.VMEM((bq, width), jnp.float32),
         pltpu.VMEM((pack, bq, 128), jnp.float32),
         pltpu.VMEM((pack, bq, 128), jnp.float32)],
        causal=causal, bq=bq, bk=bk, scale=scale, window=window,
        interpret=interpret, pack=pack, dh=dh)


@functools.partial(jax.jit, static_argnames=_LAUNCHER_STATICS)
def _flash_bwd_raw(qr, kr, vr, do, out, lse, g_lse, *, causal, bq, bk,
                   scale, tile, interpret, pack, dh, window=None):
    """dQ, dK, dV of ``_flash_fwd_raw``'s call from dO, its output and
    ``lse``: the dQ kernel also computes D = rowsum(dO ∘ O) − ``g_lse``
    (the ``lse`` cotangent, in ``lse``'s layout, or None) and hands it
    to the dK/dV kernel."""
    n, tq, c = qr.shape
    width = pack * dh
    dq_shapes = [jax.ShapeDtypeStruct(qr.shape, qr.dtype),
                 jax.ShapeDtypeStruct(lse.shape, jnp.float32)]
    dkv_shapes = [jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                  jax.ShapeDtypeStruct(vr.shape, vr.dtype)]
    corrected = g_lse is not None
    dq_operands = (qr, kr, vr, do, out, lse) + ((g_lse,) if corrected
                                                 else ())

    if tile is not None:
        whole, row = _whole(tq, width), _whole_row(tq, pack)
        dq, dvec = pl.pallas_call(
            functools.partial(_bwd_dq_causal_kernel, scale=scale, tile=tile,
                              pack=pack, corrected=corrected),
            grid=(n, c // width),
            in_specs=[whole] * 5 + [row] * (1 + corrected),
            out_specs=[whole, row],
            out_shape=dq_shapes,
            interpret=interpret,
            name="flash_bwd_dq",
        )(*dq_operands)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_causal_kernel, scale=scale,
                              tile=tile, pack=pack),
            grid=(n, c // width),
            in_specs=[whole] * 4 + [row] * 2,
            out_specs=[whole] * 2,
            out_shape=dkv_shapes,
            interpret=interpret,
            name="flash_bwd_dkv",
        )(kr, vr, qr, do, lse, dvec)
        return dq, dk, dv

    statics = dict(causal=causal, bq=bq, bk=bk, scale=scale, window=window,
                   interpret=interpret, pack=pack, dh=dh)
    dq, dvec = _grid_walk(
        functools.partial(_bwd_dq_kernel, corrected=corrected), "bwd_dq",
        dq_operands, ["q", "k", "k", "q", "q", "row"] + ["row"] * corrected,
        ["q", "row"], dq_shapes,
        [pltpu.VMEM((bq, width), jnp.float32),
         pltpu.VMEM((pack, bq, 128), jnp.float32)], **statics)
    dk, dv = _grid_walk(
        _bwd_dkv_kernel, "bwd_dkv", (kr, vr, qr, do, lse, dvec),
        ["k", "k", "q", "q", "row", "row"], ["k", "k"], dkv_shapes,
        [pltpu.VMEM((bk, width), jnp.float32),
         pltpu.VMEM((bk, width), jnp.float32)], by_keys=True, **statics)
    return dq, dk, dv

    statics = dict(causal=causal, bq=bq, bk=bk, scale=scale, window=window,
                   interpret=interpret, pack=pack, dh=dh)
    dq, = _grid_walk(
        _bwd_dq_kernel, "bwd_dq", operands,
        ["q", "k", "k", "q", "row", "row"], ["q"], [dq_shape],
        [pltpu.VMEM((bq, width), jnp.float32)], **statics)
    dk, dv = _grid_walk(
        _bwd_dkv_kernel, "bwd_dkv", (kr, vr, qr, do, lse, dvec),
        ["k", "k", "q", "q", "row", "row"], ["k", "k"], dkv_shape,
        [pltpu.VMEM((bk, width), jnp.float32),
         pltpu.VMEM((bk, width), jnp.float32)], by_keys=True, **statics)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pack(dh: int, h: int, kv: int):
    """Heads to a 128-lane block of the projected layout, read from the
    shapes: two of 64, where there are as many K/V heads as query heads
    and an even count of them; None for any other shape, whose kernels
    take transposed operands.  Heads of 128 stay transposed: read in
    place, their forward and dK/dV kernels ran 3–4 % slower (rows of
    256 bytes), and rotary embedding's slices of a head cost XLA
    relayouts of q and k in place of the transposes (v5e, PERF.md §6)."""
    if dh == 64 and h == kv and h % 2 == 0:
        return 2
    return None


def _to_kernel(x, pack):
    """(B, T, heads, Dh) as the kernels take it: (B, T, heads·Dh), a
    reshape, in place; (B·heads, T, Dh), a transpose, where ``pack`` is
    None."""
    b, t, h, dh = x.shape
    if pack is None:
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)
    return x.reshape(b, t, h * dh)


def _from_kernel(x, b: int, dh: int, pack):
    """A kernel's operand or result back to (B, T, heads, Dh)."""
    if pack is None:
        rows, t, _ = x.shape
        return x.reshape(b, rows // b, t, dh).transpose(0, 2, 1, 3)
    return x.reshape(b, x.shape[1], -1, dh)


def _operands(q, k, v, pack, tile):
    """q, k, v as the kernels take them.  The grid walk reads K and V at
    their own head count; the in-kernel causal walk's whole-sequence
    kernels take equal head counts, so for it alone K and V are repeated
    to the query heads."""
    group = q.shape[2] // k.shape[2]
    if group > 1 and tile is not None:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    return tuple(_to_kernel(x, pack) for x in (q, k, v))


def _kv_from_kernel(x, b: int, kv: int, dh: int, pack):
    """dK or dV from the kernels → (B, T, KV, Dh).  At the query heads'
    count (the in-kernel walk ran on repeated heads) they are first
    summed over each group, in float32: the repeat's transpose."""
    x = _from_kernel(x, b, dh, pack)
    heads = x.shape[2]
    if heads != kv:
        x = x.reshape(b, x.shape[1], kv, heads // kv, dh).astype(
            jnp.float32).sum(axis=3)
    return x


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

    Heads of 64, as many K/V heads as query heads and an even count of
    them (``_pack``), are read and written in the projected layout, (B,
    T, heads·64): merging the last two dimensions is a reshape, and
    nothing around a kernel transposes; a block is 128 lanes of columns,
    two heads told apart by lane masks.  Every other shape — heads of
    128 among them, and heads of 64 under a group or an odd count of
    them — runs the same kernels on (B·heads, T, Dh) transposes
    (``flash.layout_native_kernels`` / ``flash.layout_transposed_kernels``
    count the two).

    KV divides H: query head ``kv·G + g`` reads K/V head ``kv`` (G = H /
    KV, read from the shapes; ``MultiHeadAttention._expand_kv``'s order,
    which the weights and the decode cache assume).  The grid walk's
    kernels read K and V at their own head count — a K/V block's index
    map is ``head // G``, and dK/dV runs over the K/V heads, a key
    block's row walking its query blocks once for each head of the
    group into one float32 accumulator — so nothing on the K/V side is
    ever H heads wide: not dK / dV, not the K and V a checkpoint keeps
    for the backward.  The in-kernel causal walk (whole-sequence
    operands) takes equal head counts: for it alone K and V are repeated
    here and dK / dV summed over the group after the kernel
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


def _plan(q, k, causal, block_q, block_k, window, kernels: int):
    """What a call's kernels are built with, counted once for each of
    its ``kernels``: ``(bq, bk, tile, pack)``."""
    b, t, h, dh = q.shape
    kv = k.shape[2]
    bq, bk, tile = _blocks(q, k, causal, block_q, block_k, window)
    pack = _pack(dh, h, kv)
    _count_tiles(causal, t, k.shape[1], bq, bk, tile, kernels=kernels,
                 window=window, group=h // kv, pack=pack)
    return bq, bk, tile, pack


def _vjp_fwd(q, k, v, causal, block_q, block_k, window=None):
    b, t, h, dh = q.shape
    bq, bk, tile, pack = _plan(q, k, causal, block_q, block_k, window, 1)
    # "layout": what is left around the kernels (the fallback's
    # transposes, the in-kernel walk's K/V repeat), named so a trace can
    # charge its copies to attention; in place it is reshapes alone
    with jax.named_scope("layout"):
        qr, kr, vr = _operands(q, k, v, pack, tile)
    out, lse = _flash_fwd_raw(qr, kr, vr, causal=causal, bq=bq, bk=bk,
                              scale=1.0 / math.sqrt(dh), tile=tile,
                              interpret=_interpret(), pack=pack or 1, dh=dh,
                              window=window)
    # what a checkpoint around this call keeps (``models.remat``): the
    # recomputed forward then needs no kernel; outside one, nothing
    out, lse = name_kernel_outputs(out, lse)
    with jax.named_scope("layout"):
        out_bthd = _from_kernel(out, b, dh, pack)
    return out_bthd, (q, k, v, out, lse)


def _bwd_impl(causal, block_q, block_k, res, g_out, g_lse=None,
              window=None):
    """Shared backward: ``g_lse`` (the lse cotangent, (B, H, T)) folds
    into the softmax-grad correction term — ∂lse_i/∂s_ij = P_ij lands
    exactly where D_i enters dS = P∘(dP − D), so the dQ kernel's
    ``D − g_lse`` covers it with the kernels' walks unchanged."""
    q, k, v, out, lse = res
    b, t, h, dh = q.shape
    bq, bk, tile, pack = _plan(q, k, causal, block_q, block_k, window, 2)
    with jax.named_scope("layout"):
        do = _to_kernel(g_out.astype(q.dtype), pack)
        qr, kr, vr = _operands(q, k, v, pack, tile)
    if g_lse is not None:
        g_lse = g_lse.astype(jnp.float32).reshape(lse.shape)
    dq, dk, dv = _flash_bwd_raw(qr, kr, vr, do, out, lse, g_lse,
                                causal=causal, bq=bq, bk=bk,
                                scale=1.0 / math.sqrt(dh), tile=tile,
                                interpret=_interpret(), pack=pack or 1, dh=dh,
                                window=window)
    kv = k.shape[2]
    with jax.named_scope("layout"):
        return (_from_kernel(dq, b, dh, pack).astype(q.dtype),
                _kv_from_kernel(dk, b, kv, dh, pack).astype(k.dtype),
                _kv_from_kernel(dv, b, kv, dh, pack).astype(v.dtype))


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
    lse = res[4].reshape(b, h, t)  # rows of (B, H ÷ pack, pack, T), f32
    return (out, lse), res


def _vjp_lse_bwd(causal, block_q, block_k, res, cts):
    g_out, g_lse = cts
    return _bwd_impl(causal, block_q, block_k, res, g_out, g_lse)


flash_attention_lse.defvjp(_vjp_lse_fwd, _vjp_lse_bwd)
