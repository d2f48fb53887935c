"""Flash attention as Pallas TPU kernels — fused forward AND backward.

The one hot op where hand-scheduling beats XLA's fusion: dense attention
materializes the (T×T) score matrix in HBM; these kernels keep the scores
in VMEM, so HBM traffic is O(T·D) instead of O(T²) and sequence length is
limited by HBM, not by the score matrix (verified: T=16k+ on one v5e chip
where the dense path's scores alone would need tens of GB).

Two walks over the (q tile, k tile) pairs, chosen by ``_blocks`` from what
it can see (``causal``, the two lengths, T, Dh, the dtype):

* the GRID walk: K/V blocks stream through VMEM on a (batch·head, block,
  block) grid, the online-softmax running statistics in VMEM scratch that
  persists across the minor grid dimension.  Every non-causal call, every
  rectangular one (the zigzag ring's hops), every call with explicit
  ``block_q``/``block_k``, and causal lengths whose whole-sequence
  operands would not fit ``_CAUSAL_VMEM_BUDGET`` (T = 4,096 and up at
  bf16).  Blocks come from ``_auto_block``: as large as VMEM allows.
* the IN-KERNEL causal walk (default blocks, causal, Tq == Tk, 256 <= T
  within the budget): one grid step a batch·head with q, k, v (and dO)
  resident as whole-sequence blocks; the kernel body walks the static
  ``_causal_schedule`` — per tile ONE matmul over the tiles at or before
  the diagonal, the mask built only on the tile the diagonal crosses,
  no grid step and no DMA for a tile past it.  At T = 1,024 (256 tiles)
  10 of 16 tile pairs run, 4 of them masked.

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


def _causal_mask(qi, kb, block_q, block_k, shape, window=None):
    """key <= query, and with a ``window`` also query - key < window."""
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (q_pos - k_pos < window)


def _band_blocks(window: int, block: int) -> int:
    """Key blocks a query block of a sliding-window call can see: its
    own and those the ``window - 1`` keys before its first query reach
    into (2 at window = block = 512)."""
    return 1 + -(-(window - 1) // block)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc, *,
                causal: bool, scale: float, block_q: int, block_k: int,
                window=None):
    """Grid (bh, qi, kb): one K/V block per step; accumulators persist
    across kb (TPU executes the grid sequentially, minor-most last).
    With a ``window`` the last grid axis walks the BAND alone: step j is
    key block qi - (band - 1) + j (the launcher's index map clamps it),
    and a step before the sequence's first block does nothing."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    n_kb = pl.num_programs(2)
    kb = step if window is None else qi - (n_kb - 1) + step

    @pl.when(step == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    def _compute():
        # matmuls in the input dtype (f32 → HIGHEST, bf16 → full MXU
        # rate with f32 accumulation); softmax statistics always f32
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        if causal:
            mask = _causal_mask(qi, kb, block_q, block_k, s.shape, window)
            s = jnp.where(mask, s, _NEG)
        m_prev = m_acc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[:, 0] = l_acc[:, 0] * corr + jnp.sum(p, axis=-1)
        o_acc[:] = o_acc[:] * corr[:, None] + _dot(
            p.astype(v_ref.dtype), v_ref[0])
        m_acc[:, 0] = m_new

    if window is not None:
        pl.when(kb >= 0)(_compute)
    elif causal:
        # skip K/V blocks entirely in the future of this q block
        pl.when(kb * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(step == n_kb - 1)
    def _finalize():
        l = l_acc[:, 0]
        o_ref[0] = (o_acc[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_acc[:, 0] + jnp.log(l)


# ---------------------------------------------------------------------------
# backward (Dao 2022 recurrence; P recomputed blockwise from L)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref,
                   dq_acc, *, causal: bool, scale: float, block_q: int,
                   block_k: int, window=None):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    n_kb = pl.num_programs(2)
    kb = step if window is None else qi - (n_kb - 1) + step  # the band

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if causal:
            mask = _causal_mask(qi, kb, block_q, block_k, s.shape, window)
            p = jnp.where(mask, p, 0.0)
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - dvec_ref[0, 0][:, None]) * scale
        dq_acc[:] = dq_acc[:] + _dot(ds.astype(k_ref.dtype), k_ref[0])

    if window is not None:
        pl.when(kb >= 0)(_compute)
    elif causal:
        pl.when(kb * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(step == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                    scale: float, block_q: int, block_k: int, window=None,
                    n_q=None):
    """With a ``window`` the last grid axis walks the band: step j is
    query block kb + j, and a step past the last of the ``n_q`` query
    blocks does nothing."""
    kb = pl.program_id(1)
    step = pl.program_id(2)
    n_qb = pl.num_programs(2)
    qj = step if window is None else kb + step

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        s = _dot_t(q_ref[0], k_ref[0]) * scale        # (BQ, BK)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if causal:
            mask = _causal_mask(qj, kb, block_q, block_k, s.shape, window)
            p = jnp.where(mask, p, 0.0)
        # dV += P^T dO ; dS = P∘(dO V^T − D) ; dK += dS^T Q
        dv_acc[:] = dv_acc[:] + _dot(p.T.astype(do_ref.dtype), do_ref[0])
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - dvec_ref[0, 0][:, None]) * scale
        dk_acc[:] = dk_acc[:] + _dot(ds.T.astype(q_ref.dtype), q_ref[0])

    if window is not None:
        pl.when(qj < n_q)(_compute)
    elif causal:
        # skip q blocks entirely ABOVE this k block's diagonal
        pl.when(qj * block_q + block_q - 1 >= kb * block_k)(_compute)
    else:
        _compute()

    @pl.when(step == n_qb - 1)
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
                 window=None) -> None:
    """The schedule is static, so it is counted where it is built: a
    causal call adds the tile pairs its kernels execute and the tile
    pairs in all, once for each of the ``kernels`` it puts into the
    program, to the default registry's ``flash.causal_tiles_executed`` /
    ``flash.causal_tiles_total`` (10 and 16 a kernel at T = 1,024); a
    sliding-window call to ``flash.window_tiles_executed`` /
    ``flash.window_tiles_total`` instead (31 of 256 at T = 8,192,
    window 512)."""
    if not causal or sizing():  # the recompute plan's own trace of a child
        return
    if window is not None:
        band, n = _band_blocks(window, bq), tq // bq
        registry = default_registry()
        registry.counter("flash.window_tiles_executed").inc(
            kernels * sum(min(band, qi + 1) for qi in range(n)))
        registry.counter("flash.window_tiles_total").inc(kernels * n * n)
        return
    if tile is not None:
        executed = sum((hi - lo) // tile
                       for _, lo, hi in _causal_schedule(tq, tile))
        total = (tq // tile) ** 2
    else:
        total = (tq // bq) * (tk // bk)
        executed = sum(1 for qi in range(tq // bq) for kb in range(tk // bk)
                       if kb * bk <= qi * bq + bq - 1)
    registry = default_registry()
    registry.counter("flash.causal_tiles_executed").inc(kernels * executed)
    registry.counter("flash.causal_tiles_total").inc(kernels * total)


def _whole(t, dh):
    return pl.BlockSpec((1, t, dh), lambda b: (b, 0, 0))


def _kv_index(window, band):
    """Index map of the operand the last grid axis walks in the forward
    and dQ kernels: step j is block j, or with a ``window`` the band's
    block i - (band - 1) + j, held at 0 where that falls before the
    sequence (the kernel skips the step; the block is already there)."""
    if window is None:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b, jnp.maximum(i - (band - 1) + j, 0), 0)


def _whole_row(t):
    return pl.BlockSpec((1, 1, t), lambda b: (b, 0, 0))


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
    """(BH, Tq, D) + (BH, Tk, D) in → (out (BH,Tq,D), lse (BH,Tq)) via the
    fused kernel.  Rectangular Tq ≠ Tk is the ring's half-block hop shape
    (zigzag schedule); causal requires Tq == Tk (diagonal alignment).
    ``tile`` (``_causal_tile``) selects the in-kernel causal walk."""
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
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=bq, block_k=bk, window=window)
    band = tk // bk if window is None else _band_blocks(window, bk)
    kv_spec = pl.BlockSpec((1, bk, dh), _kv_index(window, band))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, tq // bq, band),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        interpret=interpret,
        name="flash_fwd" if window is None else "window_attn_fwd",
    )(qr, kr, vr)
    return out, lse


@functools.partial(jax.jit, static_argnames=_LAUNCHER_STATICS)
def _flash_bwd_raw(qr, kr, vr, do, lse, dvec, *, causal, bq, bk, scale,
                   tile, interpret, window=None):
    bh, tq, dh = qr.shape
    tk = kr.shape[1]
    dq_shape = jax.ShapeDtypeStruct((bh, tq, dh), qr.dtype)
    dkv_shape = [jax.ShapeDtypeStruct((bh, tk, dh), kr.dtype),
                 jax.ShapeDtypeStruct((bh, tk, dh), vr.dtype)]
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

    n_q = tq // bq
    band = None if window is None else _band_blocks(window, bk)
    kv_spec = pl.BlockSpec((1, bk, dh), _kv_index(window, band))
    if window is None:
        def q_walk(b, i, j):  # the dK/dV kernel's q, dO, lse, dvec blocks
            return j
    else:
        def q_walk(b, i, j):  # band: query block i + j, held at the last
            return jnp.minimum(i + j, n_q - 1)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, window=window),
        grid=(bh, n_q, band or tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),  # q
            kv_spec,                                               # k
            kv_spec,                                               # v
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # dvec
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq" if window is None else "window_attn_bwd_dq",
    )(*operands)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, window=window, n_q=n_q),
        grid=(bh, tk // bk, band or n_q),
        in_specs=[
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),  # k
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),  # v
            pl.BlockSpec((1, bq, dh),
                         lambda b, i, j: (b, q_walk(b, i, j), 0)),  # q
            pl.BlockSpec((1, bq, dh),
                         lambda b, i, j: (b, q_walk(b, i, j), 0)),  # do
            pl.BlockSpec((1, 1, bq),
                         lambda b, i, j: (b, 0, q_walk(b, i, j))),  # lse
            pl.BlockSpec((1, 1, bq),
                         lambda b, i, j: (b, 0, q_walk(b, i, j))),  # dvec
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=dkv_shape,
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv" if window is None else "window_attn_bwd_dkv",
    )(kr, vr, qr, do, lse, dvec)
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
    ``_causal_tile``) — else None and the blocks decide.  A sliding
    window keeps the grid walk, over the band's blocks alone."""
    tq, tk, dh = q.shape[1], k.shape[1], q.shape[3]
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
    """Pallas flash attention; q/k/v (B, T, H, Dh) → (B, T, H, Dh).

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
    BASELINE.md flash-vs-dense ladder).  Interpret mode is selected
    automatically off TPU.
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
    _count_tiles(causal, t, k.shape[1], bq, bk, tile, kernels=1,
                 window=window)
    scale = 1.0 / math.sqrt(dh)
    # "layout": the (B, T, H, Dh) <-> (BH, T, Dh) transposes around the
    # kernels, named so a trace can charge their copies to attention
    with jax.named_scope("layout"):
        qr, kr, vr = _to_bh(q), _to_bh(k), _to_bh(v)
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
    bq, bk, tile = _blocks(q, k, causal, block_q, block_k, window)
    _count_tiles(causal, t, k.shape[1], bq, bk, tile, kernels=2,
                 window=window)
    scale = 1.0 / math.sqrt(dh)
    with jax.named_scope("layout"):
        do = _to_bh(g_out.astype(q.dtype))
    # D_i = rowsum(dO_i ∘ O_i) — the softmax-grad correction term (f32)
    dvec = jnp.sum(do.astype(jnp.float32) * out_bh.astype(jnp.float32),
                   axis=-1)[:, None, :]
    if g_lse is not None:
        dvec = dvec - g_lse.astype(jnp.float32).reshape(b * h, 1, t)
    with jax.named_scope("layout"):
        qr, kr, vr = _to_bh(q), _to_bh(k), _to_bh(v)
    dq, dk, dv = _flash_bwd_raw(qr, kr, vr, do, lse, dvec, causal=causal,
                                bq=bq, bk=bk, scale=scale, tile=tile,
                                interpret=_interpret(), window=window)
    with jax.named_scope("layout"):
        return (_from_bh(dq, b, h).astype(q.dtype),
                _from_bh(dk, b, h).astype(k.dtype),
                _from_bh(dv, b, h).astype(v.dtype))


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
