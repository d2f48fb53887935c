"""Flash attention as Pallas TPU kernels — fused forward AND backward.

The one hot op where hand-scheduling beats XLA's fusion: dense attention
materializes the (T×T) score matrix in HBM; these kernels stream K/V
blocks through VMEM on a (batch·head, block, block) grid with the
online-softmax running statistics in VMEM scratch that persists across the
minor grid dimension — HBM traffic is O(T·D) instead of O(T²), so
sequence length is limited by HBM, not by the score matrix (verified:
T=16k+ on one v5e chip where the dense path's scores alone would need
tens of GB).

Backward is the standard flash recurrence (Dao 2022): the forward saves
only O and the per-row logsumexp L; dQ and dK/dV are each one fused kernel
re-computing P = exp(S − L) blockwise, so training memory is O(T·D) too.

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


def _causal_mask(qi, kb, block_q, block_k, shape):
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    return k_pos <= q_pos


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc, *,
                causal: bool, scale: float, block_q: int, block_k: int):
    """Grid (bh, qi, kb): one K/V block per step; accumulators persist
    across kb (TPU executes the grid sequentially, minor-most last)."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    def _compute():
        # matmuls in the input dtype (f32 → HIGHEST, bf16 → full MXU
        # rate with f32 accumulation); softmax statistics always f32
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        if causal:
            mask = _causal_mask(qi, kb, block_q, block_k, s.shape)
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

    if causal:
        # skip K/V blocks entirely in the future of this q block
        pl.when(kb * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_acc[:, 0]
        o_ref[0] = (o_acc[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_acc[:, 0] + jnp.log(l)


def _flash_fwd_raw(qr, kr, vr, *, causal, bq, bk, scale):
    """(BH, Tq, D) + (BH, Tk, D) in → (out (BH,Tq,D), lse (BH,Tq)) via the
    fused kernel.  Rectangular Tq ≠ Tk is the ring's half-block hop shape
    (zigzag schedule); causal requires Tq == Tk (diagonal alignment)."""
    bh, tq, dh = qr.shape
    tk = kr.shape[1]
    if causal and tq != tk:
        raise ValueError(f"causal flash needs equal q/k lengths, got "
                         f"{tq} vs {tk}")
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=bq, block_k=bk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            # (bh, 1, t) layout so the block's last-two dims satisfy the
            # TPU (8, 128) tiling rule (second-to-last == array dim == 1)
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dh), qr.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        interpret=_interpret(),
        name="flash_fwd",
    )(qr, kr, vr)
    return out, lse


# ---------------------------------------------------------------------------
# backward (Dao 2022 recurrence; P recomputed blockwise from L)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref,
                   dq_acc, *, causal: bool, scale: float, block_q: int,
                   block_k: int):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        s = _dot_t(q_ref[0], k_ref[0]) * scale
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if causal:
            mask = _causal_mask(qi, kb, block_q, block_k, s.shape)
            p = jnp.where(mask, p, 0.0)
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - dvec_ref[0, 0][:, None]) * scale
        dq_acc[:] = dq_acc[:] + _dot(ds.astype(k_ref.dtype), k_ref[0])

    if causal:
        pl.when(kb * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                    scale: float, block_q: int, block_k: int):
    kb = pl.program_id(1)
    qj = pl.program_id(2)
    n_qb = pl.num_programs(2)

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        s = _dot_t(q_ref[0], k_ref[0]) * scale        # (BQ, BK)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if causal:
            mask = _causal_mask(qj, kb, block_q, block_k, s.shape)
            p = jnp.where(mask, p, 0.0)
        # dV += P^T dO ; dS = P∘(dO V^T − D) ; dK += dS^T Q
        dv_acc[:] = dv_acc[:] + _dot(p.T.astype(do_ref.dtype), do_ref[0])
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - dvec_ref[0, 0][:, None]) * scale
        dk_acc[:] = dk_acc[:] + _dot(ds.T.astype(q_ref.dtype), q_ref[0])

    if causal:
        # skip q blocks entirely ABOVE this k block's diagonal
        pl.when(qj * block_q + block_q - 1 >= kb * block_k)(_compute)
    else:
        _compute()

    @pl.when(qj == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_raw(qr, kr, vr, do, lse, dvec, *, causal, bq, bk, scale):
    bh, tq, dh = qr.shape
    tk = kr.shape[1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk),
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # dvec
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, dh), qr.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qr, kr, vr, do, lse, dvec)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk),
        grid=(bh, tk // bk, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),  # k
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),  # v
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, j, 0)),  # q
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, j, 0)),  # do
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j)),   # dvec
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, dh), kr.dtype),
                   jax.ShapeDtypeStruct((bh, tk, dh), vr.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
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
    """Default block size: as LARGE as VMEM allows (measured r4 at
    T=8192/dh=64: 1024² blocks run the fused bwd 3.4× faster than the old
    128² default and 2.4× faster than XLA dense — the per-grid-step
    overhead and small-K matmuls dominated at 128).  The score block is
    b²·4 bytes of VMEM (f32), with 2-3 alive in the backward, so the cap
    shrinks as the head dim's tiles grow.

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


def _blocks(tq, tk, block_q, block_k, dh):
    if block_q is None:
        block_q = _auto_block(tq, dh)
    if block_k is None:
        block_k = _auto_block(tk, dh)
    bq, bk = min(block_q, tq), min(block_k, tk)
    if tq % bq or tk % bk:
        raise ValueError(f"sequence lengths ({tq}, {tk}) must divide "
                         f"block sizes ({bq}, {bk})")
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False, block_q=None,
                    block_k=None):
    """Pallas flash attention; q/k/v (B, T, H, Dh) → (B, T, H, Dh).

    Numerically equal to ``dot_product_attention`` (tested, gradients
    included); O(T·D) HBM traffic on BOTH forward and backward (the
    backward kernels recompute P blockwise from the saved logsumexp).
    Precision follows the input dtype (see ``_dot``): f32 inputs are
    exact (multi-pass HIGHEST); bf16 inputs run the MXU at full rate
    with f32 accumulation and f32 online-softmax statistics.
    ``block_q``/``block_k`` default to the auto rule (``_auto_block``):
    the largest VMEM-fitting block dividing T — large blocks are where
    the kernels beat XLA dense (see BASELINE.md flash-vs-dense ladder).
    Interpret mode is selected automatically off TPU.
    """
    out, _ = _vjp_fwd(q, k, v, causal, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, causal, block_q, block_k):
    b, t, h, dh = q.shape
    bq, bk = _blocks(t, k.shape[1], block_q, block_k, dh)
    scale = 1.0 / math.sqrt(dh)
    # "layout": the (B, T, H, Dh) <-> (BH, T, Dh) transposes around the
    # kernels, named so a trace can charge their copies to attention
    with jax.named_scope("layout"):
        qr, kr, vr = _to_bh(q), _to_bh(k), _to_bh(v)
    out, lse = _flash_fwd_raw(qr, kr, vr, causal=causal, bq=bq, bk=bk,
                              scale=scale)
    with jax.named_scope("layout"):
        out_bthd = _from_bh(out, b, h)
    return out_bthd, (q, k, v, out, lse)


def _bwd_impl(causal, block_q, block_k, res, g_out, g_lse=None):
    """Shared backward: ``g_lse`` (the lse cotangent, (B, H, T)) folds
    into the softmax-grad correction term — ∂lse_i/∂s_ij = P_ij lands
    exactly where D_i enters dS = P∘(dP − D), so ``dvec − g_lse`` covers
    it with the kernels unchanged."""
    q, k, v, out_bh, lse = res
    b, t, h, dh = q.shape
    bq, bk = _blocks(t, k.shape[1], block_q, block_k, dh)
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
                                bq=bq, bk=bk, scale=scale)
    with jax.named_scope("layout"):
        return (_from_bh(dq, b, h).astype(q.dtype),
                _from_bh(dk, b, h).astype(k.dtype),
                _from_bh(dv, b, h).astype(v.dtype))


def _vjp_bwd(causal, block_q, block_k, res, g):
    return _bwd_impl(causal, block_q, block_k, res, g)


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
