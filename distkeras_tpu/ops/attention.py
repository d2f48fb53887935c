"""Attention ops + MultiHeadAttention layer.

The reference predates attention entirely (SURVEY.md §5.7: its sequence
models are small LSTMs).  Long-context support is first-class here, so the
framework ships a standard MXU-friendly attention stack:

* ``dot_product_attention`` — fused-softmax reference implementation (XLA
  fuses QK^T → softmax → PV into MXU-resident loops).
* ``MultiHeadAttention`` — a ``Layer`` usable in Sequential stacks.
* The sequence-parallel ring formulation lives in
  ``distkeras_tpu.parallel.ring`` and reuses the same online-softmax math.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..models.layers import Layer, glorot_uniform, register, uniform_scale
from ..obs import get_logger

#: minimum sequence length for a causal mesh-attached layer to AUTO-pick
#: the zigzag ring layout (ADVICE r5): zigzag halves the causal ring's
#: executed FLOPs, but without :func:`models.optimize.zigzag_wrap` every
#: attention call pays a shuffle + unshuffle of its activations (two
#: global token-axis gathers) — a net loss at small T, where attention is
#: not the dominant cost.  Pin ``layer.ring_layout`` to override either
#: way; ``zigzag_wrap`` amortizes the stripe to once per batch and forces
#: zigzag regardless of this threshold.
ZIGZAG_AUTO_MIN_T = 256

#: layout decisions already logged (once per distinct choice, not per
#: trace/call — the auto-switch must not be silent, ADVICE r5)
_LAYOUT_LOGGED: set = set()


def _log_layout_choice(layout: str, t: int, sp: int) -> None:
    key = (layout, t, sp)
    if key in _LAYOUT_LOGGED:
        return
    _LAYOUT_LOGGED.add(key)
    why = (f"T={t} >= ZIGZAG_AUTO_MIN_T={ZIGZAG_AUTO_MIN_T}"
           if layout == "zigzag" else
           f"T={t} below ZIGZAG_AUTO_MIN_T={ZIGZAG_AUTO_MIN_T} or "
           f"not divisible by 2*|sp|={2 * sp}")
    get_logger("ops.attention").info(
        "causal ring auto-selected %r layout (%s); zigzag pays a per-call "
        "shuffle/unshuffle unless models.optimize.zigzag_wrap amortizes "
        "the stripe to once per batch; pin layer.ring_layout to override",
        layout, why)


def dot_product_attention(q, k, v, *, causal: bool = False, window=None):
    """Scaled dot-product attention.

    q: (B, Tq, H, Dh); k/v: (B, Tk, H, Dh) → (B, Tq, H, Dh).
    ``window`` (causal only): a query sees itself and the ``window - 1``
    keys before it.
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        seen = ki <= qi
        if window is not None:
            seen = seen & (qi - ki < window)
        scores = jnp.where(seen, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def rope_frequencies(rotary_dim: int, base: float = 10000.0,
                     scaling: Optional[dict] = None):
    """``(inverse frequencies (rotary_dim / 2,) float32, scale)`` of a
    rotary embedding: plain ``base ** (-2i / rotary_dim)`` and 1.0, or
    with ``scaling = {"rope_type": "yarn", "factor",
    "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor"}`` the YaRN blend (Peng et al. 2023): pair i keeps
    its frequency while it turns more than ``beta_fast`` times inside
    the original context, takes frequency / factor once it turns fewer
    than ``beta_slow`` times, and a linear ramp between the two pair
    indices; cos and sin are then multiplied by ``attention_factor``."""
    import numpy as np
    half = rotary_dim // 2
    freqs = base ** (-np.arange(half, dtype=np.float64) / half)
    if not scaling or scaling.get("rope_type", "default") == "default":
        return freqs.astype(np.float32), 1.0
    if scaling["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {scaling['rope_type']!r}")
    original = scaling["original_max_position_embeddings"]

    def pair_turning(turns):  # the pair that turns this often in `original`
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(pair_turning(scaling["beta_fast"])), 0)
    hi = min(math.ceil(pair_turning(scaling["beta_slow"])), rotary_dim - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    blended = freqs / scaling["factor"] * ramp + freqs * (1.0 - ramp)
    return blended.astype(np.float32), float(scaling["attention_factor"])


def apply_rope(x, positions, base: float = 10000.0, *, inv_freq=None,
               scale: float = 1.0):
    """Rotary position embedding (RoPE, Su et al. 2021), HALF-SPLIT
    (GPT-NeoX-style) convention: dim i pairs with dim i + Dh/2 — NOT the
    interleaved (2i, 2i+1) layout some implementations use; weights are
    not portable between the two conventions without a permutation.
    Rotates each pair of ``x`` (…, T, H, Dh) by position-scaled angles.
    ``positions``: (T,) int — absolute positions of x's time axis (a
    scalar-position caller passes shape (1,)) — or (B, T) for PER-ROW
    positions (ragged cached decode: each row sits at its own absolute
    position).  Attention scores between RoPE'd q/k depend only on
    RELATIVE position, which is what lets a cached decode
    rotate-then-store.
    ``inv_freq`` (n,) (``rope_frequencies``; default: the plain ones of
    ``base`` over the whole head): rotate the first 2n dims of each head
    with these frequencies, pair i with i + n, and pass the other dims
    through (a partial rotary factor); cos and sin are multiplied by
    ``scale``."""
    dh = x.shape[-1]
    if inv_freq is None:
        inv_freq, _ = rope_frequencies(dh, base)
    freqs = jnp.asarray(inv_freq, jnp.float32)
    half = freqs.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (…, T, half)
    if ang.ndim == 2:  # shared positions: broadcast over the batch
        ang = ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if 2 * half < dh:  # a partial rotary factor: the rest passes through
        parts.append(x[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1)


def _flash_with_blocking(q, k, v, causal: bool, t: int, window=None):
    """Run the Pallas flash kernel with a block the TPU can tile.

    The kernels take whole blocks that are a multiple of 128 or the
    whole (short) sequence (``pallas_attention._tileable``); a longer T
    that no multiple of 128 divides (T=200, T=544, prime T) has no such
    block, and Mosaic refuses anything else.  For causal
    attention, end-padding T to a multiple of 128 is exact: padded KEY
    positions sit strictly after every real query (never attended), and
    padded QUERY rows are sliced off (their zero cotangent keeps
    gradients exact too).  Non-causal attention would attend the padded
    keys, so there we refuse loudly instead.  ``k`` / ``v`` may carry
    fewer heads than ``q`` (grouped queries); they are padded as they are.
    """
    from .pallas_attention import _tileable, flash_attention
    if _tileable(t):
        # the kernels pick their own walk (pallas_attention._blocks):
        # causal lengths that fit VMEM whole run only the tiles at or
        # before the diagonal, inside the kernel; the rest walk the grid
        # in the largest VMEM-fitting blocks dividing T
        return flash_attention(q, k, v, causal, None, None, window)
    if not causal:
        raise ValueError(
            f"impl='flash' needs a sequence length with a block-sized "
            f"divisor; T={t} is neither a multiple of 128 nor at most "
            f"128 (one block), and no other block tiles on the TPU.  Pad "
            f"T to a multiple of 128 (with key masking) or use "
            f"impl='dense'.")
    pad = -t % 128
    padded = [jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v)]
    return flash_attention(*padded, True, None, None, window)[:, :t]


@register
class MultiHeadAttention(Layer):
    """Self-attention over (T, D) inputs; fused qkv projection (one
    MXU-shaped (D, D + 2·KV·Dh) GEMM — (D, 3D) in the classic
    full-head case) + output projection.

    ``impl``: ``"dense"`` (XLA-fused reference) or ``"flash"`` (the Pallas
    VMEM-resident kernels, ``ops.pallas_attention``: fused forward AND
    backward, both O(T·D) HBM — the forward saves only O and the per-row
    logsumexp, dQ/dK/dV recompute scores blockwise).  Flash scales a
    single chip to HBM-limited sequence lengths for training and
    inference; past one chip, attach a mesh (``layer.mesh = mesh``, find
    instances via ``model.iter_layers()``) to run the sequence-parallel
    ring path (``parallel.ring``): T shards over ``layer.ring_axis`` and
    K/V rotate via ppermute.  Like ``MoEDense.mesh`` this is TRACE-time
    runtime placement: attach before jitting, and it is not part of the
    serialized config.

    What a current decoder's attention adds, each off by default (the
    defaults build the classic layer, parameter for parameter):
    ``head_dim`` — a head size that is not dim / heads, so q is
    ``num_heads * head_dim`` wide and the output projection maps that
    back to dim; ``window`` — causal sliding-window attention, a query
    sees itself and the ``window - 1`` keys before it (``impl="flash"``
    walks the band's blocks only); ``rope_theta``, ``rope_fraction``,
    ``rope_scaling`` — the rotary base, the leading share of each head
    that is rotated (the rest passes through), and a YaRN description
    (``rope_frequencies``); ``gate`` — a per-head sigmoid gate computed
    from the layer's input, ``(D, H)`` more parameters, on each head's
    attention output before the output projection; ``qk_norm`` — an
    RMSNorm (``norm_eps``) over all H·Dh columns of q and one over all
    KV·Dh of k, each with a weight of its width, before the heads are
    split (the Olmo 2 family's).  Window and gate are
    training-path features: the decode cache does not know them yet.
    """

    time_mixing = True  # has its own apply_decode/apply_prefill rules

    def __init__(self, num_heads: int, causal: bool = False,
                 impl: str = "dense", num_kv_heads: Optional[int] = None,
                 rope: bool = False, head_dim: Optional[int] = None,
                 window: Optional[int] = None, rope_theta: float = 10000.0,
                 rope_fraction: float = 1.0,
                 rope_scaling: Optional[dict] = None, gate: bool = False,
                 qk_norm: bool = False, norm_eps: float = 1e-6):
        if impl not in ("dense", "flash"):
            raise ValueError(f"impl must be 'dense' or 'flash', got {impl!r}")
        self.num_heads = int(num_heads)
        self.qk_norm, self.norm_eps = bool(qk_norm), float(norm_eps)
        self.head_dim = None if head_dim is None else int(head_dim)
        self.window = None if window is None else int(window)
        if self.window is not None and not causal:
            raise ValueError("a sliding window needs causal=True")
        self.rope_theta = float(rope_theta)
        self.rope_fraction = float(rope_fraction)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.gate = bool(gate)
        #: rotary position embeddings applied to q/k inside the layer
        #: (``apply_rope``) — pairs with ``zoo.gpt_lm(positional="rope")``,
        #: which then drops the learned PositionalEmbedding table
        self.rope = bool(rope)
        #: grouped-query attention (GQA; num_kv_heads=1 ≡ multi-query):
        #: K/V projections and the DECODE CACHE carry only this many
        #: heads — cache memory shrinks H/kv× — while query heads share
        #: each K/V group.  None keeps classic multi-head (and the
        #: fused-qkv parameter layout, so existing checkpoints load).
        self.num_kv_heads = None if num_kv_heads is None else int(num_kv_heads)
        if self.num_kv_heads is not None:
            if self.num_kv_heads < 1:
                raise ValueError(f"num_kv_heads must be >= 1, got "
                                 f"{num_kv_heads}")
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads {num_heads} not divisible by num_kv_heads "
                    f"{num_kv_heads}")
        self.causal = bool(causal)
        self.impl = impl
        self.mesh = None        # runtime attachment → ring attention
        self.ring_axis = "sp"
        self.batch_axis = None  # optional dp axis for dp×sp composition
        #: ring hop compute: None → follow ``impl`` (flash layers ring
        #: with the fused kernel per hop, O(T_loc·D) memory); or set
        #: "blockwise"/"flash" explicitly
        self.ring_impl = None
        #: sequence layout for the causal ring: None → "zigzag" whenever
        #: causal and T divides 2·|sp| (the load-balanced schedule: every
        #: device computes the same ≈half-block work per hop instead of
        #: the contiguous layout's straggler shard); or pin
        #: "contiguous"/"zigzag" explicitly
        self.ring_layout = None
        #: set by ``models.optimize.zigzag_wrap``: activations arrive
        #: ALREADY zigzag-striped (the model re-stripes once per batch),
        #: so the per-call shuffle/unshuffle is skipped
        self.ring_pre_shuffled = False

    @property
    def _kv(self) -> int:
        return self.num_kv_heads if self.num_kv_heads is not None \
            else self.num_heads

    def _dh(self, d: int) -> int:
        return self.head_dim if self.head_dim is not None \
            else d // self.num_heads

    def init(self, rng, in_shape):
        t, d = in_shape
        if self.head_dim is None and d % self.num_heads:
            raise ValueError(f"model dim {d} not divisible by "
                             f"{self.num_heads} heads")
        dh = self._dh(d)
        if self.rope and self._rotary_dim(dh) % 2:
            raise ValueError(
                f"rope=True needs an even rotated head dim, got "
                f"{self._rotary_dim(dh)} of Dh = {dh}")
        k1, k2 = jax.random.split(rng)
        wide = self.num_heads * dh  # == d in the classic layout
        params = {
            # one fused projection for ALL head layouts: (D, H·Dh +
            # 2·KV·Dh) degenerates to the classic (D, 3D) when KV == H
            # and Dh = D / H, so pre-GQA checkpoints load unchanged and
            # the single MXU-shaped GEMM is kept under grouping too
            "qkv": glorot_uniform(k1, (d, wide + 2 * self._kv * dh)),
            "out": glorot_uniform(k2, (wide, d)),
        }
        if self.gate:
            params["gate"] = glorot_uniform(jax.random.fold_in(rng, 2),
                                            (d, self.num_heads))
        if self.qk_norm:
            params["q_norm"] = jnp.ones((wide,))
            params["k_norm"] = jnp.ones((self._kv * dh,))
        return params, {}, in_shape

    def _project(self, params, x):
        """x (B, T, D) → q (B, T, H, Dh), k/v (B, T, KV, Dh) — one fused
        GEMM, split at [H·Dh, H·Dh + KV·Dh]."""
        b, t, d = x.shape
        h = self.num_heads
        kv = self._kv
        dh = self._dh(d)
        wide = h * dh
        qkv = x @ params["qkv"].astype(x.dtype)   # (B, T, (H + 2·KV)·Dh)
        norm = self._normed if self.qk_norm else lambda a, _: a
        q = norm(qkv[..., :wide], params.get("q_norm")).reshape(b, t, h, dh)
        k = norm(qkv[..., wide:wide + kv * dh], params.get("k_norm")) \
            .reshape(b, t, kv, dh)
        v = qkv[..., wide + kv * dh:].reshape(b, t, kv, dh)
        return q, k, v

    def _normed(self, x, scale):
        """RMSNorm over all of ``x``'s columns, statistics in float32."""
        xf = x.astype(jnp.float32)
        return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                   + self.norm_eps)
                * scale.astype(jnp.float32)).astype(x.dtype)

    def _rotary_dim(self, dh: int) -> int:
        return int(round(dh * self.rope_fraction))

    def _rotate(self, q, k, positions):
        """q and k with the layer's rotary embedding at ``positions``."""
        inv_freq, scale = rope_frequencies(
            self._rotary_dim(q.shape[-1]), self.rope_theta,
            self.rope_scaling)
        return (apply_rope(q, positions, inv_freq=inv_freq, scale=scale),
                apply_rope(k, positions, inv_freq=inv_freq, scale=scale))

    def _no_cache_yet(self):
        if self.window is not None or self.gate:
            raise ValueError(
                "cached decode does not know sliding-window or gated "
                "attention yet (ROADMAP: window layers in the serving "
                "cache); generate by full-context recompute")

    def _expand_kv(self, k):
        """(B, T, KV, Dh) → (B, T, H, Dh): query groups share K/V heads,
        head ``kv·G + g`` reading K/V head ``kv``.  Only for the paths
        that take equal head counts: ``dot_product_attention``
        (``impl="dense"``), the sequence-parallel ring (its hops rotate
        K/V blocks of the query's head count) and ``apply_prefill``.
        The flash kernels of ``apply`` read K and V at their own head
        count with no repeat (``pallas_attention.flash_attention``: in
        the projected layout for equal heads of 64, on transposes for
        any other shape), and the decode CACHE stays KV-sized."""
        g = self.num_heads // self._kv
        return k if g == 1 else jnp.repeat(k, g, axis=2)

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, d = x.shape
        with jax.named_scope("qkv"):
            q, k, v = self._project(params, x)
        if self.rope:
            if self.mesh is not None:
                raise ValueError(
                    "rope=True with a mesh-attached (sequence-sharded) "
                    "layer is not supported: per-shard positions need "
                    "global offsets; detach the mesh or use the learned "
                    "PositionalEmbedding")
            with jax.named_scope("rope"):
                q, k = self._rotate(q, k, jnp.arange(t))
        if self.mesh is not None or self.impl != "flash":
            k, v = self._expand_kv(k), self._expand_kv(v)
        if self.mesh is not None:
            if self.window is not None:
                raise ValueError("a sliding window over a sequence-sharded "
                                 "(mesh-attached) layer is not supported")
            from ..parallel.ring import ring_attention_sharded
            # flash layers ring with the fused kernel per hop
            ring_impl = self.ring_impl or (
                "flash" if self.impl == "flash" else "blockwise")
            layout = self.ring_layout
            if self.ring_pre_shuffled:
                layout = "zigzag"
            elif layout is None and ring_impl != "ulysses":
                # causal rings default to the load-balanced zigzag
                # stripe when the length allows (exact; ≈half the FLOPs)
                # AND the sequence is long enough for the saved FLOPs to
                # beat the per-call stripe gathers (ADVICE r5)
                sp = self.mesh.shape[self.ring_axis]
                layout = ("zigzag" if self.causal and t % (2 * sp) == 0
                          and t >= ZIGZAG_AUTO_MIN_T else "contiguous")
                if self.causal:
                    _log_layout_choice(layout, t, sp)
            o = ring_attention_sharded(self.mesh, q, k, v,
                                       axis=self.ring_axis,
                                       batch_axis=self.batch_axis,
                                       causal=self.causal,
                                       impl=ring_impl,
                                       layout=layout or "contiguous",
                                       pre_shuffled=self.ring_pre_shuffled)
        elif self.impl == "flash":
            o = _flash_with_blocking(q, k, v, self.causal, t, self.window)
        else:
            o = dot_product_attention(q, k, v, causal=self.causal,
                                      window=self.window)
        if self.gate:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(x @ params["gate"].astype(x.dtype))
                o = o * g[..., None]
        with jax.named_scope("out_proj"):
            o = o.reshape(b, t, o.shape[2] * o.shape[3])
            return o @ params["out"].astype(x.dtype), state

    def init_cache(self, batch, in_shape):
        t, d = in_shape
        dh = self._dh(d)
        # KV-head-sized: THE GQA memory win — H/kv× smaller than the
        # activations' head count
        shape = (batch, t, self._kv, dh)
        return {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}

    def apply_decode(self, params, state, x, cache, pos):
        """One-token cached decode: append this position's K/V to the
        cache, attend the single query over positions <= pos.  O(T·D)
        per token vs the recompute path's O(T²·D).  Grouped-query
        attention attends via a (KV, G) grouped einsum so the KV-sized
        cache is never expanded to H heads.  ``pos`` may be a scalar
        (uniform batch) or (B,) — PER-ROW positions for ragged prompts:
        each row writes its K/V at its own slot (indexed scatter) and
        masks at its own horizon.  Decoding is inherently causal — only
        meaningful for ``causal=True`` layers."""
        if not self.causal:
            raise ValueError("cached decode requires causal=True attention")
        self._no_cache_yet()
        b, d = x.shape
        h = self.num_heads
        kv = self._kv
        g = h // kv
        dh = self._dh(d)
        pos = jnp.asarray(pos)
        per_row = pos.ndim == 1
        q, k, v = self._project(params, x[:, None, :])
        if self.rope:
            # rotate-then-cache: scores depend on relative position only,
            # so rotated keys compose with rotated queries at any later pos
            q, k = self._rotate(q, k, pos[:, None] if per_row else pos[None])
        if per_row:
            # indexed scatter (one (KV, Dh) row per batch element) — the
            # one-hot blend formulation costs a full-buffer
            # read-modify-write per step (measured +20% on the ragged
            # decode rate)
            rows = jnp.arange(b)
            kc = cache["k"].at[rows, pos].set(
                k[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[rows, pos].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        # head order matches _expand_kv's repeat: head = kv_idx·G + g
        qg = q[:, 0].reshape(b, kv, g, dh)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        t_idx = jnp.arange(kc.shape[1])
        horizon = pos[:, None, None, None] if per_row else pos
        s = jnp.where(t_idx[None, None, None, :] <= horizon, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", w,
                       vc.astype(jnp.float32)).astype(x.dtype)
        return o.reshape(b, h * dh) @ params["out"].astype(x.dtype), \
            {"k": kc, "v": vc}

    def apply_prefill(self, params, state, x, cache):
        """Batched prefill: one full causal forward over the buffer (via
        the layer's own configured attention impl — dense or flash) that
        also records every position's K/V into the cache.  Cache entries
        past the prompt are placeholders: masked during decode and
        overwritten position-by-position as tokens are generated."""
        if not self.causal:
            raise ValueError("cached decode requires causal=True attention")
        self._no_cache_yet()
        b, t, d = x.shape
        q, k, v = self._project(params, x)
        if self.rope:
            q, k = self._rotate(q, k, jnp.arange(t))
        cache = {"k": k.astype(cache["k"].dtype),
                 "v": v.astype(cache["v"].dtype)}
        k = self._expand_kv(k)
        v = self._expand_kv(v)
        if self.impl == "flash":
            o = _flash_with_blocking(q, k, v, True, t)
        else:
            o = dot_product_attention(q, k, v, causal=True)
        o = o.reshape(b, t, o.shape[2] * o.shape[3])
        return o @ params["out"].astype(x.dtype), cache

    def get_config(self):
        return {"num_heads": self.num_heads, "causal": self.causal,
                "impl": self.impl, "num_kv_heads": self.num_kv_heads,
                "rope": self.rope, "head_dim": self.head_dim,
                "window": self.window, "rope_theta": self.rope_theta,
                "rope_fraction": self.rope_fraction,
                "rope_scaling": self.rope_scaling, "gate": self.gate,
                "qk_norm": self.qk_norm, "norm_eps": self.norm_eps}


@register
class LayerNorm(Layer):
    def __init__(self, epsilon: float = 1e-5):
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + jnp.asarray(self.epsilon, x.dtype))
        return y * params["scale"].astype(x.dtype) \
            + params["bias"].astype(x.dtype), state

    def get_config(self):
        return {"epsilon": self.epsilon}


@register
class PositionalEmbedding(Layer):
    """Learned absolute position embeddings added to token embeddings:
    (T, D) -> (T, D).  The standard GPT-style position encoding; the
    table is sized at construction so shapes stay static under jit."""

    def __init__(self, max_len: int):
        self.max_len = int(max_len)

    def init(self, rng, in_shape):
        t, d = in_shape
        if t > self.max_len:
            raise ValueError(f"sequence length {t} exceeds "
                             f"max_len={self.max_len}")
        params = {"table": uniform_scale(rng, (self.max_len, d))}
        return params, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        t = x.shape[1]
        return x + params["table"][:t].astype(x.dtype), state

    def apply_decode(self, params, state, x, cache, pos):
        pos = jnp.asarray(pos)
        if pos.ndim == 1:  # per-row positions (ragged cached decode)
            rows = jnp.take(params["table"], pos, axis=0)  # (B, D)
            return x + rows.astype(x.dtype), cache
        row = jax.lax.dynamic_slice_in_dim(params["table"], pos, 1, 0)[0]
        return x + row.astype(x.dtype), cache

    def get_config(self):
        return {"max_len": self.max_len}


@register
class GlobalAvgPool1D(Layer):
    """Mean over the time axis: (T, D) -> (D,)."""
    time_mixing = True

    def out_shape(self, in_shape):
        return (in_shape[-1],)

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.mean(x, axis=1), state
