"""Pallas flash attention vs the dense reference (interpret mode on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu.ops.attention import dot_product_attention
from distkeras_tpu.ops.pallas_attention import flash_attention


def qkv(b=2, t=64, h=2, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, t, h, dh)
    return tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = qkv()
    dense = dot_product_attention(q, k, v, causal=causal)
    flash = flash_attention(q, k, v, causal, 16, 16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_flash_grads_match_dense():
    q, k, v = qkv(t=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


def test_mha_flash_impl():
    import distkeras_tpu as dk
    from distkeras_tpu.models.layers import Sequential, Dense, Embedding
    from distkeras_tpu.ops.attention import MultiHeadAttention

    def build(impl):
        return dk.Model(Sequential([
            Embedding(50, 32),
            MultiHeadAttention(2, impl=impl),
            Dense(2, "softmax"),
        ]), input_shape=(16,))

    m_dense, m_flash = build("dense"), build("flash")
    v = m_dense.init(0)
    x = np.arange(48, dtype=np.int32).reshape(3, 16) % 50
    yd, _ = m_dense.apply(v, x)
    yf, _ = m_flash.apply(v, x)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yd),
                               rtol=2e-5, atol=2e-5)


def test_flash_awkward_length_causal_pads_exactly():
    """Prime T has no block divisor; the causal path must transparently pad
    to a 128 multiple (exact: padded keys are never attended) instead of
    silently running a degenerate block=1 grid."""
    from distkeras_tpu.ops.attention import _flash_with_blocking
    q, k, v = qkv(b=1, t=257, h=2, dh=16, seed=1)
    dense = dot_product_attention(q, k, v, causal=True)
    flash = _flash_with_blocking(q, k, v, True, 257)
    assert flash.shape == dense.shape
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    # gradients stay exact through the pad+slice
    gf = jax.grad(lambda a: jnp.sum(
        _flash_with_blocking(a, k, v, True, 257) ** 2))(q)
    gd = jax.grad(lambda a: jnp.sum(
        dot_product_attention(a, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               rtol=5e-4, atol=1e-5)


def test_flash_awkward_length_noncausal_raises():
    from distkeras_tpu.ops.attention import _flash_with_blocking
    q, k, v = qkv(b=1, t=257, h=2, dh=16)
    with pytest.raises(ValueError, match="block-sized divisor"):
        _flash_with_blocking(q, k, v, False, 257)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_path(causal):
    """bf16 inputs take the full-rate MXU path (f32 accumulation): output
    and grads stay within bf16 tolerances of the f32 dense reference."""
    q, k, v = qkv(t=64)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    dense = dot_product_attention(q, k, v, causal=causal)
    flash = flash_attention(qb, kb, vb, causal, 16, 16)
    assert flash.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(dense), rtol=0.06, atol=0.06)

    gf = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal, 16, 16).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(qb, kb, vb)
    gd = jax.grad(lambda a, b, c: jnp.sum(
        dot_product_attention(a, b, c, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0.15, atol=0.15)


def test_flash_rectangular_lengths():
    """Tq != Tk (non-causal): the rectangular hop shape the zigzag ring
    schedule feeds the kernels — values and grads vs the dense reference
    (causal still requires equal lengths: clear error)."""
    from distkeras_tpu.ops.pallas_attention import flash_attention_lse
    rng = np.random.default_rng(3)
    B, TQ, TK, H, DH = 2, 16, 48, 2, 8
    q = jnp.asarray(rng.normal(size=(B, TQ, H, DH)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, TK, H, DH)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, TK, H, DH)), jnp.float32)

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(DH)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return out, jax.scipy.special.logsumexp(s, axis=-1)

    o, lse = flash_attention_lse(q, k, v, False)
    o_r, lse_r = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        def go(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o ** 2) + 0.3 * jnp.sum(jnp.tanh(lse))
        return go

    g = jax.grad(loss(lambda q, k, v: flash_attention_lse(q, k, v, False)),
                 argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
    with pytest.raises(ValueError, match="equal q/k"):
        flash_attention_lse(q, k, v, True)


@pytest.mark.parametrize("dh", [32, 64, 128, 256])
def test_auto_block_only_returns_tileable_blocks(dh):
    """Mosaic tiles a block that is a multiple of 128 (it maps onto lanes
    in the logsumexp spec) or the whole sequence; interpret mode accepts
    anything, so the rule is pinned here: T=200 -> 100 and T=544 -> 68
    passed every CPU test and were refused by the TPU compiler."""
    from distkeras_tpu.ops.pallas_attention import _auto_block
    for t in range(1, 2049):
        if t % 128 == 0 or t <= 128:
            b = _auto_block(t, dh)
            assert t % b == 0 and (b == t or b % 128 == 0), (t, b)
        else:
            with pytest.raises(ValueError, match="no tileable block"):
                _auto_block(t, dh)
    # the big-block regime stays, capped by the head dim's VMEM share
    assert _auto_block(8192, dh) == (1024 if dh <= 64 else
                                     512 if dh <= 128 else 256)


# ---------------------------------------------------------------------------
# the in-kernel causal walk (default blocks, causal, one length)
# ---------------------------------------------------------------------------

def _dense_lse(q, k, v, causal):
    """Dense attention and its logsumexp, (B, T, H, Dh) -> (out, lse)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return dot_product_attention(q, k, v, causal=causal), \
        jax.scipy.special.logsumexp(s, axis=-1)


def _check_causal_against_dense(t, with_lse, dtype, blocks=(None, None)):
    """Values and all three gradients of a causal call against dense
    attention, the ``lse`` output and its cotangent included."""
    from distkeras_tpu.ops.pallas_attention import flash_attention_lse
    q, k, v = qkv(b=1, t=t, h=2, dh=32, seed=t)
    xs = tuple(a.astype(dtype) for a in (q, k, v))
    val, grad = ((2e-5, 5e-4) if dtype == "float32" else (0.06, 0.15))

    def flash(q, k, v):
        if with_lse:
            return flash_attention_lse(q, k, v, True, *blocks)
        return flash_attention(q, k, v, True, *blocks), None

    def loss(fn):
        def go(q, k, v):
            o, lse = fn(q, k, v)
            out = jnp.sum(o.astype(jnp.float32) ** 2)
            return out + 0.3 * jnp.sum(jnp.tanh(lse)) if with_lse else out
        return go

    o, lse = flash(*xs)
    o_r, lse_r = _dense_lse(q, k, v, True)
    assert o.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(o_r),
                               rtol=val, atol=val)
    if with_lse:
        assert lse.dtype == jnp.float32 and lse.shape == (1, 2, t)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   rtol=val, atol=val)
    g = jax.grad(loss(flash), argnums=(0, 1, 2))(*xs)
    g_r = jax.grad(loss(lambda q, k, v: _dense_lse(q, k, v, True)),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_r):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=grad, atol=max(grad / 10, 5e-5))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("t", [256, 384, 640])
def test_default_block_causal_matches_dense(t, with_lse, dtype):
    """Default blocks + causal take the in-kernel walk (tile 128: 2, 3
    and 5 tiles a side, unmasked tiles before a masked diagonal): values
    and all three gradients against dense attention, the ``lse`` output
    and its cotangent included."""
    from distkeras_tpu.ops.pallas_attention import _blocks
    x = jnp.ones((1, t, 2, 32), dtype)
    assert _blocks(x, x, True, None, None)[2] is not None
    _check_causal_against_dense(t, with_lse, dtype)


# ---------------------------------------------------------------------------
# the causal grid walk (explicit blocks, or a length past the VMEM budget):
# a grid step only for the block pairs with work, a mask only on the diagonal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
def test_causal_grid_walk_matches_dense(bq, bk, with_lse, dtype):
    """T = 512 in explicit blocks walks ``_walk_table``'s steps: 10 of 16
    pairs at 128², 4 of them masked; 6 of 8 with two masked a row where
    the blocks are not square.  Values, the three gradients, ``lse`` and
    its cotangent against dense attention."""
    _check_causal_against_dense(512, with_lse, dtype, (bq, bk))


@pytest.mark.parametrize("t", [257, 300])
def test_causal_grid_walk_through_the_padded_awkward_length(t, monkeypatch):
    """A length with no block pads to 384 and, past the VMEM budget
    (forced here), takes the grid walk in 128-blocks: exact, gradients
    included, as on the in-kernel walk."""
    from distkeras_tpu.ops import pallas_attention
    from distkeras_tpu.ops.attention import _flash_with_blocking
    monkeypatch.setattr(pallas_attention, "_CAUSAL_VMEM_BUDGET", 0)
    q, k, v = qkv(b=1, t=t, h=2, dh=16, seed=t)
    x = jnp.ones((1, 384, 2, 16), jnp.float32)
    assert pallas_attention._blocks(x, x, True, None, None) == (128, 128,
                                                                None)
    flash = lambda q, k, v: _flash_with_blocking(q, k, v, True, t)  # noqa
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True)  # noqa
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-5,
                               atol=2e-5)
    for a, b in zip(
            jax.grad(lambda *x: jnp.sum(flash(*x) ** 2), (0, 1, 2))(q, k, v),
            jax.grad(lambda *x: jnp.sum(dense(*x) ** 2), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


#: (T, block_q, block_k): Laguna's full layers; square blocks; blocks
#: that are not (a row then holds two masked pairs, or a pair holds two
#: rows' diagonals); one block
TABLES = [(8192, 512, 512), (512, 128, 128), (512, 256, 128),
          (512, 128, 256), (1024, 128, 512), (384, 128, 128),
          (128, 128, 128)]


@pytest.mark.parametrize("by_keys", [False, True], ids=["by_queries",
                                                        "by_keys"])
@pytest.mark.parametrize("t,bq,bk", TABLES)
def test_walk_table_holds_every_needed_pair_once(t, bq, bk, by_keys):
    """The schedule alone, no kernel: a step for every block pair with a
    key at or before a query and for no other, each once; a row's steps
    contiguous and ascending with one ``first`` and one ``last``; a mask
    exactly where the block's last key is after its first query."""
    from distkeras_tpu.ops.pallas_attention import _walk_table
    table = _walk_table(t, t, bq, bk, by_keys=by_keys)
    pairs = [(qi, kb) for qi, kb, *_ in table]
    needed = {(qi, kb) for qi in range(t // bq) for kb in range(t // bk)
              if kb * bk <= qi * bq + bq - 1}
    assert len(pairs) == len(set(pairs)) and set(pairs) == needed
    row_of, along = (1, 0) if by_keys else (0, 1)
    rows = [step[row_of] for step in table]
    assert rows == sorted(rows)  # contiguous, and every output block once
    assert set(rows) == set(range(t // (bk if by_keys else bq)))
    for row in set(rows):
        steps = [step for step in table if step[row_of] == row]
        walked = [step[along] for step in steps]
        assert walked == sorted(walked)
        assert [s[2] for s in steps] == [True] + [False] * (len(steps) - 1)
        assert [s[3] for s in steps] == [False] * (len(steps) - 1) + [True]
    for qi, kb, _, _, kind in table:
        assert kind == (kb * bk + bk - 1 > qi * bq), (qi, kb)
    if (t, bq, bk) == (8192, 512, 512):
        assert len(table) == 136 and sum(s[4] for s in table) == 16
        # dK/dV meets its diagonal block first, the forward and dQ last
        assert table[1][:2] == (1, 0) and table[2][:2] == (
            (2, 0) if by_keys else (1, 1))
        assert [s[4] for s in table if s[row_of] == 3] == (
            [1] + [0] * 12 if by_keys else [0, 0, 0, 1])


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _pallas_calls(fn, *args) -> dict:
    """{kernel name: (grid, block shape of every operand and output)} of
    the ``pallas_call``s a function traces to (nothing runs)."""
    return {
        e.params["name"]: (
            tuple(e.params["grid_mapping"].grid),
            [tuple(getattr(d, "block_size", d) for d in m.block_shape)
             for m in e.params["grid_mapping"].block_mappings])
        for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if e.primitive.name == "pallas_call"}


def _layout(b, kv, group, dh):
    """How the kernels lay out (b, T, kv·group, dh) queries over kv K/V
    heads: ``(query side's leading axes, K/V side's, block lanes,
    pack)``.  In place — Dh = 64 with equal and even head counts — the
    arrays are (B, T, heads·64) and the grid leads with (batch, head
    pair), two heads a 128-lane block; any other shape runs on (B·heads,
    T, Dh) transposes, one head a block."""
    heads = kv * group
    if dh == 64 and group == 1 and heads % 2 == 0:
        return (b, heads // 2), (b, kv // 2), 128, 2
    return (b * heads, 1), (b * kv, 1), dh, 1


def _grid_walk(kv, tq, tk, dh, bq, bk, steps=None, group=1, with_lse=False):
    """What the three grid-walk kernels are built with, one block of each
    operand a step: a (batch, head, q blocks, k blocks) grid, the dK/dV
    kernel's the other way round (PR 26's tree); for a causal call a
    (batch, head, ``steps``) grid, the block pairs with work alone.
    ``kv`` counts the K/V heads: with ``group`` query heads to each, the
    forward and dQ lead with ``kv * group`` heads, and dK/dV stays at
    ``kv``, a key block's row ``group`` times as long (one axis more off
    the table).  A head is a head block of ``_layout``'s.  dQ also reads
    O (and ``with_lse`` the ``lse`` cotangent) and writes D for dK/dV."""
    by_q_lead, by_k_lead, width, pack = _layout(1, kv, group, dh)
    qb, kb, row = (1, bq, width), (1, bk, width), (1, 1, pack, bq)
    by_q = (*by_q_lead, steps) if steps else (*by_q_lead, tq // bq, tk // bk)
    by_k = (*by_k_lead, steps * group) if steps else (
        (*by_k_lead, tk // bk, tq // bq) if group == 1
        else (*by_k_lead, tk // bk, group, tq // bq))
    return {
        "flash_fwd": (by_q, [qb, kb, kb, qb, row]),
        "flash_bwd_dq": (by_q, [qb, kb, kb, qb, qb, row] + [row] * with_lse
                         + [qb, row]),
        "flash_bwd_dkv": (by_k, [kb, kb, qb, qb, row, row, kb, kb]),
    }


@pytest.mark.parametrize("tq,tk,dh,causal,bq,bk,steps,group", [
    (1024, 1024, 64, False, 1024, 1024, None, 1),  # non-causal: a ring's far hop
    (1024, 512, 64, False, 1024, 512, None, 1),    # rectangular: a zigzag half hop
    (384, 384, 32, False, 128, 128, None, 1),      # non-causal, 3 blocks a side
    (4096, 4096, 64, True, 1024, 1024, 10, 1),  # causal, K/V over the VMEM budget
    (4096, 4096, 128, True, 512, 512, 36, 1),   # the same at head 128
    (8192, 8192, 128, True, 512, 512, 136, 1),  # Laguna's full layers
    (128, 128, 64, True, 128, 128, 1, 1),       # causal, one tile: nothing to skip
    (8192, 8192, 128, True, 512, 512, 136, 6),  # Laguna's: 48 heads over 8
    (384, 384, 32, False, 128, 128, None, 4),   # non-causal, 4 heads a K/V head
    (384, 384, 64, False, 128, 128, None, 2),   # head 64 grouped: transposed
], ids=["noncausal", "rectangular", "noncausal-384", "causal-4096",
        "causal-4096-dh128", "causal-8192-dh128", "causal-128",
        "causal-8192-dh128-group6", "noncausal-384-group4",
        "noncausal-384-dh64-group2"])
def test_other_calls_keep_the_grid_walk(tq, tk, dh, causal, bq, bk, steps,
                                        group):
    """Non-causal and rectangular calls keep the dense grids and blocks;
    over-budget causal calls keep the blocks, on a grid of the pairs
    with work alone.  K/V heads shared by a group keep their own count
    in all three.  Heads of 64 go two to a 128-lane block of the
    projected layout; heads of 32 or 128, or of 64 under a group, one to
    a block of transposed operands."""
    from distkeras_tpu.ops.pallas_attention import flash_attention_lse
    q = jnp.ones((1, tq, 2 * group, dh), jnp.bfloat16)
    kv = jnp.ones((1, tk, 2, dh), jnp.bfloat16)

    def loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)

    assert _pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) \
        == _grid_walk(2, tq, tk, dh, bq, bk, steps, group, with_lse=True)


def test_explicit_blocks_keep_the_grid_walk_and_default_takes_the_kernel_walk():
    q = jnp.ones((1, 1024, 2, 64), jnp.bfloat16)

    def loss(*blocks):
        return lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, *blocks).astype(jnp.float32))

    grad = lambda *blocks: jax.grad(loss(*blocks), argnums=(0, 1, 2))  # noqa
    assert _pallas_calls(grad(256, 256), q, q, q) \
        == _grid_walk(2, 1024, 1024, 64, 256, 256, steps=10)
    # four query heads over the two K/V heads: dK/dV's rows are twice as
    # long (head 64 under a group: on transposed operands)
    q4 = jnp.ones((1, 1024, 4, 64), jnp.bfloat16)
    assert _pallas_calls(grad(256, 256), q4, q, q) \
        == _grid_walk(2, 1024, 1024, 64, 256, 256, steps=10, group=2)
    # the two heads of 64 are one 128-lane block of the projected layout
    whole, row = (1, 1024, 128), (1, 1, 2, 1024)
    assert _pallas_calls(grad(), q, q, q) == {
        "flash_fwd": ((1, 1), [whole] * 4 + [row]),
        "flash_bwd_dq": ((1, 1), [whole] * 5 + [row] + [whole, row]),
        "flash_bwd_dkv": ((1, 1), [whole] * 4 + [row] * 2 + [whole] * 2),
    }


def _tile_counts(names=("tiles_executed", "tiles_total")):
    from distkeras_tpu.obs.registry import default_registry
    registry = default_registry()
    return tuple(registry.counter(f"flash.causal_{name}").value
                 for name in names)


@pytest.mark.parametrize("dh", [64, 128])
def test_causal_schedule_runs_the_triangle_and_masks_the_diagonal(dh):
    """The static schedule the three kernels walk, tile pair by tile
    pair: executed are exactly the pairs with a key at or before the q
    tile's last query; the one pair a walk masks (the tile at its own
    start) is exactly the one the diagonal crosses; and the dK/dV walk
    (by keys) covers the same pairs."""
    from distkeras_tpu.ops.pallas_attention import (_CAUSAL_VMEM_BUDGET,
                                                    _causal_schedule,
                                                    _causal_tile)
    seen = 0
    for t in range(128, 2049, 128):
        tile = _causal_tile(t, dh, 2)
        if tile is None:
            assert t == 128 or 12 * t * 128 * 2 > _CAUSAL_VMEM_BUDGET, t
            continue
        seen += 1
        assert t % tile == 0 and tile % 128 == 0 and t > tile, (t, tile)
        n = t // tile
        executed = {(qi, kb) for qi in range(n) for kb in range(n)
                    if kb * tile <= qi * tile + tile - 1}
        crossed = {(qi, kb) for qi, kb in executed
                   if kb * tile + tile - 1 > qi * tile}
        for by_keys in (False, True):
            got, masked = set(), set()
            for start, lo, hi in _causal_schedule(t, tile, by_keys):
                assert lo % tile == 0 and hi % tile == 0 and lo <= start < hi
                for other in range(lo, hi, tile):
                    pair = (other, start) if by_keys else (start, other)
                    got.add((pair[0] // tile, pair[1] // tile))
                masked.add((start // tile, start // tile))
            assert got == executed and masked == crossed, (t, tile, by_keys)
    assert seen >= 8


@pytest.mark.parametrize("t,executed,total", [(1024, 10, 16), (640, 15, 25),
                                               (128, 1, 1)])
def test_registry_counts_the_tiles_a_kernel_will_run(t, executed, total):
    """``flash.causal_tiles_executed`` / ``_total`` grow by the schedule's
    counts once a kernel built (three for a forward + backward trace);
    on the grid walk with one block, 1 and 1; a non-causal call adds
    nothing."""
    q = jnp.ones((1, t, 2, 64), jnp.bfloat16)

    def grad(causal):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal).astype(jnp.float32)), argnums=(0, 1, 2))

    before = _tile_counts()
    jax.make_jaxpr(grad(True))(q, q, q)
    after = _tile_counts()
    assert (after[0] - before[0], after[1] - before[1]) \
        == (3 * executed, 3 * total)
    jax.make_jaxpr(grad(False))(q, q, q)
    assert _tile_counts() == after


@pytest.mark.parametrize("t,dh,blocks,steps,masked", [
    (8192, 128, (), 136, 16),         # Laguna's full layers: the grid walk
    (4096, 64, (), 10, 4),            # 1,024-blocks past the VMEM budget
    (512, 64, (256, 128), 6, 4),      # explicit blocks, two masked a row
    (1024, 64, (), 10, 4),            # the in-kernel walk: what it does
    (128, 64, (), 1, 1),              # one block
], ids=["laguna-full", "causal-4096", "explicit-256x128", "in-kernel-1024",
        "one-block"])
def test_registry_counts_the_steps_a_walk_takes_and_the_tiles_it_masks(
        t, dh, blocks, steps, masked):
    """``flash.causal_grid_steps`` / ``flash.causal_tiles_masked`` beside
    the two above, once a kernel built: the grid walk takes a step only
    where there is work (so its steps equal its executed tiles: 136 of
    256 at T = 8,192) and masks only where the diagonal passes (16)."""
    q = jnp.ones((1, t, 2, dh), jnp.bfloat16)
    names = ("grid_steps", "tiles_masked", "tiles_executed")
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, *blocks).astype(jnp.float32)), argnums=(0, 1, 2))
    before = _tile_counts(names)
    jax.make_jaxpr(grad)(q, q, q)
    got = tuple(a - b for a, b in zip(_tile_counts(names), before))
    assert got == (3 * steps, 3 * masked, 3 * steps)
    jax.make_jaxpr(lambda q: flash_attention(q, q, q, False))(q)
    assert _tile_counts(names) == tuple(
        b + g for b, g in zip(before, got))


# ---------------------------------------------------------------------------
# grouped queries: K and V at their own head count
# ---------------------------------------------------------------------------

#: walk -> (T, (causal, block_q, block_k, window)): each of the four grids
GROUP_WALKS = {
    "in-kernel-causal": (256, (True, None, None, None)),
    "causal-table": (384, (True, 128, 128, None)),
    "window-band": (384, (True, 128, 128, 168)),
    "dense-noncausal": (256, (False, 128, 128, None)),
}


def _grouped(group, t, dtype, kv=2, dh=32, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.normal(size=(2, t, heads, dh)), dtype)
                  for heads in (kv * group, kv, kv, kv * group))
    return q, k, v, w.astype(jnp.float32)


def _native_and_repeated(group, args, q, k, v, w):
    """``(out, dq, dk, dv)`` of a call on (B, T, KV, Dh) K/V and of the
    same call on K/V repeated to the query heads, the repeated call's
    dk / dv summed over each group (the repeat's transpose)."""
    native = lambda q, k, v: flash_attention(q, k, v, *args)  # noqa: E731
    repeated = lambda q, k, v: native(  # noqa: E731
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2))

    def both(attn):
        out, grads = jax.value_and_grad(
            lambda q, k, v: (lambda o: (jnp.sum(o.astype(jnp.float32) * w),
                                        o))(attn(q, k, v)),
            argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out[1], *grads)

    return both(native), both(repeated)


@pytest.mark.parametrize("walk", list(GROUP_WALKS))
@pytest.mark.parametrize("group", [2, 4])
def test_kv_heads_at_their_own_count_equal_repeated_heads(group, walk):
    """``flash_attention`` on (B, T, KV, Dh) K/V against the same call
    on ``jnp.repeat``-ed K/V, on each of the four grids: the output and
    dq, dk, dv in float32 to 1e-5."""
    t, args = GROUP_WALKS[walk]
    native, repeated = _native_and_repeated(
        group, args, *_grouped(group, t, jnp.float32))
    assert native[2].shape == native[3].shape == (2, t, 2, 32)
    for name, a, b in zip(("out", "dq", "dk", "dv"), native, repeated):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("window", [None, 200], ids=["full", "window"])
def test_kv_heads_at_their_own_count_in_bf16_at_lagunas_proportions(window):
    """Six query heads of 128 to a K/V head, 128-blocks, bf16: the output
    and dq are the repeated call's to the bit (the same bodies over the
    same blocks in the same order); dk and dv are one float32 sum over
    the group rounded once, where the repeated call rounds a head's and
    sums after: within bf16's rounding of each other."""
    native, repeated = _native_and_repeated(
        6, (True, 128, 128, window),
        *_grouped(6, 384, jnp.bfloat16, kv=1, dh=128))
    for a, b in zip(native[:2], repeated[:2]):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for a, b in zip(native[2:], repeated[2:]):
        assert a.dtype == jnp.bfloat16 and a.shape == (2, 384, 1, 128)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0.02,
                                   atol=0.01 * float(np.abs(b).max()))


def test_heads_that_do_not_divide_are_refused():
    q = jnp.ones((1, 128, 3, 32))
    k = jnp.ones((1, 128, 2, 32))
    with pytest.raises(ValueError, match="whole multiple"):
        flash_attention(q, k, k, True)


def _kv_kernel_counts():
    from distkeras_tpu.obs.registry import default_registry
    return tuple(default_registry().counter(f"flash.kv_{kind}_kernels").value
                 for kind in ("native", "expanded"))


@pytest.mark.parametrize("dh", [32, 64])
@pytest.mark.parametrize("walk", list(GROUP_WALKS))
def test_registry_counts_the_kernels_on_native_and_on_repeated_heads(walk,
                                                                      dh):
    """``flash.kv_native_kernels`` / ``flash.kv_expanded_kernels``, once a
    kernel put into a program: the grid walk's three read K/V at their
    own head count; the in-kernel causal walk's three run on K/V
    repeated inside ``flash_attention`` (and keep the whole-sequence
    blocks, a grid step a query head: a (batch·head, 1) grid, heads
    shared by a group being transposed at any Dh).  Equal head counts
    add nothing."""
    t, args = GROUP_WALKS[walk]
    q, k, v, _ = _grouped(2, t, jnp.bfloat16, dh=dh)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, *args).astype(jnp.float32)), argnums=(0, 1, 2))
    before = _kv_kernel_counts()
    calls = _pallas_calls(grad, q, k, v)
    native, expanded = (a - b for a, b in zip(_kv_kernel_counts(), before))
    if walk == "in-kernel-causal":
        assert (native, expanded) == (0, 3)
        assert {grid for grid, _ in calls.values()} == {(2 * 4, 1)}
    else:
        assert (native, expanded) == (3, 0)
        assert calls[("window_attn" if args[3] else "flash")
                     + "_bwd_dkv"][0][:2] == (2 * 2, 1)
    jax.make_jaxpr(grad)(q, q, q)
    assert _kv_kernel_counts() == tuple(
        b + n for b, n in zip(before, (native, expanded)))


def _index_maps(fn, *args) -> dict:
    """{kernel name: (scalar-prefetch operands, the primitives its
    blocks' index maps are made of)} of a function's ``pallas_call``s."""
    return {
        e.params["name"]: (
            e.params["grid_mapping"].num_index_operands,
            {eqn.primitive.name
             for m in e.params["grid_mapping"].block_mappings
             for eqn in _eqns(m.index_map_jaxpr.jaxpr)})
        for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("dh", [32, 64], ids=["transposed", "in-place"])
@pytest.mark.parametrize("walk", list(GROUP_WALKS))
def test_equal_head_counts_build_the_programs_they_always_built(walk, dh):
    """What keeps the GPT-2 cells and the ring's hops still: with as many
    K/V heads as query heads no index map divides or multiplies a head
    index (transposed at Dh = 32, in place at 64), the table is three
    scalar-prefetched columns and no grid has a group axis (the grids
    themselves are pinned above).  With a group the forward and dQ
    divide (``head // G``, the row of transposed operands) and dK/dV
    multiplies (``kv·G + g``), on a fourth column or a fifth axis."""
    t, args = GROUP_WALKS[walk]
    q, k, v, _ = _grouped(2, t, jnp.bfloat16, dh=dh)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, *args).astype(jnp.float32)), argnums=(0, 1, 2))
    table = walk == "causal-table"
    head_arithmetic = {"div", "mul", "rem", "jit", "pjit"}
    for name, (prefetched, primitives) in _index_maps(grad, q, q, q).items():
        assert prefetched == (3 if table else 0), name
        assert not primitives & head_arithmetic, (name, primitives)
    if walk == "in-kernel-causal":
        return  # repeated heads: the equal-heads program
    grouped = _index_maps(grad, q, k, v)
    for name, (prefetched, primitives) in grouped.items():
        dkv = name.endswith("bwd_dkv")
        assert prefetched == ((4 if dkv else 3) if table else 0), name
        assert ("mul" if dkv else "div") in primitives, (name, primitives)
    assert len(_pallas_calls(grad, q, k, v)[
        ("window_attn" if args[3] else "flash") + "_bwd_dkv"][0]) == (
            3 if table else 5)


# ---------------------------------------------------------------------------
# the projected layout: blocks of (B, T, heads·Dh) taken in place
# ---------------------------------------------------------------------------

def _layout_counts():
    from distkeras_tpu.obs.registry import default_registry
    return tuple(
        default_registry().counter(f"flash.layout_{kind}_kernels").value
        for kind in ("native", "transposed"))


def _check_against_dense(q, k, v, args, dtype):
    """Output and the three gradients of ``flash_attention(q, k, v,
    *args)`` against ``dot_product_attention`` on K/V repeated to the
    query heads, in float32: to the file's float32 tolerances, or its
    bf16 ones with bf16 operands.  Returns the change in the two layout
    counters over the call's trace."""
    causal, window = args[0], args[3]
    group = q.shape[2] // k.shape[2]
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                    jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(
            jnp.float32) * w)

    def dense(q, k, v):
        return dot_product_attention(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
            causal=causal, window=window)

    xs = tuple(a.astype(dtype) for a in (q, k, v))
    val, grad = (2e-5, 5e-4) if dtype == jnp.float32 else (0.06, 0.15)
    before = _layout_counts()
    out, g = jax.value_and_grad(loss(lambda q, k, v: flash_attention(
        q, k, v, *args)), argnums=(0, 1, 2))(*xs)
    counted = tuple(a - b for a, b in zip(_layout_counts(), before))
    out_r, g_r = jax.value_and_grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    o = flash_attention(*xs, *args)
    assert o.dtype == dtype
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(dense(q, k, v)), rtol=val,
                               atol=val)
    for name, a, b in zip(("dq", "dk", "dv"), g, g_r):
        assert a.shape == b.shape and a.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=grad, atol=max(grad / 10, 5e-5),
                                   err_msg=name)
    return counted


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("walk", list(GROUP_WALKS))
@pytest.mark.parametrize("dh,group,counted", [
    (64, 1, (3, 0)), (128, 1, (0, 3)), (128, 2, (0, 3))],
    ids=["dh64-in-place", "dh128", "dh128-group2"])
def test_each_layout_matches_dense(dh, group, counted, walk, dtype):
    """Heads of 64 two to a 128-lane block in place; heads of 128, with
    and without K/V heads shared by a group, on transposed operands: on
    each walk (the in-kernel causal walk, the causal table, the window's
    band, the dense grid) the output and dq, dk, dv against dense
    attention, and the kernels counted in their layout."""
    t, args = GROUP_WALKS[walk]
    q, k, v, _ = _grouped(group, t, jnp.float32, dh=dh, seed=dh + group)
    assert _check_against_dense(q, k, v, args, dtype) == counted


@pytest.mark.parametrize("heads,kv,dh", [(2, 2, 96), (3, 3, 64), (4, 2, 64)],
                         ids=["dh96", "dh64-odd-heads", "dh64-group2"])
def test_shapes_the_blocks_do_not_fit_run_on_transposed_operands(heads, kv,
                                                                 dh):
    """Dh not 64, heads of 64 an odd count of them, heads of 64 under a
    group: the same kernels on (B·heads, T, Dh) transposes, equal to
    dense attention and counted transposed."""
    rng = np.random.default_rng(heads + dh)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, n, dh)), jnp.float32)
               for n in (heads, kv, kv))
    assert _check_against_dense(q, k, v, (True, None, None, None),
                                jnp.float32) == (0, 3)


def _blocks_at(fn, args, point) -> dict:
    """{kernel name: the block index of each operand and output at a grid
    index} of a function's ``pallas_call``s (grids without a table)."""
    found = {}
    for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if e.primitive.name == "pallas_call":
            name = e.params["name"]
            found[name] = [
                tuple(int(i) for i in jax.core.eval_jaxpr(
                    m.index_map_jaxpr.jaxpr, m.index_map_jaxpr.consts,
                    *(jnp.int32(i) for i in point[name])))
                for m in e.params["grid_mapping"].block_mappings]
    return found


@pytest.mark.parametrize("dh,heads,kv", [(64, 6, 6), (128, 4, 2)],
                         ids=["dh64-in-place", "dh128-group2-transposed"])
def test_blocks_are_column_blocks_of_the_projected_layout(dh, heads, kv):
    """The index maps of the dense grid (batch, head, q blocks, k
    blocks).  In place a query-side block is (batch, query block, head
    pair), a K/V block (batch, key block, head pair), a row of ``lse`` /
    ``dvec`` (batch, head pair, 0, query block).  On transposed operands
    the row is batch·head (``// G`` on the K/V side) and the column block
    0; dK/dV's grid (batch·K/V head, 0, key block, g, query block) reads
    query row ``kv·G + g``."""
    t, args = GROUP_WALKS["dense-noncausal"]
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, t, n, dh)), jnp.bfloat16)
               for n in (heads, kv, kv))
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, *args).astype(jnp.float32)), argnums=(0, 1, 2))
    if dh == 64:  # (batch 1, head pair 2, q block 1, k block 0)
        point = (1, 2, 1, 0)
        qb, kb, row = (1, 1, 2), (1, 0, 2), (1, 2, 0, 1)
        dkv, dkv_k, dkv_q, dkv_row = (1, 2, 0, 1), (1, 0, 2), (1, 1, 2), (
            1, 2, 0, 1)
    else:  # batch·head 7 reads K/V row 3; K/V row 3, g 1 reads query row 7
        point = (7, 0, 1, 0)
        qb, kb, row = (7, 1, 0), (3, 0, 0), (7, 0, 0, 1)
        dkv, dkv_k, dkv_q, dkv_row = (3, 0, 0, 1, 1), (3, 0, 0), (7, 1, 0), (
            7, 0, 0, 1)
    assert _blocks_at(grad, (q, k, v), {
        "flash_fwd": point, "flash_bwd_dq": point, "flash_bwd_dkv": dkv}) == {
        "flash_fwd": [qb, kb, kb, qb, row],
        "flash_bwd_dq": [qb, kb, kb, qb, qb, row, qb, row],
        "flash_bwd_dkv": [dkv_k] * 2 + [dkv_q] * 2 + [dkv_row] * 2
        + [dkv_k] * 2,
    }


def test_gpt2_small_attention_has_no_transpose_around_the_kernels():
    """At GPT-2 small's shapes (12 heads of 64, T = 1,024) the gradient
    of a flash ``MultiHeadAttention`` holds no transpose of an
    activation — the two left are the weight matrices' (D, D) and
    (3D, D) — and its three kernels count in place."""
    from distkeras_tpu.ops.attention import MultiHeadAttention
    layer = MultiHeadAttention(12, causal=True, impl="flash")
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), (1024, 768))[0])
    x = jax.ShapeDtypeStruct((2, 1024, 768), jnp.bfloat16)
    grad = jax.grad(lambda p, x: jnp.sum(layer.apply(p, {}, x)[0].astype(
        jnp.float32)), argnums=(0, 1))
    before = _layout_counts()
    jaxpr = jax.make_jaxpr(grad)(params, x)
    assert tuple(a - b for a, b in zip(_layout_counts(), before)) == (3, 0)
    transposed = [e.invars[0].aval.shape for e in _eqns(jaxpr.jaxpr)
                  if e.primitive.name == "transpose"]
    assert sorted(transposed) == [(768, 768), (2304, 768)], transposed
