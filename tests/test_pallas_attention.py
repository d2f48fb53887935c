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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("t", [256, 384, 640])
def test_default_block_causal_matches_dense(t, with_lse, dtype):
    """Default blocks + causal take the in-kernel walk (tile 128: 2, 3
    and 5 tiles a side, unmasked tiles before a masked diagonal): values
    and all three gradients against dense attention, the ``lse`` output
    and its cotangent included."""
    from distkeras_tpu.ops.pallas_attention import (_blocks,
                                                    flash_attention_lse)
    q, k, v = qkv(b=1, t=t, h=2, dh=32, seed=t)
    xs = tuple(a.astype(dtype) for a in (q, k, v))
    assert _blocks(xs[0], xs[1], True, None, None)[2] is not None
    val, grad = ((2e-5, 5e-4) if dtype == "float32" else (0.06, 0.15))

    def flash(q, k, v):
        if with_lse:
            return flash_attention_lse(q, k, v, True)
        return flash_attention(q, k, v, True), None

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


def _grid_walk(bh, tq, tk, dh, bq, bk):
    """What the three kernels were built with before the in-kernel walk
    (PR 26's tree): a (bh, q blocks, k blocks) grid, the dK/dV kernel's
    the other way round, one block of each operand a step."""
    qb, kb, row = (1, bq, dh), (1, bk, dh), (1, 1, bq)
    return {
        "flash_fwd": ((bh, tq // bq, tk // bk), [qb, kb, kb, qb, row]),
        "flash_bwd_dq": ((bh, tq // bq, tk // bk),
                         [qb, kb, kb, qb, row, row, qb]),
        "flash_bwd_dkv": ((bh, tk // bk, tq // bq),
                          [kb, kb, qb, qb, row, row, kb, kb]),
    }


@pytest.mark.parametrize("tq,tk,dh,causal,bq,bk", [
    (1024, 1024, 64, False, 1024, 1024),   # non-causal: a ring's far hop
    (1024, 512, 64, False, 1024, 512),     # rectangular: a zigzag half hop
    (384, 384, 32, False, 128, 128),       # non-causal, 3 blocks a side
    (4096, 4096, 64, True, 1024, 1024),    # causal, K/V over the VMEM budget
    (4096, 4096, 128, True, 512, 512),     # the same at head 128
    (128, 128, 64, True, 128, 128),        # causal, one tile: nothing to skip
], ids=["noncausal", "rectangular", "noncausal-384", "causal-4096",
        "causal-4096-dh128", "causal-128"])
def test_other_calls_keep_the_grid_walk(tq, tk, dh, causal, bq, bk):
    """Non-causal, rectangular and over-budget causal calls build the
    ``pallas_call``s they built before: same grids, same blocks."""
    from distkeras_tpu.ops.pallas_attention import flash_attention_lse
    q = jnp.ones((1, tq, 2, dh), jnp.bfloat16)
    kv = jnp.ones((1, tk, 2, dh), jnp.bfloat16)

    def loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)

    assert _pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) \
        == _grid_walk(2, tq, tk, dh, bq, bk)


def test_explicit_blocks_keep_the_grid_walk_and_default_takes_the_kernel_walk():
    q = jnp.ones((1, 1024, 2, 64), jnp.bfloat16)

    def loss(*blocks):
        return lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, *blocks).astype(jnp.float32))

    grad = lambda *blocks: jax.grad(loss(*blocks), argnums=(0, 1, 2))  # noqa
    assert _pallas_calls(grad(256, 256), q, q, q) \
        == _grid_walk(2, 1024, 1024, 64, 256, 256)
    whole, row = (1, 1024, 64), (1, 1, 1024)
    assert _pallas_calls(grad(), q, q, q) == {
        "flash_fwd": ((2,), [whole] * 4 + [row]),
        "flash_bwd_dq": ((2,), [whole] * 4 + [row] * 2 + [whole]),
        "flash_bwd_dkv": ((2,), [whole] * 4 + [row] * 2 + [whole] * 2),
    }


def _tile_counts():
    from distkeras_tpu.obs.registry import default_registry
    registry = default_registry()
    return (registry.counter("flash.causal_tiles_executed").value,
            registry.counter("flash.causal_tiles_total").value)


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
