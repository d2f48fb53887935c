"""``ops.gated_delta``: the Gated DeltaNet mixer's chunked rule against the
recurrence itself (``benchmark/reference/olmo_hybrid.py:delta_rule``, one
position after another), the Pallas kernels (interpret mode) against both,
and the triangular inverse at beta near 2.  Small sizes, CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import olmo_hybrid  # noqa: E402

from distkeras_tpu.obs.registry import default_registry  # noqa: E402
from distkeras_tpu.ops import gated_delta, pallas_gdn  # noqa: E402

SIZES = dict(linear_num_key_heads=2, linear_key_head_dim=8,
             linear_value_head_dim=16, linear_conv_kernel_dim=4,
             linear_allow_neg_eigval=True, rms_norm_eps=1e-6)


def rule_inputs(t, *, beta=(0.0, 2.0), aligned=False, b=2, h=2, dk=8,
                dv=16, seed=0):
    """q^, k^ of unit norm (q scaled), v, log alpha in (-0.5, 0) and beta
    uniform in ``beta``; ``aligned``: every key near one direction, the
    case where A's entries are largest."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = jax.random.normal(ks[0], (b, t, h, dk))
    if aligned:
        k = 0.1 * k + jnp.ones((dk,))
    q = gated_delta.l2_normalised(jax.random.normal(ks[1], (b, t, h, dk))) \
        * dk ** -0.5
    return (q, gated_delta.l2_normalised(k),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -0.5 * jax.random.uniform(ks[3], (b, t, h)),
            jax.random.uniform(ks[4], (b, t, h), minval=beta[0],
                               maxval=beta[1]),
            jax.random.normal(ks[5], (b, t, h, dv)))


def values_and_grads(fn, args, w):
    def loss(*a):
        return jnp.sum(w * fn(*a).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: (fn(*a).astype(jnp.float32),)
                       + jax.grad(loss, argnums=tuple(range(5)))(*a))(*args)


def assert_close(got, want, rtol):
    for i, (a, b) in enumerate(zip(got, want, strict=True)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, i  # the input is used
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=rtol,
                                   atol=rtol * scale, err_msg=str(i))


# 72, 40 and 100 are no multiple of the chunk: the last one is padded with
# k = 0, beta = 0, alpha = 1
@pytest.mark.parametrize("impl,chunk,t,beta", [
    ("chunked", 16, 72, (0.0, 1.0)), ("chunked", 32, 64, (1.0, 2.0)),
    ("chunked", 64, 100, (0.0, 2.0)), ("pallas", 16, 40, (1.0, 2.0)),
    ("pallas", 32, 64, (0.0, 1.0)), ("pallas", 64, 100, (0.0, 2.0))])
def test_the_chunked_rule_equals_the_recurrence(impl, chunk, t, beta):
    """Values and the gradients by q, k, v, log alpha and beta, over
    beta in (0, 1) (the plain delta rule's half), (1, 2) (the negative
    eigenvalues') and the whole of (0, 2).  Float32 at HIGHEST against
    the float32 recurrence: 2e-4 of the largest entry is rounding over a
    few hundred sequential updates."""
    *args, w = rule_inputs(t, beta=beta)
    got = values_and_grads(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=chunk, impl=impl), args, w)
    want = values_and_grads(olmo_hybrid.delta_rule, args, w)
    assert_close(got, want, 2e-4)


def test_beta_near_two_with_aligned_keys():
    """The hardest inverse: beta in (1.9, 2) and keys within a few degrees
    of one direction, so A's entries are near 2 below the diagonal.  The
    kernels' doubled block inverse stays on the recurrence; the product
    (I - A)(I + A^2)(I + A^4)... of the same A misses T by orders of
    magnitude more than the limit: its factors' entries pass 1e9."""
    *args, w = rule_inputs(64, beta=(1.9, 2.0), aligned=True, b=1)
    got = values_and_grads(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=64, impl="pallas"), args, w)
    want = values_and_grads(olmo_hybrid.delta_rule, args, w)
    assert_close(got, want, 2e-4)

    k, beta = args[1][0, :, 0], args[4][0, :, 0]
    a = jnp.tril(beta[:, None] * (k @ k.T), -1)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        exact = np.linalg.inv(np.asarray(eye + a, np.float64))
        doubled = pallas_gdn.unit_lower_inverse(a)
        product, power = eye - a, a @ a
        for _ in range(5):
            product, power = product @ (eye + power), power @ power
    limit = 2e-4 * np.max(np.abs(exact))
    assert np.max(np.abs(np.asarray(doubled) - exact)) < limit
    assert np.max(np.abs(np.asarray(product) - exact)) > 100 * limit


@pytest.mark.parametrize("dtype,rtol", [("float32", 2e-5),
                                        ("bfloat16", 3e-2)])
def test_the_kernels_equal_the_chunked_einsums(dtype, rtol):
    """``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` against the einsums in the
    inputs' dtype: float32 at HIGHEST agrees to rounding; in bf16 the
    kernels' products take bf16 operands where the einsums take float32,
    so the limit is bf16's (its 8 bits of mantissa over sums of 64)."""
    q, k, v, g, beta, w = rule_inputs(128, b=1)
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    got, want = (values_and_grads(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=64, impl=impl), args, w) for impl in ("pallas", "chunked"))
    assert_close(got, want, rtol)


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_the_mixer_equals_the_reference(impl):
    """The whole mixer (projections, conv, norms, beta and alpha, the
    gated norm) and every gradient against the reference's
    ``gated_deltanet`` on drawn float32 leaves; 40 positions in chunks of
    16, the last padded."""
    layer = gated_delta.GatedDeltaNet(2, 8, 16, conv_kernel=4, chunk_size=16,
                                      impl=impl)
    params = layer.init(jax.random.PRNGKey(0), (40, 24))[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    params = dict(params, dt_bias=params["dt_bias"] + 0.5 * jax.random
                  .normal(next(keys), (2,)),
                  norm={"scale": 1.0 + 0.2 * jax.random.normal(
                      next(keys), (16,))})
    u = jax.random.normal(next(keys), (2, 40, 24))
    w = jax.random.normal(next(keys), (2, 40, 24))

    def both(fn):
        def loss(p, u):
            y = fn(p, u)
            return jnp.sum(w * y), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))(params, u)

    (_, y_got), got = both(lambda p, u: layer.apply(p, {}, u)[0])
    (_, y_want), want = both(lambda p, u: olmo_hybrid.gated_deltanet(
        p, u, SIZES))
    np.testing.assert_allclose(y_got, y_want, rtol=2e-4, atol=2e-5)
    assert len(jax.tree_util.tree_leaves(got[0])) == 6
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(b))))


def test_a_rule_counts_its_chunks():
    chunks = default_registry().counter("gdn.chunks")
    before = chunks.value
    *args, _ = rule_inputs(200, b=1)
    jax.make_jaxpr(lambda *a: gated_delta.gated_delta_rule(
        *a, chunk=64, impl="chunked"))(*args)
    assert chunks.value - before == 4  # 200 positions: 3 chunks and a part


def test_the_kernels_names_are_the_traces_rows():
    *args, _ = rule_inputs(64, b=1)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        gated_delta.gated_delta_rule(*a, chunk=32, impl="pallas"))))(*args))
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text


def test_a_mixer_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="impl"):
        gated_delta.GatedDeltaNet(2, 8, 16, impl="scan")
