"""``ops.ssm``: the Mamba-2 mixer's chunked scan against the recurrence
itself (``benchmark/reference/nemotron_h.py:mamba``, one position after
another), and the Pallas kernels (interpret mode) against the chunked
einsums.  Small sizes, CPU, float32 leaves drawn at random."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import nemotron_h  # noqa: E402

from distkeras_tpu.obs.registry import default_registry  # noqa: E402
from distkeras_tpu.ops import pallas_ssm, ssm  # noqa: E402

SIZES = dict(mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
             n_groups=2, conv_kernel=4, chunk_size=16,
             layer_norm_epsilon=1e-5)


def mixer(impl, **over):
    s = dict(SIZES, **over)
    return ssm.Mamba2Mixer(
        s["mamba_num_heads"], s["mamba_head_dim"], s["ssm_state_size"],
        n_groups=s["n_groups"], conv_kernel=s["conv_kernel"],
        chunk_size=s["chunk_size"], norm_eps=s["layer_norm_epsilon"],
        impl=impl)


def drawn(params, seed):
    """The mixer's parameters with its float32 leaves, the conv bias and
    the norm's weight moved off their initial values."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    noise = lambda a, s: a + s * jax.random.normal(next(keys), a.shape)  # noqa: E731
    return dict(params, A_log=noise(params["A_log"], 0.5),
                dt_bias=noise(params["dt_bias"], 0.5),
                D=noise(params["D"], 0.5),
                conv={"kernel": params["conv"]["kernel"],
                      "bias": noise(params["conv"]["bias"], 0.2)},
                norm={"scale": noise(params["norm"]["scale"], 0.2)})


def close(got, want, rtol=2e-4):
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want),
                            strict=True):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path  # the leaf is used
        np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("impl,t", [("chunked", 64), ("chunked", 72),
                                    ("pallas", 64), ("pallas", 40)])
def test_the_mixer_equals_the_recurrence(impl, t):
    """Values and every gradient, at a length of several chunks; 72 and
    40 are no multiple of 16: the last chunk is padded with dt = 0."""
    layer = mixer(impl)
    params = drawn(layer.init(jax.random.PRNGKey(0), (t, 24))[0], 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, t, 24))
    w = jax.random.normal(jax.random.PRNGKey(3), (2, t, 24))
    def both(fn):
        def loss(p, u):
            y = fn(p, u)
            return jnp.sum(w * y), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, u)

    with jax.default_matmul_precision("highest"):
        (_, y_got), got = both(lambda p, u: layer.apply(p, {}, u)[0])
        (_, y_want), want = both(lambda p, u: nemotron_h.mamba(p, u, SIZES))
    np.testing.assert_allclose(y_got, y_want, rtol=2e-4, atol=2e-5)
    assert len(jax.tree_util.tree_leaves(got[0])) == 8
    close(got, want)


def scan_inputs(dtype, b=2, t=256, h=8, p=16, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    return (jax.random.normal(ks[0], (b, t, h, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (b, t, g, n)).astype(dtype),
            jax.random.normal(ks[4], (b, t, g, n)).astype(dtype),
            jax.random.normal(ks[5], (b, t, h, p)))


@pytest.mark.parametrize("dtype,chunk,rtol", [
    ("float32", 64, 2e-5), ("float32", 128, 2e-5), ("bfloat16", 64, 3e-2)])
def test_the_kernels_equal_the_chunked_einsums(dtype, chunk, rtol):
    """``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` in interpret mode: values and
    the gradients by x, dt, A, B and C; 4 heads a group of 16 lanes are
    taken side by side, as 2 of 64 are on the chip."""
    *args, w = scan_inputs(dtype)
    assert pallas_ssm._pack(4, 16) == 4 and pallas_ssm._pack(8, 64) == 2 \
        and pallas_ssm._pack(8, 128) == 1 and pallas_ssm._pack(2, 8) == 2

    def both(impl):
        def loss(*a):
            return jnp.sum(w * ssm.ssd(*a, chunk=chunk, impl=impl)
                           .astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: (
                ssm.ssd(*a, chunk=chunk, impl=impl).astype(jnp.float32),)
                + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a))(*args)

    for got, want in zip(both("pallas"), both("chunked"), strict=True):
        assert got.dtype == want.dtype
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), rtol=rtol,
            atol=rtol * scale)


def test_a_scan_counts_its_chunks():
    chunks = default_registry().counter("ssm.chunks")
    before = chunks.value
    *args, _ = scan_inputs("float32", b=1, t=200)
    jax.make_jaxpr(lambda *a: ssm.ssd(*a, chunk=64, impl="chunked"))(*args)
    assert chunks.value - before == 4  # 200 positions: 3 chunks and a part


def test_the_kernels_names_are_the_traces_rows():
    *args, _ = scan_inputs("float32", b=1, t=128)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        ssm.ssd(*a, chunk=64, impl="pallas"))))(*args))
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text


def test_a_mixer_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="impl"):
        mixer("scan")
    with pytest.raises(ValueError, match="groups"):
        mixer("chunked", n_groups=3)
