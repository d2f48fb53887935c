"""``reference/gpt2.py`` computes what ``zoo.gpt_lm`` computes."""

import numpy as np

SIZES = dict(vocab_size=96, dim=32, num_heads=4, num_blocks=2, seq_len=48)


def test_reference_equals_dense_predict_fn():
    import jax

    import distkeras_tpu as dk
    from reference import gpt2
    model = dk.zoo.gpt_lm(**SIZES, attention_impl="dense")
    variables = model.init(3)
    x = np.random.default_rng(0).integers(0, 96, (2, 48)).astype(np.int32)
    got = np.asarray(jax.jit(model.predict_fn())(variables, x))
    want = np.asarray(gpt2.forward(variables, x, SIZES))
    assert got.shape == want.shape == (2, 48, 96)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_reference_is_causal():
    import distkeras_tpu as dk
    from reference import gpt2
    variables = dk.zoo.gpt_lm(**SIZES).init(1)
    x = np.random.default_rng(1).integers(0, 96, (1, 48)).astype(np.int32)
    y = x.copy()
    y[0, 30:] = (y[0, 30:] + 1) % 96
    a = np.asarray(gpt2.forward(variables, x, SIZES))
    b = np.asarray(gpt2.forward(variables, y, SIZES))
    np.testing.assert_array_equal(a[0, :30], b[0, :30])
    assert np.abs(a[0, 30:] - b[0, 30:]).max() > 1e-4
