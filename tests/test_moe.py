"""Expert parallelism: switch-MoE over the ``ep`` mesh axis.

The reference has NO expert parallelism (SURVEY.md §2: strategy ABSENT);
this is a TPU-native extension.  Correctness bar: with capacity high
enough that nothing drops, the all_to_all-dispatched sharded MoE must
equal the dense per-token formula out_n = gate_n · FFN_{e(n)}(x_n) —
forward and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.moe import init_moe_params, switch_moe_sharded
from distkeras_tpu.parallel.mesh import make_mesh

D, H, N = 8, 16, 64


def dense_reference(params, x):
    """Per-token top-1 expert, no capacity limit."""
    wg = params["router"]["wg"]
    ex = params["experts"]
    gates = jax.nn.softmax(x @ wg, axis=-1)
    idx = jnp.argmax(gates, axis=-1)
    gate = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0]
    h = jax.nn.relu(jnp.einsum("nd,edh->neh", x, ex["w1"]) + ex["b1"])
    y = jnp.einsum("neh,ehd->ned", h, ex["w2"]) + ex["b2"]
    picked = jnp.take_along_axis(y, idx[:, None, None], 1)[:, 0]
    return gate[:, None] * picked


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(8, ("ep",))


@pytest.mark.parametrize("num_experts", [8, 16])
def test_moe_matches_dense_reference(mesh, num_experts):
    params = init_moe_params(0, num_experts, D, H)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(N, D)),
                    jnp.float32)
    # capacity ≥ any possible per-device per-expert load → no drops
    out, aux = switch_moe_sharded(mesh, params, x,
                                  capacity_factor=2.0 * num_experts)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_reference(params, x)),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) >= 1.0 - 1e-5  # = 1 iff perfectly balanced


def test_moe_gradients_match_dense_reference(mesh):
    params = init_moe_params(2, 8, D, H)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(N, D)),
                    jnp.float32)

    def sharded_loss(p):
        out, _ = switch_moe_sharded(mesh, p, x, capacity_factor=16.0)
        return jnp.mean(out ** 2)

    def dense_loss(p):
        return jnp.mean(dense_reference(p, x) ** 2)

    gs = jax.grad(sharded_loss)(params)
    gd = jax.grad(dense_loss)(params)
    for a, b in zip(jax.tree_util.tree_leaves(gs),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens(mesh):
    """Overflow tokens get ZERO output (the switch contract: callers add
    a residual), never garbage."""
    params = init_moe_params(4, 8, D, H)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(N, D)),
                    jnp.float32)
    out, _ = switch_moe_sharded(mesh, params, x, capacity_factor=0.125)
    dense = np.asarray(dense_reference(params, x))
    got = np.asarray(out)
    zero_rows = np.all(got == 0.0, axis=1)
    assert zero_rows.any(), "expected overflow drops at capacity 1"
    kept = ~zero_rows
    np.testing.assert_allclose(got[kept], dense[kept], rtol=1e-5,
                               atol=1e-5)


def test_moe_bf16_tokens(mesh):
    """Slot bookkeeping stays int32 regardless of token dtype (bf16 can't
    count past 256 exactly); outputs track the f32 path."""
    params = init_moe_params(8, 8, D, H)
    xf = jnp.asarray(np.random.default_rng(9).normal(size=(N, D)),
                     jnp.float32)
    out_f, _ = switch_moe_sharded(mesh, params, xf, capacity_factor=16.0)
    out_b, _ = switch_moe_sharded(mesh, params, xf.astype(jnp.bfloat16),
                                  capacity_factor=16.0)
    np.testing.assert_allclose(np.asarray(out_b, np.float32),
                               np.asarray(out_f), rtol=0.1, atol=0.05)


def test_moe_validates_shapes(mesh):
    params = init_moe_params(10, 8, D, H)
    with pytest.raises(ValueError, match="not divisible"):
        switch_moe_sharded(mesh, params, jnp.zeros((60, D)))
    with pytest.raises(ValueError, match="experts not divisible"):
        switch_moe_sharded(mesh, init_moe_params(10, 12, D, H),
                           jnp.zeros((64, D)))


def test_dense_moe_matches_local_reference():
    from distkeras_tpu.ops.moe import dense_moe, init_moe_params
    params = init_moe_params(11, 8, D, H)
    x = jnp.asarray(np.random.default_rng(12).normal(size=(N, D)),
                    jnp.float32)
    out, aux = dense_moe(params, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_reference(params, x)),
                               rtol=1e-6)
    assert float(aux) >= 1.0 - 1e-5


def test_moe_dense_layer_in_transformer(mesh):
    """MoEDense as the transformer FF block: trains through the public
    trainer API, serde round-trips, and attaching a mesh switches to the
    ep-sharded path with identical outputs."""
    import distkeras_tpu as dk
    from distkeras_tpu.ops.moe import MoEDense
    from distkeras_tpu.utils import serde

    model = dk.zoo.transformer_classifier(
        vocab_size=50, dim=16, num_heads=2, num_blocks=1, seq_len=12,
        num_classes=2, moe_experts=8)
    rng = np.random.default_rng(13)
    x = rng.integers(0, 50, size=(256, 12))
    # learnable rule: class = leading token id parity
    y = (x[:, 0] % 2).astype(np.int64)
    ds = dk.Dataset({"features": x, "label": y})
    from distkeras_tpu.data.transformers import OneHotTransformer
    ds = OneHotTransformer(2, "label", "label_onehot").transform(ds)

    t = dk.SingleTrainer(model, "sgd", label_col="label_onehot",
                         num_epoch=8, batch_size=32, learning_rate=0.2)
    m = t.train(ds)
    hist = t.get_averaged_history()
    assert hist[-1] < hist[0] * 0.9, hist
    # the router aux loss is surfaced through layer state
    aux_leaves = [v for k, v in jax.tree_util.tree_flatten_with_path(
        m.variables["state"])[0] if "aux_loss" in str(k)]
    assert aux_leaves and np.isfinite(aux_leaves[0])

    # serde round-trip (MoEDense registered; mesh is runtime, not config)
    blob = serde.serialize_model(m, m.variables)
    m2, vars2 = serde.deserialize_model(blob)
    xin = x[:16].astype(np.float32)
    a, _ = m.layer.apply(m.variables["params"], m.variables["state"], xin)
    b, _ = m2.layer.apply(vars2["params"], vars2["state"], xin)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # attaching a mesh flips the SAME layer to expert-sharded execution
    # (trace-time state: valid here because nothing jitted is reused;
    # model.iter_layers() is the public way to find nested instances)
    moe_layers = [l for l in m.iter_layers() if isinstance(l, MoEDense)]
    assert moe_layers
    for ml in moe_layers:
        ml.mesh = mesh
        ml.capacity_factor = 32.0  # no drops → exact parity with dense
    c, _ = m.layer.apply(m.variables["params"], m.variables["state"], xin)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=1e-5,
                               atol=1e-6)
    # a bf16 step hands the router its float32 master weights beside
    # bf16 experts: the sharded path still answers in the tokens' dtype
    ml = moe_layers[0]
    params, state, _ = ml.init(jax.random.PRNGKey(0), (N, D))
    half = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    out, _ = ml.apply(dict(half, router=params["router"]), state,
                      jnp.ones((N, D), jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    for ml in moe_layers:
        ml.mesh = None


def test_moe_model_deserializes_in_fresh_process(tmp_path):
    """serde must work in a process that never imported ops.moe — the
    layer registry fills from package import side effects, not from
    whoever happened to build the model (async PS wire format / job
    deployment both ship blobs to fresh processes)."""
    import os
    import subprocess
    import sys

    import distkeras_tpu as dk
    from distkeras_tpu.utils import serde

    model = dk.zoo.transformer_classifier(
        vocab_size=20, dim=8, num_heads=2, num_blocks=1, seq_len=6,
        num_classes=2, moe_experts=4)
    blob_path = tmp_path / "moe_model.blob"
    blob_path.write_bytes(serde.serialize_model(model, model.init(0)))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        f"import sys; sys.path.insert(0, {root!r})\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from distkeras_tpu.utils import serde\n"
        f"m, v = serde.deserialize_model(open({str(blob_path)!r}, "
        "'rb').read())\n"
        "import numpy as np\n"
        "y, _ = m.apply(v, np.zeros((2, 6), np.int32))\n"
        "assert y.shape == (2, 2), y.shape\n"
        "print('FRESH_DESERIALIZE_OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "FRESH_DESERIALIZE_OK" in out.stdout


def test_moe_trains_and_balances(mesh):
    """jitted SGD through router + experts: task loss falls and the aux
    loss keeps routing near balanced."""
    params = init_moe_params(6, 8, D, H)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    tgt = jnp.asarray(np.tanh(rng.normal(size=(N, D))), jnp.float32)

    @jax.jit
    def step(p):
        def loss(p):
            out, aux = switch_moe_sharded(mesh, p, x, capacity_factor=2.0)
            return jnp.mean((x + out - tgt) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss)(p)
        return jax.tree_util.tree_map(lambda w, d: w - 0.2 * d, p, g), l

    losses = []
    for _ in range(60):
        params, l = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.85, losses


def test_trainer_aux_weight_folds_balance_loss():
    """aux_weight folds the router load-balance scalars into the trainer
    objective (ADVICE r3): the recorded loss history must differ from the
    task-loss-only run, and training still converges."""
    from distkeras_tpu.models import zoo
    import distkeras_tpu as dk
    from distkeras_tpu.data.datasets import load_lm_corpus
    ds = load_lm_corpus(n_train=256, seq_len=16, vocab_size=17, seed=0)[0]

    def run(aux_weight):
        t = dk.SingleTrainer(
            zoo.gpt_lm(vocab_size=17, dim=32, num_heads=2, num_blocks=1,
                       seq_len=16, moe_experts=4),
            "adam", "sparse_categorical_crossentropy",
            features_col="features", label_col="label", num_epoch=4,
            batch_size=64, learning_rate=3e-3, aux_weight=aux_weight)
        t.train(ds)
        return t.get_averaged_history()

    plain = run(0.0)
    weighted = run(0.01)
    assert not np.allclose(plain, weighted)  # the aux term is in the loss
    assert weighted[-1] < weighted[0]        # and training still converges
