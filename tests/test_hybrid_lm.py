"""``zoo.hybrid_lm`` (one mixer a layer by pattern: Mamba-2 on a chunked
scan, relu² experts under a sigmoid router, attention without positions,
a dense relu² MLP) against the plain reference
``benchmark/reference/nemotron_h.py``, through the trainers' step.  Small
sizes, CPU, the Pallas kernels in interpret mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import nemotron_h  # noqa: E402

import distkeras_tpu as dk  # noqa: E402
from distkeras_tpu.data.datasets import load_lm_corpus  # noqa: E402
from distkeras_tpu.models import zoo  # noqa: E402
from distkeras_tpu.models.layers import layer_from_config  # noqa: E402
from distkeras_tpu.obs.registry import default_registry  # noqa: E402
from distkeras_tpu.ops.moe import SparseMoE  # noqa: E402
from distkeras_tpu.ops.ssm import Mamba2Mixer  # noqa: E402

SIZES = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=6,
    hybrid_override_pattern="ME*-EMEM", seq_len=64, mamba_num_heads=4,
    mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=24, n_routed_experts=8, num_experts_per_tok=3,
    moe_intermediate_size=12, moe_shared_expert_intermediate_size=20,
    routed_scaling_factor=2.5, norm_topk_prob=True, layer_norm_epsilon=1e-5,
    experts_held=4, first_expert=2)
FLOAT32 = ("A_log", "dt_bias", "D", "router")


def tokens(seed, shape, vocab=64):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(
        np.int32)


def drawn(variables, seed=11):
    """The variables with A_log, dt_bias, D and the routers' b moved off
    their initial values."""
    def move(path, a):
        keys = [getattr(p, "key", None) for p in path]
        if keys[-1] in ("A_log", "dt_bias", "D", "bias") \
                and "conv" not in keys:
            return a + 0.3 * jax.random.normal(jax.random.fold_in(
                jax.random.PRNGKey(seed), len(str(path))), a.shape)
        return a
    return dict(variables, params=jax.tree_util.tree_map_with_path(
        move, variables["params"]))


@pytest.fixture(scope="module")
def built():
    model = zoo.hybrid_lm(**SIZES, attention_impl="flash",
                          ssm_impl="pallas")
    return model, drawn(model.init(3))


def test_one_mixer_a_layer_by_pattern(built):
    model, variables = built
    kinds = [type(lyr.inner.layers[1]).__name__
             for lyr in model.layer.layers[1:-2]]
    assert kinds == ["Mamba2Mixer", "SparseMoE", "MultiHeadAttention",
                     "Sequential", "SparseMoE", "Mamba2Mixer"]
    attention = model.layer.layers[3].inner.layers[1]
    assert not attention.rope and attention.causal \
        and attention.num_kv_heads == 2 and attention.window is None
    moe = model.layer.layers[2].inner.layers[1]
    assert (moe.expert_activation, moe.scoring, moe.experts_held,
            moe.first_expert) == ("relu2", "sigmoid", 4, 2)
    with pytest.raises(ValueError, match="unknown layer kind"):
        zoo.hybrid_lm(**dict(SIZES, hybrid_override_pattern="MXE*--"))
    with pytest.raises(ValueError, match="pattern"):
        zoo.hybrid_lm(**dict(SIZES, num_hidden_layers=9))


def test_logits_equal_the_reference(built):
    model, variables = built
    x = tokens(0, (2, 64))
    got = jax.jit(model.predict_fn())(variables, x)
    want, gaps, bears = nemotron_h.passes(SIZES)(variables, x)
    assert got.shape == want.shape == (2, 64, 64)
    assert gaps.shape == bears.shape == (2, 2, 64)  # two E layers
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_a_float32_trainer_step_equals_the_reference():
    """One SGD step of ``SingleTrainer`` from ``model.init(seed)``: the
    loss it logs and the step it takes are the reference's loss and
    gradient, leaf by leaf (``remat`` on: children under checkpoints)."""
    model = zoo.hybrid_lm(**SIZES, attention_impl="flash",
                          ssm_impl="pallas")
    train = load_lm_corpus(n_train=2, seq_len=64, vocab_size=64, seed=1)[0]
    rate = 0.5
    trainer = dk.SingleTrainer(
        model, "sgd", "sparse_categorical_crossentropy", num_epoch=1,
        batch_size=2, learning_rate=rate, seed=5, remat=True)
    after = trainer.train(train).variables
    before = model.init(5)
    want_loss, want = nemotron_h.loss_and_grads(
        before, np.asarray(train["features"]), np.asarray(train["label"]),
        SIZES)
    np.testing.assert_allclose(trainer.get_averaged_history()[0], want_loss,
                               rtol=1e-5)
    step = jax.tree_util.tree_map(lambda a, b: (a - b) / rate,
                                  before["params"], after["params"])
    got, _ = jax.tree_util.tree_flatten_with_path(step)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 41
    for (path, a), b in zip(got, want):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router']['bias']"):
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name  # the leaf is used
        # the step is read off float32 parameters: 1e-7 of their size
        np.testing.assert_allclose(
            a, b, rtol=5e-3, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 2e-6,
            err_msg=name)


def test_a_bf16_step_hands_the_float32_leaves_over_uncast(monkeypatch):
    """``make_local_step``'s cast: A_log, dt_bias, D and everything under
    ``router`` reach the forward as the float32 master weights, every
    other float leaf as a bf16 copy; the scan's decays and sums are
    float32."""
    import optax
    from distkeras_tpu.ops import ssm
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.sync import FLOAT32_KEYS, make_local_step
    # this model's keys, and a looped model's exit gate (test_looped_lm.py)
    assert FLOAT32_KEYS == set(FLOAT32) | {"exit_gate"}
    model = zoo.hybrid_lm(**dict(SIZES, num_hidden_layers=2))
    variables = model.init(0)
    seen, scans = {}, []
    plain_apply, plain_ssd = model.layer.apply, ssm.ssd

    def spy(params, *a, **kw):
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            seen[jax.tree_util.keystr(path)] = leaf.dtype
        return plain_apply(params, *a, **kw)

    def spy_ssd(x, dt, a, b, c, **kw):
        scans.append((x.dtype, dt.dtype, a.dtype, b.dtype))
        return plain_ssd(x, dt, a, b, c, **kw)

    monkeypatch.setattr(model.layer, "apply", spy)
    monkeypatch.setattr(ssm, "ssd", spy_ssd)
    optimizer = optax.adam(1e-3)
    step = make_local_step(model, get_loss("sparse_categorical_crossentropy"),
                           optimizer, compute_dtype=jnp.bfloat16)
    x = tokens(5, (2, 64))
    jax.make_jaxpr(step)((variables, optimizer.init(variables["params"]),
                          jax.random.PRNGKey(0)), (x, x))
    kept = {k for k, d in seen.items() if d == jnp.float32}
    assert kept == {k for k in seen if any(f"['{f}']" in k for f in FLOAT32)}
    assert {k.split("[")[-1] for k in kept} == {
        "'A_log']", "'dt_bias']", "'D']", "'kernel']", "'bias']"}
    assert all(d == jnp.bfloat16 for k, d in seen.items() if k not in kept)
    assert scans == [(jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16)]


def test_a_bf16_trainer_keeps_the_scan_kernels_outputs_and_learns():
    """``remat`` on: the recompute plan's checkpoints keep ``ssd_out`` /
    ``ssd_state`` beside the flash kernels' outputs, so a recomputed
    mixer runs no forward kernel again; the loss falls."""
    from distkeras_tpu.models.remat import KERNEL_OUTPUTS
    assert KERNEL_OUTPUTS == ("flash_out", "flash_lse", "ssd_out",
                              "ssd_state", "gdn_out", "gdn_state")
    registry = default_registry()
    chunks = registry.counter("ssm.chunks")
    before = chunks.value
    rounds = registry.counter("moe.rounds")
    rounds_before = rounds.value
    train = load_lm_corpus(n_train=8, seq_len=64, vocab_size=64, seed=2)[0]
    trainer = dk.SingleTrainer(
        zoo.hybrid_lm(**SIZES, attention_impl="flash", ssm_impl="pallas"),
        "adam", "sparse_categorical_crossentropy", num_epoch=3,
        batch_size=2, learning_rate=3e-3, compute_dtype="bfloat16",
        remat=True)
    model = trainer.train(train)
    history = trainer.get_averaged_history()
    assert np.all(np.isfinite(history)) and history[-1] < history[0]
    # two mixers of 4 chunks, counted where the step was traced (the
    # plan's own sizing traces are not counted)
    assert (chunks.value - before) % 8 == 0 and chunks.value > before
    mixer = model.variables["params"][1]["inner"][1]
    assert all(mixer[k].dtype == jnp.float32 for k in ("A_log", "D"))
    routed = model.variables["state"][2]["inner"][1]
    assert routed["rows_needed"] > 0
    # the two routed layers' last step: a round each, of R_c rows
    from distkeras_tpu.ops.moe import round_rows
    assert routed["rounds"] == 1
    assert rounds.value - rounds_before == 2
    assert registry.gauge("moe.round_rows").value == routed["round_rows"] \
        == round_rows(2 * 64, 3, 4, 8, 128) == 7 * 128


@pytest.mark.parametrize("layer", [
    Mamba2Mixer(4, 8, 16, n_groups=2, conv_kernel=3, chunk_size=32,
                norm_eps=1e-6, impl="pallas"),
    SparseMoE(8, 3, 12, shared_hidden=20, routed_scale=2.5,
              experts_held=4, first_expert=2, expert_activation="relu2",
              scoring="sigmoid"),
    SparseMoE(8, 2, 12)], ids=["mamba2", "relu2-sigmoid", "defaults"])
def test_layers_round_trip_through_their_config(layer):
    again = layer_from_config(layer.config())
    assert type(again) is type(layer) and again.config() == layer.config()
    shapes = [jax.eval_shape(lambda l=l: l.init(jax.random.PRNGKey(0),
                                               (16, 32))[0])
              for l in (layer, again)]
    assert shapes[0] == shapes[1]
    if isinstance(layer, SparseMoE):
        assert layer.get_config()["expert_activation"] in ("swiglu", "relu2")
        assert ("bias" in shapes[0]["router"]) == (layer.scoring == "sigmoid")
