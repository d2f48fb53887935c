"""The ``olmo-hybrid-7b-pp8`` configuration's files agree with each other
and with the published widths, its parameters and FLOPs are the issue's
arithmetic, its readers read a trace's rows by the kernels' names, the
program equals ``reference/olmo_hybrid.py`` (logits, loss, gradients), and
the other ``decoder_lm`` configurations build the programs they built.
(The cell itself is rehearsed, like every cell, by tier-1's
``tests/test_benchmark_rehearsal.py``; the mixer and its kernels against
the recurrence are ``tests/test_gated_delta.py``.)"""

import hashlib
import json
import os

import pytest

from conftest import BENCH
from run import load_module, merged


def load(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load("olmo-hybrid-7b-pp8")


def test_the_builders_sizes_are_the_published_keys_cut_as_reduced_says(
        config):
    sizes = config["sizes"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == period * 8
    assert config["published"]["num_hidden_layers"] == 32 \
        == len(config["layer_types"]) == 8 * config["num_hidden_layers"]
    assert config["published"]["vocab_size"] == 100352 \
        == 8 * config["vocab_size"]
    assert config["deployment"]["pipeline_stages"] == 8
    assert sizes["num_hidden_layers"] == 4 and sizes["vocab_size"] == 12544
    assert sizes["layer_types"] == config["layer_types"][:4] == period
    # no width differs: 3,840; 30 heads of (96, 192); 4 taps; negative
    # eigenvalues; 30 over 30 heads of 128; 11,008; 1e-6
    for key, value in dict(
            hidden_size=3840, intermediate_size=11008,
            num_key_value_heads=30, rms_norm_eps=1e-06,
            linear_num_key_heads=30, linear_num_value_heads=30,
            linear_key_head_dim=96, linear_value_head_dim=192,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True).items():
        assert sizes[key] == config[key] == value, key
    assert sizes["num_attention_heads_per_layer"] \
        == [config["num_attention_heads"]] * 4 == [30] * 4
    assert sizes["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    assert config["rope_parameters"] == {"rope_theta": None}
    assert sizes["rope_parameters"] == {"full_attention": {"rope_theta": None}}
    assert sizes["norm_placement"] == "post" and sizes["qk_norm"] is True
    assert sizes["mlp_layer_types"] == ["dense"] * 4
    assert not config["tie_word_embeddings"] and sizes["seq_len"] == 8192
    assert config["train"]["remat"] is True
    assert config["builder_args"] == {"attention_impl": "flash",
                                      "linear_impl": "pallas"}
    for key in ("output_gate", "l2_norms", "conv", "alpha", "norm_placement",
                "qk_norm", "positions", "head_dim", "chunk_size",
                "initialisation", "learning_rate", "seq_len"):
        assert key in config["assumed"], key


def test_the_parameter_count_recounted_from_the_config(config):
    s = config["sizes"]
    d, f, h = s["hidden_size"], s["intermediate_size"], \
        s["linear_num_key_heads"]
    qk, vz = h * s["linear_key_head_dim"], h * s["linear_value_head_dim"]
    mixer = d * (2 * qk + 2 * vz + 2 * h) + s["linear_conv_kernel_dim"] \
        * (2 * qk + vz) + 2 * h + s["linear_value_head_dim"] + vz * d
    heads, dh = s["num_attention_heads_per_layer"][3], s["head_dim"]
    attention = d * 3 * heads * dh + heads * dh * d + 2 * heads * dh
    ff = 3 * d * f
    linear_layer, full_layer = mixer + ff + 2 * d, attention + ff + 2 * d
    assert (linear_layer, full_layer) == (215570172, 185809920)
    period = 3 * linear_layer + full_layer
    assert period == 832520436           # the catalog's "about 208M a layer"
    assert round(period / 4 / 1e6, 1) == 208.1
    assert period + 2 * s["vocab_size"] * d + d == config["parameters"] \
        == 928862196
    # 11.15 GB of float32 weights and adam moments, 1.86 GB of bf16 copies
    assert round(config["parameters"] * 12 / 1e9, 2) == 11.15
    assert round(config["parameters"] * 2 / 1e9, 2) == 1.86
    whole = 8 * period + 2 * 100352 * d + d
    assert whole == 7430870688


def test_flops_are_the_issues_arithmetic(config):
    import flops_olmo_hybrid as flops
    sizes = config["sizes"]
    parts = flops.forward_per_row(sizes)
    assert round(flops.train(sizes) / 1e12, 1) == 45.3
    matmuls = 3 * sum(v for k, v in parts.items()
                      if k not in ("attention", "delta_rule"))
    assert round(matmuls / 1e12, 1) == 43.3
    rule, moved = flops.gdn_train(sizes, 1)
    assert rule == 3 * parts["delta_rule"]
    assert round(rule / 1e12, 2) == 0.44
    assert moved / 819e9 > rule / 197e12  # the bytes bound it
    attention, _ = flops.flash_train(sizes, 1)
    assert round(attention / 1e12, 1) == 1.8
    # the kernels' work is three layers' of 30 heads: one layer's a third
    one = dict(sizes, num_hidden_layers=1)
    assert flops.gdn_train(one, 1)[0] * 3 == pytest.approx(rule)


def test_readers_read_the_kernels_rows(config):
    ops = [["tpu_custom_call:gdn_chunk_fwd", 1.0],
           ["tpu_custom_call:gdn_chunk_bwd", 2.0],
           ["tpu_custom_call:flash_fwd", 0.5], ["fusion", 6.5]]
    sources = {"config": config, "batch": 1, "steps_per_epoch": 8,
               "trace": {"device_ops": ops, "busy_s": 10.0,
                         "module_runs": 3},
               "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(name, over=None):
        return load_module("layer_metrics", name).read(dict(sources,
                                                            **(over or {})))

    assert read("gdn_time_share") == 30.0
    assert read("flash_time_share") == 5.0
    import flops_olmo_hybrid as flops
    least = 24 * flops.gdn_train(config["sizes"], 1)[1] / 819e9
    assert read("gdn_roofline") == pytest.approx(100 * least / 3.0)
    assert 0 < read("flash_roofline") < 100
    # no trace, or a program from before the kernels: nothing is read and
    # nothing raises
    for over in ({"trace": None}, {"trace": dict(
            sources["trace"], device_ops=[["fusion", 2.25]])}):
        for name in ("gdn_time_share", "gdn_roofline"):
            assert read(name, over) is None


def test_the_program_equals_the_reference(config):
    """``decoder_lm`` at the rehearsal's widths, 40 positions (the rule in
    chunks of 32, the last padded), float32 leaves drawn at random, at
    HIGHEST: logits, the loss and every gradient against the reference's
    recurrence.  2e-4 of the largest entry is float32 rounding over four
    layers and forty sequential updates."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.losses import sparse_categorical_crossentropy
    from reference import olmo_hybrid
    sizes = dict(merged(config, config["rehearse"])["sizes"], seq_len=40)
    model = zoo.decoder_lm(**sizes, linear_impl="chunked")
    variables = model.init(5)
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    variables["params"] = jax.tree_util.tree_map(  # norms and biases off 1
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, variables["params"])
    rng = np.random.RandomState(0)
    x, y = rng.randint(0, 128, (2, 40)), rng.randint(0, 128, (2, 40))

    def loss(params):
        return sparse_categorical_crossentropy(
            model.apply({"params": params, "state": variables["state"]},
                        jnp.asarray(x))[0], jnp.asarray(y))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.predict_fn())(variables, x)
        got_loss, got_grads = jax.jit(jax.value_and_grad(loss))(
            variables["params"])
    want = olmo_hybrid.forward(variables, x, sizes)
    want_loss, want_grads = olmo_hybrid.loss_and_grads(variables, x, y, sizes)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * scale)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5)
    leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(leaves) == 3 * 10 + 8 + 3
    for (path, b), a in zip(leaves, jax.tree_util.tree_leaves(got_grads),
                            strict=True):
        assert float(jnp.max(jnp.abs(b))) > 0, path  # the leaf is used
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(b))),
                                   err_msg=jax.tree_util.keystr(path))


#: sha256 (first 16 hex) of each other ``decoder_lm`` / ``hybrid_lm``
#: configuration's parameter tree (paths, shapes, dtypes) and of the
#: jaxpr of its forward and gradient at the rehearsal's sizes, taken on
#: the tree from before the Olmo Hybrid configuration came in
PINNED = {"laguna-xs2-ep8": "a5f8a63ba7cb251d",
          "ouro-2.6b-pp8": "f22c5052b87e6814",
          "nemotron3-nano-ep16": "5aa35323f6670e0e"}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_other_configurations_build_the_programs_they_built(name):
    import jax
    import jax.numpy as jnp
    from common import resolve
    config = load(name)
    sizes = merged(config, config["rehearse"])["sizes"]
    model = resolve(config["builder"])(**sizes,
                                       **config.get("builder_args", {}))
    variables = jax.eval_shape(lambda: model.init(0))
    shapes = [(jax.tree_util.keystr(p), a.shape, str(a.dtype)) for p, a in
              jax.tree_util.tree_flatten_with_path(variables)[0]]

    def loss(v, x):
        out = model.predict_fn()(v, x)
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree_util.tree_leaves(out))

    x = jax.ShapeDtypeStruct((2, sizes["seq_len"]), jnp.int32)
    text = repr(shapes) + str(jax.make_jaxpr(jax.value_and_grad(loss))(
        variables, x))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED[name]
