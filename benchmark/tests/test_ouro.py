"""The ``ouro-2.6b-pp8`` configuration's files agree with each other and
with the published widths, its FLOPs count every pass and are the issue's
arithmetic, its readers read what the program emits, and its runner's
comparison looks at every pass and at the exit distribution.  (The cell
itself is rehearsed, like every cell, by tier-1's
``tests/test_benchmark_rehearsal.py``; program against reference is
``tests/test_looped_lm.py``.)"""

import json
import os

import pytest

from conftest import BENCH
from run import load_module


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b-pp8.json")) as f:
        return json.load(f)


def test_the_builders_sizes_are_the_published_keys_cut_as_reduced_says(
        config):
    sizes = config["sizes"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 48 \
        == len(config["layer_types"]) == 8 * config["num_hidden_layers"]
    assert config["published"]["vocab_size"] == 49152 \
        == 8 * config["vocab_size"]
    assert config["deployment"]["pipeline_stages"] == 8
    assert sizes["num_hidden_layers"] == config["num_hidden_layers"] == 6
    assert sizes["vocab_size"] == config["vocab_size"] == 6144
    # no width differs: 2,048; 16 over 16 heads of 128; 5,632; 1e-6; 4 passes
    for key, value in dict(hidden_size=2048, num_key_value_heads=16,
                           head_dim=128, intermediate_size=5632,
                           rms_norm_eps=1e-06, total_ut_steps=4,
                           early_exit_threshold=1).items():
        assert sizes[key] == config[key] == value, key
    assert sizes["num_attention_heads_per_layer"] \
        == [config["num_attention_heads"]] * 6 == [16] * 6
    assert sizes["layer_types"] == config["layer_types"][:6]
    assert sizes["mlp_layer_types"] == ["dense"] * 6
    rope = sizes["rope_parameters"]["full_attention"]
    assert rope["rope_theta"] == config["rope_theta"] == 1000000
    assert rope["partial_rotary_factor"] == 1 and config["rope_scaling"] \
        is None and config["sliding_window"] is None
    assert sizes["sandwich_norm"] is True and sizes["seq_len"] == 8192
    assert config["train"]["loss"] == "exit_weighted_crossentropy"
    assert config["train"]["remat"] is True
    for key in ("loss", "beta", "exit_distribution", "loop", "sandwich_norm",
                "exit_gate", "initialisation", "learning_rate", "seq_len"):
        assert key in config["assumed"], key
    assert config["assumed"]["beta"] == 0.1


def test_the_parameter_count_recounted_from_the_sizes(config):
    sizes = config["sizes"]
    d, f, v = (sizes[k] for k in ("hidden_size", "intermediate_size",
                                  "vocab_size"))
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert layer == 51388416
    count = sizes["num_hidden_layers"] * layer + 2 * v * d + d + (d + 1)
    assert count == config["parameters"] == 333500417
    # embedding and head keep their published share (7.5 %)
    whole = 48 * layer + 2 * 49152 * d + d + (d + 1)
    assert whole == 2667974657
    assert round(1000 * 2 * v * d / count) == round(
        1000 * 2 * 49152 * d / whole) == 75


def test_flops_count_every_pass_and_are_a_hand_count(config):
    import flops_laguna
    import flops_ouro as flops
    sizes = config["sizes"]
    d, f, v, t = 2048, 5632, 6144, 8192
    layer = 2 * d * (3 * 16 * 128) + 2 * (16 * 128) * d + 3 * 2 * d * f
    attention = 2 * 2 * 128 * 16 * t * (t + 1) / 2
    forward = 4 * (t * (6 * layer + 2 * d * v + 2 * d) + 6 * attention)
    assert flops.train(sizes) == 3 * forward
    assert round(flops.train(sizes) / 1e12, 1) == 82.9
    # four times the one-pass count (the embedding is a gather: nothing),
    # where a weight counted once would read a quarter
    once = dict(sizes, total_ut_steps=1)
    assert flops.train(sizes) == 4 * flops.train(once)
    assert flops.train(once) == flops_laguna.train(once) + 3 * t * 2 * d
    parts = flops.forward_per_row(sizes)
    share = {k: 100 * x / sum(parts.values()) for k, x in parts.items()}
    assert round(share["full_attention"]) == 24
    assert round(share["projections"] + share["dense_ff"]) == 73
    assert round(share["head"]) == 3
    # the kernels: 24 applications, 16 heads, K and V at 16
    work, moved = flops.flash_train(sizes, 1)
    assert work == 24 * 16 * 7 * 2 * 128 * t * (t + 1) / 2
    assert round(work / 1e12, 1) == 23.1
    assert moved == 24 * (6 * 16 + 6 * 16) * t * 128 * 2


def test_readers_read_the_loop_and_the_kernels(config):
    ops = [["tpu_custom_call:flash_fwd", 1.0],
           ["tpu_custom_call:flash_bwd_dq", 1.25],
           ["tpu_custom_call:flash_bwd_dkv", 1.75], ["fusion", 6.0]]
    spans = [{"name": "jit_compile", "seconds": 60.0,
              "remat_children_kept": 21, "remat_children_recomputed": 33,
              "remat_bytes_estimated": 15e9, "remat_bytes_compiled": 14e9}]
    sources = {"config": config, "batch": 1, "steps_per_epoch": 4,
               "setup_compile_spans": spans,
               "window": {"rate_per_chip": 1.0},
               "trace": {"device_ops": ops, "busy_s": 10.0,
                         "module_runs": 3},
               "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(name, sources=sources):
        return load_module("layer_metrics", name).read(sources)

    assert read("loop_recompute_share") == pytest.approx(100 * 33 / 54)
    assert read("flash_time_share") == 40.0
    assert read("flash_bwd_dkv_time_share") == 17.5
    import flops_ouro as flops
    least = 12 * flops.flash_train(config["sizes"], 1)[0] / 197e12
    assert read("flash_roofline") == pytest.approx(100 * least / 4.0)
    assert read("model_flops_util") == pytest.approx(
        100 * flops.train(config["sizes"]) / 197e12)
    assert read("window_flash_time_share") is None   # no such kernels here
    # a judge that stepped back wrote the record again: the last counts
    again = spans + [dict(spans[0], remat_children_kept=20,
                          remat_children_recomputed=34)]
    assert read("loop_recompute_share", dict(
        sources, setup_compile_spans=again)) == pytest.approx(100 * 34 / 54)
    # a program without a plan, or from before the record carried the
    # fields (the parent's on a standing cell): nothing, and no raise
    for other in ({"config": {}, "trace": None},
                  dict(sources, setup_compile_spans=[]),
                  dict(sources, setup_compile_spans=[
                      {"name": "jit_compile", "seconds": 1.0}])):
        assert read("loop_recompute_share", other) is None


def test_the_comparison_looks_at_every_pass_and_at_p(config):
    import jax.numpy as jnp
    import numpy as np
    looped = load_module("runners", "train_looped")
    tol = dict(config["reference_tolerance"])
    assert tol["rtol"] == tol["atol"] == 0.002 and 0 < tol["p_atol"] < 0.01
    rng = np.random.default_rng(0)
    want = jnp.asarray(rng.normal(size=(4, 2, 8, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32)
    lam = 1 / (1 + np.exp(-np.asarray(gate, np.float64)))
    left = np.cumprod(1 - lam, axis=-1)
    p = np.concatenate([lam[..., :1], lam[..., 1:3] * left[..., :2],
                        left[..., 2:3]], axis=-1)
    out = {"logits": tuple(want), "exit_gate": gate}
    reasons, readings = looped.compare(out, want, jnp.asarray(p), tol)
    assert reasons == [] and readings["logits"] == [0.0] * 4
    assert readings["p"] < 1e-6
    # one logit of one pass off: that pass is named, and no other
    off = list(want)
    off[2] = off[2].at[1, 3, 5].add(0.05)
    reasons, readings = looped.compare(dict(out, logits=tuple(off)), want,
                                       jnp.asarray(p), tol)
    assert len(reasons) == 1 and reasons[0].startswith("pass 2's logits")
    assert "1 token(s)" in reasons[0]
    assert readings["logits"][2] == pytest.approx(0.05, rel=1e-3)
    # every logit right and the gate wrong: p decides
    reasons, _ = looped.compare(dict(out, exit_gate=gate + 0.01), want,
                                jnp.asarray(p), tol)
    assert len(reasons) == 1 and "exit distribution" in reasons[0]
    # a NaN misses, a pass too few is said
    reasons, _ = looped.compare(dict(out, logits=tuple(
        [want[0].at[0, 0, 0].set(jnp.nan), *want[1:]])), want,
        jnp.asarray(p), tol)
    assert len(reasons) == 1 and reasons[0].startswith("pass 0's logits")
    reasons, _ = looped.compare(dict(out, logits=tuple(want[:3])), want,
                                jnp.asarray(p), tol)
    assert len(reasons) == 1 and "against the reference's" in reasons[0]
