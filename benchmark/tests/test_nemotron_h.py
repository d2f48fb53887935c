"""The ``nemotron3-nano-ep16`` configuration's files agree with each other
and with the published widths, its FLOPs are the issue's arithmetic, its
readers read a trace's rows by the kernels' names, and its reference
settles a tie of the sigmoid router's choice and nothing else.  (The cell
itself is rehearsed, like every cell, by tier-1's
``tests/test_benchmark_rehearsal.py``.)"""

import json
import os

import pytest

from conftest import BENCH
from run import load_module, merged


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "nemotron3-nano-ep16.json")) as f:
        return json.load(f)


def test_the_builders_sizes_are_the_published_keys_cut_as_reduced_says(
        config):
    sizes = config["sizes"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 52 \
        == len(config["hybrid_override_pattern"])
    assert config["published"]["n_routed_experts"] == 128
    assert config["published"]["vocab_size"] == 131072 \
        == 8 * config["vocab_size"]
    assert config["deployment"]["chips_sharing_a_layer"] == 16
    # the router keeps its published width; the file's count is held here
    assert sizes["n_routed_experts"] == 128 and sizes["experts_held"] \
        == config["n_routed_experts"] == 128 // 16
    # no width differs: 2,688; 64 x 64; 128; 8; 4; 128; 32 / 2 x 128; 1,856;
    # 3,712; top 6; 2.5
    published = dict(
        hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        intermediate_size=1856, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, num_experts_per_tok=6,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        layer_norm_epsilon=1e-05)
    for key, value in published.items():
        assert sizes[key] == config[key] == value, key
    assert sizes["hybrid_override_pattern"] \
        == config["hybrid_override_pattern"]
    kept = sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]
    assert kept == "MEMEM*EME" and config["num_hidden_layers"] == 9
    whole = config["hybrid_override_pattern"]
    assert (whole.count("M"), whole.count("E"), whole.count("*")) \
        == (23, 23, 6)
    for key in ("positions", "e_score_correction_bias", "initialisation",
                "learning_rate", "seq_len"):
        assert key in config["assumed"], key


def test_the_parameter_count_recounted_from_the_sizes(config):
    s = config["sizes"]
    d, inner = s["hidden_size"], s["mamba_num_heads"] * s["mamba_head_dim"]
    bc = s["n_groups"] * s["ssm_state_size"]
    mamba = d + d * (2 * inner + 2 * bc + s["mamba_num_heads"]) \
        + (s["conv_kernel"] + 1) * (inner + 2 * bc) \
        + 3 * s["mamba_num_heads"] + inner + inner * d
    expert = 2 * d * s["moe_intermediate_size"]
    sparse = d + d * s["n_routed_experts"] + s["n_routed_experts"] \
        + 2 * d * s["moe_shared_expert_intermediate_size"] \
        + s["experts_held"] * expert
    heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                     s["head_dim"])
    attention = d + d * (heads + 2 * kv) * dh + heads * dh * d
    assert (mamba, expert, attention) == (38744896, 9977856, 23399040)
    assert sparse == 100125440
    total = 4 * mamba + 4 * sparse + attention + 2 * s["vocab_size"] * d + d
    assert total == config["parameters"] == 666963456


def test_flops_are_the_issues_arithmetic(config):
    import flops_nemotron_h as flops
    sizes = config["sizes"]
    parts = flops.forward_per_row(sizes)
    per_token = sum(parts.values()) / sizes["seq_len"]
    assert round(per_token / 1e9, 2) == 0.72
    assert round(flops.train(sizes) / 1e12, 1) == 17.6
    share = {k: 100 * v / sum(parts.values()) for k, v in parts.items()}
    assert round(share["mamba_projections"] + share["mamba_scan"]) == 45
    assert round(share["router"] + share["shared_expert"]
                 + share["routed_experts"]) == 27
    assert round(share["attention_projections"] + share["attention"]) == 16
    assert round(share["head"]) == 12 and share["dense_mlp"] == 0
    # a mixer is 80.8 MFLOP a token, of which the scan itself 3.4
    mixer = (parts["mamba_projections"] + parts["mamba_scan"]) / 4 / 8192
    assert round(mixer / 1e6, 1) == 80.8
    assert round(flops.scan_flops_per_token(sizes) / 1e6, 1) == 3.4
    # the experts' rows are the 384 x 8 expected here, 6 matmuls each
    expected, _ = flops.experts_train(sizes, 1)
    assert expected == 4 * 384 * 8 * 6 * 2 * 2688 * 1856
    scan, moved = flops.ssd_train(sizes, 1)
    assert scan == 3 * parts["mamba_scan"]
    assert moved / 819e9 > scan / 197e12  # the bytes bound it
    for work in (flops.flash_train, flops.experts_train, flops.ssd_train):
        assert all(v > 0 for v in work(sizes, 1))


def test_readers_read_the_kernels_rows(config):
    ops = [["tpu_custom_call:ssd_chunk_fwd", 1.0],
           ["tpu_custom_call:ssd_chunk_bwd", 2.0],
           ["tpu_custom_call:flash_fwd", 4.0],
           ["tpu_custom_call:moe_gmm", 0.5],
           ["tpu_custom_call:moe_tgmm", 0.25], ["fusion", 2.25]]
    sources = {"config": config, "batch": 1, "steps_per_epoch": 16,
               "trace": {"device_ops": ops, "busy_s": 10.0,
                         "module_runs": 3},
               "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(name):
        return load_module("layer_metrics", name).read(sources)

    assert read("ssd_time_share") == 30.0
    assert read("experts_time_share") == 7.5
    assert read("flash_time_share") == read("flash_fwd_time_share") == 40.0
    import flops_nemotron_h as flops
    least = 48 * flops.ssd_train(config["sizes"], 1)[1] / 819e9
    assert read("ssd_roofline") == pytest.approx(100 * least / 3.0)
    assert 0 < read("experts_roofline") < 100
    assert 0 < read("flash_roofline") < 100
    assert read("window_flash_time_share") is None  # no such kernels here
    # no trace, or a program from before the kernels: nothing is read and
    # nothing raises
    for other in (dict(sources, trace=None), dict(sources, trace=dict(
            sources["trace"], device_ops=[["fusion", 2.25]]))):
        for name in ("ssd_time_share", "ssd_roofline"):
            assert load_module("layer_metrics", name).read(other) is None


def test_the_reference_settles_a_tie_and_nothing_else(config):
    """Two held experts with one router column and one ``b``: wherever
    they are a token's 3rd and 4th of ``s + b`` the gap is 0.0, and
    ``swap`` takes the other there; a routing float32 can decide is never
    swapped at a small ``tie``."""
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.models import zoo
    from reference import nemotron_h
    sizes = dict(merged(config, config["rehearse"])["sizes"], seq_len=32)
    model = zoo.hybrid_lm(**sizes)
    variables = model.init(3)
    moe = variables["params"][2]["inner"][1]
    kernel = np.array(moe["router"]["kernel"])
    kernel[:, 1] = kernel[:, 0]
    moe["router"]["kernel"] = jnp.asarray(kernel)
    x = np.random.RandomState(0).randint(0, 64, (2, 32))
    run = nemotron_h.passes(sizes)
    plain, gaps, bears = run(variables, x)
    gaps, bears = np.asarray(gaps), np.asarray(bears)
    assert gaps.shape == bears.shape == (1, 2, 32)
    ties = np.argwhere((gaps == 0.0) & bears)
    assert len(ties) >= 1 and np.sort(gaps.ravel())[len(ties)] > 1e-4

    def moved(at, tie):
        swap = np.zeros(gaps.shape, bool)
        swap[tuple(np.asarray(at).T)] = True
        other = run(variables, x, swap=swap, tie=tie)[0]
        return np.asarray(jnp.any(jnp.abs(other - plain) > 1e-6, axis=-1))

    # the tie taken the other way moves its own token (attention after it
    # may carry that on to later ones), and nothing before it
    layer, row, token = ties[0]
    changed = moved(ties[:1], 1e-6)
    assert changed[row, token] and not changed[row, :token].any() \
        and not changed[1 - row].any()
    assert not moved(ties[:1], 0.0).any()        # no gap counts as a tie
    decided = np.argwhere((gaps > 1e-3) & bears)[:1]
    assert not moved(decided, 1e-6).any()        # float32 can tell
    assert moved(decided, 1.0).any()             # a wrong routing would
