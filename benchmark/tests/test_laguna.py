"""The ``laguna-xs2-ep8`` configuration's files agree with each other, its
FLOPs are the issue's arithmetic, its readers read a trace's rows by the
kernels' names, and its cell rehearses on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from run import load_module

CELL = "laguna-xs2-train-8k"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "laguna-xs2-ep8.json")) as f:
        return json.load(f)


def test_the_builders_sizes_are_the_published_keys_cut_as_reduced_says(
        config):
    sizes, layers = config["sizes"], config["num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    # the router keeps its published width; the file's count is held here
    assert sizes["num_experts"] == config["published"]["num_experts"] == 256
    assert sizes["experts_held"] == config["num_experts"] == 256 // 8
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(config[key]) == 40  # copied whole
        assert sizes[key] == config[key][:layers]
    assert sizes["layer_types"].count("sliding_attention") == 3  # a period
    for key in ("hidden_size", "intermediate_size", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "sliding_window",
                "rms_norm_eps", "moe_routed_scaling_factor", "vocab_size",
                "num_hidden_layers"):
        assert sizes[key] == config[key], key  # no width differs
    for kind in ("full_attention", "sliding_attention"):
        assert sizes["rope_parameters"][kind] \
            == config["rope_parameters"][kind]
    assert config["deployment"]["chips_sharing_a_layer"] == 8


def test_flops_are_the_issues_arithmetic(config):
    import flops_laguna
    sizes = config["sizes"]
    parts = flops_laguna.forward_per_row(sizes)
    per_token = sum(parts.values()) / sizes["seq_len"]
    assert round(per_token / 1e6, 1) == 801.8
    assert round(flops_laguna.train(sizes) / 1e12, 1) == 19.7
    share = {k: round(100 * v / sum(parts.values())) for k, v in
             parts.items()}
    assert share["full_attention"] == 25 and share["sliding_attention"] == 6
    assert share["router"] + share["shared_expert"] \
        + share["routed_experts"] == 7 + 0  # rounds 0.5 + 3.1 + 3.1
    # a window layer needs 1,030 of the 4,097 keys a query a full one does
    full, window = (flops_laguna.attended_pairs(sizes, k) for k in
                    ("full_attention", "sliding_attention"))
    assert window == 512 * 513 / 2 + (8192 - 512) * 512 and full > 8 * window
    for work in (flops_laguna.full_flash_train,
                 flops_laguna.window_flash_train,
                 flops_laguna.experts_train):
        flops, bytes_ = work(sizes, 1)
        assert flops > 0 and bytes_ > 0


def test_readers_read_the_kernels_rows(config):
    ops = [["tpu_custom_call:window_attn_fwd", 1.0],
           ["tpu_custom_call:window_attn_bwd_dkv", 2.0],
           ["tpu_custom_call:flash_fwd", 4.0],
           ["tpu_custom_call:moe_gmm", 0.5],
           ["tpu_custom_call:moe_tgmm", 0.25], ["fusion", 2.25]]
    sources = {"config": config, "batch": 1, "steps_per_epoch": 16,
               "trace": {"device_ops": ops, "busy_s": 10.0,
                         "module_runs": 3},
               "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(name):
        return load_module("layer_metrics", name).read(sources)

    assert read("window_flash_time_share") == 30.0
    assert read("experts_time_share") == 7.5
    assert read("flash_time_share") == 40.0  # the full layers alone
    assert read("flash_fwd_time_share") == 40.0
    import flops_laguna
    least = 48 * flops_laguna.window_flash_train(config["sizes"], 1)[0] \
        / 197e12
    assert read("window_flash_roofline") == pytest.approx(100 * least / 3.0)
    assert 0 < read("experts_roofline") < 100
    # no trace, no counters: nothing is read and nothing raises
    empty = dict(sources, trace=None)
    for name in ("window_flash_time_share", "window_flash_roofline",
                 "experts_time_share", "experts_roofline"):
        assert load_module("layer_metrics", name).read(empty) is None


def test_row_fill_reads_the_registrys_counters():
    from distkeras_tpu.obs.registry import default_registry
    reader = load_module("layer_metrics", "moe_row_fill")
    assert reader.read({"config": {}, "trace": None}) is None
    registry = default_registry()
    if registry.get("moe.rows_run") is None:
        assert reader.read({"window": {}}) is None  # as on the parent
    registry.counter("moe.rows_needed").inc(300)
    registry.counter("moe.rows_run").inc(400)
    value = reader.read({"window": {}})
    assert 0 < value <= 100


def test_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147484000", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert {"train_retraces", "moe_row_fill"} <= set(
        line["rehearsal_values"])
    assert line["rehearsal_values"]["train_retraces"]["value"] == 0
    # no device trace off the chip: nothing stands under those names
    assert "window_flash_roofline" not in line["rehearsal_values"]


class _Answers:
    """A model whose ``predict_fn`` answers with given logits."""

    def __init__(self, variables, logits):
        self.variables, self.logits = variables, logits

    def predict_fn(self):
        return lambda variables, x: self.logits


def test_the_comparison_settles_ties_and_nothing_else(config, capsys):
    """``runners/train_routed.py``: a token whose last chosen and first
    unchosen probabilities float32 cannot tell apart may be routed either
    way; every other difference is held to ``rtol`` / ``atol`` as in
    ``runners/train.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.models import zoo
    from reference import laguna
    from run import merged
    routed = load_module("runners", "train_routed")
    small = merged(config, config["rehearse"])
    sizes = dict(small["sizes"], seq_len=32)
    small = dict(small, sizes=sizes, reference_tolerance=dict(
        small["reference_tolerance"], rtol=1e-4, atol=1e-4))
    model = zoo.decoder_lm(**sizes)
    variables = model.init(3)
    # two held experts with one router column: wherever they are a token's
    # 4th and 5th, the probabilities are EQUAL
    moe = variables["params"][4]["inner"][1]
    kernel = np.array(moe["router"]["kernel"])
    kernel[:, 1] = kernel[:, 0]
    moe["router"]["kernel"] = jnp.asarray(kernel)
    x = np.random.RandomState(0).randint(0, 64, (2, 32))
    ds = {"features": x}
    _, gaps, bears = laguna.forward_choices(variables, x, sizes)
    gaps, bears = np.asarray(gaps), np.asarray(bears)
    ties = np.argwhere((gaps == 0.0) & bears)
    assert 2 <= len(ties) <= small["reference_tolerance"]["max_ties"]
    assert (np.sort(gaps.ravel())[len(ties)] > 1e-4)  # no other is near

    def verdict(logits, **tolerance):
        said = []
        routed.check_reference(
            dict(small, reference_tolerance=dict(
                small["reference_tolerance"], **tolerance)),
            _Answers(variables, logits), ds, said)
        return said

    def answers(at, tie):
        swap = np.zeros(gaps.shape, bool)
        swap[tuple(np.asarray(at).T)] = True
        return laguna.forward_choices(variables, x, sizes, swap=swap,
                                      tie=tie)[0]

    # the program itself, and the reference's own answer: no tie is tried
    model.variables = variables
    assert verdict(jax.jit(model.predict_fn())(variables, x)) == []
    assert "settled" not in capsys.readouterr().err
    # ties settled the other way, one and two at once: correct, and said
    for taken in (ties[1:2], ties[[0, -1]]):
        assert verdict(answers(taken, 1e-6)) == []
        assert "settled the other way: correct" in capsys.readouterr().err
    # the same answer is not correct where no gap counts as a tie ...
    assert "logits differ" in verdict(answers(ties[:1], 1e-6), tie=0.0)[0]
    # ... nor where the ties of the tokens that differ are more than are
    # tried
    assert "2 ties are more than the 1 that are tried" in verdict(
        answers(ties[[0, -1]], 1e-6), max_ties=1)[0]
    # a choice taken the other way where float32 can tell (a wrong
    # routing), and a scale on every logit, agree with no settling
    decided = np.argwhere((gaps > 1e-3) & bears)[:1]
    wrong = answers(decided, 1.0)
    assert "under every way to settle" in verdict(wrong)[0]
    assert "under every way to settle" in verdict(
        1.01 * answers(ties[:1], 1e-6) + 1e-3)[0]
