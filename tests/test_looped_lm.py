"""``zoo.decoder_lm(total_ut_steps > 1, sandwich_norm=True)``: a stack of
layers run several times over one set of parameters (``layers.Looped``),
one head and one exit gate over every pass (``layers.ExitHeads``) and the
loss over every pass (``ops.losses.exit_weighted_crossentropy``), against
the plain reference ``benchmark/reference/ouro.py`` and against plain
formulas; and the recompute plan over applications.  Small sizes, CPU,
the Pallas kernels in interpret mode."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import ouro  # noqa: E402

import distkeras_tpu as dk  # noqa: E402
from distkeras_tpu.models import remat, zoo  # noqa: E402
from distkeras_tpu.models.layers import (Dense, ExitHeads, Looped,  # noqa: E402
                                         RMSNorm, Sequential,
                                         layer_from_config)
from distkeras_tpu.models.model import Model  # noqa: E402
from distkeras_tpu.obs.registry import default_registry  # noqa: E402
from distkeras_tpu.ops.losses import (LOSSES, exit_log_probs,  # noqa: E402
                                      exit_weighted_crossentropy,
                                      sparse_categorical_crossentropy)

STEPS, T, VOCAB = 3, 256, 96
PLAIN = dict(
    vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
    layer_types=["full_attention"] * 2,
    num_attention_heads_per_layer=[4, 4], num_key_value_heads=4,
    head_dim=16, intermediate_size=48, mlp_layer_types=["dense"] * 2,
    seq_len=T, rms_norm_eps=1e-6,
    rope_parameters={"full_attention": {"rope_theta": 1000000}})
SIZES = dict(PLAIN, total_ut_steps=STEPS, early_exit_threshold=1.0,
             sandwich_norm=True)
#: embedding, 3 passes of (2 layers x 2 sublayers + the final norm), heads
APPLICATIONS = 1 + STEPS * 5 + 1


def tokens(seed, shape=(2, T)):
    return np.random.default_rng(seed).integers(0, VOCAB, shape).astype(
        np.int32)


@pytest.fixture(scope="module")
def built():
    model = zoo.decoder_lm(**SIZES, attention_impl="flash")
    variables = model.init(3)
    # an untrained gate's bias is 0: move it, so that a bias left out shows
    gate = variables["params"][2]["exit_gate"]
    gate["bias"] = gate["bias"] + 0.3
    return model, variables


def test_every_passes_logits_and_p_equal_the_reference(built):
    model, variables = built
    x = tokens(0)
    out = jax.jit(model.predict_fn())(variables, x)
    want_logits, want_p = ouro.forward(variables, x, SIZES)
    assert len(out["logits"]) == STEPS == len(want_logits)
    for got, want in zip(out["logits"], want_logits):
        assert got.shape == want.shape == (2, T, VOCAB)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the passes differ: a loop that ran once would give one answer
    assert float(jnp.max(jnp.abs(want_logits[0] - want_logits[-1]))) > 0.1
    p = jnp.exp(exit_log_probs(out["exit_gate"]))
    assert p.shape == want_p.shape == (2, T, STEPS)
    np.testing.assert_allclose(p, want_p, rtol=1e-5, atol=1e-6)
    # every pass has a share worth comparing
    assert float(jnp.min(jnp.mean(want_p, axis=(0, 1)))) > 0.05


def test_loss_and_every_gradient_leaf_equal_the_reference(built):
    model, variables = built
    x, y = tokens(1), tokens(2)

    def loss(params):
        out, _ = model.layer.apply(params, variables["state"], x,
                                   train=True, remat=True)
        return LOSSES["exit_weighted_crossentropy"](out, y)

    got_loss, got = jax.jit(jax.value_and_grad(loss))(variables["params"])
    want_loss, want = ouro.loss_and_grads(variables, x, y, SIZES)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 21
    for (path, a), b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 0, path
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_a_looped_weights_gradient_is_the_sum_over_its_passes(built):
    """Against the same function with a copy of the stack's parameters a
    pass, untied: the looped gradient is the copies' gradients summed."""
    model, variables = built
    looped = model.layer.layers[1]
    embed, loop, heads = variables["params"]
    x, y = tokens(3), tokens(4)

    def tied(loop):
        out, _ = model.layer.apply([embed, loop, heads], variables["state"],
                                   x, train=True)
        return exit_weighted_crossentropy(out, y)

    def untied(copies):
        h, _ = model.layer.layers[0].apply(embed, {}, x)
        passes = []
        for p in copies:
            (h,), _ = Looped(looped.body, 1, looped.closing).apply(
                p, variables["state"][1], h, train=True)
            passes.append(h)
        out, _ = model.layer.layers[2].apply(
            heads, variables["state"][2], tuple(passes), train=True)
        return exit_weighted_crossentropy(out, y)

    got = jax.jit(jax.grad(tied))(loop)
    apart = jax.jit(jax.grad(untied))([loop] * STEPS)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *apart)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(summed)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # and no pass's share is nothing
    for copy in apart:
        assert all(float(jnp.max(jnp.abs(g))) > 0
                   for g in jax.tree_util.tree_leaves(copy))


def test_one_step_builds_what_sequential_builds():
    """``Looped(body, 1, closing)`` is ``Sequential([*body, closing])``:
    the same parameters from the same key, the same output bitwise."""
    body = lambda: [Dense(24, "tanh"), RMSNorm(), Dense(16)]  # noqa: E731
    looped = Looped(body(), 1, RMSNorm())
    plain = Sequential([*body(), RMSNorm()])
    key = jax.random.PRNGKey(5)
    p, s, shape = looped.init(key, (8, 16))
    q, r, plain_shape = plain.init(key, (8, 16))
    assert shape == (plain_shape,)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(q)):
        np.testing.assert_array_equal(a, b)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 8, 16))
    (got,), _ = jax.jit(lambda p, x: looped.apply(p, s, x))(p, x)
    want, _ = jax.jit(lambda q, x: plain.apply(q, r, x))(q, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # more steps: each pass is the plain stack on the one before's output
    twice = Looped(looped.body, 2, looped.closing)
    first, second = jax.jit(lambda p, x: twice.apply(p, s, x))(p, x)[0]
    np.testing.assert_array_equal(np.asarray(first), np.asarray(got))
    np.testing.assert_array_equal(
        np.asarray(second),
        np.asarray(jax.jit(lambda q, x: plain.apply(q, r, x))(q, want)[0]))
    with pytest.raises(ValueError, match="cannot be run again"):
        Looped([Dense(24)], 2, RMSNorm()).init(key, (8, 16))


def test_one_pass_without_the_extra_norms_is_todays_decoder_lm():
    """``total_ut_steps = 1`` and no sandwich norms: the model, the
    parameters and the lowered step are ``decoder_lm``'s as it was."""
    grown = zoo.decoder_lm(**PLAIN, total_ut_steps=1, sandwich_norm=False,
                           early_exit_threshold=1.0)
    plain = zoo.decoder_lm(**PLAIN)
    assert grown.config() == plain.config()
    assert not any(isinstance(l, (Looped, ExitHeads))
                   for l in grown.iter_layers())

    def lowered(model):
        variables = model.init(0)
        text = jax.jit(jax.grad(lambda p: sparse_categorical_crossentropy(
            model.layer.apply(p, variables["state"], tokens(0), train=True,
                              remat=True)[0], tokens(1)))).lower(
            variables["params"]).as_text()
        return re.sub(r"@([A-Za-z_]\w*?)_\d+\b", r"@\1", text)

    assert lowered(grown) == lowered(plain)


def test_configs_round_trip(built):
    model, variables = built
    again = Model.from_config(model.config())
    assert again.config() == model.config()
    looped = layer_from_config(model.layer.layers[1].config())
    assert isinstance(looped, Looped) and looped.steps == STEPS
    assert len(looped.body) == 4 and isinstance(looped.closing, RMSNorm)
    x = tokens(0)
    got = jax.jit(again.predict_fn())(variables, x)
    want = jax.jit(model.predict_fn())(variables, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every layer is found, the loop's body and closing norm among them
    kinds = [type(l).__name__ for l in model.iter_layers()]
    assert kinds.count("MultiHeadAttention") == 2
    assert kinds.count("RMSNorm") == 2 * 4 + 1


def test_the_exit_chances_add_up_to_one():
    gate = jax.random.normal(jax.random.PRNGKey(0), (5, 7, 4)) * 3.0
    log_p = exit_log_probs(gate)
    assert log_p.dtype == jnp.float32 and log_p.shape == gate.shape
    np.testing.assert_allclose(jnp.sum(jnp.exp(log_p), -1), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(gate)
    np.testing.assert_allclose(jnp.exp(log_p[..., 0]), lam[..., 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        jnp.exp(log_p[..., 2]),
        lam[..., 2] * (1 - lam[..., 0]) * (1 - lam[..., 1]), rtol=1e-4)
    np.testing.assert_allclose(
        jnp.exp(log_p[..., 3]), jnp.prod(1 - lam[..., :3], -1), rtol=1e-4)
    # gates far out: finite, where the log of a product would not be
    far = exit_log_probs(jnp.array([[-200.0, 300.0, 0.0, 5.0]]))
    assert bool(jnp.all(jnp.isfinite(far)))
    # one pass: it answers
    np.testing.assert_array_equal(exit_log_probs(jnp.ones((3, 1))), 0.0)


def test_the_loss_at_the_last_pass_is_plain_crossentropy():
    """beta = 0 and p forced to the last pass (every gate far below
    zero): the loss is the last pass's mean cross-entropy."""
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    logits = tuple(jax.random.normal(k, (2, 16, VOCAB)) for k in key[:3])
    y = tokens(5, (2, 16))
    out = {"logits": logits, "exit_gate": jnp.full((2, 16, 3), -60.0)}
    np.testing.assert_allclose(
        exit_weighted_crossentropy(out, y, beta=0.0),
        sparse_categorical_crossentropy(logits[-1], y), rtol=1e-6)
    # p forced to the first pass: the first pass's
    out["exit_gate"] = jnp.full((2, 16, 3), 60.0)
    np.testing.assert_allclose(
        exit_weighted_crossentropy(out, y, beta=0.0),
        sparse_categorical_crossentropy(logits[0], y), rtol=1e-6)
    # an even gate, beta = 0.1 (the default): the weighted mean less the
    # entropy's tenth, by hand
    out["exit_gate"] = jnp.zeros((2, 16, 3))
    p = np.array([0.5, 0.25, 0.25])
    nll = [sparse_categorical_crossentropy(l, y) for l in logits]
    np.testing.assert_allclose(
        exit_weighted_crossentropy(out, y),
        sum(w * n for w, n in zip(p, nll)) + 0.1 * float(np.sum(
            p * np.log(p))), rtol=1e-6)
    # bf16 logits are read in float32
    low = dict(out, logits=tuple(l.astype(jnp.bfloat16) for l in logits))
    assert exit_weighted_crossentropy(low, y).dtype == jnp.float32


def test_decode_logits_takes_the_first_pass_that_reaches_the_threshold(
        built):
    model, variables = built
    out = jax.jit(model.predict_fn())(variables, tokens(0))
    # the published threshold, 1.0: the last pass, for every token
    np.testing.assert_array_equal(np.asarray(model.decode_logits(out)),
                                  np.asarray(out["logits"][-1]))
    heads = ExitHeads(VOCAB, threshold=0.6)
    logits = tuple(jnp.full((1, 4, VOCAB), float(t)) for t in range(3))
    big = 20.0  # sigmoid(20) = 1 - 2e-9
    gate = jnp.array([[[big, 0.0, 0.0],        # all of it at pass 0
                       [0.0, big, 0.0],        # 0.5, then the rest: pass 1
                       [-big, -big, 0.0],      # nothing before the last
                       [0.5, 0.5, 0.0]]])      # 0.62 at pass 0
    got = heads.decode_logits({"logits": logits, "exit_gate": gate})
    np.testing.assert_array_equal(np.asarray(got[0, :, 0]), [0, 1, 2, 0])
    # a plain model's output is its own answer
    plain = zoo.decoder_lm(**PLAIN)
    assert plain.decode_logits(logits[0]) is logits[0]


def test_the_gate_stays_float32_in_a_bf16_step(built):
    import optax

    from distkeras_tpu.parallel.sync import FLOAT32_KEYS, make_local_step
    model, variables = built
    assert "exit_gate" in FLOAT32_KEYS
    seen = {}

    def loss(out, y):
        seen["gate"] = out["exit_gate"].dtype
        seen["logits"] = {l.dtype for l in out["logits"]}
        return exit_weighted_crossentropy(out, y)

    heads = model.layer.layers[2]
    apply = heads.apply

    def spy(params, *args, **kwargs):
        seen["params"] = jax.tree_util.tree_map(lambda a: a.dtype, params)
        return apply(params, *args, **kwargs)

    heads.apply = spy
    try:
        optimizer = optax.adam(1e-3)
        step = make_local_step(model, loss, optimizer, jnp.bfloat16)
        carry = (variables, optimizer.init(variables["params"]),
                 jax.random.PRNGKey(0))
        jax.eval_shape(step, carry, (tokens(0), tokens(1)))
    finally:
        del heads.apply
    assert seen["gate"] == jnp.float32
    assert seen["logits"] == {jnp.dtype(jnp.bfloat16)}
    assert seen["params"] == {
        "head": {"kernel": jnp.bfloat16},
        "exit_gate": {"kernel": jnp.float32, "bias": jnp.float32}}


# -- the recompute plan over applications -------------------------------------

def loss_of(built, plan):
    model, variables = built
    x, y = tokens(1), tokens(2)

    def loss(params):
        out, _ = model.layer.apply(params, variables["state"], x,
                                   train=True, remat=plan)
        return exit_weighted_crossentropy(out, y)

    return loss


@pytest.fixture(scope="module")
def plain_step(built):
    return jax.jit(jax.value_and_grad(loss_of(built, False)))(
        built[1]["params"])


@pytest.mark.parametrize("first_kept", range(APPLICATIONS))
def test_loss_and_gradients_equal_the_plain_steps_bitwise(built, plain_step,
                                                          first_kept):
    """Whatever the plan keeps: every ``first_kept`` from 0 (all kept) to
    the last application (the frugal plan), reached as the judge reaches
    it, one step back at a time from a budget that fits everything."""
    plan = remat.Plan(budget=1e12)
    plan.stepped_back = first_kept
    got_loss, got = jax.jit(jax.value_and_grad(loss_of(built, plan)))(
        built[1]["params"])
    assert (plan.first_kept, plan.children) == (first_kept, APPLICATIONS)
    assert float(got_loss) == float(plain_step[0])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(plain_step[1])):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))


def test_the_plan_sizes_distinct_children_once_and_counts_applications(
        built, monkeypatch):
    traced = []
    trace_child = remat._trace_child
    monkeypatch.setattr(remat, "_trace_child", lambda call, *a: (
        traced.append(call.func.__self__), trace_child(call, *a))[1])
    model, variables = built
    registry = default_registry()
    counters = {name: registry.counter(name) for name in (
        "remat.children_kept", "remat.children_recomputed", "loop.passes",
        "loop.applications")}
    before = {name: c.value for name, c in counters.items()}
    plan = remat.Plan(budget=0)
    jax.eval_shape(lambda p: model.layer.apply(
        p, variables["state"], tokens(1), train=True, remat=plan)[0],
        variables["params"])
    # embedding, attention block, feed-forward block, final norm, heads
    assert len(traced) == 5 and plan.children == APPLICATIONS
    assert {name: c.value - before[name] for name, c in counters.items()} \
        == {"remat.children_kept": 1,
            "remat.children_recomputed": APPLICATIONS - 1,
            "loop.passes": STEPS, "loop.applications": STEPS * 4}
    by = [(s["saved"], s["whole"]) for s in plan.sizes]
    one_pass = by[1:6]
    assert by[1:-1] == one_pass * STEPS           # a pass is a pass
    carried = 2 * T * 32 * 4                      # an application's input
    assert one_pass[0][0] > carried == one_pass[1][0] == one_pass[4][0]
    assert by[-1][0] == STEPS * carried           # the heads read every pass
    # a looped parameter's gradient is there once, from its last
    # application on: the earlier passes add to it
    grads = [s["grads"] for s in plan.sizes]
    loop = variables["params"][1]
    own = [4 * sum(a.size for a in jax.tree_util.tree_leaves(p))
           for p in [*loop["body"], loop["closing"]]]
    assert grads[1:-1] == [0] * 5 * (STEPS - 1) + own
    assert grads[0] == 4 * VOCAB * 32 and grads[-1] == 4 * (32 * VOCAB + 33)


def test_the_judge_takes_back_one_application_at_a_time(built):
    model, variables = built
    plan = remat.Plan(budget=1e12)
    plan.limit = 100

    def decided():
        jax.eval_shape(lambda p: model.layer.apply(
            p, variables["state"], tokens(1), train=True, remat=plan)[0],
            variables["params"])
        return plan.first_kept

    assert decided() == 0
    for back in range(1, APPLICATIONS):
        assert plan.judge(96) is True             # over REFUSE of the limit
        assert plan.first_kept == back == decided()
        assert plan.record()["remat_children_recomputed"] == back
        assert plan.record()["remat_children_kept"] == APPLICATIONS - back
    assert plan.judge(96) is False                # the last is never wrapped
    assert plan.first_kept == APPLICATIONS - 1


def test_each_pass_has_its_scope_in_the_lowered_step(built):
    import optax

    from distkeras_tpu.parallel.sync import make_local_step
    model, variables = built
    optimizer = optax.adam(1e-3)
    step = make_local_step(model, exit_weighted_crossentropy, optimizer,
                           jnp.bfloat16, remat=True)
    carry = (variables, optimizer.init(variables["params"]),
             jax.random.PRNGKey(0))
    text = jax.jit(step).lower(carry, (tokens(0), tokens(1))).as_text(
        debug_info=True)
    for t in range(STEPS):
        for scope in (f"looped)/pass_{t}/residual/",
                      f"exitheads)/pass_{t}/exit_gate/",
                      f"loss)/pass_{t}/"):
            assert scope in text, scope
    assert f"pass_{STEPS}" not in text


def test_a_trainer_trains_it_and_brings_the_exit_shares_back():
    from distkeras_tpu.data.datasets import load_lm_corpus
    train = load_lm_corpus(n_train=4, seq_len=64, vocab_size=64, seed=1)[0]
    sizes = dict(SIZES, seq_len=64, vocab_size=64, total_ut_steps=2)
    registry = default_registry()
    counters = [registry.counter(f"remat.children_{w}")
                for w in ("kept", "recomputed")]
    before = [c.value for c in counters]
    trainer = dk.SingleTrainer(
        zoo.decoder_lm(**sizes), "adam", "exit_weighted_crossentropy",
        num_epoch=3, batch_size=2, learning_rate=1e-3,
        compute_dtype="bfloat16", remat=True)
    model = trainer.train(train)
    losses = [r["mean_loss"] for r in trainer.metrics.records
              if r["event"] == "epoch"]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    # no limit on the CPU: the frugal plan, the last of 12 applications kept
    assert [c.value - b for c, b in zip(counters, before)] == [1, 11]
    record = [r for r in trainer.metrics.records
              if r["event"] == "span" and r["name"] == "jit_compile"]
    assert len(record) == 1
    assert (record[0]["remat_children_kept"],
            record[0]["remat_children_recomputed"]) == (1, 11)
    shares = [registry.get(f"loop.exit_share.{t}").value for t in range(2)]
    assert all(0.0 < s < 1.0 for s in shares)
    np.testing.assert_allclose(sum(shares), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        shares, np.asarray(model.variables["state"][2]["exit_share"]))
    assert registry.get("loop.exit_share.2") is None
