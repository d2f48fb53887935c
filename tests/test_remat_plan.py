"""The recompute plan (``models.remat``): what a step's backward keeps is
decided from shapes and the device's limit, and whatever is decided the
values are the same.  Small sizes, CPU, Pallas in interpret mode."""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import remat, zoo
from distkeras_tpu.obs.registry import default_registry
from distkeras_tpu.ops.losses import sparse_categorical_crossentropy

#: both attention kinds, a dense and two sparse feed-forwards: 9 children
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[4, 8, 4], num_key_value_heads=2,
    head_dim=16, intermediate_size=64,
    mlp_layer_types=["dense", "sparse", "sparse"], seq_len=256,
    sliding_window=64, gating=True, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=16)


def tokens(seed):
    return np.random.default_rng(seed).integers(0, 96, (2, 256)).astype(
        np.int32)


@pytest.fixture(scope="module")
def built():
    model = zoo.decoder_lm(**SIZES, attention_impl="flash")
    return model, model.init(3)


def bare(seq, params, state, x):
    """The plan this one replaced: a bare ``jax.checkpoint`` around every
    child, the last included."""
    for i, layer in enumerate(seq.layers):
        x, _ = jax.checkpoint(functools.partial(layer.apply, train=True))(
            params[i], state[i], x, rng=None)
    return x


def loss_of(built, plan):
    """``plan``: False, True, a ``remat.Plan``, or "bare"."""
    model, variables = built
    x, y = tokens(1), tokens(2)

    def loss(params):
        if plan == "bare":
            out = bare(model.layer, params, variables["state"], x)
        else:
            out, _ = model.layer.apply(params, variables["state"], x,
                                       train=True, remat=plan)
        return sparse_categorical_crossentropy(out, y)

    return loss


PLANS = {"every_child_recomputed": lambda: "bare",
         "frugal": lambda: True,
         "mixed": lambda: remat.Plan(budget=2e6),
         "every_child_kept": lambda: remat.Plan(budget=1e12)}


@pytest.fixture(scope="module")
def plain(built):
    return jax.jit(jax.value_and_grad(loss_of(built, False)))(
        built[1]["params"])


@pytest.mark.parametrize("name", list(PLANS))
def test_loss_and_gradients_equal_the_plain_steps_bitwise(built, plain, name):
    plan = PLANS[name]()
    got_loss, got = jax.jit(jax.value_and_grad(loss_of(built, plan)))(
        built[1]["params"])
    assert float(got_loss) == float(plain[0])
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(plain[1])):
        assert float(jnp.max(jnp.abs(b))) > 0, path
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    if isinstance(plan, remat.Plan):
        first = {"mixed": 4, "every_child_kept": 0}[name]
        assert (plan.first_kept, plan.children) == (first, 9)
        assert plan.bytes_estimated > 0


def kernels(jaxpr, counts=None):
    """How often each Pallas kernel appears in a jaxpr, inner ones too."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name") or eqn.params["name_and_src_info"]
            counts[str(name).split(" ")[0]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(sub, "jaxpr"):
                    kernels(sub.jaxpr, counts)
                elif hasattr(sub, "eqns"):
                    kernels(sub, counts)
    return counts


def test_the_forward_kernels_run_once_under_the_frugal_plan(built):
    """The regression PR 30's gain rests on: the kernels' outputs are
    kept, so a recomputed child needs no forward kernel."""
    def count(plan):
        return kernels(jax.make_jaxpr(jax.grad(loss_of(built, plan)))(
            built[1]["params"]).jaxpr)

    plain, frugal, bare_ = count(False), count(True), count("bare")
    for name, n in (("flash_fwd", 2), ("window_attn_fwd", 1)):
        assert plain[name] == frugal[name] == n
        assert bare_[name] == 2 * n
    # a routed child's backward rebuilds its rounds' rows from the child's
    # input, kept or not: recomputing it runs no grouped matmul more (its
    # recomputed forward is its residuals, which are its arguments)
    assert frugal["moe_gmm"] == plain["moe_gmm"] == 2 * (2 + 4)
    assert bare_["moe_gmm"] == plain["moe_gmm"]
    assert all(frugal[k] == plain[k] for k in plain if "bwd" in k)


def sized(whole, saved=None, grads=None):
    saved = [0] * len(whole) if saved is None else saved
    grads = [0] * len(whole) if grads is None else grads
    return [{"whole": w, "saved": s, "grads": g}
            for w, s, g in zip(whole, saved, grads)]


@pytest.mark.parametrize("sizes, budget, first", [
    (sized([5, 5, 5, 5]), None, 3),       # limit unknown: the last alone
    (sized([5, 5, 5, 5]), 5, 3),
    (sized([5, 5, 5, 5]), 4, 3),          # the frugal plan is over already
    (sized([5, 5, 5, 5]), -1, 3),
    (sized([5, 5, 5, 5]), 10, 2),
    (sized([5, 5, 5, 5]), 19, 1),
    (sized([5, 5, 5, 5]), 20, 0),
    (sized([5, 5, 5, 5]), 1e12, 0),
    (sized([1, 9, 1, 7]), 9, 2),          # from the end, not the cheapest
    (sized([1, 9, 1, 7]), 16, 2),         # stops where the next does not fit
    (sized([1, 9, 1, 7]), 17, 1),
    (sized([1, 9, 1, 7]), 18, 0),
    # what a checkpoint holds counts against the children before it
    (sized([5, 5, 5, 5], saved=[2, 2, 2, 2]), 11, 3),
    (sized([5, 5, 5, 5], saved=[2, 2, 2, 2]), 14, 2),
    # late residuals are gone before the early gradients are there ...
    (sized([5, 5, 5, 5], grads=[1, 1, 1, 1]), 16, 1),
    # ... but gradients larger than what they replace raise the peak
    (sized([5, 5, 5, 5], grads=[9, 9, 9, 9]), 40, 3),
    (sized([5, 5, 5, 5], grads=[9, 9, 9, 9]), 41, 0),
    (sized([7]), None, 0), (sized([7]), 0, 0),   # an only child is the last
    ([], 3, 0),
])
def test_the_planner_keeps_from_the_end_within_its_budget(sizes, budget,
                                                          first):
    got = remat.keep_from_end(sizes, budget)
    assert got == remat.keep_from_end(list(sizes), budget) == first
    assert got <= max(len(sizes) - 1, 0)          # never wraps the last
    frugal = remat.peak(sizes, max(len(sizes) - 1, 0))
    if budget is not None and budget >= frugal:
        assert remat.peak(sizes, got) <= budget   # never passes the budget


def test_children_of_equal_shape_are_estimated_once(built, monkeypatch):
    traced = []
    trace_child = remat._trace_child
    monkeypatch.setattr(remat, "_trace_child", lambda call, *a: (
        traced.append(call.func.__self__), trace_child(call, *a))[1])
    model, variables = built
    plan = remat.Plan(budget=0)
    jax.eval_shape(lambda p: model.layer.apply(
        p, variables["state"], tokens(1), train=True, remat=plan)[0],
        variables["params"])
    # embedding, full and window attention, dense and sparse FF, norm, head
    assert len(traced) == 7 and plan.children == 9
    by = [(s["saved"], s["whole"], s["grads"]) for s in plan.sizes]
    assert by[1] == by[5] and by[4] == by[6]      # the equal children
    carried = 2 * 256 * 32 * 4                    # a child's input
    # an attention child's checkpoint holds the kernel's outputs as well
    assert by[1][0] > carried and by[3][0] > carried and by[2][0] == carried
    assert all(whole >= saved for saved, whole, _ in by)
    assert by[4][2] == 4 * sum(a.size for a in jax.tree_util.tree_leaves(
        variables["params"][4]))
    # a child run several times is still one estimate: the blocks as the
    # body of a loop of 3 passes are 3 x 6 + 3 applications more, and the
    # final norm (now the loop's) is the only layer not traced before
    del traced[:]
    looped = zoo.decoder_lm(**SIZES, attention_impl="flash",
                            total_ut_steps=3)
    shapes = jax.eval_shape(looped.init)
    plan = remat.Plan(budget=0)
    jax.eval_shape(lambda p: looped.layer.apply(
        p, shapes["state"], tokens(1), train=True, remat=plan)[0],
        shapes["params"])
    assert plan.children == 1 + 3 * 7 + 1
    assert [type(layer).__name__ for layer in traced] == [
        "Embedding", "Residual", "Residual", "Residual", "Residual",
        "RMSNorm", "ExitHeads"]


def test_a_trainer_counts_its_plan_and_judges_the_compiled_step():
    from distkeras_tpu.data.datasets import load_lm_corpus
    train = load_lm_corpus(n_train=4, seq_len=64, vocab_size=64, seed=1)[0]
    sizes = dict(SIZES, num_hidden_layers=2, seq_len=64, vocab_size=64)
    registry = default_registry()
    counters = [registry.counter(f"remat.children_{w}")
                for w in ("kept", "recomputed")]
    before = [c.value for c in counters]
    trainer = dk.SingleTrainer(
        zoo.decoder_lm(**sizes), "adam", "sparse_categorical_crossentropy",
        num_epoch=2, batch_size=2, learning_rate=1e-3,
        compute_dtype="bfloat16", remat=True)
    trainer.train(train)
    # no limit on the CPU: the frugal plan, the last of 7 children kept
    assert [c.value - b for c, b in zip(counters, before)] == [1, 6]
    spans = {r["name"]: r for r in trainer.metrics.records
             if r["event"] == "span"}
    record = spans["jit_compile"]
    assert record["remat_bytes_estimated"] == 0
    compiled = record["remat_bytes_compiled"]
    assert compiled > 0 and compiled == record["program_bytes"]
    assert (record["remat_children_kept"], record["remat_children_recomputed"],
            record["remat_bytes_estimated"], record["remat_bytes_compiled"]) \
        == (1, 6, 0, compiled)
    assert spans["train.remat_plan"]["path"].endswith(
        "jit_compile/train.remat_plan")
    compiles = [r for r in trainer.metrics.records
                if r["event"] == "span" and r["name"] == "jit_compile"]
    assert len(compiles) == 1


def test_a_refused_program_steps_back_a_child_at_a_time(monkeypatch):
    """The judge: a compiled step over ``REFUSE`` of the device's limit
    has the plan recompute one child more and compiles again, down to
    the frugal plan; the training is the same training."""
    from distkeras_tpu.data.datasets import load_lm_corpus
    train = load_lm_corpus(n_train=4, seq_len=64, vocab_size=64, seed=1)[0]

    def trained(remat_):
        trainer = dk.SingleTrainer(
            zoo.gpt_lm(vocab_size=64, dim=32, num_heads=2, num_blocks=1,
                       seq_len=64), "adam",
            "sparse_categorical_crossentropy", num_epoch=2, batch_size=2,
            learning_rate=1e-3, remat=remat_)
        return trainer, trainer.train(train).variables["params"]

    _, want = trained(False)
    # a device with room for everything, and a judge nothing satisfies
    monkeypatch.setattr(remat, "device_limit", lambda: 10 ** 12)
    monkeypatch.setattr(remat, "REFUSE", 0.0)
    trainer, got = trained(True)
    plan = trainer._run_cache[1].remat_plan
    n = plan.children
    assert n > 2 and plan.stepped_back == n - 1 == plan.first_kept
    record = [r for r in trainer.metrics.records
              if r["event"] == "span" and r["name"] == "jit_compile"]
    assert len(record) == 1     # one cold call, n compiles inside it
    assert record[0]["remat_children_kept"] == 1
    assert record[0]["remat_bytes_compiled"] > 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_without_remat_the_tags_cost_the_step_nothing(monkeypatch):
    """A ``remat=False`` step of a small ``gpt_lm`` (the GPT-2 cells'
    program) holds no checkpoint, and lowers to the text it lowers to
    with ``checkpoint_name`` patched to the identity."""
    import optax

    from distkeras_tpu.parallel.sync import make_local_step
    model = zoo.gpt_lm(vocab_size=64, dim=32, num_heads=2, num_blocks=2,
                       seq_len=256, attention_impl="flash")
    variables = model.init(0)
    optimizer = optax.adam(1e-3)
    carry = (variables, optimizer.init(variables["params"]),
             jax.random.PRNGKey(0))
    x = np.random.default_rng(0).integers(0, 64, (2, 256)).astype(np.int32)

    def lowered():
        step = make_local_step(model, sparse_categorical_crossentropy,
                               optimizer, jnp.bfloat16, remat=False)
        assert step.remat_plan is None
        text = str(jax.make_jaxpr(step)(carry, (x, x)))
        assert "checkpoint" not in text and "remat" not in text
        # the inner functions' symbols are numbered as they are made
        return re.sub(r"@([A-Za-z_]\w*?)_\d+\b", r"@\1", jax.jit(
            step).lower(carry, (x, x)).as_text())

    tagged = lowered()
    assert "flash_fwd" in tagged
    monkeypatch.setattr(remat, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()  # the jitted launchers' and the step's traces
    assert lowered() == tagged


@pytest.mark.parametrize("window", [None, 64], ids=["in-kernel-walk",
                                                     "window-band"])
def test_a_grouped_attention_child_is_sized_at_its_own_kv_heads(
        window, monkeypatch):
    """What a kept attention child holds for its backward is K and V as
    projected: against the same child on K/V repeated to the query heads
    (the step before the kernels read K/V at their own head count) the
    plan's ``whole`` falls by 2·(H − KV)·B·T·Dh·itemsize, on either walk,
    and what a checkpoint keeps (``saved``) is what it was."""
    from distkeras_tpu.ops import attention
    heads, kv, dh, (b, t, d) = 8, 2, 16, (2, 256, 32)
    layer = attention.MultiHeadAttention(
        heads, causal=True, impl="flash", num_kv_heads=kv, head_dim=dh,
        window=window)
    params, state, _ = layer.init(jax.random.PRNGKey(0), (t, d))
    args = (params, state, jax.ShapeDtypeStruct((b, t, d), jnp.float32), None)
    call = functools.partial(layer.apply, train=True)
    _, whole, named = remat._trace_child(call, *args)
    flash = attention._flash_with_blocking
    monkeypatch.setattr(
        attention, "_flash_with_blocking", lambda q, k, v, *rest: flash(
            q, layer._expand_kv(k), layer._expand_kv(v), *rest))
    _, whole_repeated, named_repeated = remat._trace_child(call, *args)
    assert named == named_repeated > 0
    assert whole_repeated - whole == 2 * (heads - kv) * b * t * dh * 4


def test_a_routed_child_holds_nothing_a_round_or_a_layout_long():
    """What a kept share of the experts holds for its backward: its
    input, the router's scores, the plan's integers and the shared
    expert's intermediates.  No row buffer (R = 3,072 rows in the layout,
    R_c = 1,536 a round), no (N·k, D) gather: the rounds' rows are built
    again in the backward, and a loop's temporaries are not residuals."""
    from distkeras_tpu.ops.moe import SparseMoE, round_rows
    (b, t, d), k = (2, 256, 32), 4
    layer = SparseMoE(16, k, 24, shared_hidden=24, experts_held=4,
                      first_expert=4)
    params, state, _ = layer.init(jax.random.PRNGKey(0), (t, d))
    args = (params, state, jax.ShapeDtypeStruct((b, t, d), jnp.float32), None)
    call = functools.partial(layer.apply, train=True)
    assert round_rows(b * t, k, 4, 16, 128) == 1536

    def residuals(p, s, x, rng):
        y, vjp, _ = jax.vjp(lambda p, x: call(p, s, x, rng=rng), p, x,
                            has_aux=True)
        return y, jax.tree_util.tree_leaves(vjp)

    held = [v.aval for v in jax.make_jaxpr(residuals)(*args).jaxpr.outvars[1:]]
    wide = [a for a in held if jnp.issubdtype(a.dtype, jnp.floating)
            and a.ndim >= 2 and a.shape[-1] > 1]
    assert wide and all(a.shape[0] in (b * t, 4, d, 24) for a in wide)
    _, whole, named = remat._trace_child(call, *args)
    # the layer with one buffer for every row held 2,206,292 here
    assert (whole, named) == (387188, 0)
