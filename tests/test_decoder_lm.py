"""``zoo.decoder_lm`` (window + full attention with per-layer head counts,
partial rotary + YaRN, a per-head gate, a dense and dropless sparse
SwiGLU FFs) against the plain reference ``benchmark/reference/laguna.py``,
and the pieces it is made of against plain formulas.  Small sizes, CPU,
the Pallas kernels in interpret mode."""

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import laguna  # noqa: E402

from distkeras_tpu.models import zoo  # noqa: E402
from distkeras_tpu.models.layers import RMSNorm, Sequential, SwiGLU  # noqa: E402
from distkeras_tpu.obs.registry import default_registry  # noqa: E402
from distkeras_tpu.ops.attention import (MultiHeadAttention,  # noqa: E402
                                         dot_product_attention,
                                         rope_frequencies)
from distkeras_tpu.ops.moe import (MoEDense, SparseMoE, dense_moe,  # noqa: E402
                                   dispatch_plan, init_moe_params)
from distkeras_tpu.ops.pallas_attention import flash_attention  # noqa: E402

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=5,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    num_attention_heads_per_layer=[4, 8, 8, 8, 4], num_key_value_heads=2,
    head_dim=16, intermediate_size=64,
    mlp_layer_types=["dense"] + ["sparse"] * 4, seq_len=256,
    sliding_window=40,
    rope_parameters={"full_attention": YARN, "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}},
    gating=True, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    moe_routed_scaling_factor=2.5, norm_topk_prob=True, experts_held=4,
    first_expert=4)


def tokens(seed, shape, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(
        np.int32)


@pytest.fixture(scope="module")
def built():
    model = zoo.decoder_lm(**SIZES, attention_impl="flash")
    return model, model.init(3)


def test_logits_equal_the_reference(built):
    model, variables = built
    x = tokens(0, (2, 256))
    got = jax.jit(model.predict_fn())(variables, x)
    want = laguna.forward(variables, x, SIZES)
    assert got.shape == want.shape == (2, 256, 96)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_loss_and_every_gradient_leaf_equal_the_reference(built):
    from distkeras_tpu.ops.losses import sparse_categorical_crossentropy
    model, variables = built
    x, y = tokens(1, (2, 256)), tokens(2, (2, 256))

    def loss(params):
        out, _ = model.layer.apply(params, variables["state"], x,
                                   train=True, remat=True)
        return sparse_categorical_crossentropy(out, y)

    got_loss, got = jax.jit(jax.value_and_grad(loss))(variables["params"])
    want_loss, want = laguna.loss_and_grads(variables, x, y, SIZES)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got, tree = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 50
    for (path, a), b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 0, path  # the leaf is used
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-8,
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_is_causal_and_windowed():
    variables = zoo.decoder_lm(**SIZES).init(1)
    x = tokens(3, (1, 256))
    y = x.copy()
    y[0, 200:] = (y[0, 200:] + 1) % 96
    a = np.asarray(laguna.forward(variables, x, SIZES))
    b = np.asarray(laguna.forward(variables, y, SIZES))
    np.testing.assert_array_equal(a[0, :200], b[0, :200])
    assert np.abs(a[0, 200:] - b[0, 200:]).max() > 1e-4


def masked_softmax_attention(q, k, v, window):
    t, dh = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None]
    s = jnp.where((ki <= qi) & (qi - ki < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


#: (T, block, window): the band's edges cross block edges (200 in 128s),
#: lie on them (128), one key past them (129), a window of one key, one
#: block for the whole sequence, a window longer than the sequence
WINDOWS = [(512, 128, 200), (384, 128, 128), (512, 128, 129),
           (256, 128, 1), (256, 256, 100), (256, 128, 300)]


@pytest.mark.parametrize("t,block,window", WINDOWS)
def test_window_kernels_equal_masked_dense_softmax(t, block, window):
    rng = np.random.default_rng(t + window)
    q, k, v = (jnp.asarray(rng.normal(size=(2, t, 2, 32)), jnp.float32)
               for _ in range(3))

    def via(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    def kernel(q, k, v):
        return flash_attention(q, k, v, True, block, block, window)

    def dense(q, k, v):
        return masked_softmax_attention(q, k, v, window)

    np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v), atol=2e-6)
    np.testing.assert_allclose(
        dot_product_attention(q, k, v, causal=True, window=window),
        dense(q, k, v), atol=2e-6)
    for a, b in zip(jax.grad(via(kernel), (0, 1, 2))(q, k, v),
                    jax.grad(via(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_window_schedule_is_counted_and_named():
    q = jnp.zeros((1, 1024, 1, 32), jnp.float32)
    counters = [default_registry().counter(f"flash.window_tiles_{w}")
                for w in ("executed", "total")]
    before = [c.value for c in counters]
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(flash_attention(q, q, q, True, 128, 128, 200))
    ))(q))
    # 8 query blocks, a band of 3 blocks: 1 + 2 + 6 x 3 = 21 of 64, x 3
    assert [c.value - b for c, b in zip(counters, before)] == [63, 192]
    import re
    from distkeras_tpu.models.remat import KERNEL_OUTPUTS
    kernels = {n for n in re.findall(r"name=(\w+)", text)
               if not n.startswith("_")  # the pallas_calls, not the jits
               and n not in KERNEL_OUTPUTS}  # nor their outputs' tags
    assert kernels == {"window_attn_fwd", "window_attn_bwd_dq",
                       "window_attn_bwd_dkv"}
    # the readers of the full-attention kernels go by these names
    assert not any(old in k for k in kernels for old in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    with pytest.raises(ValueError, match="sliding window"):
        flash_attention(q, q, q, False, None, None, 200)


def test_yarn_frequencies_at_the_published_numbers():
    freq, scale = rope_frequencies(64, 500000.0, {
        "rope_type": "yarn", "factor": 64, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 4096,
        "attention_factor": 1.4158883083359672})
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert scale == 1.4158883083359672 and freq.shape == (32,)
    np.testing.assert_allclose(freq[:6], plain[:6], rtol=1e-6)  # lo = 5
    np.testing.assert_allclose(freq[16:], plain[16:] / 64, rtol=1e-6)
    ramp = (np.arange(32) - 5) / 11
    np.testing.assert_allclose(
        freq[6:16], (plain / 64 * ramp + plain * (1 - ramp))[6:16],
        rtol=1e-6)
    assert rope_frequencies(128)[1] == 1.0


def test_attention_defaults_build_the_classic_layer():
    classic = MultiHeadAttention(4, causal=True, num_kv_heads=2, rope=True)
    params, _, _ = classic.init(jax.random.PRNGKey(0), (16, 32))
    assert {k: v.shape for k, v in params.items()} == {
        "qkv": (32, 32 + 2 * 2 * 8), "out": (32, 32)}
    wide = MultiHeadAttention(6, causal=True, num_kv_heads=2, head_dim=16,
                              window=8, gate=True, rope=True,
                              rope_fraction=0.5, rope_theta=5e5)
    params, _, _ = wide.init(jax.random.PRNGKey(0), (16, 32))
    assert {k: v.shape for k, v in params.items()} == {
        "qkv": (32, (6 + 4) * 16), "out": (96, 32), "gate": (32, 6)}
    again = MultiHeadAttention.from_config(wide.get_config())
    assert again.get_config() == wide.get_config()
    x = jnp.ones((2, 16, 32))
    y, _ = wide.apply(params, {}, x)
    assert y.shape == (2, 16, 32)
    with pytest.raises(ValueError, match="cached decode"):
        wide.apply_prefill(params, {}, x, wide.init_cache(2, (16, 32)))
    with pytest.raises(ValueError, match="causal"):
        MultiHeadAttention(4, window=8)


def sparse_layer(**kw):
    return SparseMoE(16, 4, 24, shared_hidden=24, routed_scale=2.5, **kw)


def test_the_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts: their routed parts, and
    the shared expert counted once, are the whole layer."""
    whole = sparse_layer()
    params, state, _ = whole.init(jax.random.PRNGKey(5), (64, 32))
    u = jnp.asarray(np.random.default_rng(6).normal(size=(2, 64, 32)),
                    jnp.float32)
    sizes = dict(SIZES, experts_held=16, first_expert=0)
    want = laguna.sparse_ff(params, u, sizes)[0]
    shared = laguna.swiglu(params["shared"]["gate_up"],
                           params["shared"]["down"], u)
    total, needed = shared, 0.0
    for share in range(8):
        part = sparse_layer(experts_held=2, first_expert=2 * share)
        mine = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[2 * share:2 * share + 2], params["experts"]))
        out, st = part.apply(mine, state, u)
        # the same share, from the reference
        np.testing.assert_allclose(out, laguna.sparse_ff(
            mine, u, dict(sizes, first_expert=2 * share))[0], atol=1e-5)
        total = total + (out - shared)
        needed += float(st["rows_needed"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(whole.apply(params, state, u)[0], want,
                               atol=2e-5)
    assert needed == 2 * 64 * 4  # every assignment landed on one share


def test_a_skewed_router_drops_no_token():
    """Every token to the same two experts: no capacity, nothing lost."""
    layer = SparseMoE(8, 2, 16, normalise=True)
    params, state, _ = layer.init(jax.random.PRNGKey(7), (300, 8))
    kernel = np.zeros((8, 8), np.float32)
    kernel[0, 3], kernel[0, 5] = 9.0, 8.0
    params["router"]["kernel"] = jnp.asarray(kernel)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(1, 300, 8)),
                    jnp.float32).at[..., 0].set(1.0)
    out, st = layer.apply(params, state, u)
    p = jax.nn.softmax(jnp.asarray([9.0, 8.0]))
    want = sum(p[j] * laguna.swiglu(params["experts"]["gate_up"][e],
                                    params["experts"]["down"][e], u)
               for j, e in enumerate((3, 5)))
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert float(st["rows_needed"]) == 600
    assert float(st["rows_run"]) == 2 * 384 + 6 * 128  # whole 128-row tiles
    assert float(st["load_max_over_mean"]) == 4.0


def test_dispatch_plan_keeps_every_assignment_in_its_experts_tiles():
    idx = jnp.asarray(np.random.default_rng(9).integers(0, 12, (50, 3)))
    plan = dispatch_plan(idx, 4, 4, 8)
    here = np.asarray(plan.here)
    assert here.sum() == int(np.sum((idx >= 4) & (idx < 8)))
    dest = np.asarray(plan.dest)[here]
    assert len(set(dest)) == len(dest)  # one row an assignment
    np.testing.assert_array_equal(
        np.asarray(plan.tile_expert)[dest // 8], np.asarray(idx)[here] - 4)
    assert np.asarray(plan.row_used).sum() == here.sum()
    assert int(plan.num_tiles[0]) * 8 >= here.sum()


def test_moedense_without_a_mesh_gives_dense_moes_numbers():
    params = init_moe_params(0, 8, 16, 32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 40, 16)),
                    jnp.float32)
    layer = MoEDense(8, 32)
    out, state = layer.apply(params, {}, x)
    want, aux = dense_moe(params, x.reshape(-1, 16))
    np.testing.assert_allclose(out.reshape(-1, 16), want, atol=1e-6)
    np.testing.assert_allclose(state["aux_loss"], aux, rtol=1e-6)
    text = str(jax.make_jaxpr(lambda p: layer.apply(p, {}, x)[0])(params))
    assert "moe_gmm" in text  # routed, not every expert for every token
    got = jax.grad(lambda p: jnp.sum(jnp.sin(layer.apply(p, {}, x)[0])))(
        params)
    ref = jax.grad(lambda p: jnp.sum(jnp.sin(dense_moe(
        p, x.reshape(-1, 16))[0])))(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_remat_checkpoints_a_sequential_child_by_child():
    seq = Sequential([RMSNorm(), SwiGLU(16), RMSNorm()])
    params, state, _ = seq.init(jax.random.PRNGKey(0), (8,))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8)),
                    jnp.float32)

    def loss(remat):
        return lambda p: jnp.sum(seq.apply(p, state, x, train=True,
                                           remat=remat)[0] ** 2)

    for a, b in zip(jax.tree_util.tree_leaves(jax.grad(loss(True))(params)),
                    jax.tree_util.tree_leaves(jax.grad(loss(False))(params))):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    text = str(jax.make_jaxpr(jax.grad(loss(True)))(params))
    # jax.checkpoint's primitive around the first two children: the last
    # child is never wrapped
    assert text.count("remat2") == 2
    assert "remat2" not in str(jax.make_jaxpr(jax.grad(loss(False)))(
        params))


def test_a_trainer_publishes_the_routed_layers_rows():
    import distkeras_tpu as dk
    from distkeras_tpu.data.datasets import load_lm_corpus
    sizes = dict(SIZES, num_hidden_layers=2, seq_len=64, vocab_size=64)
    train = load_lm_corpus(n_train=4, seq_len=64, vocab_size=64, seed=1)[0]
    counters = [default_registry().counter(f"moe.rows_{w}")
                for w in ("needed", "run")]
    before = [c.value for c in counters]
    trainer = dk.SingleTrainer(
        zoo.decoder_lm(**sizes), "adam", "sparse_categorical_crossentropy",
        num_epoch=2, batch_size=2, learning_rate=1e-3,
        compute_dtype="bfloat16", remat=True)
    model = trainer.train(train)
    needed, run = (c.value - b for c, b in zip(counters, before))
    assert 0 < needed <= 2 * 64 * 4 and run >= 4 * 128 and run % 128 == 0
    assert default_registry().gauge(
        "moe.expert_load_max_over_mean").value >= 1.0
    history = trainer.get_averaged_history()
    assert np.all(np.isfinite(history)) and history[-1] < history[0]
    assert model.variables["state"][4]["inner"][1]["rows_needed"] > 0


def test_the_router_scores_in_float32_under_mixed_precision(monkeypatch):
    """A bf16 step hands the router its float32 master weights and the
    experts their bf16 copies (``make_local_step``'s cast)."""
    import optax
    from distkeras_tpu.ops import moe
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.sync import make_local_step
    model = zoo.decoder_lm(**dict(SIZES, num_hidden_layers=2, seq_len=64))
    variables = model.init(0)
    seen = []
    plain = moe.route_top_k

    def spy(x, kernel, k, **kw):
        seen.append((x.dtype, kernel.dtype))
        return plain(x, kernel, k, **kw)

    monkeypatch.setattr(moe, "route_top_k", spy)
    optimizer = optax.adam(1e-3)
    step = make_local_step(model, get_loss("sparse_categorical_crossentropy"),
                           optimizer, compute_dtype=jnp.bfloat16)
    x = tokens(5, (2, 64))
    text = str(jax.make_jaxpr(step)(
        (variables, optimizer.init(variables["params"]),
         jax.random.PRNGKey(0)), (x, x)))
    assert seen == [(jnp.bfloat16, jnp.float32)]
    assert "bf16[4,32,48]" in text  # the held experts' gate_up, cast


def test_a_step_past_the_vmem_budget_walks_the_causal_grid(monkeypatch):
    """The rehearsal's Laguna step is short enough for the in-kernel
    walk, so the causal GRID walk (the 8k cell's full layers) is driven
    through the model here: the budget forced to 0 sends a full layer of
    T = 1,024, head 128 down it in 512-blocks (three pairs walked, one
    left out, two masked), beside a window layer, GQA and the gate,
    under ``remat``'s kept kernel outputs — loss and every gradient leaf
    against the dense implementation."""
    from distkeras_tpu.ops import pallas_attention
    from distkeras_tpu.ops.losses import sparse_categorical_crossentropy
    monkeypatch.setattr(pallas_attention, "_CAUSAL_VMEM_BUDGET", 0)
    sizes = dict(
        SIZES, num_hidden_layers=2, seq_len=1024, head_dim=128,
        layer_types=["full_attention", "sliding_attention"],
        num_attention_heads_per_layer=[4, 4], mlp_layer_types=["dense"] * 2,
        sliding_window=300)
    q = jnp.ones((1, 1024, 4, 128), jnp.float32)
    assert pallas_attention._blocks(q, q, True, None, None) == (512, 512,
                                                                None)
    flash = zoo.decoder_lm(**sizes, attention_impl="flash")
    dense = zoo.decoder_lm(**sizes, attention_impl="dense")
    variables = flash.init(5)
    x, y = tokens(6, (1, 1024)), tokens(7, (1, 1024))

    def loss(model, remat):
        def go(params):
            out, _ = model.layer.apply(params, variables["state"], x,
                                       train=True, remat=remat)
            return sparse_categorical_crossentropy(out, y)
        return jax.jit(jax.value_and_grad(go))

    names = ("grid_steps", "tiles_masked", "tiles_executed", "tiles_total")
    counters = [default_registry().counter(f"flash.causal_{name}")
                for name in names]
    before = [c.value for c in counters]
    got_loss, got = loss(flash, True)(variables["params"])
    steps, masked, executed, total = (
        c.value - b for c, b in zip(counters, before))
    # the forward's kernel and the backward's two, the full layer alone
    assert steps == executed and steps % 3 == 0 and steps >= 9
    assert (masked, total) == (steps // 3 * 2, steps // 3 * 4)
    want_loss, want = loss(dense, False)(variables["params"])
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(b))) > 0, path  # the leaf is used
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-8,
            err_msg=jax.tree_util.keystr(path))


def test_a_flash_step_holds_no_key_or_value_at_the_query_heads(monkeypatch):
    """Grouped queries through the layer, on the 8k cell's own builder
    arguments at a short T (48 and 64 query heads of 128 over 8 K/V
    heads; 128-blocks forced so the full layer walks the table as at
    T = 8,192): the step's jaxpr holds no K or V repeated to the query
    heads, forward or backward, each ``pallas_call`` takes its key side
    at 8 heads, the kernels are counted as reading K/V at their own head
    count, and the dense implementation (which still repeats) gives the
    same output and gradients."""
    from distkeras_tpu.ops import attention as attention_ops
    t, kv, dh = 256, 8, 128

    def layer(impl, heads, window):
        return MultiHeadAttention(heads, causal=True, impl=impl,
                                  num_kv_heads=kv, head_dim=dh,
                                  window=window, rope=True, gate=True)

    def blocked(q, k, v, causal, t, window=None):
        assert k.shape == v.shape == (1, t, kv, dh)  # as projected
        return flash_attention(q, k, v, causal, 128, 128, window)

    monkeypatch.setattr(attention_ops, "_flash_with_blocking", blocked)
    counters = [default_registry().counter(f"flash.kv_{kind}_kernels")
                for kind in ("native", "expanded")]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, t, 64)),
                    jnp.float32)
    for heads, window in ((48, None), (64, 100)):
        flash, dense = layer("flash", heads, window), layer("dense", heads,
                                                             window)
        params, state, _ = flash.init(jax.random.PRNGKey(1), (t, 64))
        def loss(params, x, layer=flash):
            return jnp.sum(layer.apply(params, state, x, train=True)[0] ** 2)

        before = [c.value for c in counters]
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x)
        got = jax.grad(loss)(params, x)
        assert [c.value - b for c, b in zip(counters, before)] == [6, 0]
        calls = [e for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 3
        for call in calls:
            leading = sorted({v.aval.shape[0] for v in call.invars
                              if len(v.aval.shape) == 3})
            assert leading == [kv, heads], (call.params["name"], leading)
        # a repeat is a broadcast to (B, T, KV, G, Dh) and a reshape: the
        # dense step holds one, the flash step nothing of that shape

        def repeats(jaxpr):
            return [v for eqn in _eqns(jaxpr.jaxpr) for v in eqn.outvars
                    if v.aval.shape == (1, t, kv, heads // kv, dh)]

        assert not repeats(jaxpr)
        assert repeats(jax.make_jaxpr(jax.grad(
            functools.partial(loss, layer=dense)))(params, x))
        want = jax.grad(functools.partial(loss, layer=dense))(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(
                a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(
            flash.apply(params, state, x)[0], dense.apply(params, state, x)[0],
            rtol=2e-4, atol=2e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
