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


# ---------------------------------------------------------------------------
# relu² experts under a sigmoid router (``SparseMoE``'s options), against
# the plain reference ``benchmark/reference/nemotron_h.py``
# ---------------------------------------------------------------------------

def _reference(name):
    import importlib
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(f"reference.{name}")


def _nemotron_reference():
    return _reference("nemotron_h")


ROUTED = dict(n_routed_experts=16, num_experts_per_tok=3,
              routed_scaling_factor=2.5, norm_topk_prob=True)


def relu2_layer(**kw):
    from distkeras_tpu.ops.moe import SparseMoE
    return SparseMoE(16, 3, 24, shared_hidden=40, routed_scale=2.5,
                     expert_activation="relu2", scoring="sigmoid", **kw)


def relu2_setup(bias_scale=0.0):
    layer = relu2_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(5), (64, 32))
    assert set(params["experts"]) == {"up", "down"} \
        and set(params["shared"]) == {"up", "down"} \
        and params["router"]["bias"].shape == (16,)
    params["router"]["bias"] = bias_scale * jax.random.normal(
        jax.random.PRNGKey(7), (16,))
    u = jnp.asarray(np.random.default_rng(6).normal(size=(2, 64, 32)),
                    jnp.float32)
    return layer, params, state, u


def test_relu2_sigmoid_layer_equals_the_reference_values_and_gradients():
    ref = _nemotron_reference()
    layer, params, state, u = relu2_setup(bias_scale=0.3)
    w = jax.random.normal(jax.random.PRNGKey(8), u.shape)
    got = jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(
        w * layer.apply(p, state, u)[0]), argnums=(0, 1)))(params, u)
    want = jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(
        w * ref.sparse_ff(p, u, ROUTED)[0]), argnums=(0, 1)))(params, u)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want),
                            strict=True):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    # the bias bears on the choice alone: no gradient reaches it
    assert not np.any(np.asarray(got[1][0]["router"]["bias"]))


def test_the_bias_changes_a_choice_and_no_weight():
    """``b`` large enough to change which experts are taken; a taken
    expert's weight is its own sigmoid score over the taken scores' sum,
    whatever ``b``."""
    from distkeras_tpu.ops.moe import route_top_k
    _, params, _, u = relu2_setup()
    tokens, kernel = u.reshape(-1, 32), params["router"]["kernel"]
    bias = jnp.zeros((16,)).at[3].set(5.0)
    idx0, w0, _ = route_top_k(tokens, kernel, 3, normalise=True, scale=2.5,
                              bias=jnp.zeros((16,)))
    idx1, w1, _ = route_top_k(tokens, kernel, 3, normalise=True, scale=2.5,
                              bias=bias)
    assert np.all(np.any(np.asarray(idx1) == 3, axis=1))   # always taken
    assert not np.all(np.any(np.asarray(idx0) == 3, axis=1))
    scores = np.asarray(jax.nn.sigmoid(tokens @ kernel))
    taken = np.take_along_axis(scores, np.asarray(idx1), axis=1)
    np.testing.assert_allclose(
        w1, 2.5 * taken / taken.sum(axis=1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w1.sum(axis=1), 2.5, rtol=1e-5)
    np.testing.assert_allclose(w0.sum(axis=1), 2.5, rtol=1e-5)


def test_the_relu2_shares_add_up_to_the_uncut_layer():
    """Each of 4 chips holds 4 of 16 experts: their routed parts, and the
    shared expert counted once, are the whole layer."""
    ref = _nemotron_reference()
    whole, params, state, u = relu2_setup(bias_scale=0.3)
    want = ref.sparse_ff(params, u, ROUTED)[0]
    shared = ref.relu2_mlp(params["shared"]["up"],
                           params["shared"]["down"], u)
    total, needed = shared, 0.0
    for share in range(4):
        part = relu2_layer(experts_held=4, first_expert=4 * share)
        mine = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[4 * share:4 * share + 4], params["experts"]))
        out, st = part.apply(mine, state, u)
        # the same share, from the reference
        np.testing.assert_allclose(out, ref.sparse_ff(
            mine, u, dict(ROUTED, first_expert=4 * share))[0], atol=2e-5)
        total = total + (out - shared)
        needed += float(st["rows_needed"])
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(whole.apply(params, state, u)[0], want,
                               atol=5e-5)
    assert needed == 2 * 64 * 3  # every assignment landed on one share


def test_sparse_moe_refuses_unknown_options():
    from distkeras_tpu.ops.moe import SparseMoE
    with pytest.raises(ValueError, match="expert_activation"):
        SparseMoE(4, 2, 8, expert_activation="gelu")
    with pytest.raises(ValueError, match="scoring"):
        SparseMoE(4, 2, 8, scoring="tanh")


# ---------------------------------------------------------------------------
# rounds: a share of the experts walks the rows that arrive, R_c at a time
# ---------------------------------------------------------------------------

#: 1,024 tokens take 3 of 64 experts, 4 held from expert 8: 192 rows are
#: expected here, so a round is 2 x 192 in tiles + a tile an expert = 7
#: tiles, and the layout's 24 + 4 tiles are 4 rounds
WALK = dict(tokens=1024, width=32, experts=64, k=3, held=4, first=8,
            hidden=24, shared=40, round_tiles=7)


def walk_layer(activation, **kw):
    from distkeras_tpu.ops.moe import SparseMoE
    kw = dict(dict(experts_held=WALK["held"], first_expert=WALK["first"]),
              **kw)
    return SparseMoE(
        WALK["experts"], WALK["k"], WALK["hidden"],
        shared_hidden=WALK["shared"], routed_scale=2.5,
        expert_activation=activation,
        scoring="sigmoid" if activation == "relu2" else "softmax", **kw)


def walk_setup(activation, forced, dtype, **kw):
    """The layer, its parameters and tokens of which the share ``forced``
    send all their choices to experts 8, 9, 10 (their first feature is 1
    and the router's first row favours the three; the others' is 0)."""
    layer = walk_layer(activation, **kw)
    n, d = WALK["tokens"], WALK["width"]
    params, state, _ = layer.init(jax.random.PRNGKey(11), (n, d))
    kernel = 0.05 * jax.random.normal(jax.random.PRNGKey(12),
                                      (d, WALK["experts"]))
    params["router"]["kernel"] = kernel.at[0].set(0.0).at[0, 8:11].set(
        jnp.asarray([6.0, 5.0, 4.0]))
    u = np.random.default_rng(13).normal(size=(1, n, d)).astype(np.float32)
    u[0, :, 0] = np.arange(n) % 10 < round(10 * forced)
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(dtype), t)
    # a bf16 step leaves the router its float32 master weights
    return layer, dict(cast(params), router=params["router"]), state, \
        cast(jnp.asarray(u))


def walk_reference(activation, params, u):
    first = {"first_expert": WALK["first"]} \
        if params["experts"]["down"].shape[0] < WALK["experts"] else {}
    if activation == "relu2":
        return _reference("nemotron_h").sparse_ff(params, u, dict(
            n_routed_experts=WALK["experts"], num_experts_per_tok=WALK["k"],
            routed_scaling_factor=2.5, norm_topk_prob=True, **first))[0]
    return _reference("laguna").sparse_ff(params, u, dict(
        num_experts_per_tok=WALK["k"], moe_routed_scaling_factor=2.5,
        norm_topk_prob=True, **first))[0]


def walk_compare(activation, layer, params, state, u, dtype):
    """Values and gradients (tokens, router, expert and shared matrices)
    against the plain reference on the same numbers in float32; the
    layer's state."""
    w = jax.random.normal(jax.random.PRNGKey(14), u.shape)

    def mine(p, u):
        out, st = layer.apply(p, state, u)
        return jnp.sum(w * out.astype(jnp.float32)), (out, st)

    (_, (out, st)), got = jax.jit(jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True))(params, u)
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  (params, u))
    want_out, want = jax.jit(lambda p, u: (
        walk_reference(activation, p, u),
        jax.grad(lambda p, u: jnp.sum(w * walk_reference(activation, p, u)),
                 argnums=(0, 1))(p, u)))(*wide)
    exact = dtype == jnp.float32
    flat, _ = jax.tree_util.tree_flatten_with_path(((out,) + got))
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(
            (want_out,) + want), strict=True):
        assert a.dtype == (jnp.float32 if "router" in jax.tree_util.keystr(
            path) else dtype)
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=1e-4 if exact else 0.05,
            atol=(1e-5 if exact else 0.02) * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    return {name: float(v) for name, v in st.items()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("forced,rounds", [(0.0, 1), (0.5, 3), (1.0, 4)],
                         ids=["expected", "triple", "worst"])
def test_a_share_walks_what_arrives_in_rounds(forced, rounds, activation,
                                              dtype):
    """As many rows as expected: one round.  Every other token forced
    here: three rounds, experts 9 and 10 straddle their boundaries (tiles
    5-9 and 10-14 over rounds of 7), expert 8 has no tile in the second
    and third and expert 11 none in the first two.  Every choice of every
    token here, the layout's worst case: all four.  Nothing is dropped at
    any load."""
    from distkeras_tpu.ops.moe import route_top_k
    from distkeras_tpu.ops.pallas_moe import TILE_ROWS
    layer, params, state, u = walk_setup(activation, forced, dtype)
    st = walk_compare(activation, layer, params, state, u, dtype)
    idx = np.asarray(route_top_k(
        u[0], params["router"]["kernel"], WALK["k"], normalise=True,
        scale=2.5, bias=params["router"].get("bias"))[0])
    here = (idx >= WALK["first"]) & (idx < WALK["first"] + WALK["held"])
    assert st["rows_needed"] == here.sum()   # counted on the host
    assert st["round_rows"] == WALK["round_tiles"] * TILE_ROWS
    assert st["rounds"] == rounds
    counts = [(idx == WALK["first"] + e).sum() for e in range(WALK["held"])]
    tiles = sum(max(-(-c // TILE_ROWS), 1) for c in counts)
    assert st["rows_run"] == tiles * TILE_ROWS
    assert rounds == -(-tiles // WALK["round_tiles"])
    if forced == 0.5:
        assert [max(-(-c // TILE_ROWS), 1) for c in counts] == [5, 5, 5, 1]
    if forced == 1.0:  # and every one of them landed here
        assert here.all() and st["rows_needed"] == 3 * WALK["tokens"]


def test_a_layer_holding_every_expert_is_one_round_of_all_its_rows():
    """``experts_held == num_experts``: R_c is the whole layout (N·k in
    tiles + a tile an expert), whatever arrives is one round, and no loop
    is built."""
    from distkeras_tpu.ops.moe import round_rows
    from distkeras_tpu.ops.pallas_moe import TILE_ROWS
    layer, params, state, u = walk_setup(
        "swiglu", 0.5, jnp.float32, experts_held=None, first_expert=0)
    st = walk_compare("swiglu", layer, params, state, u, jnp.float32)
    whole = (WALK["tokens"] * WALK["k"] // TILE_ROWS + WALK["experts"]) \
        * TILE_ROWS
    assert st["round_rows"] == whole == round_rows(
        WALK["tokens"], WALK["k"], WALK["experts"], WALK["experts"],
        TILE_ROWS)
    assert st["rounds"] == 1 and st["rows_needed"] == 3 * WALK["tokens"]
    text = str(jax.make_jaxpr(lambda p, u: jax.grad(lambda p: jnp.sum(
        layer.apply(p, state, u)[0]))(p))(params, u))
    assert "while" not in text and "moe_tgmm" in text
    # Nemotron's and Laguna's shares: 56 tiles of 392, 160 of 544
    assert round_rows(8192, 6, 8, 128, 128) == 56 * 128
    assert round_rows(8192, 8, 32, 256, 128) == 160 * 128


def test_a_sum_too_long_for_vmem_is_scattered_to_the_same_numbers(
        monkeypatch):
    """Where no column block of the (N, D) float32 sum fits the kernel's
    VMEM budget the round's rows are scatter-added by XLA: the same
    tokens' sums, unused rows adding nothing."""
    from distkeras_tpu.ops import moe, pallas_moe
    n, k, tile = 300, 2, pallas_moe.TILE_ROWS
    idx = jax.random.randint(jax.random.PRNGKey(1), (n, k), 0, 6)
    plan = moe.dispatch_plan(idx, 1, 3, tile)
    rnd = moe._round_of(plan, 0, k, plan.row_assign.shape[0], tile)
    rows = jax.random.normal(jax.random.PRNGKey(2), (rnd.used.shape[0], 32))
    scale = jnp.where(rnd.used, 0.5, 0.0)
    kernel = moe._to_tokens(rows, scale, rnd, n)
    monkeypatch.setattr(pallas_moe, "_SUM_BUDGET", 1024)
    assert pallas_moe.rows_to_tokens_block(n, 32) is None
    np.testing.assert_allclose(moe._to_tokens(rows, scale, rnd, n), kernel,
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(kernel).sum()) > 0


def test_a_rounds_gradients_are_taken_for_its_present_experts_alone():
    """``moe_tgmm`` never writes the block of an expert with no tile in
    the round: whatever is there (NaN here) is selected away.  An expert
    seen before and present again straddles the boundary: the float32 sum
    of both parts, rounded once."""
    from distkeras_tpu.ops.moe import _merge_by_expert
    nan = float("nan")
    so_far = {"w": jnp.asarray([[1.0, 1.0], [2.0, 258.0], [nan, nan]],
                               jnp.bfloat16)}
    part = {"w": jnp.asarray([[nan, nan], [10.0, 1.0], [5.0, 5.0]],
                             jnp.bfloat16)}
    got = _merge_by_expert(so_far, part, jnp.asarray([True, True, False]),
                           jnp.asarray([False, True, True]))["w"]
    assert got.dtype == jnp.bfloat16
    # 258 + 1 = 259 in float32, which bfloat16 rounds to 260
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), [[1.0, 1.0], [12.0, 260.0], [5.0, 5.0]])


# ---------------------------------------------------------------------------
# the grouped matmuls' tiles
# ---------------------------------------------------------------------------

def test_tiles_of_the_published_widths():
    from distkeras_tpu.ops.pallas_moe import (_BLOCK_BUDGET, _DEPTH_BUDGET,
                                              _col_tile, _depth_tile)
    # Laguna's: (columns, depth, bytes an element) -> columns, as before
    for (n, c, size), tile in {(1024, 2048, 2): 512, (2048, 512, 2): 1024,
                               (512, 2048, 2): 512, (1024, 2048, 4): 256,
                               (2048, 512, 4): 1024,
                               (512, 2048, 4): 256}.items():
        assert _col_tile(n, c, size) == tile
        # ... and the depth whole, in moe_gmm and in moe_tgmm
        assert _depth_tile(c, 2 * tile * size, _DEPTH_BUDGET) == c
        assert _depth_tile(c, tile * 4, _BLOCK_BUDGET) == c
    # Nemotron's: 2,688 = 21 x 128 takes 384 columns, not 128; 1,856 =
    # 14.5 x 128 is one block, over a depth of 2,688 in 3 steps of 896
    # (moe_tgmm's float32 accumulator: 7 blocks of 384 of its rows)
    assert _col_tile(2688, 1856, 2) == 384
    assert _depth_tile(1856, 2 * 384 * 2, _DEPTH_BUDGET) == 1856
    assert _col_tile(1856, 2688, 2) == 1856
    assert _depth_tile(2688, 2 * 1856 * 2, _DEPTH_BUDGET) == 896
    assert _depth_tile(2688, 1856 * 4, _BLOCK_BUDGET) == 384
    assert _col_tile(2688, 64, 2) == 896  # the widest divisor under 1,024


@pytest.mark.parametrize("depth,width", [(384, 200), (200, 384), (256, 256)])
def test_grouped_matmul_tiles_the_depth(monkeypatch, depth, width):
    """A width that 128 does not divide (one block) over a depth that
    takes three contraction steps, a depth that 128 does not divide
    (whole), and both tiled: values and both gradients against plain
    matmuls, the matrices' gradient a block of columns at a time."""
    from distkeras_tpu.ops import pallas_moe
    from distkeras_tpu.ops.moe import dispatch_plan
    monkeypatch.setattr(pallas_moe, "_BLOCK_BUDGET", 128 * 1024)
    monkeypatch.setattr(pallas_moe, "_DEPTH_BUDGET", 128 * 1024)
    tn = pallas_moe._col_tile(width, depth, 4)
    steps = depth // pallas_moe._depth_tile(depth, 2 * tn * 4, 128 * 1024)
    assert (tn, steps) == {(384, 200): (200, 3), (200, 384): (128, 1),
                           (256, 256): (128, 2)}[(depth, width)]
    experts, tile = 3, pallas_moe.TILE_ROWS
    plan = dispatch_plan(jax.random.randint(jax.random.PRNGKey(1), (300, 2),
                                            0, experts + 1), 0, experts,
                         tile)
    rows = plan.row_used.shape[0]
    lhs = jax.random.normal(jax.random.PRNGKey(2), (rows, depth)) \
        * plan.row_used[:, None]
    rhs = jax.random.normal(jax.random.PRNGKey(3), (experts, depth, width))
    w = jax.random.normal(jax.random.PRNGKey(4), (rows, width))
    row_expert = jnp.repeat(plan.tile_expert, tile)
    used = (jnp.arange(rows) < plan.num_tiles[0] * tile)[:, None]

    def plain(lhs, rhs):
        return jnp.where(used, jnp.einsum("rc,rcn->rn", lhs,
                                          rhs[row_expert]), 0.0)

    def grouped(lhs, rhs):
        return pallas_moe.grouped_matmul(lhs, rhs, plan.tile_expert,
                                         plan.num_tiles)

    with jax.default_matmul_precision("highest"):
        for got, want in zip(*(jax.jit(lambda l, r, fn=fn: (fn(l, r),) + jax.grad(
                lambda l, r: jnp.sum(w * fn(l, r)), argnums=(0, 1))(l, r))(
                    lhs, rhs) for fn in (grouped, plain)), strict=True):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
