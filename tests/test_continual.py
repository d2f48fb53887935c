"""Continual-learning subsystem (ISSUE 8): the windowed drift classifier
(step change vs gradual trend goldens), interval deltas, deploy-gate
accept/reject accounting, the ``promote`` RPC on the shared server
frame, checkpoint/exact-resume metadata, and the e2e acceptance run —
train on a simulated unbounded feed, deploy drift-clean checkpoints into
a live ``DecodeEngine`` with ``jit.retraces == 0`` under the committed
``OBS_BASELINE.json`` zero-tolerance rule, and an injected drift-dirty
window provably blocking deployment as a recorded rejection."""

import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from distkeras_tpu.continual import (ContinualConfig, ContinualTrainer,
                                     DeployGate, synthetic_lm_feed)
from distkeras_tpu.continual.config import LOSS_BUCKETS
from distkeras_tpu.models import zoo
from distkeras_tpu.models.generation import generate_tokens
from distkeras_tpu.obs import Registry, drift
from distkeras_tpu.serve import (DecodeEngine, ServeClient, ServeConfig,
                                 ServeServer)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, SEQ = 16, 16


def _ctr(v):
    return {"type": "counter", "value": float(v)}


def _counter_intervals(values, name="continual.loss_rate"):
    """Interval snapshots carrying ONE counter metric — the cleanest
    fixture for exact step/trend arithmetic (rel threshold 0.25)."""
    return [{name: _ctr(v)} for v in values]


def _loss_interval(values):
    """Interval snapshot with a real ``continual.loss`` histogram built
    from observations."""
    reg = Registry()
    h = reg.histogram("continual.loss", LOSS_BUCKETS)
    for v in values:
        h.observe(float(v))
    return reg.snapshot()


# ---------------------------------------------------------------------------
# windowed drift classifier (obs.drift): step vs trend goldens
# ---------------------------------------------------------------------------

def test_classify_window_stable_and_thin():
    assert drift.classify_window([]).clean
    assert drift.classify_window(_counter_intervals([100])).clean
    v = drift.classify_window(_counter_intervals([100, 105, 98, 103, 101]))
    assert v.clean and v.kind == "stable"
    assert v["intervals"] == 5


def test_classify_window_step_change_golden():
    """An abrupt jump in ONE consecutive pair classifies step — and
    names the metric."""
    v = drift.classify_window(_counter_intervals([100, 100, 100, 100, 180]))
    assert v.kind == "step" and not v.clean
    assert v["step_metrics"] == ["continual.loss_rate"]
    assert v["trend_metrics"] == []
    assert any("step 3->4" in d for d in v["details"])


def test_classify_window_gradual_trend_golden():
    """Every consecutive pair under threshold, first->last over it:
    trend — the shape no pairwise gate can see."""
    v = drift.classify_window(_counter_intervals([100, 115, 132, 152, 175]))
    assert v.kind == "trend" and not v.clean
    assert v["trend_metrics"] == ["continual.loss_rate"]
    assert v["step_metrics"] == []
    assert any("trend 0->4" in d for d in v["details"])


def test_classify_window_step_slides_out():
    """Once the offending pair leaves the rolling window (every retained
    interval is post-jump), the window is stable again — the property
    that lets deploys resume after the model relearns."""
    dirty = drift.classify_window(_counter_intervals([100, 180, 180, 180]))
    assert dirty.kind == "step"
    clean = drift.classify_window(_counter_intervals([180, 180, 180, 181]))
    assert clean.clean


def test_classify_window_histogram_step():
    """The real gate signal: a loss-distribution jump between intervals
    (converged ~0.01 -> cold ~3) is a step on ``continual.loss``."""
    quiet = [_loss_interval(np.linspace(0.011, 0.049, 32))
             for _ in range(3)]
    assert drift.classify_window(quiet).clean
    jumped = quiet + [_loss_interval(np.linspace(2.5, 3.5, 32))]
    v = drift.classify_window(jumped)
    assert v.kind == "step" and "continual.loss" in v["step_metrics"]


def test_snapshot_delta_semantics():
    base = {"c": _ctr(10), "g": {"type": "gauge", "value": 5.0},
            "h": {"type": "histogram", "bounds": [1, 2], "counts": [3, 1, 0],
                  "sum": 4.0, "count": 4}}
    cand = {"c": _ctr(25), "g": {"type": "gauge", "value": 7.0},
            "h": {"type": "histogram", "bounds": [1, 2], "counts": [5, 4, 1],
                  "sum": 11.0, "count": 10},
            "new": _ctr(2)}
    d = drift.snapshot_delta(base, cand)
    assert d["c"]["value"] == 15          # counters subtract
    assert d["g"]["value"] == 7.0         # gauges keep the later level
    assert d["h"]["counts"] == [2, 3, 1]  # histograms subtract bucketwise
    assert d["h"]["count"] == 6 and d["h"]["sum"] == 7.0
    assert d["new"]["value"] == 2         # born mid-interval: enters as-is
    # a restarted process (counter went backwards) clamps to the cand
    # value instead of reporting a negative interval
    d2 = drift.snapshot_delta({"c": _ctr(100)}, {"c": _ctr(7)})
    assert d2["c"]["value"] == 7


# ---------------------------------------------------------------------------
# deploy gate
# ---------------------------------------------------------------------------

def test_gate_warmup_then_clean_deploy():
    reg = Registry()
    gate = DeployGate(history=3, min_history=2, registry=reg,
                      watch=("m",))
    v = gate.observe({"m": _ctr(100)})
    entry = gate.decide(v, interval=0)
    assert not entry["deploy"] and "warmup" in entry["reason"]
    v = gate.observe({"m": _ctr(101)})
    entry = gate.decide(v, interval=1)
    assert entry["deploy"] and not entry["deployed"]
    gate.record_deployed(entry)
    assert entry["deployed"]
    snap = reg.snapshot()
    assert snap["continual.deploys"]["value"] == 1
    assert snap["continual.rejected_warmup"]["value"] == 1
    assert snap["continual.deploys_rejected"]["value"] == 1
    assert snap["continual.window_dirty"]["value"] == 0.0


def test_gate_dirty_window_blocks_with_recorded_rejection():
    reg = Registry()
    gate = DeployGate(history=4, min_history=2, registry=reg, watch=("m",))
    for v in (100, 102, 180):
        verdict = gate.observe({"m": _ctr(v)})
    entry = gate.decide(verdict, interval=2)
    assert not entry["deploy"]
    assert "drift-dirty" in entry["reason"] and entry["kind"] == "step"
    snap = reg.snapshot()
    assert snap["continual.rejected_dirty"]["value"] == 1
    assert snap["continual.verdicts_step"]["value"] == 1
    assert snap["continual.window_dirty"]["value"] == 1.0
    assert gate.history_log()[-1]["reason"] == entry["reason"]


def test_gate_watch_filter_ignores_bookkeeping():
    """Metrics outside the watch list cannot dirty the window — deploy
    counters, wire bytes and cold compiles are not drift."""
    gate = DeployGate(history=3, min_history=1, watch=("continual.loss",))
    gate.observe({"continual.loss": _ctr(100), "jit.compiles": _ctr(1)})
    v = gate.observe({"continual.loss": _ctr(101), "jit.compiles": _ctr(0)})
    assert v.clean  # the compiles 1 -> 0 swing was filtered out


def test_gate_validation():
    with pytest.raises(ValueError):
        DeployGate(history=0)
    with pytest.raises(ValueError):
        DeployGate(history=2, min_history=3)
    with pytest.raises(ValueError):
        ContinualConfig(min_history=5, history=3)
    with pytest.raises(ValueError):
        ContinualConfig(window_steps=0)


# ---------------------------------------------------------------------------
# the simulated unbounded feed
# ---------------------------------------------------------------------------

def test_synthetic_feed_rule_and_injected_step():
    feed = synthetic_lm_feed(VOCAB, SEQ, 4, seed=0, drift_after=3,
                             drift_step=5)
    batches = [next(feed) for _ in range(5)]
    for x, y in batches[:3]:
        assert x.shape == (4, SEQ) and x.dtype == np.int32
        assert y.shape == (4, SEQ) and y.dtype == np.int64
        assert np.array_equal(y, (x + 1) % VOCAB)   # the counting rule
    for x, y in batches[3:]:
        assert np.array_equal(y, (x + 5) % VOCAB)   # post-drift rule


def test_synthetic_feed_ramp_is_gradual():
    feed = synthetic_lm_feed(VOCAB, SEQ, 64, seed=1, drift_after=1,
                             drift_step=5, drift_ramp=8)
    fracs = []
    for b, (x, y) in zip(range(9), feed):
        drifted = np.mean(np.all(y == (x + 5) % VOCAB, axis=1))
        fracs.append(float(drifted))
    assert fracs[0] == 0.0          # pre-drift
    assert fracs[-1] == 1.0         # fully switched
    assert 0.0 < fracs[3] < 1.0     # mid-ramp is mixed


# ---------------------------------------------------------------------------
# engine/server promote seam (ISSUE 8 hardening + RPC)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    model = zoo.gpt_lm(vocab_size=VOCAB, dim=16, num_heads=2,
                       num_blocks=1, seq_len=SEQ)
    return model, model.init(0)


def _engine(lm, registry=None, **kw):
    model, v = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_new_tokens", 8)
    return DecodeEngine(model, v, ServeConfig(**kw),
                        registry=registry if registry is not None
                        else Registry())


def _ref(model, variables, prompt, steps):
    out = generate_tokens(model, variables,
                          np.asarray(prompt, np.int32)[None, :],
                          int(steps))
    return np.asarray(out)[0, len(prompt):]


def test_engine_promote_rejects_mismatched_tree(lm):
    model, v = lm
    eng = _engine(lm)
    with pytest.raises(ValueError):
        eng.promote({"params": v["params"]})  # structure mismatch
    other = zoo.gpt_lm(vocab_size=VOCAB, dim=8, num_heads=2,
                       num_blocks=1, seq_len=SEQ)
    with pytest.raises(ValueError):
        eng.promote(other.init(0))            # leaf shape mismatch
    assert eng.registry.counter("serve.promotions").value == 0


def test_promote_rpc_swaps_weights_over_the_wire(lm):
    """The cross-process deploy seam: ``ServeClient.promote`` hot-swaps
    the serving weights through the shared server frame; served outputs
    reflect the new checkpoint, a mismatched tree answers an error on a
    connection that stays alive, and nothing re-traces."""
    model, _ = lm
    v_new = model.init(3)
    prompt = np.arange(5) % VOCAB
    reg = Registry()
    with ServeServer(_engine(lm, registry=reg).warmup()) as srv:
        with ServeClient("127.0.0.1", srv.port) as c:
            before = c.generate(prompt, 6)
            reply = c.promote(v_new)
            assert reply["ok"] and reply["promotions"] == 1
            after = c.generate(prompt, 6)
            # a tree for a DIFFERENT model is a bad request, not a crash
            other = zoo.gpt_lm(vocab_size=VOCAB, dim=8, num_heads=2,
                               num_blocks=1, seq_len=SEQ)
            bad = c.promote(other.init(0))
            assert bad["ok"] is False and "error" in bad
            still = c.generate(prompt, 6)  # connection + service alive
    assert before["ok"] and after["ok"] and still["ok"]
    assert np.array_equal(np.asarray(after["tokens"]),
                          _ref(model, v_new, prompt, 6))
    assert np.array_equal(np.asarray(still["tokens"]),
                          np.asarray(after["tokens"]))
    assert not np.array_equal(np.asarray(before["tokens"]),
                              np.asarray(after["tokens"]))
    assert reg.counter("jit.retraces").value == 0


# ---------------------------------------------------------------------------
# ContinualTrainer: e2e acceptance + checkpoint/resume + daemon shape
# ---------------------------------------------------------------------------

def _trainer(lm, registry, deploy_to=None, history=3, min_history=2,
             **kw):
    model, _ = lm
    cfg = ContinualConfig(batch_size=16, window_steps=4, snapshot_every=4,
                          history=history, min_history=min_history)
    return ContinualTrainer(model, "adam",
                            "sparse_categorical_crossentropy", config=cfg,
                            learning_rate=1e-2, registry=registry,
                            deploy_to=deploy_to, **kw)


def test_e2e_continual_deploys_into_live_engine_drift_gated(lm):
    """THE acceptance run: a bounded slice of the train-forever loop on
    a simulated unbounded feed with a LIVE engine as deploy target —

    * >= 1 drift-clean gated deploy happens (in-process promote());
    * the engine then serves the DEPLOYED checkpoint: its decode equals
      the offline decode under ``trainer.deployed`` exactly;
    * an injected drift-dirty window provably BLOCKS deployment — a
      recorded rejection (``continual.rejected_dirty``), never a deploy
      from a non-stable interval;
    * the whole run holds ``jit.retraces == 0``, gated by the committed
      ``OBS_BASELINE.json`` zero-tolerance rule."""
    model, v0 = lm
    reg = Registry()
    engine = _engine(lm, registry=reg)
    engine.warmup()
    engine.start()
    trainer = _trainer(lm, reg, deploy_to=engine)
    feed = synthetic_lm_feed(VOCAB, SEQ, 16, seed=0,
                             drift_after=10 * 4 * 4)  # step at interval 10
    try:
        trainer.run(feed, intervals=16)
        snap = reg.snapshot()
        assert snap["continual.deploys"]["value"] >= 1
        assert trainer.deployed is not None
        # the serving side now answers under the deployed checkpoint
        prompt = np.arange(6) % VOCAB
        got = engine.submit(prompt, 6).result(timeout=60)
        assert np.array_equal(got, _ref(model, trainer.deployed, prompt, 6))
        assert not np.array_equal(got, _ref(model, v0, prompt, 6)), \
            "served decode should reflect the trained deploy, not init"
    finally:
        engine.stop()

    # the injected step provably blocked deployment, loudly
    log = trainer.gate.history_log()
    dirty = [e for e in log if e["interval"] >= 10 and
             e["reason"].startswith("drift-dirty")]
    assert dirty, "the injected drift never produced a recorded rejection"
    assert snap["continual.rejected_dirty"]["value"] >= len(dirty)
    assert all(e["kind"] == "stable" for e in log if e["deployed"])
    assert snap["continual.deploys"]["value"] == \
        sum(1 for e in log if e["deployed"])
    assert snap["serve.promotions"]["value"] == \
        snap["continual.deploys"]["value"]
    # every interval closed, was judged once and ended in a deploy or a
    # recorded rejection
    assert snap["continual.intervals"]["value"] == 16
    assert snap["continual.windows"]["value"] == 16 * 4
    assert sum(snap[f"continual.verdicts_{k}"]["value"]
               for k in drift.WINDOW_KINDS) == 16
    assert snap["continual.deploys"]["value"] + \
        snap["continual.deploys_rejected"]["value"] == 16
    assert snap["continual.stream_lag_seconds"]["count"] > 0

    # retrace contract under the committed zero-tolerance rule
    assert snap["jit.retraces"]["value"] == 0
    baseline = drift.load_baseline(os.path.join(_ROOT, "OBS_BASELINE.json"))
    doc = {"config": {"mode": "continual"}, "continual": snap}
    report = drift.diff_docs(doc, copy.deepcopy(doc), baseline=baseline)
    assert not report.drifted
    bumped = copy.deepcopy(doc)
    bumped["continual"]["jit.retraces"]["value"] += 1
    report = drift.diff_docs(doc, bumped, baseline=baseline)
    assert any(m.endswith("jit.retraces") for m in report.drifted_metrics)


def test_continual_deploys_over_promote_rpc(lm):
    """Cross-process deploy path: the trainer's target is a
    ``ServeClient`` — drift-clean checkpoints ride the ``promote`` RPC
    into a served engine, and the service answers under them."""
    model, _ = lm
    reg = Registry()
    with ServeServer(_engine(lm, registry=reg).warmup()) as srv:
        with ServeClient("127.0.0.1", srv.port) as client:
            trainer = _trainer(lm, Registry(), deploy_to=client,
                               history=2, min_history=1)
            trainer.run(synthetic_lm_feed(VOCAB, SEQ, 16, seed=2),
                        intervals=2)
            assert trainer.deployed is not None
            prompt = np.arange(4) % VOCAB
            reply = client.generate(prompt, 5)
    assert reply["ok"]
    assert np.array_equal(np.asarray(reply["tokens"]),
                          _ref(model, trainer.deployed, prompt, 5))
    assert reg.counter("serve.promotions").value == \
        trainer.registry.counter("continual.deploys").value >= 1
    assert reg.counter("jit.retraces").value == 0


def test_deploy_failure_is_recorded_and_training_continues(lm):
    calls = []

    def broken(host_vars):
        calls.append(host_vars)
        raise ConnectionError("deploy target gone")

    reg = Registry()
    trainer = _trainer(lm, reg, deploy_to=broken, history=2, min_history=1)
    trainer.run(synthetic_lm_feed(VOCAB, SEQ, 16, seed=3), intervals=2)
    assert calls, "the gate never tried to deploy"
    snap = reg.snapshot()
    assert snap["continual.deploy_errors"]["value"] == len(calls)
    assert snap["continual.deploys"]["value"] == 0  # intents don't count
    assert snap["continual.intervals"]["value"] == 2  # loop survived
    assert trainer.deployed is None
    log = trainer.gate.history_log()
    assert any(e["reason"].startswith("deploy failed") for e in log)


def test_checkpoint_rolling_keep_and_exact_resume(lm, tmp_path):
    reg = Registry()
    trainer = _trainer(lm, reg, checkpoint_dir=str(tmp_path))
    trainer.config.checkpoint_keep = 2
    trainer.run(synthetic_lm_feed(VOCAB, SEQ, 16, seed=4), intervals=4)
    from distkeras_tpu.utils.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    assert ckpt.steps() == [2, 3]  # rolling keep pruned 0 and 1
    # exact-resume metadata: interval index + the batch offset a
    # replayable feed fast-forwards to (one interval == a fixed count)
    import jax
    v = trainer.model.init(0)
    _, meta = ckpt.restore((v, trainer._optimizer.init(v["params"]),
                            jax.random.PRNGKey(0)))
    assert meta["interval"] == 3
    assert meta["batches_consumed"] == 4 * 4 * 4  # intervals*snap*window

    trainer2 = _trainer(lm, Registry(), checkpoint_dir=str(tmp_path))
    trainer2.run(synthetic_lm_feed(VOCAB, SEQ, 16, seed=4), intervals=2,
                 resume=True)
    log = trainer2.gate.history_log()
    assert [e["interval"] for e in log] == [4, 5]  # continued, not restarted
    assert ckpt.latest_step() == 5
    # batches_consumed stays GLOBAL across restarts (a session-local
    # window counter would record 2*16=32 here and a replayable feed
    # fast-forwarded by it would re-train 4 intervals' worth of batches)
    _, meta2 = ckpt.restore((v, trainer._optimizer.init(v["params"]),
                             jax.random.PRNGKey(0)))
    assert meta2["batches_consumed"] == 6 * 4 * 4


def test_partial_interval_never_reaches_the_gate(lm):
    """A feed that dies (or a stop()) mid-interval must not produce an
    interval edge: its thin loss delta would be skipped by min_count and
    the window could read stable — deploying unvetted weights on the
    way out."""
    feed = synthetic_lm_feed(VOCAB, SEQ, 16, seed=7)
    batches = [next(feed) for _ in range(16 + 6)]  # 1 interval + 1.5 windows
    reg = Registry()
    trainer = _trainer(lm, reg, deploy_to=lambda v: None, history=2,
                       min_history=1)
    trainer.run(iter(batches))
    snap = reg.snapshot()
    assert snap["continual.intervals"]["value"] == 1
    assert snap["continual.verdicts_stable"]["value"] + \
        snap["continual.verdicts_step"]["value"] + \
        snap["continual.verdicts_trend"]["value"] == 1
    assert snap["continual.windows"]["value"] == 5  # the partial trained
    assert len(trainer.gate.history_log()) == 1
    # a feed too short for even ONE window is a loud error, not a no-op
    with pytest.raises(ValueError):
        _trainer(lm, Registry()).run(iter(batches[:2]))


def test_daemon_restart_resumes_exact_stream(lm, tmp_path):
    """Self-healing daemon (ISSUE 9): a crash mid-stream restarts the
    loop from the latest checkpoint with the feed rebuilt at the EXACT
    recorded batch offset — the interval sequence continues to the
    original end, no sample trained twice, every restart a recorded
    ``continual.restarts`` metric."""
    reg = Registry()
    trainer = _trainer(lm, reg, checkpoint_dir=str(tmp_path))
    calls = {"n": 0}
    orig = trainer._run_fn

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 6:  # interval 0 checkpointed; dies inside 1
            raise RuntimeError("injected continual crash")
        return orig(*a, **kw)

    trainer._run_fn = flaky
    offsets = []

    def feed_factory(offset):
        # exact stream resume: a replayable feed fast-forwarded to the
        # checkpointed batch offset (deterministic generator + skip)
        offsets.append(offset)
        f = synthetic_lm_feed(VOCAB, SEQ, 16, seed=0)
        for _ in range(offset):
            next(f)
        return f

    trainer.start(synthetic_lm_feed(VOCAB, SEQ, 16, seed=0), intervals=4,
                  max_restarts=1, feed_factory=feed_factory)
    trainer._thread.join(300)
    assert not trainer._thread.is_alive(), "daemon never finished"
    assert trainer.variables is not None
    # one recorded restart, resumed at the exact offset the checkpoint
    # recorded (1 interval x 4 windows x 4 steps = 16 batches)
    assert reg.counter("continual.restarts").value == 1
    assert offsets == [16]
    # the interval sequence CONTINUED to the original end — 4 total, not
    # 4-more-after-restart
    assert trainer.intervals_done == 4
    assert reg.counter("continual.intervals").value == 4
    assert reg.counter("continual.checkpoints").value == 4


def test_daemon_start_stop_trains_until_stopped(lm):
    reg = Registry()
    trainer = _trainer(lm, reg)
    trainer.start(synthetic_lm_feed(VOCAB, SEQ, 16, seed=5))
    import time
    deadline = time.monotonic() + 60
    while reg.counter("continual.intervals").value < 2:
        assert time.monotonic() < deadline, "daemon never reached interval 2"
        time.sleep(0.01)
    variables = trainer.stop()
    assert variables is not None
    assert reg.counter("continual.intervals").value >= 2
    with pytest.raises(RuntimeError):
        trainer._thread = object()  # simulate still-running
        trainer.start(synthetic_lm_feed(VOCAB, SEQ, 16))


# ---------------------------------------------------------------------------
# obsview --continual
# ---------------------------------------------------------------------------

def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obsview_continual_renders_offline_and_alarms(capsys, tmp_path):
    # a persisted document of the shape the panel reads: one named
    # registry beside the gate's verdict log
    reg = Registry()
    reg.counter("continual.intervals").inc(2)
    reg.counter("continual.deploys").inc(1)
    reg.counter("jit.retraces")
    reg.histogram("continual.loss", LOSS_BUCKETS).observe(2.5)
    reg.histogram("continual.stream_lag_seconds").observe(1e-4)
    doc = {"config": {"mode": "continual"}, "continual": reg.snapshot(),
           "verdicts": [
               {"interval": 1, "kind": "stable", "deploy": False,
                "deployed": False, "reason": "warmup (1/2 intervals)"},
               {"interval": 2, "kind": "stable", "deploy": True,
                "deployed": True, "reason": "drift-clean"}]}
    path = tmp_path / "continual_obs.json"
    path.write_text(json.dumps(doc))
    obsview = _load_obsview()
    rc = obsview.run_continual(str(path))
    out = capsys.readouterr().out
    assert rc == 0
    assert "Continual training" in out
    assert "Window verdicts" in out and "DEPLOYED" in out
    assert "stream lag" in out
    # alarm rendering: dirty window + retraces
    stats = {"continual.window_dirty": {"type": "gauge", "value": 1.0},
             "jit.retraces": {"type": "counter", "value": 2},
             "jit.compiles": {"type": "counter", "value": 3}}
    text = obsview.summarize_continual(stats)
    assert "DRIFT-DIRTY" in text and "RETRACING" in text
    clean = obsview.summarize_continual(
        {"continual.window_dirty": {"type": "gauge", "value": 0.0}})
    assert "DRIFT-DIRTY" not in clean and "RETRACING" not in clean


def test_obsview_continual_live_poll(lm):
    """Live mode: the trainer shares the engine's registry, so one
    ``stats`` RPC reply carries the whole loop next to the SLO surface."""
    obsview = _load_obsview()
    reg = Registry()
    engine = _engine(lm, registry=reg)
    trainer = _trainer(lm, reg, deploy_to=engine, history=2, min_history=1)
    with ServeServer(engine.warmup()) as srv:
        trainer.run(synthetic_lm_feed(VOCAB, SEQ, 16, seed=6), intervals=2)
        rc = obsview.run_continual(f"127.0.0.1:{srv.port}")
    assert rc == 0


def test_obsview_continual_bad_target(capsys):
    obsview = _load_obsview()
    assert obsview.run_continual("/nonexistent/file.json") == 2
