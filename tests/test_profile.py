"""Profiling layer + Chrome-trace export (ISSUE 6).

Covers the retrace sentinel (signature semantics, cold/warm/retrace
states, exactly-once logging, the trainer wiring: zero retraces on warm
steady state, exactly one on a deliberate shape change), memory
watermarks at the heartbeat points, the ``jax.profiler`` capture seam
(one-time announce, exception-safe stop, per-epoch capture from trainer
config), the
``obsview --export-trace`` Chrome Trace Event export (synthetic
two-process round-trip + the acceptance scenario: a real 2-worker async
DynSGD run whose server ``ps.apply`` events re-parse as children of the
worker commit spans that caused them), and the ``jit.retraces`` drift
gate against the committed ``OBS_BASELINE.json``."""

import importlib.util
import io
import json
import logging
import os

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.obs import (ProfileConfig, Registry, RetraceSentinel,
                               drift, records_to_chrome_trace,
                               tree_signature)
from distkeras_tpu.obs import profile as obs_profile
from distkeras_tpu.utils.metrics import MetricsLogger
from tests.test_trainers_sync import COMMON, make_model, toy_problem

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


obsview = _load_obsview()


@pytest.fixture(scope="module")
def ds():
    return toy_problem()


# -- signatures & sentinel ---------------------------------------------------

def test_tree_signature_shapes_and_structure_not_values():
    a = np.zeros((2, 3), np.float32)
    assert tree_signature((a,)) == tree_signature((np.ones((2, 3),
                                                           np.float32),))
    assert tree_signature((a,)) != tree_signature(
        (np.zeros((3, 2), np.float32),))
    assert tree_signature((a,)) != tree_signature(
        (a.astype(np.float64),))
    assert tree_signature(({"x": a},)) != tree_signature(([a],))
    # python scalars contribute their type, never their value (a step
    # counter changing every call is not a retrace)
    assert tree_signature((1,)) == tree_signature((2,))
    assert tree_signature((1,)) != tree_signature((1.5,))


def test_retrace_sentinel_cold_warm_retrace(caplog):
    reg = Registry()
    buf = io.StringIO()
    s = RetraceSentinel("fn", registry=reg, sink=MetricsLogger(buf))
    a = np.zeros((4, 2), np.float32)
    assert s.observe((a,)) == "cold"
    for _ in range(5):
        assert s.observe((a,)) == "warm"
    assert reg.counter("jit.compiles").value == 1
    assert reg.counter("jit.retraces").value == 0
    b = np.zeros((8, 2), np.float32)
    with caplog.at_level(logging.WARNING,
                         logger="distkeras_tpu.obs.profile"):
        assert s.observe((b,)) == "retrace"
        for _ in range(3):   # the new signature is warm from then on
            assert s.observe((b,)) == "warm"
        assert s.observe((a,)) == "warm"  # the old one still is too
    assert reg.counter("jit.retraces").value == 1
    assert reg.counter("jit.compiles").value == 2  # a retrace IS a compile
    warns = [r for r in caplog.records if "retrace" in r.message]
    assert len(warns) == 1  # logged once per offending signature
    recs = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert [r["event"] for r in recs] == ["retrace"]
    assert recs[0]["entry"] == "fn" and recs[0]["retraces"] == 1
    assert recs[0]["signature"]  # the shape/dtype tree hash rides along


def test_retrace_sentinel_observe_key_and_warn_off(caplog):
    # ISSUE 7: entry points with their own program cache (the decode
    # runners) count by CACHE KEY — value-level program changes the
    # shape signature cannot see (temperature, beam width) still count;
    # warn=False keeps counters but silences the per-signature log
    reg = Registry()
    s = RetraceSentinel("decode", registry=reg, warn=False)
    assert s.observe_key((16, 0.0)) == "cold"
    assert s.observe_key((16, 0.0)) == "warm"
    with caplog.at_level(logging.WARNING,
                         logger="distkeras_tpu.obs.profile"):
        assert s.observe_key((16, 0.8)) == "retrace"  # same shapes!
    assert reg.counter("jit.compiles").value == 2
    assert reg.counter("jit.retraces").value == 1
    assert not [r for r in caplog.records if "retrace" in r.message]


def test_generate_tokens_feeds_decode_sentinel():
    from distkeras_tpu.models import generation, zoo
    model = zoo.gpt_lm(vocab_size=16, dim=8, num_heads=2, num_blocks=1,
                       seq_len=16)
    v = model.init(0)
    reg = Registry()
    generation.set_decode_registry(reg)
    try:
        prompt = np.zeros((1, 4), np.int32)
        generation.generate_tokens(model, v, prompt, 2)
        c0 = reg.counter("jit.compiles").value
        assert c0 >= 1
        r0 = reg.counter("jit.retraces").value
        # same config: steady state — no new compile, no retrace
        generation.generate_tokens(model, v, prompt, 2)
        assert reg.counter("jit.compiles").value == c0
        assert reg.counter("jit.retraces").value == r0
        # a VALUE-level program change (temperature) is a new program
        # even though every arg shape is identical
        generation.generate_tokens(model, v, prompt, 2, temperature=0.5)
        assert reg.counter("jit.retraces").value == r0 + 1
    finally:
        generation.set_decode_registry(None)


def test_sentinel_wrap_counts_without_changing_results():
    reg = Registry()
    s = RetraceSentinel("f", registry=reg)
    fn = s.wrap(lambda x: x + 1)
    assert fn(np.float32(1.0)) == 2.0
    assert reg.counter("jit.compiles").value == 1


def test_trainer_steady_state_never_retraces(ds):
    """Warm steady state — repeated train() on unchanged shapes — must
    count exactly one cold compile and ZERO retraces (the acceptance
    ground truth the drift gate protects)."""
    reg = Registry()
    t = dk.SingleTrainer(make_model(), "sgd", **COMMON)
    t.tracer.registry = reg
    t.train(ds)
    t.train(ds)  # second run reuses the compiled program: all warm
    assert reg.counter("jit.compiles").value == 1
    assert reg.counter("jit.retraces").value == 0
    # memory watermarks sampled at the per-epoch heartbeat points
    assert reg.gauge("mem.peak_live_bytes").value > 0
    assert reg.gauge("mem.live_bytes").value > 0
    epochs = [r for r in t.metrics.records if r["event"] == "epoch"]
    assert epochs and all(e["live_bytes"] > 0 for e in epochs)


def test_trainer_retrace_fires_once_on_shape_change(ds, caplog):
    reg = Registry()
    t = dk.SingleTrainer(make_model(), "sgd", **COMMON)
    t.tracer.registry = reg
    t.train(ds)
    t.batch_size = 64  # same program config, new data shapes
    with caplog.at_level(logging.WARNING,
                         logger="distkeras_tpu.obs.profile"):
        t.train(ds)
    assert reg.counter("jit.retraces").value == 1  # once, not per epoch
    warns = [r for r in caplog.records if "retrace" in r.message]
    assert len(warns) == 1
    # the recompile is visible in the span stream, flagged as a retrace
    spans = [r for r in t.metrics.records
             if r["event"] == "span" and r["name"] == "jit_compile"]
    assert any(s.get("retrace") for s in spans)
    # and as a structured retrace record naming the entry point
    retr = [r for r in t.metrics.records if r["event"] == "retrace"]
    assert len(retr) == 1 and "SingleTrainer" in retr[0]["entry"]


def test_predictor_retraces_counted():
    from distkeras_tpu.data.dataset import Dataset
    model = make_model()
    model.variables = model.init(0)
    p = dk.ModelPredictor(model, "features", batch_size=16)
    x = np.random.default_rng(0).random((32, 10)).astype(np.float32)
    p.predict(Dataset({"features": x}))
    p.predict(Dataset({"features": x}))
    assert p._sentinel.compiles == 1  # padded batches: one shape, ever


# -- memory watermarks -------------------------------------------------------

def test_memory_watermarks_track_peak():
    import jax.numpy as jnp
    reg = Registry()
    keep = jnp.ones((256, 256), jnp.float32)
    snap = obs_profile.observe_memory(reg)
    assert snap["live_arrays"] >= 1
    assert snap["live_bytes"] >= keep.nbytes
    assert reg.gauge("mem.live_bytes").value == snap["live_bytes"]
    peak = reg.gauge("mem.peak_live_bytes").value
    assert peak >= snap["live_bytes"]
    del keep
    after = obs_profile.observe_memory(reg)
    # live fell with the deletion; the watermark must NOT fall with it
    assert after["live_bytes"] < snap["live_bytes"]
    assert reg.gauge("mem.peak_live_bytes").value == peak


def test_async_worker_heartbeats_carry_live_bytes(ds, tmp_path):
    run = str(tmp_path / "run.jsonl")
    t = dk.DOWNPOUR(make_model(), "sgd", num_workers=2, mode="async",
                    communication_window=4,
                    **{**COMMON, "num_epoch": 1},
                    metrics=MetricsLogger(run))
    t.train(ds)
    hbs = [r for r in obsview.load_records(run)
           if r["event"] == "heartbeat"]
    assert hbs and all(h["live_bytes"] > 0 for h in hbs)


def test_profile_memory_off_disables_worker_sampling(ds, tmp_path):
    """ProfileConfig(memory=False) must reach the async workers (review
    fix): no per-window ``jax.live_arrays()`` walk, no ``live_bytes``
    on their heartbeats."""
    run = str(tmp_path / "run.jsonl")
    t = dk.DOWNPOUR(make_model(), "sgd", num_workers=2, mode="async",
                    communication_window=4,
                    **{**COMMON, "num_epoch": 1},
                    profile=ProfileConfig(memory=False),
                    metrics=MetricsLogger(run))
    t.train(ds)
    hbs = [r for r in obsview.load_records(run)
           if r["event"] == "heartbeat"]
    assert hbs and all("live_bytes" not in h for h in hbs)


# -- device trace seam -------------------------------------------------------

def test_device_trace_announces_once_and_writes(tmp_path, caplog):
    import jax.numpy as jnp
    d1 = str(tmp_path / "cap")
    with caplog.at_level(logging.INFO, logger="distkeras_tpu.obs.profile"):
        with obs_profile.device_trace(d1):
            jnp.ones((16, 16)).block_until_ready()
        with obs_profile.device_trace(d1):  # same dir: no second announce
            pass
    announces = [r for r in caplog.records if d1 in r.getMessage()]
    assert len(announces) == 1
    assert sum(len(f) for _, _, f in os.walk(d1)) >= 1  # capture landed


def test_device_trace_exception_does_not_leak_session(tmp_path):
    import jax.numpy as jnp
    with pytest.raises(RuntimeError, match="boom"):
        with obs_profile.device_trace(str(tmp_path / "a")):
            raise RuntimeError("boom")
    # a leaked open session would make the next start_trace fail
    with obs_profile.device_trace(str(tmp_path / "b")):
        jnp.ones((4,)).block_until_ready()


def test_profile_trace_delegates_to_seam(tmp_path, caplog):
    from distkeras_tpu.utils.metrics import profile_trace
    d = str(tmp_path / "legacy")
    with caplog.at_level(logging.INFO, logger="distkeras_tpu.obs.profile"):
        with profile_trace(d):
            pass
    assert any(d in r.getMessage() for r in caplog.records)


def test_per_epoch_capture_from_trainer_config(ds, tmp_path):
    tdir = str(tmp_path / "traces")
    t = dk.SingleTrainer(make_model(), "sgd",
                         profile={"trace_dir": tdir, "trace_epochs": (1,)},
                         **COMMON)
    t.train(ds)
    assert os.path.isdir(os.path.join(tdir, "epoch1"))
    assert not os.path.exists(os.path.join(tdir, "epoch0"))


def test_profile_config_resolve():
    assert ProfileConfig.resolve(None).trace_dir is None
    assert ProfileConfig.resolve("/tmp/x").trace_dir == "/tmp/x"
    pc = ProfileConfig.resolve({"trace_epochs": None, "memory": False})
    assert pc.trace_epochs is None and not pc.memory
    with pytest.raises(TypeError):  # the fence-per-call split is gone
        ProfileConfig.resolve({"step_split": True})
    assert ProfileConfig.resolve(pc) is pc
    with pytest.raises(TypeError):
        ProfileConfig.resolve(3)
    assert ProfileConfig(trace_dir="/x", trace_epochs=None).trace_epoch(7)
    assert not ProfileConfig().trace_epoch(0)  # no trace_dir: never


# -- Chrome trace export -----------------------------------------------------

def _two_process_records():
    """Synthetic two-worker stream: each worker's commit span plus the
    server's apply span that ADOPTED its trace over the wire (PR 5
    shapes, exactly what a real async run writes)."""
    return [
        {"ts": 10.0, "event": "span", "name": "ps.commit",
         "path": "ps.commit", "depth": 0, "seconds": 0.5,
         "trace_id": "w0", "span_id": "w0.s1", "worker": 0},
        {"ts": 9.9, "event": "span", "name": "ps.apply",
         "path": "ps.apply", "depth": 0, "seconds": 0.1,
         "trace_id": "w0", "span_id": "w0.s2", "parent_span": "w0.s1",
         "worker": 0},
        {"ts": 10.4, "event": "span", "name": "ps.commit",
         "path": "ps.commit", "depth": 0, "seconds": 0.3,
         "trace_id": "w1", "span_id": "w1.s1", "worker": 1},
        {"ts": 10.35, "event": "span", "name": "ps.apply",
         "path": "ps.apply", "depth": 0, "seconds": 0.05,
         "trace_id": "w1", "span_id": "w1.s2", "parent_span": "w1.s1",
         "worker": 1},
        {"ts": 10.0, "event": "heartbeat", "worker_id": 0, "window": 1,
         "epoch": 0, "gap_s": 0.5, "mean_loss": 0.3, "live_bytes": 2048},
        {"ts": 11.0, "event": "epoch", "trainer": "DynSGD", "epoch": 0,
         "mean_loss": 0.3, "epoch_seconds": 1.0, "samples_per_sec": 100.0},
    ]


def test_export_round_trip_linkage_survives(tmp_path):
    """Satellite acceptance: synthesize a two-process span JSONL, export,
    re-parse the Chrome JSON, and assert parent/child and pid/tid
    linkage survives."""
    run = str(tmp_path / "run.jsonl")
    with open(run, "w") as f:
        for r in _two_process_records():
            f.write(json.dumps(r) + "\n")
    out = str(tmp_path / "trace.json")
    assert obsview.main([run, "--export-trace", out]) == 0
    with open(out) as f:
        doc = json.load(f)  # valid JSON: the tier-1 smoke contract
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    for w in ("w0", "w1"):
        commit = next(e for e in xs if e["name"] == "ps.commit"
                      and e["args"]["trace_id"] == w)
        apply_ = next(e for e in xs if e["name"] == "ps.apply"
                      and e["args"]["trace_id"] == w)
        # same process row (the worker), different thread rows
        assert apply_["pid"] == commit["pid"]
        assert apply_["tid"] != commit["tid"]
        # parent/child survived, and the child nests temporally inside
        assert apply_["args"]["parent_span"] == commit["args"]["span_id"]
        assert commit["ts"] <= apply_["ts"] + 1e-6
        assert apply_["ts"] + apply_["dur"] <= \
            commit["ts"] + commit["dur"] + 1e-6
    # distinct pids per worker, named for Perfetto's process rail
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"worker 0", "worker 1", "run"} <= names
    w0 = next(e for e in xs if e["args"].get("trace_id") == "w0")
    w1 = next(e for e in xs if e["args"].get("trace_id") == "w1")
    assert w0["pid"] != w1["pid"]
    # cross-thread flow arrows pair up by id
    starts = {e["id"]: e for e in evs if e.get("ph") == "s"}
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert finishes and all(e["id"] in starts for e in finishes)
    # heartbeats as instants, memory as counter track, epochs on the run
    assert any(e.get("ph") == "i" and e["name"] == "heartbeat"
               for e in evs)
    assert any(e.get("ph") == "C" and e["name"] == "live_bytes"
               for e in evs)
    assert any(e.get("ph") == "X" and e.get("cat") == "epoch" for e in evs)
    # rebased: nothing before t=0
    assert min(e["ts"] for e in evs if "ts" in e) >= 0


def test_export_tolerates_hostile_records():
    records = [{"event": "span", "ts": "NaN", "seconds": 0.1},
               {"event": "span"},  # no ts at all
               {"event": "heartbeat", "worker_id": 0, "ts": 1.0,
                "gap_s": "Infinity"},
               {"event": "epoch", "ts": 2.0, "epoch_seconds": "NaN"}]
    doc = records_to_chrome_trace(records)
    json.dumps(doc)  # whatever survived must still serialize
    assert all(e["ts"] >= 0 for e in doc["traceEvents"] if "ts" in e)


def test_export_trace_rejects_snapshot_files(tmp_path):
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps(
        {"ps.commits": {"type": "counter", "value": 1.0}}))
    assert obsview.main([str(snap), "--export-trace",
                         str(tmp_path / "o.json")]) == 2


# -- acceptance: real async run -> linked Chrome trace + retrace gate --------

def test_async_dynsgd_export_and_retrace_gate(ds, tmp_path):
    """ISSUE 6 acceptance: ``obsview --export-trace`` on a real 2-worker
    async DynSGD run produces a Chrome-trace JSON where a server
    ``ps.apply`` event is a child of the worker window (commit) span that
    caused it, and ``jit.retraces`` == 0 after warmup (one cold compile),
    drift-gated against the committed ``OBS_BASELINE.json``."""
    run = str(tmp_path / "run.jsonl")
    reg = Registry()
    t = dk.DynSGD(make_model(), "sgd", num_workers=2, mode="async",
                  communication_window=4, **COMMON,
                  metrics=MetricsLogger(run))
    t.tracer.registry = reg
    t.train(ds)
    # retrace ground truth: the shared window program compiled once,
    # cold; every subsequent window was warm
    assert reg.counter("jit.compiles").value == 1
    assert reg.counter("jit.retraces").value == 0

    out = str(tmp_path / "trace.json")
    assert obsview.main([run, "--export-trace", out]) == 0
    with open(out) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    commits = {e["args"]["span_id"]: e for e in xs
               if e["name"] == "ps.commit"}
    applies = [e for e in xs if e["name"] == "ps.apply"]
    linked = [(a, commits[a["args"]["parent_span"]]) for a in applies
              if a["args"].get("parent_span") in commits]
    assert linked, "no server apply linked to a worker commit span"
    for a, c in linked:
        assert a["pid"] == c["pid"]      # child lives on the worker's row
        assert a["tid"] != c["tid"]      # on the server thread rail
    # both workers present as named processes
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"worker 0", "worker 1"} <= names

    # the committed OBS_BASELINE.json gates jit.retraces: equal counts
    # compare clean, ANY increase is drift
    bl = drift.load_baseline(os.path.join(_ROOT, "OBS_BASELINE.json"))

    def doc_of(retraces):
        r = Registry()
        r.counter("jit.compiles").inc()
        if retraces:
            r.counter("jit.retraces").inc(retraces)
        else:
            r.counter("jit.retraces")
        return {"config": {"workers": 2}, "trainer": r.snapshot()}

    clean = drift.diff_docs(doc_of(0), doc_of(0), baseline=bl)
    assert not clean.drifted
    gate = [f for f in clean.findings
            if f["metric"] == "trainer/jit.retraces"]
    assert gate and not gate[0].get("skipped")  # compared, not skipped
    bad = drift.diff_docs(doc_of(0), doc_of(1), baseline=bl)
    assert "trainer/jit.retraces" in bad.drifted_metrics
