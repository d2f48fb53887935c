"""Telemetry subsystem (ISSUE 2): instruments, spans, STATS RPC, obsview.

Covers the obs core (counter/gauge/histogram semantics and merge, span
nesting + JSONL round-trip, Prometheus exposition), the instrumented PS
stack (live ``stats`` RPC matching the server's ground truth, bounded
staleness memory), the MetricsLogger JSON hardening, the no-bare-print
gate, and ``scripts/obsview.py`` end to end — synthetic JSONL plus real
``SingleTrainer`` / async-PS runs (the acceptance criterion)."""

import importlib.util
import io
import json
import math
import os
import threading

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.obs import (Counter, Gauge, Histogram, Registry,
                               SpanTracer, snapshot_quantile,
                               to_prometheus_text)
from distkeras_tpu.ps import (DeltaParameterServer, DynSGDParameterServer,
                              PSClient, SocketParameterServer)
from distkeras_tpu.utils.metrics import MetricsLogger, json_safe
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


# -- instrument semantics ----------------------------------------------------

def test_counter_semantics():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_semantics():
    g = Gauge("g")
    g.set(10)
    g.inc(2)
    g.dec()
    assert g.value == 11.0


def test_histogram_buckets_and_quantiles():
    h = Histogram("h", buckets=(1, 2, 4))
    for v in (0.5, 1.5, 3, 100):
        h.observe(v)
    # cumulative-le semantics: one obs per bucket + one in +Inf
    assert h.counts == [1, 1, 1, 1]
    assert h.count == 4 and h.sum == 105.0
    assert 0 <= h.quantile(0.25) <= 1
    assert h.quantile(1.0) == 4  # capped at the top finite bound
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2, 1))


def test_histogram_merge_and_snapshot_roundtrip():
    a = Histogram("h", buckets=(1, 10))
    b = Histogram("h", buckets=(1, 10))
    for v in (0.5, 5):
        a.observe(v)
    b.observe(20)
    b.merge(a)                      # live merge
    assert b.counts == [1, 1, 1] and b.count == 3 and b.sum == 25.5
    b.merge(a.snapshot())           # snapshot merge
    assert b.count == 5
    with pytest.raises(ValueError):
        b.merge(Histogram("other", buckets=(1, 2)))


def test_registry_get_or_create_and_type_conflict():
    r = Registry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    assert r.names() == ["x"]


def test_registry_snapshot_merge():
    r1, r2 = Registry(), Registry()
    r1.counter("c").inc(2)
    r2.counter("c").inc(3)
    r1.gauge("g").set(1)
    r2.gauge("g").set(7)
    r1.histogram("h", (1, 2)).observe(0.5)
    r2.histogram("h", (1, 2)).observe(1.5)
    m = Registry.merge_snapshots(r1.snapshot(), r2.snapshot())
    assert m["c"]["value"] == 5
    assert m["g"]["value"] == 7       # gauges: last value wins
    assert m["h"]["counts"] == [1, 1, 0] and m["h"]["count"] == 2
    # merge must not mutate its inputs
    assert r1.snapshot()["c"]["value"] == 2


def test_counter_thread_safety():
    c = Counter("c")

    def spin():
        for _ in range(1000):
            c.inc()
    ts = [threading.Thread(target=spin) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000


def test_prometheus_exposition():
    r = Registry()
    r.counter("ps.commits").inc(3)
    r.gauge("ps.inflight").set(2)
    r.histogram("rtt", (0.1, 1.0)).observe(0.5)
    text = to_prometheus_text(r)
    assert "# TYPE ps_commits_total counter" in text
    assert "ps_commits_total 3" in text
    assert "ps_inflight 2" in text
    assert 'rtt_bucket{le="0.1"} 0' in text
    assert 'rtt_bucket{le="+Inf"} 1' in text
    assert "rtt_count 1" in text


# -- spans -------------------------------------------------------------------

def test_span_nesting_jsonl_roundtrip():
    buf = io.StringIO()
    tracer = SpanTracer(MetricsLogger(buf))
    with tracer.span("outer", tag="t"):
        with tracer.span("inner"):
            pass
        assert tracer.depth == 1
    recs = [json.loads(l) for l in buf.getvalue().splitlines()]
    inner, outer = recs               # inner closes (and logs) first
    assert inner["path"] == "outer/inner" and inner["depth"] == 1
    assert outer["path"] == "outer" and outer["depth"] == 0
    assert outer["tag"] == "t"
    assert outer["seconds"] >= inner["seconds"] >= 0


def test_span_records_on_exception():
    buf = io.StringIO()
    tracer = SpanTracer(MetricsLogger(buf))
    with pytest.raises(RuntimeError):
        with tracer.span("doomed"):
            raise RuntimeError("boom")
    rec = json.loads(buf.getvalue())
    assert rec["name"] == "doomed" and rec["error"] is True
    assert tracer.depth == 0          # stack unwound


def test_span_registry_histogram():
    r = Registry()
    tracer = SpanTracer(None, registry=r)
    with tracer.span("step"):
        pass
    assert r.get("span.step.seconds").count == 1


# -- MetricsLogger JSON hardening (satellite) --------------------------------

def test_json_safe_ndarray_and_nonfinite():
    small = np.arange(4, dtype=np.float32)
    big = np.ones((100, 10))
    out = json_safe({"a": small, "b": big, "nan": float("nan"),
                     "inf": float("inf"), "ninf": -np.inf,
                     "i": np.int64(3), "arr_nan": np.array([1.0, np.nan])})
    assert out["a"] == [0.0, 1.0, 2.0, 3.0]
    assert out["b"]["shape"] == [100, 10] and out["b"]["mean"] == 1.0
    assert out["nan"] == "NaN" and out["inf"] == "Infinity"
    assert out["ninf"] == "-Infinity" and out["i"] == 3
    assert out["arr_nan"] == [1.0, "NaN"]
    # strictly valid JSON — would raise on bare NaN/Infinity tokens
    parsed = json.loads(json.dumps(out, allow_nan=False))
    assert parsed["nan"] == "NaN"


def test_metrics_logger_writes_valid_json_for_hostile_fields():
    buf = io.StringIO()
    m = MetricsLogger(buf)
    m.log("weird", arr=np.ones((3, 3)), loss=float("nan"),
          big=np.zeros(1000))
    rec = json.loads(buf.getvalue())  # must parse
    assert rec["loss"] == "NaN"
    assert rec["big"]["shape"] == [1000]
    # in-memory records keep raw values (benchmarks read them back)
    assert isinstance(m.records[-1]["arr"], np.ndarray)


def test_metrics_logger_concurrent_lines_stay_whole():
    buf = io.StringIO()
    m = MetricsLogger(buf)

    def spin(k):
        for i in range(200):
            m.log("beat", worker=k, i=i)
    ts = [threading.Thread(target=spin, args=(k,)) for k in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    lines = buf.getvalue().splitlines()
    assert len(lines) == 800
    assert all(json.loads(l)["event"] == "beat" for l in lines)


# -- no bare prints in library code (satellite) ------------------------------
# PR 2's one-off AST gate lived here; ISSUE 3 migrated it into the dklint
# ``bare-print`` rule, enforced repo-wide by
# tests/test_analysis.py::test_repo_is_dklint_clean — one analysis
# framework, not two.


# -- instrumented PS stack ---------------------------------------------------

def _tree(v):
    return {"params": [{"w": np.asarray(v, dtype=np.float32)}], "state": [{}]}


def test_dynsgd_staleness_bounded_and_histogrammed():
    ps = DynSGDParameterServer(_tree([0.0]), num_workers=2)
    n = ps.staleness_keep + 100
    for i in range(n):
        ps.handle_commit(_tree([0.0]), {"last_update": max(0, i - 3),
                                        "worker_id": i % 2})
    # the verbatim window is bounded; the histogram saw every commit
    assert len(ps.staleness_seen) == ps.staleness_keep
    h = ps.registry.get("ps.staleness")
    assert h.count == n
    assert ps.registry.get("ps.staleness.worker0").count == n // 2
    assert ps.registry.get("ps.commits").value == n


def test_stats_rpc_matches_ground_truth(devices):
    """Live ``STATS`` polling of a running SocketParameterServer returns
    commit/pull counters and a staleness histogram matching the server's
    actual state (acceptance criterion)."""
    ps = DynSGDParameterServer(_tree([0.0, 0.0]), num_workers=2)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, 0) as c:
            for i in range(5):
                _center, seen = c.pull()
                c.commit(_tree([1.0, 0.0]), last_update=max(0, seen - 2))
            reply = c.stats()
    assert reply["server"] == "DynSGDParameterServer"
    assert reply["num_updates"] == ps.num_updates == 5
    assert reply["commits_by_worker"] == {0: 5} or \
        reply["commits_by_worker"] == {"0": 5}  # msgpack int keys survive
    stats = reply["stats"]
    assert stats["ps.commits"]["value"] == 5
    assert stats["ps.pulls"]["value"] == 5
    hist = stats["ps.staleness"]
    assert hist["count"] == len(list(ps.staleness_seen)) == 5
    assert hist["sum"] == sum(ps.staleness_seen)
    assert stats["ps.apply_seconds"]["count"] == 5
    # wire accounting: the snapshot is taken after the stats REQUEST is
    # received but before its reply is sent, so recv leads sent by one
    assert stats["net.msgs_recv"]["value"] == \
        stats["net.msgs_sent"]["value"] + 1
    assert stats["net.bytes_sent"]["value"] > 0
    # connection gauge returned to zero after the client closed
    assert ps.registry.get("ps.connections").value == 0


def test_stats_rpc_while_commits_in_flight():
    """STATS is answerable mid-run: concurrent committers + a poller.
    Each committer keeps a registry of its own; merged, they count every
    RPC of the fleet once."""
    ps = DeltaParameterServer(_tree([0.0]), num_workers=4)
    replies = []
    regs = [Registry() for _ in range(4)]
    with SocketParameterServer(ps) as server:
        def hammer(k):
            with PSClient("127.0.0.1", server.port, k,
                          registry=regs[k]) as c:
                c.pull()
                for _ in range(20):
                    c.commit(_tree([1.0]))
        ts = [threading.Thread(target=hammer, args=(k,)) for k in range(4)]
        [t.start() for t in ts]
        with PSClient("127.0.0.1", server.port, 99) as poller:
            replies.append(poller.stats())
        [t.join() for t in ts]
        with PSClient("127.0.0.1", server.port, 99) as poller:
            replies.append(poller.stats())
    mid, final = replies
    assert 0 <= mid["stats"]["ps.commits"]["value"] <= 80
    assert final["stats"]["ps.commits"]["value"] == 80
    assert final["num_updates"] == 80
    merged = Registry.merge_snapshots(*(r.snapshot() for r in regs))
    assert merged["ps.client.rtt_seconds"]["count"] == 4 * (1 + 20)


def test_client_reconnect_counter():
    reg = Registry()
    ps = DeltaParameterServer(_tree([0.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        c = PSClient("127.0.0.1", server.port, 0, registry=reg)
        try:
            c.pull()
            c.sock.close()  # simulate a dropped connection
            c.pull()        # idempotent read reconnects transparently
        finally:
            c.close()
    assert reg.get("ps.client.reconnects").value == 1
    assert reg.get("ps.client.rtt_seconds").count >= 2


# -- obsview -----------------------------------------------------------------

def _synthetic_records():
    recs = [
        {"ts": 1.0, "event": "epoch", "trainer": "SingleTrainer", "epoch": 0,
         "mean_loss": 0.9, "epoch_seconds": 2.0, "samples_per_sec": 500.0},
        {"ts": 3.0, "event": "epoch", "trainer": "SingleTrainer", "epoch": 1,
         "mean_loss": 0.5, "epoch_seconds": 1.0, "samples_per_sec": 1000.0},
        {"ts": 3.1, "event": "span", "name": "jit_compile",
         "path": "train/jit_compile", "depth": 1, "seconds": 1.5},
        {"ts": 3.2, "event": "span", "name": "train", "path": "train",
         "depth": 0, "seconds": 3.2},
        {"ts": 2.0, "event": "heartbeat", "worker": 0, "window": 3,
         "epoch": 0, "mean_loss": 0.7},
        {"ts": 1.0, "event": "ps_stats", "num_updates": 4,
         "commits_by_worker": {"0": 4},
         "stats": {"ps.commits": {"type": "counter", "value": 4},
                   "ps.staleness": {"type": "histogram",
                                    "bounds": [0, 1, 2],
                                    "counts": [2, 1, 1, 0],
                                    "sum": 4.0, "count": 4}}},
    ]
    return recs


def test_obsview_summary_synthetic(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        for r in _synthetic_records():
            f.write(json.dumps(r) + "\n")
    out = obsview.summarize(obsview.load_records(path))
    assert "Per-epoch" in out and "SingleTrainer" in out
    assert "Throughput timeline" in out
    assert "Staleness distribution" in out and "commits: 4" in out
    assert "Top spans" in out and "jit_compile" in out
    assert "Worker heartbeats" in out


def test_obsview_main_and_prometheus(tmp_path, capsys):
    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        for r in _synthetic_records():
            f.write(json.dumps(r) + "\n")
    assert obsview.main([path]) == 0
    assert "Per-epoch" in capsys.readouterr().out
    assert obsview.main([path, "--prometheus"]) == 0
    out = capsys.readouterr().out
    assert "ps_commits_total 4" in out and "ps_staleness_bucket" in out


def test_obsview_live_ps_poll(capsys):
    ps = DynSGDParameterServer(_tree([0.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            c.commit(_tree([1.0]), last_update=0)
        assert obsview.main(["--ps", f"127.0.0.1:{server.port}"]) == 0
        live = capsys.readouterr().out
        assert "Live PS" in live and "DynSGDParameterServer" in live
        assert "ps.commits: 1" in live
        assert obsview.main(["--ps", f"127.0.0.1:{server.port}",
                             "--prometheus"]) == 0
        assert "ps_commits_total 1" in capsys.readouterr().out


def test_obsview_live_fleet_liveness(capsys):
    """ISSUE 9 satellite: the live ``--ps`` view surfaces per-worker
    liveness (last-seen age, generation, eviction/respawn/join/tombstone
    tallies) so a stalled or self-healing fleet is visible IN-run — the
    old end-of-run-only retry path had no such window."""
    ps = DynSGDParameterServer(_tree([0.0]), num_workers=2)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, worker_id=0) as c:
            c.commit(_tree([1.0]), last_update=0)
        ps.evict_worker(0)
        ps.register_respawn(0)
        ps.register_join(1)
        assert obsview.main(["--ps", f"127.0.0.1:{server.port}"]) == 0
        live = capsys.readouterr().out
    assert "Fleet liveness" in live
    assert "evictions 1" in live and "respawns 1" in live
    assert "joins 1" in live
    assert "never" in live  # worker 1 joined but has not committed yet


def test_obsview_tolerates_nonfinite_string_coercions(tmp_path):
    """A diverged run logs mean_loss=NaN; json_safe writes the string
    "NaN" — obsview must render it, not crash (it exists for exactly
    these pathological runs)."""
    recs = [{"ts": 1.0, "event": "epoch", "trainer": "SingleTrainer",
             "epoch": 0, "mean_loss": "NaN", "epoch_seconds": "Infinity",
             "samples_per_sec": "NaN"},
            {"ts": 2.0, "event": "epoch", "trainer": "SingleTrainer",
             "epoch": 1, "mean_loss": 0.5, "epoch_seconds": 1.0,
             "samples_per_sec": 100.0}]
    out = obsview.summarize(recs)
    assert "nan" in out.lower()
    assert "Throughput timeline" in out
    assert obsview._num("NaN") != obsview._num("NaN")  # NaN round-trip
    assert obsview._num("-Infinity") == float("-inf")
    assert obsview._num(None, 0.0) == 0.0


def test_quantile_estimates():
    snap = {"type": "histogram", "bounds": [0, 1, 2, 4],
            "counts": [0, 10, 0, 0, 0], "sum": 10.0, "count": 10}
    assert 0 < snapshot_quantile(snap, 0.5) <= 1
    assert snapshot_quantile({"type": "histogram", "bounds": [1],
                              "counts": [0, 0], "sum": 0, "count": 0},
                             0.5) == 0.0


# -- end-to-end: real runs through obsview (acceptance criterion) ------------

def test_obsview_on_real_single_and_async_runs(ds, tmp_path, capsys):
    """`obsview.py <jsonl>` over a real SingleTrainer run and a real async
    PS trainer run on CPU: per-epoch summary, staleness distribution and
    top-spans table all present and consistent."""
    single = str(tmp_path / "single.jsonl")
    t1 = dk.SingleTrainer(make_model(), "sgd", **COMMON,
                          metrics=MetricsLogger(single))
    t1.train(ds)
    assert obsview.main([single]) == 0
    out = capsys.readouterr().out
    assert "Per-epoch" in out and "SingleTrainer" in out
    assert "Top spans" in out and "train" in out
    # compile split out: a jit_compile span is in the stream
    assert "jit_compile" in out

    run = str(tmp_path / "async.jsonl")
    t2 = dk.DynSGD(make_model(), "sgd", num_workers=4, mode="async",
                   communication_window=4, **COMMON,
                   metrics=MetricsLogger(run))
    t2.train(ds)
    assert obsview.main([run]) == 0
    out = capsys.readouterr().out
    assert "Per-epoch" in out and "DynSGD" in out
    assert "Staleness distribution" in out
    assert "Worker heartbeats" in out
    # ground truth agreement: the stream's ps_stats matches trainer.ps_stats
    recs = obsview.load_records(run)
    stats = [r for r in recs if r["event"] == "ps_stats"][-1]
    assert stats["num_updates"] == t2.ps_stats["num_updates"]
    assert stats["stats"]["ps.staleness"]["count"] == \
        len(t2.ps_stats["staleness_seen"])
    hbs = [r for r in recs if r["event"] == "heartbeat"]
    assert len(hbs) == t2.ps_stats["num_updates"]
    epochs = [r for r in recs if r["event"] == "epoch"]
    assert len(epochs) == COMMON["num_epoch"]
    assert epochs[-1]["mean_loss"] < epochs[0]["mean_loss"]


def test_async_epoch_records_scoped_per_run(ds, tmp_path):
    """Repeated train() on one async trainer: run 2's epoch records must
    not absorb run 1's heartbeats (same epoch indices, earlier
    timestamps) into their wall-clock window."""
    kw = dict(COMMON, num_epoch=1)
    t = dk.DOWNPOUR(make_model(), "sgd", num_workers=2, mode="async",
                    communication_window=4, **kw)
    t.train(ds)
    wall1 = t.training_time
    import time as _time
    _time.sleep(1.0)  # an inter-run gap a leaky window would absorb
    t.train(ds)
    epochs = [r for r in t.metrics.records if r["event"] == "epoch"]
    assert len(epochs) == 2  # one per run, same epoch index 0
    # the second run's epoch window is bounded by ITS wall time, not the
    # gap back to run 1's heartbeats
    assert epochs[-1]["epoch_seconds"] <= t.training_time + 0.1
    assert epochs[-1]["epoch_seconds"] < wall1 + 1.0


def test_streaming_instruments(tmp_path):
    from distkeras_tpu.data.streaming import ShardedFileDataset
    from distkeras_tpu.data.dataset import Dataset
    reg = obs.default_registry()
    before = reg.counter("stream.batches").value
    data = Dataset({"x": np.arange(64, dtype=np.float32).reshape(32, 2),
                    "y": np.arange(32, dtype=np.int32)})
    src = ShardedFileDataset.write(data, str(tmp_path / "sh"),
                                   rows_per_shard=8)
    batches = list(src.batches(["x", "y"], 4, engine="thread"))
    assert len(batches) == 8
    assert reg.counter("stream.batches").value - before == 8
    assert reg.counter("stream.stall_seconds").value >= 0
