"""Scenario harness (ISSUE 17): seeded open-loop trace generators
(determinism + shape goldens + replay round-trip), SLO-attainment
accounting units (``hist_fraction_le`` exactness on bucket bounds, the
:class:`PhaseAccountant` interval math), autoscaler hysteresis as a
pure ``decide()`` unit plus the ``tick()`` action wiring over a real
two-engine fleet, the tier-1 open-loop runner smoke (exact
``dispatched == completed + rejected + timeouts`` accounting, client
deadline timeouts, recovery stamping, ``jit.retraces == 0``), the
``obsview --scenario`` panel over a persisted ``row.scenarios``
document (an injected attainment regression fails ``obsview --diff``
with exit 1), and the slow chaos acceptance: a REAL engine subprocess
killed with SIGKILL mid-trace while the fleet keeps serving."""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.models import zoo
from distkeras_tpu.obs import Registry
from distkeras_tpu.scenario import (AutoScaler, AutoscalePolicy,
                                    LengthModel, PhaseAccountant,
                                    PrefixMix, SCENARIO_COUNTERS,
                                    SCENARIO_HISTOGRAMS, SLOTarget,
                                    ScenarioRunner, Signals, build_prompt,
                                    diurnal_trace, hist_fraction_le,
                                    poisson_trace, precreate_metrics,
                                    replay_trace, save_trace, spike_trace)
from distkeras_tpu.serve import (DecodeEngine, RouterConfig, ServeClient,
                                 ServeConfig, ServeRouter, ServeServer)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, SEQ = 32, 48
BLOCK = 8


@pytest.fixture(scope="module")
def lm():
    model = zoo.gpt_lm(vocab_size=VOCAB, dim=16, num_heads=2,
                       num_blocks=1, seq_len=SEQ)
    return model, model.init(0)


def _engine(lm, registry=None, **kw):
    model, v = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 16)
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("prefill_buckets", (BLOCK * 2, SEQ))
    kw.setdefault("prefix_cache", True)
    kw.setdefault("prefix_cache_mb", 8.0)
    kw.setdefault("prefix_block", BLOCK)
    return DecodeEngine(model, v, ServeConfig(**kw),
                        registry=registry if registry is not None
                        else Registry()).warmup()


def _router(servers, **cfg_kw):
    cfg_kw.setdefault("affinity_block", BLOCK)
    cfg_kw.setdefault("stats_interval_s", 30.0)
    cfg_kw.setdefault("kv_fabric", False)
    return ServeRouter([("127.0.0.1", s.port) for s in servers],
                       config=RouterConfig(**cfg_kw)).start()


# ---------------------------------------------------------------------------
# trace generators: determinism + shape goldens
# ---------------------------------------------------------------------------

def test_poisson_trace_seeded_determinism():
    a = poisson_trace(50.0, 2.0, seed=7)
    b = poisson_trace(50.0, 2.0, seed=7)
    assert a == b  # frozen dataclasses: full bit-exact schedule equality
    c = poisson_trace(50.0, 2.0, seed=8)
    assert a != c
    # rate golden: ~100 expected, Poisson sd ~10
    assert 50 < len(a.arrivals) < 160
    assert all(0 <= x.t < 2.0 for x in a.arrivals)
    with pytest.raises(ValueError):
        poisson_trace(0.0, 1.0)


def test_diurnal_trace_shape():
    spec = diurnal_trace(10.0, 200.0, 10.0, seed=3)
    assert spec.phases == ["night", "ramp_up", "peak", "ramp_down",
                           "evening"]
    counts = spec.counts_by_phase()
    # sin^2 day: the peak window must dominate the night trough
    per_s = {p: counts[p] / w for p, w in
             (("night", 2.5), ("peak", 2.0), ("evening", 2.5))}
    assert per_s["peak"] > 3 * per_s["night"]
    assert per_s["peak"] > 3 * per_s["evening"]
    # phase attribution consistent with the bound map
    assert all(a.phase == spec.phase_at(a.t) for a in spec.arrivals)
    assert spec == diurnal_trace(10.0, 200.0, 10.0, seed=3)
    with pytest.raises(ValueError):
        diurnal_trace(50.0, 10.0, 10.0)  # base > peak


def test_spike_trace_shape():
    spec = spike_trace(20.0, 300.0, 6.0, spike_start=2.0,
                       spike_duration=1.0, seed=11)
    assert spec.phases == ["pre", "spike", "post"]
    counts = spec.counts_by_phase()
    assert counts["spike"] / 1.0 > 4 * (counts["pre"] / 2.0)
    assert all(2.0 <= a.t < 3.0 for a in spec.arrivals
               if a.phase == "spike")
    with pytest.raises(ValueError):
        spike_trace(20.0, 300.0, 6.0, spike_start=5.5,
                    spike_duration=1.0)  # window leaves the trace


def test_heavy_tail_lengths_and_prefix_mix():
    lens = LengthModel(prompt_median=12, new_median=8, prompt_sigma=0.8,
                       new_sigma=0.5, prompt_min=4, prompt_max=40,
                       new_min=2, new_max=20)
    spec = poisson_trace(400.0, 2.0, seed=5, lengths=lens,
                         mix=PrefixMix(groups=6, share=0.7))
    pl = np.array([a.prompt_len for a in spec.arrivals])
    nt = np.array([a.new_tokens for a in spec.arrivals])
    assert pl.min() >= 4 and pl.max() <= 40
    assert nt.min() >= 2 and nt.max() <= 20
    assert len(np.unique(pl)) > 5  # actually heavy-tailed, not fixed
    g = np.array([a.group for a in spec.arrivals])
    share = (g >= 0).mean()
    assert 0.55 < share < 0.85  # ~0.7 grouped
    grouped = g[g >= 0]
    # power-law popularity: rank 0 strictly the most popular group
    top = np.bincount(grouped, minlength=6)
    assert top[0] == top.max() and top[0] > top[-1]
    # sigma 0 -> fixed lengths
    fixed = poisson_trace(50.0, 1.0, seed=5, lengths=LengthModel())
    assert {a.prompt_len for a in fixed.arrivals} == {12}


def test_replay_round_trip(tmp_path):
    spec = spike_trace(20.0, 120.0, 4.0, spike_start=1.0,
                       spike_duration=1.0, seed=13,
                       lengths=LengthModel(prompt_sigma=0.5),
                       mix=PrefixMix(groups=4, share=0.5))
    path = str(tmp_path / "trace.jsonl")
    save_trace(spec, path)
    back = replay_trace(path)
    assert back.arrivals == spec.arrivals  # bit-exact timestamps
    assert back.phase_bounds == spec.phase_bounds
    assert back.duration_s == spec.duration_s
    # a shard-assembled log (shuffled lines) re-sorts into schedule order
    with open(path) as f:
        header, *rows = f.read().splitlines()
    with open(path, "w") as f:
        f.write("\n".join([header] + rows[::-1]) + "\n")
    assert replay_trace(path).arrivals == spec.arrivals
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"schema": "other/v0"}) + "\n")
    with pytest.raises(ValueError):
        replay_trace(str(bad))


def test_build_prompt_shared_prefix_determinism():
    from distkeras_tpu.scenario.traces import Arrival
    a1 = Arrival(t=0.0, phase="p", prompt_len=16, new_tokens=4, group=2)
    a2 = Arrival(t=1.0, phase="p", prompt_len=20, new_tokens=4, group=2)
    u = Arrival(t=2.0, phase="p", prompt_len=16, new_tokens=4, group=-1)
    p1 = build_prompt(a1, 0, VOCAB, prefix_len=8)
    p2 = build_prompt(a2, 1, VOCAB, prefix_len=8)
    assert np.array_equal(p1[:8], p2[:8])       # same group -> same head
    assert not np.array_equal(p1, build_prompt(u, 2, VOCAB, prefix_len=8))
    assert np.array_equal(p1, build_prompt(a1, 0, VOCAB, prefix_len=8))
    assert p1.dtype == np.int32 and len(p1) == 16


# ---------------------------------------------------------------------------
# SLO accounting units
# ---------------------------------------------------------------------------

def _hist_snap(values):
    from distkeras_tpu.obs import TIME_BUCKETS
    reg = Registry()
    h = reg.histogram("serve.e2e_seconds", TIME_BUCKETS)
    h2 = reg.histogram("serve.ttft_seconds", TIME_BUCKETS)
    for v in values:
        h.observe(v)
        h2.observe(v / 2)
    return reg.snapshot()


def test_hist_fraction_le_exact_on_bucket_bounds():
    snap = _hist_snap([0.1, 0.2, 0.3, 0.9, 2.0])["serve.e2e_seconds"]
    # 1.0 is a TIME_BUCKETS bound: 4 of 5 observations land <= 1.0
    assert hist_fraction_le(snap, 1.0) == pytest.approx(0.8)
    assert hist_fraction_le(snap, 10.0) == pytest.approx(1.0)
    assert hist_fraction_le(snap, 0.25) == pytest.approx(0.4)
    assert hist_fraction_le(None, 1.0) is None
    assert hist_fraction_le({"type": "counter", "value": 3}, 1.0) is None
    assert hist_fraction_le({"type": "histogram", "count": 0,
                             "bounds": [], "counts": []}, 1.0) is None


def test_phase_accountant_interval_math():
    target = SLOTarget(ttft_s=0.25, e2e_s=1.0, attainment=0.95)
    acct = PhaseAccountant(target)
    base = _hist_snap([])
    acct.open(base)
    # phase A: 4 fast requests (all within both bounds)
    acct.cut("a", _hist_snap([0.1, 0.2, 0.3, 0.4]), 2.0,
             {"offered": 5, "completed": 4, "rejected": 1, "timeouts": 0,
              "slo_met": 4, "goodput_tokens": 40})
    # phase B: 2 more, one blowing the e2e bound (cumulative snapshots —
    # the accountant diffs, so only the interval's 2 count here)
    acct.cut("b", _hist_snap([0.1, 0.2, 0.3, 0.4, 0.5, 2.0]), 1.0,
             {"offered": 2, "completed": 2, "rejected": 0, "timeouts": 0,
              "slo_met": 1, "goodput_tokens": 8})
    a, b = acct.reports
    assert a.attainment == pytest.approx(1.0)
    assert a.shed_rate == pytest.approx(0.2)
    assert a.goodput_tps == pytest.approx(20.0)
    assert b.attainment == pytest.approx(0.5)   # 1 of 2 in-bound
    assert b.offered == 2 and b.wall_s == 1.0
    assert acct.misses() == ["b"]
    assert a.meets(target) and not b.meets(target)
    with pytest.raises(RuntimeError):
        PhaseAccountant(target).cut("x", base, 1.0, {})


def test_phase_report_meets_edge_cases():
    from distkeras_tpu.scenario.slo import PhaseReport
    target = SLOTarget()

    def rep(offered, attainment):
        return PhaseReport(phase="p", offered=offered, completed=0,
                           rejected=0, timeouts=0, slo_met=0,
                           attainment=attainment, shed_rate=0.0,
                           goodput_tps=0.0, ttft_p50=None, ttft_p99=None,
                           e2e_p50=None, e2e_p99=None, wall_s=1.0)

    # offered traffic with NO attainment signal is a fail, not a pass
    assert not rep(10, None).meets(target)
    assert rep(0, None).meets(target)  # an idle phase is vacuously fine
    assert rep(10, 0.96).meets(target)
    assert not rep(10, 0.90).meets(target)
    assert SLOTarget().met(0.2, 0.9) and not SLOTarget().met(0.3, 0.9)


def test_precreate_metrics_all_present_at_zero():
    reg = precreate_metrics(Registry())
    snap = reg.snapshot()
    for name in SCENARIO_COUNTERS:
        assert snap[name]["value"] == 0, name
    for name in SCENARIO_HISTOGRAMS:
        assert snap[name]["count"] == 0, name


# ---------------------------------------------------------------------------
# autoscaler: pure hysteresis unit + tick wiring over a real fleet
# ---------------------------------------------------------------------------

def _scaler(policy, router=None):
    return AutoScaler(router, policy, target=SLOTarget(),
                      registry=Registry())


def test_autoscaler_decide_hysteresis_and_cooldown():
    p = AutoscalePolicy(min_engines=1, max_engines=3, up_after=2,
                        down_after=3, queue_high=4.0, queue_low=0.5,
                        cooldown_s=10.0)
    s = _scaler(p)
    hot = Signals(alive=1, queue_depth=8.0, attainment=0.99)
    idle = Signals(alive=2, queue_depth=0.0, attainment=None)
    # one hot tick is not enough; the second fires
    assert s.decide(hot, now=0.0) is None
    assert s.decide(hot, now=1.0) == "up"
    # cooldown: pressure keeps streaking but no action until it expires
    assert s.decide(hot, now=2.0) is None
    assert s.decide(hot, now=5.0) is None
    assert s.decide(hot, now=11.5) == "up"
    # streaks reset after an action: idle ticks must re-accumulate
    assert s.decide(idle, now=30.0) is None
    assert s.decide(idle, now=31.0) is None
    assert s.decide(idle, now=32.0) == "down"


def test_autoscaler_decide_no_flap_on_noisy_signals():
    p = AutoscalePolicy(min_engines=1, max_engines=3, up_after=2,
                        down_after=3, queue_high=4.0, queue_low=0.5,
                        cooldown_s=0.0)
    s = _scaler(p)
    hot = Signals(alive=2, queue_depth=10.0, attainment=0.99)
    idle = Signals(alive=2, queue_depth=0.0, attainment=0.99)
    mid = Signals(alive=2, queue_depth=3.0, attainment=0.95)
    # alternating pressure/slack never sustains a streak: no decision
    for i in range(20):
        assert s.decide([hot, idle][i % 2], now=float(i)) is None
    # mid-band signals (neither pressure nor slack) hold steady too
    for i in range(10):
        assert s.decide(mid, now=20.0 + i) is None


def test_autoscaler_decide_attainment_and_bounds():
    p = AutoscalePolicy(min_engines=2, max_engines=2, up_after=1,
                        down_after=1, attainment_low=0.90,
                        attainment_high=0.98, cooldown_s=0.0)
    s = _scaler(p)
    # attainment below the floor is pressure even with an empty queue —
    # but alive == max_engines: no up
    bad = Signals(alive=2, queue_depth=0.0, attainment=0.5)
    assert s.decide(bad, now=0.0) is None
    assert s._up_streak >= 1
    # slack at alive == min_engines: no down
    good = Signals(alive=2, queue_depth=0.0, attainment=1.0)
    assert s.decide(good, now=1.0) is None
    # mediocre attainment (between low and high) blocks the slack path
    s2 = _scaler(AutoscalePolicy(min_engines=1, down_after=1,
                                 cooldown_s=0.0))
    meh = Signals(alive=2, queue_depth=0.0, attainment=0.95)
    assert s2.decide(meh, now=0.0) is None
    assert s2._down_streak == 0


@pytest.mark.slow
def test_autoscaler_tick_drives_router_scale_cycle(lm):
    """tick() wiring against a REAL two-engine fleet: synthetic slack
    parks an engine through router.scale_down, synthetic pressure
    un-drains it back through router.scale_up; every decision lands in
    the counters and the history trail, and nothing retraces."""
    servers = [ServeServer(_engine(lm)).start() for _ in range(2)]
    router = _router(servers)
    try:
        scaler = AutoScaler(
            router,
            AutoscalePolicy(min_engines=1, max_engines=2, up_after=1,
                            down_after=1, cooldown_s=0.0),
            target=SLOTarget(), registry=Registry())
        scaler.read_signals = lambda: Signals(  # type: ignore[method-assign]
            alive=sum(b.alive for b in router.backends),
            queue_depth=0.0, attainment=None)
        assert scaler.tick() == "down"
        assert sum(b.alive for b in router.backends) == 1
        scaler.read_signals = lambda: Signals(  # type: ignore[method-assign]
            alive=sum(b.alive for b in router.backends),
            queue_depth=50.0, attainment=0.2)
        assert scaler.tick() == "up"
        assert sum(b.alive for b in router.backends) == 2
        assert int(scaler._c_up.value) == 1
        assert int(scaler._c_down.value) == 1
        assert [e["action"] for e in scaler.history] == ["down", "up"]
        assert all(e["ok"] for e in scaler.history)
        # the rejoined engine still answers (and never recompiled)
        with ServeClient("127.0.0.1", router.port) as c:
            r = c.generate(np.arange(6, dtype=np.int32), max_new_tokens=3)
            assert r["ok"]
            st = c.stats()["stats"]
        assert st.get("jit.retraces", {}).get("value", 0) == 0
    finally:
        router.stop()
        for s in servers:
            s.stop()


@pytest.mark.slow
def test_router_scale_up_idempotent_and_unknown(lm):
    servers = [ServeServer(_engine(lm)).start() for _ in range(2)]
    router = _router(servers)
    try:
        addr = router.backends[1].addr
        assert router.scale_down(addr, timeout_s=5.0)["ok"]
        assert not router.backends[1].alive
        up = router.scale_up(addr)
        assert up["ok"] and up["was_draining"]
        again = router.scale_up(addr)        # already in rotation: no-op
        assert again["ok"] and again.get("already_alive")
        assert not router.scale_up("127.0.0.1:1")["ok"]  # unknown addr
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# the open-loop runner (tier-1 smoke)
# ---------------------------------------------------------------------------

def test_runner_open_loop_invariant_and_recovery(lm):
    """Tier-1 smoke: a tiny Poisson trace through one engine behind the
    router.  Exact 3-way accounting at drain, attainment from the
    fleet's own histograms, a recovery window stamped and closed, zero
    retraces."""
    servers = [ServeServer(_engine(lm)).start()]
    router = _router(servers)
    stats_client = ServeClient("127.0.0.1", router.port)
    try:
        spec = poisson_trace(30.0, 1.0, seed=4,
                             mix=PrefixMix(groups=3, share=0.6),
                             lengths=LengthModel(prompt_median=8,
                                                 new_median=4))
        runner = ScenarioRunner(
            spec,
            lambda: ServeClient("127.0.0.1", router.port,
                                registry=Registry()),
            snap=lambda: stats_client.stats()["stats"],
            registry=Registry(), target=SLOTarget(ttft_s=2.5, e2e_s=10.0),
            workers=4, vocab=VOCAB, prefix_len=BLOCK)
        runner.mark_eviction()  # the first completion closes the window
        row = runner.run()
        assert row["accounting_exact"]
        c = row["counts"]
        assert c["dispatched"] == len(spec.arrivals)
        assert c["dispatched"] == (c["completed"] + c["rejected"]
                                   + c["timeouts"])
        assert c["completed"] > 0
        assert row["recoveries"] == 1
        snap = runner.registry.snapshot()
        assert snap["scenario.recovery_seconds"]["count"] == 1
        assert snap["scenario.dispatch_skew_seconds"]["count"] == \
            c["dispatched"]
        # every scenario.* metric present (0 is present-not-missing)
        for name in SCENARIO_COUNTERS:
            assert name in snap
        assert [p["phase"] for p in row["phases"]] == ["steady"]
        assert row["phases"][0]["offered"] == c["dispatched"]
        assert stats_client.stats()["stats"]["jit.retraces"]["value"] == 0
    finally:
        stats_client.close()
        router.stop()
        for s in servers:
            s.stop()


@pytest.mark.slow
def test_runner_client_deadline_counts_timeouts(lm):
    """A deadline far below service time fires mid-reply: the request
    counts under ``scenario.timeouts`` (the CLIENT gave up), the worker
    replaces its poisoned connection, and the 3-way invariant stays
    exact."""
    servers = [ServeServer(_engine(lm)).start()]
    try:
        spec = poisson_trace(40.0, 0.4, seed=6,
                             lengths=LengthModel(prompt_median=8,
                                                 new_median=8))
        runner = ScenarioRunner(
            spec,
            lambda: ServeClient("127.0.0.1", servers[0].port,
                                registry=Registry()),
            registry=Registry(), target=SLOTarget(),
            workers=2, deadline_s=1e-4, vocab=VOCAB)
        row = runner.run()
        c = row["counts"]
        assert c["timeouts"] > 0
        assert c["dispatched"] == (c["completed"] + c["rejected"]
                                   + c["timeouts"])
        assert row["accounting_exact"]
    finally:
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# obsview panel + diff gate over a persisted scenario document
# ---------------------------------------------------------------------------

def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phase(name, offered):
    return {"phase": name, "offered": offered, "completed": offered,
            "attainment": 1.0, "shed_rate": 0.0, "goodput_tps": 400.0,
            "ttft_p99": 0.19, "e2e_p99": 0.2}


def _scenario(phases, **extra):
    n = sum(p["offered"] for p in phases)
    return {"seed": 17, "arrivals": n, "wall_s": 4.0, "engines": 2,
            "phases": phases,
            "counts": {"dispatched": n, "completed": n, "rejected": 0,
                       "timeouts": 0}, **extra}


def _snapshot_doc():
    """A persisted scenario document of the shape the panel and the diff
    read: the harness's ``row`` beside one named registry a scenario."""
    reg = precreate_metrics(Registry())
    for name in ("serve.ttft_seconds", "serve.e2e_seconds"):
        h = reg.histogram(name)
        for _ in range(32):
            h.observe(0.02)
    row = {"slo": {"ttft_s": 0.5, "e2e_s": 2.5, "attainment": 0.95},
           "attainment_ok": True, "autoscaler_tracked": True,
           "jit_retraces": 0,
           "scenarios": {
               "diurnal": _scenario(
                   [_phase("night", 8), _phase("ramp_up", 16),
                    _phase("peak", 32)],
                   scale_up=1, scale_down=1, scale_events=[
                       {"t": 2.5, "action": "up", "engine": "127.0.0.1:1",
                        "ok": True, "alive": 2,
                        "reason": "queue/engine=4.0"},
                       {"t": 3.5, "action": "down",
                        "engine": "127.0.0.1:1", "ok": True, "alive": 1,
                        "reason": "queue/engine=0.0"}]),
               "spike": _scenario([_phase("pre", 8), _phase("spike", 32)]),
               "chaos": _scenario([_phase("steady", 16)],
                                  recovery_s_p50=0.4, engines_alive_end=1)}}
    return {"config": {"mode": "scenario"}, "row": row,
            **{f"scenario_{name}": reg.snapshot()
               for name in row["scenarios"]}}


@pytest.fixture()
def snap_path(tmp_path):
    path = tmp_path / "scenario_obs.json"
    path.write_text(json.dumps(_snapshot_doc()))
    return str(path)


def test_obsview_scenario_panel_renders(snap_path, capsys):
    obsview = _load_obsview()
    assert obsview.run_scenario(snap_path) == 0
    out = capsys.readouterr().out
    assert "Scenario harness" in out
    assert "diurnal" in out and "spike" in out and "chaos" in out
    assert "scale events" in out
    assert "SLO-MISS phases: none" in out
    assert "autoscaler_tracked: True" in out


def test_obsview_scenario_slo_miss_alarm(tmp_path, capsys):
    doc = _snapshot_doc()
    ph = doc["row"]["scenarios"]["diurnal"]["phases"][2]
    ph["attainment"] = 0.5  # inject a miss into the peak phase
    bad = tmp_path / "bad_snap.json"
    bad.write_text(json.dumps(doc))
    obsview = _load_obsview()
    assert obsview.run_scenario(str(bad)) == 0
    out = capsys.readouterr().out
    assert "<< SLO-MISS" in out
    assert "diurnal/peak" in out


@pytest.mark.slow
def test_obsview_scenario_live_and_bad_targets(lm, capsys):
    obsview = _load_obsview()
    assert obsview.run_scenario("/nonexistent/file.json") == 2
    server = ServeServer(_engine(lm)).start()
    try:
        with ServeClient("127.0.0.1", server.port) as c:
            assert c.generate(np.arange(4, dtype=np.int32),
                              max_new_tokens=2)["ok"]
        capsys.readouterr()
        assert obsview.run_scenario(f"127.0.0.1:{server.port}") == 0
        out = capsys.readouterr().out
        assert "Scenario signals" in out
        assert "attainment" in out
    finally:
        server.stop()


def test_obsview_diff_flags_injected_attainment_regression(
        snap_path, tmp_path, capsys):
    """The CI gate: shift the diurnal part's e2e mass past the SLO bound
    (every request suddenly slow) -> ``obsview --diff`` exits 1 under
    the committed thresholds; the document against itself exits 0."""
    obsview = _load_obsview()
    assert obsview.run_diff(snap_path, snap_path) == 0
    capsys.readouterr()
    bad = _snapshot_doc()
    h = bad["scenario_diurnal"]["serve.e2e_seconds"]
    # p50 explodes: all observations land in the top bucket
    h["counts"] = [0] * (len(h["counts"]) - 1) + [h["count"]]
    h["sum"] = float(h["count"]) * 10.0
    regressed = tmp_path / "regressed.json"
    regressed.write_text(json.dumps(bad))
    assert obsview.run_diff(snap_path, str(regressed)) == 1
    out = capsys.readouterr().out
    assert "serve.e2e_seconds" in out


# ---------------------------------------------------------------------------
# chaos acceptance: SIGKILL a real engine subprocess mid-trace
# ---------------------------------------------------------------------------

_CHILD_SRC = """
import threading
from distkeras_tpu.models import zoo
from distkeras_tpu.obs import Registry
from distkeras_tpu.serve import DecodeEngine, ServeConfig, ServeServer

model = zoo.gpt_lm(vocab_size={vocab}, dim=16, num_heads=2,
                   num_blocks=1, seq_len={seq})
engine = DecodeEngine(model, model.init(0),
                      ServeConfig(slots=2, max_queue=16,
                                  max_new_tokens=12,
                                  prefill_buckets=({block} * 2, {seq}),
                                  prefix_cache=True, prefix_cache_mb=8.0,
                                  prefix_block={block}),
                      registry=Registry()).warmup()
server = ServeServer(engine).start()
print(server.port, flush=True)
threading.Event().wait()  # serve until SIGKILL
"""


@pytest.mark.slow
def test_chaos_sigkill_subprocess_engine_acceptance(lm):
    """ISSUE 17 chaos acceptance with a REAL kill -9: one engine runs
    in a subprocess; SIGKILL lands mid-trace.  The router evicts it and
    requeues onto the in-process survivor, the runner's recovery window
    closes, accounting stays exact, and the survivor never retraces."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    child = subprocess.Popen(
        [sys.executable, "-c",
         _CHILD_SRC.format(vocab=VOCAB, seq=SEQ, block=BLOCK)],
        stdout=subprocess.PIPE, text=True, env=env, cwd=_ROOT)
    try:
        line = child.stdout.readline().strip()
        assert line, "engine subprocess died before binding"
        child_port = int(line)
        survivor = ServeServer(_engine(lm)).start()
        router = ServeRouter(
            [("127.0.0.1", child_port), ("127.0.0.1", survivor.port)],
            config=RouterConfig(affinity_block=BLOCK, kv_fabric=False,
                                stats_interval_s=0.25,
                                evict_failures=1)).start()
        stats_client = ServeClient("127.0.0.1", router.port)
        try:
            spec = poisson_trace(25.0, 3.0, seed=9,
                                 mix=PrefixMix(groups=3, share=0.6),
                                 lengths=LengthModel(prompt_median=8,
                                                     new_median=4))
            runner = ScenarioRunner(
                spec,
                lambda: ServeClient("127.0.0.1", router.port,
                                    registry=Registry()),
                snap=lambda: stats_client.stats()["stats"],
                registry=Registry(),
                target=SLOTarget(ttft_s=2.5, e2e_s=10.0),
                workers=6, deadline_s=15.0, vocab=VOCAB,
                prefix_len=BLOCK)

            def _kill():
                runner.mark_eviction()
                os.kill(child.pid, signal.SIGKILL)

            killer = threading.Timer(1.0, _kill)
            killer.start()
            try:
                row = runner.run()
            finally:
                killer.cancel()
            assert child.wait(timeout=10) == -signal.SIGKILL
            c = row["counts"]
            assert row["accounting_exact"]
            assert c["dispatched"] == (c["completed"] + c["rejected"]
                                       + c["timeouts"])
            # the fleet kept serving: most of the trace completed
            assert c["completed"] > 0.6 * c["dispatched"]
            assert row["recoveries"] == 1
            snap = runner.registry.snapshot()
            assert snap["scenario.recovery_seconds"]["count"] == 1
            st = stats_client.stats()
            assert st["engines_alive"] == 1
            assert st["stats"]["serve.router.evictions"]["value"] >= 1
            assert st["stats"]["jit.retraces"]["value"] == 0
        finally:
            stats_client.close()
            router.stop()
            survivor.stop()
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=10)
