"""Fleet KV fabric (ISSUE 16): the version-stamp refusal rule (a stale
checkpoint push is refused, NEVER joined), replication-on-spill landing
the secondary owner + warm repeat overflow, migration on planned drain,
the in-flight byte budget, single-flight dedup under a spill storm,
chaos resets mid-``kv_fetch``, engine death around the fetch with exact
router accounting — and the acceptance run: forced overflow on a
3-engine fleet where replicated-spill TTFT p50 provably beats cold-spill
p50 at ``jit.retraces == 0``, drift-gated."""

import copy
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.chaos import SocketFaults
from distkeras_tpu.models import zoo
from distkeras_tpu.models.generation import generate_tokens
from distkeras_tpu.obs import Registry, drift
from distkeras_tpu.obs.registry import snapshot_quantile
from distkeras_tpu.serve import (DecodeEngine, RouterConfig, ServeClient,
                                 ServeConfig, ServeRouter, ServeServer)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, SEQ = 32, 32
BLOCK = 8


@pytest.fixture(scope="module")
def lm():
    model = zoo.gpt_lm(vocab_size=VOCAB, dim=16, num_heads=2,
                       num_blocks=1, seq_len=SEQ)
    return model, model.init(0)


def _engine(lm, registry=None, variables=None, **kw):
    model, v = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 16)
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("prefill_buckets", (BLOCK * 2, SEQ))
    kw.setdefault("prefix_cache", True)
    kw.setdefault("prefix_cache_mb", 8.0)
    kw.setdefault("prefix_block", BLOCK)
    return DecodeEngine(model, v if variables is None else variables,
                        ServeConfig(**kw),
                        registry=registry if registry is not None
                        else Registry()).warmup()


def _fleet(lm, n, **kw):
    return [ServeServer(_engine(lm, **kw)).start() for _ in range(n)]


def _router(servers, **cfg_kw):
    cfg_kw.setdefault("affinity_block", BLOCK)
    # poller OFF the critical path: these tests drive spill/migration
    # deterministically and must not race a stats tick
    cfg_kw.setdefault("stats_interval_s", 30.0)
    return ServeRouter([("127.0.0.1", s.port) for s in servers],
                       config=RouterConfig(**cfg_kw)).start()


def _stop_all(router, servers):
    router.stop()
    for s in servers:
        s.stop()


def _ref(lm, prompt, steps, variables=None):
    model, v = lm
    out = generate_tokens(model, v if variables is None else variables,
                          np.asarray(prompt, np.int32)[None, :],
                          int(steps))
    return np.asarray(out)[0, len(prompt):]


def _prompt(rng, shared, tail=3):
    return np.concatenate([shared,
                           rng.integers(0, VOCAB, tail).astype(np.int32)])


def _wait_for(cond, what, deadline_s=15.0):
    deadline = time.monotonic() + deadline_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting: {what}"
        time.sleep(0.02)


def _v(snap, name):
    return snap[name]["value"]


# ---------------------------------------------------------------------------
# config + the version-stamp refusal rule
# ---------------------------------------------------------------------------

def test_kvfabric_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(kv_fabric_mb=0.0)
    with pytest.raises(ValueError):
        RouterConfig(kv_link_inflight=0)
    with pytest.raises(ValueError):
        RouterConfig(kv_migrate_entries=0)
    # kv_fabric=False builds a router with NO fabric at all
    r = ServeRouter([("127.0.0.1", 1)],
                    config=RouterConfig(kv_fabric=False))
    assert r._kv_fabric is None
    # the engine-side knob surfaces in the comparable-config row only
    # when the prefix cache actually backs it
    assert ServeConfig(prefix_cache=True).config_row(SEQ)["kv_fabric"]
    assert not ServeConfig(prefix_cache=False).config_row(SEQ)["kv_fabric"]


def test_stale_checkpoint_push_refused_never_joined(lm):
    """The fabric's correctness core: KV is a pure function of
    (tokens, weights), so a push stamped with a superseded checkpoint
    version is REFUSED — after a promote, yesterday's KV can never
    serve a token, it costs one cold prefill instead."""
    model, _ = lm
    v_new = model.init(1)
    rng = np.random.default_rng(20)
    servers = _fleet(lm, 2)
    eng_b = servers[1].engine
    try:
        prompt = rng.integers(0, VOCAB, BLOCK * 2 + 3).astype(np.int32)
        with ServeClient("127.0.0.1", servers[0].port) as ca, \
                ServeClient("127.0.0.1", servers[1].port) as cb:
            assert ca.generate(prompt, 4)["ok"]  # warm engine A
            doc = ca.kv_fetch(prompt=prompt)
            assert doc["ok"] and doc["found"]
            assert len(doc["entries"]) == 1 and doc["version"] == 0
            # fresh stamp joins: B now serves the prefix warm, exactly
            r = cb.kv_push(doc["entries"], doc["version"])
            assert r["ok"] and r["joined"] == 1 and r["refused"] == 0
            warm = cb.generate(prompt, 4)
            assert warm["ok"] and warm["warm"] is True
            assert np.array_equal(np.asarray(warm["tokens"]),
                                  _ref(lm, prompt, 4))
            # promote B: its kv_version bumps at decode-thread adoption
            assert cb.promote(v_new)["ok"]
            _wait_for(lambda: eng_b.kv_version == 1,
                      "promotion adoption")
            # the SAME entries, stamped with the superseded version:
            # refused as stale, never joined
            r = cb.kv_push(doc["entries"], doc["version"])
            assert r["ok"] and r["joined"] == 0
            assert r["refused_stale"] == 1 and r["refused"] == 1
            cold = cb.generate(prompt, 4)
            assert cold["ok"] and cold["warm"] is False, \
                "stale KV must never serve — this must cold-prefill"
            assert np.array_equal(np.asarray(cold["tokens"]),
                                  _ref(lm, prompt, 4, variables=v_new))
            # a malformed push is an answered error, not a join
            assert cb.kv_push(doc["entries"], 1)["joined"] == 1  # sanity
            bad = cb.kv_push([{"host_tokens": prompt,
                               "cache": {"not": "a cache"}}], 1)
            assert bad["ok"] and bad["joined"] == 0 and bad["refused"] == 1
            assert "reason" in bad
            no_ver = cb._rpc({"action": "kv_push",
                              "entries": doc["entries"]})
            assert no_ver["ok"] is False and "version" in no_ver["error"]
    finally:
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# replication on spill through a live fleet
# ---------------------------------------------------------------------------

def test_spill_replicates_then_secondary_serves_warm(lm):
    """The tentpole loop: overflow of a warm prefix spills COLD once,
    the fabric replicates the owner's entry to the spill target, the
    target becomes a bounded secondary owner, and repeat overflow routes
    there WARM — with the TTFT split recording both outcomes and router
    accounting staying exact."""
    rng = np.random.default_rng(21)
    shared = rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
    servers = _fleet(lm, 2)
    router = _router(servers, max_inflight=2)
    fabric = router._kv_fabric
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            assert client.generate(_prompt(rng, shared), 4)["ok"]
            owner = next(b for b in router.backends if b.requests == 1)
            # force the spill: the affine owner sits at its in-flight
            # bound, so the next request of this prefix overflows
            with router._lock:
                owner.inflight = 2
            p1 = _prompt(rng, shared)
            r1 = client.generate(p1, 4)
            assert r1["ok"] and r1["warm"] is False  # cold spill
            assert np.array_equal(np.asarray(r1["tokens"]),
                                  _ref(lm, p1, 4))
            _wait_for(lambda: router.registry.counter(
                "serve.router.kv_replications").value >= 1,
                "spill replication")
            p2 = _prompt(rng, shared)
            r2 = client.generate(p2, 4)
            assert r2["ok"] and r2["warm"] is True  # replicated spill
            assert r2["engine"] == r1["engine"] != owner.addr
            assert np.array_equal(np.asarray(r2["tokens"]),
                                  _ref(lm, p2, 4))
            with router._lock:
                owner.inflight = 0
        snap = router.registry.snapshot()
        # owner lists stay bounded at two (primary + the replica)
        with router._lock:
            assert all(1 <= len(owners) <= 2
                       for owners in router._affinity.values())
            assert any(len(owners) == 2
                       for owners in router._affinity.values())
        assert fabric is not None and not fabric._jobs
        assert fabric._inflight_bytes == 0
    finally:
        _stop_all(router, servers)
    assert _v(snap, "serve.router.kv_replications") == 1
    assert _v(snap, "serve.router.kv_push_bytes") > 0
    assert _v(snap, "serve.router.kv_refused_stale") == 0
    assert _v(snap, "serve.router.affinity_secondary_hits") == 1
    assert snap["serve.router.ttft_spill_warm_seconds"]["count"] == 1
    assert snap["serve.router.ttft_spill_cold_seconds"]["count"] == 1
    assert _v(snap, "serve.router.requests") == \
        _v(snap, "serve.router.completed") + \
        _v(snap, "serve.router.rejected")


def test_engine_death_around_fetch_cold_prefills_exact_accounting(lm):
    """The owner dying around the fabric's fetch is ABSORBED: the
    spilled request cold-prefills on the survivor, the fabric's fetch
    (and the eviction's best-effort migration off the corpse) fail
    silently, and ``requests == completed + rejected`` stays exact."""
    rng = np.random.default_rng(22)
    shared = rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
    servers = _fleet(lm, 2)
    router = _router(servers, max_inflight=2)
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            assert client.generate(_prompt(rng, shared), 4)["ok"]
            owner_idx = next(b.idx for b in router.backends
                             if b.requests == 1)
            # the owner goes dark; the router still believes it alive
            servers[owner_idx].stop()
            p1 = _prompt(rng, shared)
            r1 = client.generate(p1, 4)
            # routed affine to the corpse -> forward fails -> evicted ->
            # re-queued to the survivor -> COLD prefill, exact output
            assert r1["ok"] and r1["warm"] is False
            assert np.array_equal(np.asarray(r1["tokens"]),
                                  _ref(lm, p1, 4))
            # the eviction queued a best-effort migration off a corpse:
            # it must drain silently, moving nothing
            fabric = router._kv_fabric
            _wait_for(lambda: not fabric._jobs and not fabric._inflight,
                      "fabric queue drain")
        snap = router.registry.snapshot()
    finally:
        _stop_all(router, servers)
    assert _v(snap, "serve.router.evictions") == 1
    assert _v(snap, "serve.router.requeues") == 1
    assert _v(snap, "serve.router.kv_replications") == 0
    assert _v(snap, "serve.router.kv_migrations") == 0
    assert _v(snap, "serve.router.requests") == 2
    assert _v(snap, "serve.router.requests") == \
        _v(snap, "serve.router.completed") + \
        _v(snap, "serve.router.rejected")


def test_chaos_reset_mid_kv_fetch_is_absorbed(lm):
    """A connection reset mid ``kv_fetch`` stream (the chaos seam's
    ``send:kv_fetch_stream`` stage) costs that one replication and
    NOTHING else: the worker survives, the next transfer lands."""
    rng = np.random.default_rng(23)
    shared = rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
    servers = _fleet(lm, 2)
    router = _router(servers, max_inflight=2)
    fabric = router._kv_fabric
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            p0 = _prompt(rng, shared)
            assert client.generate(p0, 4)["ok"]
        owner = next(b for b in router.backends if b.requests == 1)
        target = next(b for b in router.backends if b is not owner)
        key = router._affinity_keys(p0)[0]
        # drive the transfer synchronously so the fault ordinal is
        # deterministic: the FIRST kv_fetch stream send resets mid-reply
        with SocketFaults({"send:kv_fetch_stream": [1]}) as faults:
            fabric._run_replicate(key, owner.idx, target.idx, p0)
        assert faults.injected == 1
        snap = router.registry.snapshot()
        assert _v(snap, "serve.router.kv_replications") == 0
        assert fabric._inflight_bytes == 0
        # faults cleared: the identical transfer now lands
        fabric._run_replicate(key, owner.idx, target.idx, p0)
        snap = router.registry.snapshot()
        assert _v(snap, "serve.router.kv_replications") == 1
        # and the replica actually serves: direct warm hit on the target
        with ServeClient("127.0.0.1",
                         servers[target.idx].port) as ct:
            r = ct.generate(_prompt(rng, shared), 4)
            assert r["ok"] and r["warm"] is True
    finally:
        _stop_all(router, servers)


def test_budget_bounds_inflight_transfer_bytes(lm):
    """The ``kv_fabric_mb`` budget is an IN-FLIGHT bound: a fetch whose
    bytes would exceed it is dropped (retried on a later spill), and a
    completed transfer returns its bytes to the pool."""
    rng = np.random.default_rng(24)
    shared = rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
    servers = _fleet(lm, 2)
    router = _router(servers, max_inflight=2)
    fabric = router._kv_fabric
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            p0 = _prompt(rng, shared)
            assert client.generate(p0, 4)["ok"]
        owner = next(b for b in router.backends if b.requests == 1)
        target = next(b for b in router.backends if b is not owner)
        key = router._affinity_keys(p0)[0]
        # every budget byte is already committed to in-flight transfers:
        # this fetch completes, the push is refused BEFORE any bytes move
        with fabric._lock:
            fabric._inflight_bytes = fabric._budget
        fabric._run_replicate(key, owner.idx, target.idx, p0)
        snap = router.registry.snapshot()
        assert _v(snap, "serve.router.kv_replications") == 0
        assert _v(snap, "serve.router.kv_push_bytes") == 0
        with fabric._lock:
            assert fabric._inflight_bytes == fabric._budget  # untouched
            fabric._inflight_bytes = 0
        # budget back: the same transfer lands and releases its bytes
        fabric._run_replicate(key, owner.idx, target.idx, p0)
        snap = router.registry.snapshot()
        assert _v(snap, "serve.router.kv_replications") == 1
        assert 0 < _v(snap, "serve.router.kv_push_bytes") <= \
            fabric._budget
        assert fabric._inflight_bytes == 0
    finally:
        _stop_all(router, servers)


def test_single_flight_dedup_under_concurrent_spill_storm():
    """A spill storm (every request of a hot group overflowing at once)
    collapses to ONE replication job per (target, prefix) and at most
    ``kv_link_inflight`` jobs per link — dedup IS the storm defense.
    Pure queue semantics: no sockets, worker not started."""
    router = ServeRouter([("127.0.0.1", 1), ("127.0.0.1", 2)],
                         config=RouterConfig(affinity_block=BLOCK,
                                             kv_link_inflight=1))
    fabric = router._kv_fabric
    prompt = np.arange(BLOCK * 2, dtype=np.int32)
    key = router._affinity_keys(prompt)[0]
    accepted = []
    barrier = threading.Barrier(8)

    def storm():
        barrier.wait()
        accepted.append(fabric.note_spill(key, 0, 1, prompt))

    threads = [threading.Thread(target=storm) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(accepted) == 1, "single-flight: one job per (target, key)"
    # a DIFFERENT key on the same saturated link is deferred too
    other = router._affinity_keys(
        np.arange(1, BLOCK * 2 + 1, dtype=np.int32))[0]
    assert fabric.note_spill(other, 0, 1, prompt) is False
    # but the reverse link has its own budget
    assert fabric.note_spill(other, 1, 0, prompt) is True
    # migrations single-flight per victim the same way
    assert fabric.note_eviction(0) is True
    assert fabric.note_eviction(0) is False
    assert len(fabric._jobs) == 3


# ---------------------------------------------------------------------------
# migration on planned drain
# ---------------------------------------------------------------------------

def test_planned_drain_migrates_hot_kv_then_drains(lm):
    """``drain`` with an engine address is a PLANNED transition: the
    victim's hottest entries move to survivors first, its affinity keys
    re-point at the recipients, THEN it drains and leaves rotation —
    the fleet keeps serving and the moved prefixes stay warm.  The
    poller must NOT rejoin the drained (still answering) engine."""
    rng = np.random.default_rng(25)
    shared = [rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
              for _ in range(2)]
    servers = _fleet(lm, 2)
    router = _router(servers, stats_interval_s=0.1)
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            for g in range(2):  # one warm group per engine
                for _ in range(2):
                    assert client.generate(_prompt(rng, shared[g]),
                                           4)["ok"]
            victim = router.backends[0]
            reply = router._handle_drain({"engine": victim.addr})
            assert reply["ok"], reply
            assert reply["engine"] == victim.addr
            assert reply["migrated"] >= 1 and reply["drained"]
            with router._lock:
                assert victim.alive is False
            # the front door is NOT draining — only the victim left
            follow = client.generate(_prompt(rng, shared[0]), 4)
            assert follow["ok"], "fleet must keep serving"
            # the migrated prefix landed warm on the survivor
            assert follow["warm"] is True
            assert follow["engine"] == router.backends[1].addr
            # the drained engine still answers stats (draining=True);
            # two poll ticks must not resurrect it
            time.sleep(0.3)
            with router._lock:
                assert victim.alive is False, \
                    "poller must not rejoin a draining engine"
            snap = router.registry.snapshot()
            assert _v(snap, "serve.router.rejoins") == 0
    finally:
        _stop_all(router, servers)
    assert _v(snap, "serve.router.kv_migrations") >= 1
    assert _v(snap, "serve.router.kv_refused_stale") == 0
    assert _v(snap, "serve.router.evictions") == 1
    assert _v(snap, "serve.router.requests") == \
        _v(snap, "serve.router.completed") + \
        _v(snap, "serve.router.rejected")


# ---------------------------------------------------------------------------
# acceptance: forced overflow, warm beats cold, drift-gated
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_acceptance_replicated_spill_ttft_beats_cold_drift_gated():
    """Acceptance (ISSUE 16): on a 3-engine fleet with forced overflow
    (``max_inflight=1``), the first overflow of each hot prefix
    cold-prefills and triggers replication; every later overflow lands
    warm on the secondary owner.  The replicated-spill TTFT p50 is
    provably below the cold-spill p50, the fabric moved real bytes with
    ZERO stale refusals, ``jit.retraces == 0`` fleet-wide, all
    drift-gated against the committed baseline."""
    # a model big enough that cold prefill DOMINATES ttft: the proof
    # must measure prefill avoided, not scheduler noise
    vocab, seq, block = 64, 128, 16
    model = zoo.gpt_lm(vocab_size=vocab, dim=64, num_heads=4,
                       num_blocks=2, seq_len=seq)
    v = model.init(0)
    groups, rounds = 3, 3
    rng = np.random.default_rng(26)
    shared = [rng.integers(0, vocab, block * 4).astype(np.int32)
              for _ in range(groups)]
    servers = [ServeServer(DecodeEngine(
        model, v, ServeConfig(slots=2, max_queue=16, max_new_tokens=8,
                              # suffix bucket ≪ prefill bucket: a warm
                              # join replays only the short tail in the
                              # tiny bucket while a cold spill pays the
                              # full prefill — the split measures
                              # prefill avoided, not scheduler noise
                              prefill_buckets=(block, seq),
                              prefix_cache=True, prefix_cache_mb=16.0,
                              prefix_block=block),
        registry=Registry()).warmup()).start() for _ in range(3)]
    router = ServeRouter(
        [("127.0.0.1", s.port) for s in servers],
        config=RouterConfig(affinity_block=block, max_inflight=1,
                            stats_interval_s=30.0)).start()
    fabric = router._kv_fabric
    errors: list = []

    def storm_pair(g):
        """Two concurrent requests of group g: one holds the affine
        owner's single in-flight slot, the other MUST spill."""
        barrier = threading.Barrier(2)

        def drive():
            try:
                with ServeClient("127.0.0.1", router.port) as c:
                    barrier.wait()
                    tail = rng.integers(0, vocab, 4).astype(np.int32)
                    r = c.generate(np.concatenate([shared[g], tail]), 4)
                    assert r["ok"], r
            except BaseException as e:  # surfaced after join
                errors.append(e)

        threads = [threading.Thread(target=drive) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    try:
        with ServeClient("127.0.0.1", router.port) as client:
            for g in range(groups):  # pin affinity + warm each owner
                assert client.generate(
                    np.concatenate([shared[g],
                                    rng.integers(0, vocab, 4)
                                    .astype(np.int32)]), 4)["ok"]
        for rnd in range(rounds):
            for g in range(groups):
                storm_pair(g)
            if rnd == 0:
                # round 0's spills were cold and seeded replications;
                # let them land so later rounds' spills find replicas
                _wait_for(lambda: router.registry.counter(
                    "serve.router.kv_replications").value >= 1,
                    "first replication", deadline_s=30.0)
                _wait_for(lambda: not fabric._jobs
                          and not fabric._inflight, "fabric drain",
                          deadline_s=30.0)
        assert not errors, errors
        with ServeClient("127.0.0.1", router.port) as client:
            st = client.stats()
    finally:
        _stop_all(router, servers)
    stats = st["stats"]
    warm = stats["serve.router.ttft_spill_warm_seconds"]
    cold = stats["serve.router.ttft_spill_cold_seconds"]
    assert cold["count"] >= 1, "forced overflow must cold-spill first"
    assert warm["count"] >= 1, "replicated overflow must land warm"
    warm_p50 = snapshot_quantile(warm, 0.5)
    cold_p50 = snapshot_quantile(cold, 0.5)
    assert warm_p50 < cold_p50, \
        (f"replicated-spill ttft p50 {warm_p50:.4f}s must beat "
         f"cold-spill p50 {cold_p50:.4f}s")
    assert stats["serve.router.kv_replications"]["value"] >= 1
    assert stats["serve.router.kv_push_bytes"]["value"] > 0
    assert stats["serve.router.kv_refused_stale"]["value"] == 0
    assert stats["jit.retraces"]["value"] == 0
    assert stats["serve.router.requests"]["value"] == \
        stats["serve.router.completed"]["value"] + \
        stats["serve.router.rejected"]["value"]
    # the drift gate: identical fabric snapshots are clean; a stale
    # refusal over the committed zero-tolerance rule is DRIFT
    baseline = drift.load_baseline(os.path.join(_ROOT,
                                                "OBS_BASELINE.json"))
    doc = {"config": {"mode": "serve_fleet_kv"}, "fleet": stats}
    report = drift.diff_docs(doc, copy.deepcopy(doc), baseline=baseline)
    assert not report.drifted
    bumped = copy.deepcopy(doc)
    bumped["fleet"]["serve.router.kv_refused_stale"]["value"] += 1
    report = drift.diff_docs(doc, bumped, baseline=baseline)
    assert any(m.endswith("kv_refused_stale")
               for m in report.drifted_metrics)


# ---------------------------------------------------------------------------
# obsview: the KV fabric panel + COLD-SPILL alarm
# ---------------------------------------------------------------------------

def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fabric_stats(warm_n, cold_n, replications=3, stale=0):
    from distkeras_tpu.obs import TIME_BUCKETS
    reg = Registry()
    reg.counter("serve.router.requests").inc(20)
    reg.counter("serve.router.kv_replications").inc(replications)
    reg.counter("serve.router.kv_migrations").inc(1)
    reg.counter("serve.router.kv_push_bytes").inc(4096)
    reg.counter("serve.router.kv_refused_stale").inc(stale)
    reg.counter("serve.router.affinity_secondary_hits").inc(warm_n)
    hw = reg.histogram("serve.router.ttft_spill_warm_seconds",
                       TIME_BUCKETS)
    hc = reg.histogram("serve.router.ttft_spill_cold_seconds",
                       TIME_BUCKETS)
    for _ in range(warm_n):
        hw.observe(0.002)
    for _ in range(cold_n):
        hc.observe(0.02)
    return reg.snapshot()


def test_obsview_kvfabric_panel_and_cold_spill_alarm():
    obsview = _load_obsview()
    healthy = obsview.summarize_serve(
        {"server": "ServeRouter", "stats": _fabric_stats(9, 1)})
    assert "== KV fabric ==" in healthy
    assert "replications 3" in healthy
    assert "spill warm fraction: 90%" in healthy
    assert "COLD-SPILL" not in healthy
    # spill traffic mostly cold-prefilling -> the alarm renders
    failing = obsview.summarize_serve(
        {"server": "ServeRouter", "stats": _fabric_stats(1, 9)})
    assert "COLD-SPILL" in failing
    # no spill traffic at all: panel renders, no fraction, no alarm
    idle = obsview.summarize_serve(
        {"server": "ServeRouter", "stats": _fabric_stats(0, 0)})
    assert "== KV fabric ==" in idle
    assert "spill warm fraction" not in idle and "COLD-SPILL" not in idle
    # a plain engine (no router counters) renders no fabric panel
    eng = obsview.summarize_serve(
        {"server": "ServeServer", "stats": Registry().snapshot()})
    assert "== KV fabric ==" not in eng
    # snapshot mode (a persisted registry-snapshot document) renders
    # the same panel per fabric-bearing registry
    out = obsview.summarize_snapshot(
        {"config": {"mode": "serve_bench"},
         "serve_router": _fabric_stats(9, 1)})
    assert "== KV fabric ==" in out and "COLD-SPILL" not in out


@pytest.mark.slow
def test_obsview_kvfabric_panel_live_router_poll(lm):
    """End-to-end: a fabric-active router poll renders the panel with
    real transfer counters."""
    obsview = _load_obsview()
    rng = np.random.default_rng(27)
    shared = rng.integers(0, VOCAB, BLOCK * 2).astype(np.int32)
    servers = _fleet(lm, 2)
    router = _router(servers, max_inflight=2)
    try:
        with ServeClient("127.0.0.1", router.port) as client:
            assert client.generate(_prompt(rng, shared), 4)["ok"]
            owner = next(b for b in router.backends if b.requests == 1)
            with router._lock:
                owner.inflight = 2
            assert client.generate(_prompt(rng, shared), 4)["ok"]
            _wait_for(lambda: router.registry.counter(
                "serve.router.kv_replications").value >= 1,
                "replication")
            assert client.generate(_prompt(rng, shared), 4)["ok"]
            with router._lock:
                owner.inflight = 0
        out = obsview.summarize_serve(
            obsview.poll_serve("127.0.0.1", router.port))
    finally:
        _stop_all(router, servers)
    assert "== KV fabric ==" in out
    assert "replications 1" in out
    assert "refused stale 0" in out
    assert "COLD-SPILL" not in out  # 1 warm / 1 cold = 50%, at threshold
