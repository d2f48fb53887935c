"""Sharded parameter server (ISSUE 10): placement plans, the
consistent-cut pull contract, wire interop, per-shard codec isolation,
the dead-shard fatal path, and the bench/obsview tooling.

The acceptance criteria live here: a property test hammers the fleet
with commits while a client pulls concurrently and asserts every
assembled center is a valid cut (no torn pytree); ``ps_shards=1`` keeps
the pre-shard single-server path (and ``ps_shards=2`` with a single
deterministic worker is BIT-identical to it); a 4-shard async DynSGD
run converges at the existing gate with ``jit.retraces == 0``
drift-gated against the committed OBS_BASELINE.json.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.obs import Registry
from distkeras_tpu.ps import (ConsistentCutError, PSClient,  # noqa: F401
                              ShardedParameterServer, ShardedPSClient,
                              ShardFleetError, ShardPlan, ShardPlanMismatch,
                              SocketParameterServer, WorkerEvicted)
from distkeras_tpu.ps.servers import (DeltaParameterServer,
                                      DynSGDParameterServer)
from tests.test_trainers_sync import COMMON, accuracy, make_model, toy_problem

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def center_tree(sizes=(2048, 1024, 512, 256)):
    return {"params": [{"w": np.zeros(n, np.float32)} for n in sizes],
            "state": [{} for _ in sizes]}


def ones_like_center(sizes=(2048, 1024, 512, 256), v=1.0):
    return {"params": [{"w": np.full(n, v, np.float32)} for n in sizes],
            "state": [{} for _ in sizes]}


def _wait(cond, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out after {timeout_s}s waiting for {what}")


# -- ShardPlan ---------------------------------------------------------------

def test_plan_is_deterministic_and_balanced():
    c = center_tree()
    p1 = ShardPlan.build(c, 2)
    p2 = ShardPlan.build(c, 2)
    assert p1.digest == p2.digest
    assert p1.assignments == p2.assignments
    # greedy byte balance: 2048 | 1024+512+256 is the best 2-way split
    loads = [0, 0]
    for path, shard in p1.assignments.items():
        loads[shard] += p1.leaf_bytes[path]
    assert max(loads) / min(loads) < 1.5, loads
    # different structure or shard count -> different digest
    assert ShardPlan.build(c, 3).digest != p1.digest
    assert ShardPlan.build(center_tree((8, 4)), 2).digest != p1.digest
    # epoch is part of the agreement token
    assert ShardPlan.build(c, 2, epoch=1).digest != p1.digest


def test_plan_split_assemble_roundtrip(rng):
    c = {"params": [{"w": rng.normal(size=(4, 5)).astype(np.float32)},
                    {"w": rng.normal(size=(7,)).astype(np.float32),
                     "b": rng.normal(size=(3,)).astype(np.float32)}],
         "state": [{}, {"step": np.array(3, np.int64)}]}
    plan = ShardPlan.build(c, 3)
    slices = plan.split(c)
    assert sum(len(s) for s in slices) == 4
    back = plan.assemble(*slices)
    assert back["state"][0] == {}  # empty containers survive
    np.testing.assert_array_equal(back["params"][0]["w"],
                                  c["params"][0]["w"])
    np.testing.assert_array_equal(back["params"][1]["b"],
                                  c["params"][1]["b"])
    assert back["state"][1]["step"] == 3
    # missing leaves refuse to assemble
    with pytest.raises(KeyError, match="missing leaf"):
        plan.assemble(slices[0])


def test_plan_doc_lists_per_shard_leaves():
    plan = ShardPlan.build(center_tree(), 2)
    doc = plan.doc(addresses=[("127.0.0.1", 1001), ("127.0.0.1", 1002)])
    assert doc["num_shards"] == 2 and doc["digest"] == plan.digest
    assert [s["port"] for s in doc["shards"]] == [1001, 1002]
    all_paths = sorted(p for s in doc["shards"] for p in s["paths"])
    assert all_paths == sorted(plan.assignments)


# -- hello negotiation + plan agreement --------------------------------------

def test_hello_carries_shard_descriptor_and_plan_rpc():
    c = center_tree()
    with ShardedParameterServer(c, 2, DeltaParameterServer) as sps:
        host, port = sps.addrs()[0]
        with PSClient(host, port) as raw:
            assert raw.shard_info["index"] == 0
            assert raw.shard_info["num_shards"] == 2
            assert raw.shard_info["digest"] == sps.plan.digest
            resp = raw._rpc({"action": "plan"})
            assert resp["ok"] and resp["plan"]["digest"] == sps.plan.digest
        # stats RPC names the shard too
        with PSClient(*sps.addrs()[1]) as raw:
            assert raw.stats()["shard"]["index"] == 1


def test_plan_mismatch_refused_at_connect():
    c = center_tree()
    with ShardedParameterServer(c, 3, DeltaParameterServer) as sps:
        # a 2-shard client over the first two shards of a 3-shard fleet
        with pytest.raises(ShardPlanMismatch, match="disagrees"):
            ShardedPSClient(sps.addrs()[:2], c)
    # a plain (un-sharded) server does not speak the shard protocol
    ps = DeltaParameterServer(center_tree(), num_workers=1)
    with SocketParameterServer(ps) as server:
        with pytest.raises(ShardPlanMismatch):
            ShardedPSClient([("127.0.0.1", server.port)], c,
                            wire_version=1)


def test_v1_interop_verifies_via_plan_rpc(monkeypatch):
    """A v1-pinned sharded client sends no hello, so plan agreement goes
    through the ``plan`` RPC — pulls/commits then ride v1 frames."""
    c = center_tree((64, 32))
    delta = ones_like_center((64, 32))
    with ShardedParameterServer(c, 2, DeltaParameterServer) as sps:
        with ShardedPSClient(sps.addrs(), c, wire_version=1) as cl:
            assert cl.wire_version == 1
            assert all(sub.shard_info is None for sub in cl.clients)
            assert cl.commit(delta)
            tree, updates = cl.pull()
            np.testing.assert_allclose(tree["params"][0]["w"][:3], 1.0)
        # the env pin works the same way (whole-process legacy opt-out)
        monkeypatch.setenv("DKTPU_WIRE", "1")
        with ShardedPSClient(sps.addrs(), c) as cl:
            assert cl.wire_version == 1
            tree, _ = cl.pull()
            np.testing.assert_allclose(tree["params"][1]["w"][:3], 1.0)


# -- the consistent-cut contract ---------------------------------------------

def test_consistent_cut_under_concurrent_commits():
    """ISSUE 10 acceptance property: one client hammers logical commits
    (each adds 1.0 to EVERY leaf, so a valid cut has one single value
    across the whole center) while another pulls concurrently — every
    assembled center must be untorn: all leaves agree on the commit
    count they reflect."""
    sizes = (2048, 1024, 512, 256)
    c = center_tree(sizes)
    delta = ones_like_center(sizes)
    n_commits = 40
    creg = Registry()
    stop = threading.Event()
    errors: list = []
    cuts: list = []

    with ShardedParameterServer(c, 2, DeltaParameterServer,
                                num_workers=2) as sps:
        def committer():
            try:
                with ShardedPSClient(sps.addrs(), c, worker_id=0) as cl:
                    for _ in range(n_commits):
                        assert cl.commit(delta)
            except BaseException as e:
                errors.append(e)
            finally:
                stop.set()

        def puller():
            try:
                with ShardedPSClient(sps.addrs(), c, worker_id=1,
                                     registry=creg) as cl:
                    while not stop.is_set():
                        tree, _ = cl.pull()
                        vals = {float(leaf["w"][0])
                                for leaf in tree["params"]}
                        # the cut invariant: every leaf reflects the SAME
                        # set of commits — exactly one value fleet-wide
                        assert len(vals) == 1, f"torn pull: {vals}"
                        cuts.append(vals.pop())
            except BaseException as e:
                errors.append(e)

        ts = [threading.Thread(target=committer),
              threading.Thread(target=puller)]
        [t.start() for t in ts]
        [t.join(120) for t in ts]
        assert not any(t.is_alive() for t in ts)
    assert not errors, errors
    assert cuts, "the puller never completed a pull"
    assert max(cuts) <= n_commits
    # the final center is the full sum on every shard
    final = sps.get_model()
    for leaf in final["params"]:
        np.testing.assert_allclose(leaf["w"], n_commits)
    snap = creg.snapshot()
    assert snap["ps.shard.pull_rounds"]["value"] >= len(cuts)
    # permanently-torn fallback never fired on a healthy fleet
    assert snap.get("ps.shard.cut_incomplete", {}).get("value", 0) == 0


def test_dynsgd_staleness_is_per_shard():
    """Sharded DynSGD: staleness is measured against each shard's own
    counter (lockstep with the single-server math)."""
    c = center_tree((8, 4))
    with ShardedParameterServer(c, 2, DynSGDParameterServer,
                                num_workers=1) as sps:
        with ShardedPSClient(sps.addrs(), c) as cl:
            _, seen = cl.pull()  # per-shard counters [0, 0]
            # fresh commit: staleness 0 on both shards -> full delta
            assert cl.commit(ones_like_center((8, 4)), last_update=seen)
            # second commit WITHOUT a fresh pull: each shard is now one
            # update ahead of the per-shard last_update the client
            # resolved from that pull -> delta / (1 + 1)
            assert cl.commit(ones_like_center((8, 4)), last_update=seen)
            tree, _ = cl.pull()
            np.testing.assert_allclose(tree["params"][0]["w"], 1.5)
            np.testing.assert_allclose(tree["params"][1]["w"], 1.5)
    for ps in sps.shards:
        assert list(ps.staleness_seen) == [0, 1]


# -- per-shard codec / error-feedback isolation ------------------------------

def test_codec_state_is_per_shard(rng):
    c = center_tree((600, 300))
    with ShardedParameterServer(c, 2, DeltaParameterServer) as sps:
        with ShardedPSClient(sps.addrs(), c, codec="int8") as cl:
            codecs_ = [sub.codec for sub in cl.clients]
            assert codecs_[0] is not codecs_[1]  # EF residual isolation
            g = {"params": [{"w": rng.normal(size=600).astype(np.float32)},
                            {"w": rng.normal(size=300).astype(np.float32)}],
                 "state": [{}, {}]}
            for _ in range(30):
                cl.commit(g)
            tree, _ = cl.pull()
            # EF property holds per shard: the decoded SUM tracks the sum
            # of raw deltas within ~a step's residual on every leaf
            for i in (0, 1):
                drift = np.max(np.abs(np.asarray(tree["params"][i]["w"])
                                      - 30 * g["params"][i]["w"]))
                assert drift < 1.5 * np.max(np.abs(g["params"][i]["w"])), \
                    (i, drift)
    # codec accounting landed per shard
    for ps in sps.shards:
        snap = ps.registry.snapshot()
        assert snap["ps.codec.decode_seconds"]["count"] == 30


# -- partial-drop repair -----------------------------------------------------

def test_partial_drop_is_repaired():
    """A fault injector eating SOME shards' slices but not others would
    leave a permanently torn logical commit (diverged version vectors,
    every future pull degraded to the cut_incomplete fallback) — the
    client re-sends just the dropped slices instead, so the commit lands
    everywhere and the vectors stay aligned."""
    c = center_tree()
    calls = {"n": 0}

    def drop_first_slice(action, msg):
        if action != "commit":
            return False
        calls["n"] += 1
        return calls["n"] == 1  # exactly one shard's slice, once

    reg = Registry()
    with ShardedParameterServer(c, 3, DeltaParameterServer, num_workers=1,
                                fault_injector=drop_first_slice) as sps:
        with ShardedPSClient(sps.addrs(), c, registry=reg) as cl:
            assert cl.commit(ones_like_center())  # repaired -> applied
            tree, _ = cl.pull()
    snap = reg.snapshot()
    assert snap["ps.shard.commit_repairs"]["value"] == 1
    # the full delta landed on EVERY shard exactly once...
    for leaf in tree["params"]:
        np.testing.assert_allclose(leaf["w"], 1.0)
    # ...so the vectors re-agreed: no torn rounds, no fallback
    assert snap.get("ps.shard.torn_pulls", {}).get("value", 0) == 0
    assert snap.get("ps.shard.cut_incomplete", {}).get("value", 0) == 0


def test_permanent_drop_gives_up_bounded():
    """A shard that drops the same slice every time exhausts the bounded
    repair budget: the commit reports False, and the (documented) torn
    fallback serves the freshest cut instead of spinning."""
    c = center_tree()

    def drop_shard0_always(action, msg):
        return action == "commit" and "params/0/w" in (msg.get("delta") or {})

    reg = Registry()
    with ShardedParameterServer(c, 3, DeltaParameterServer, num_workers=1,
                                fault_injector=drop_shard0_always) as sps:
        with ShardedPSClient(sps.addrs(), c, registry=reg) as cl:
            assert cl.commit(ones_like_center()) is False
            tree, _ = cl.pull()  # torn forever -> fallback, still served
    snap = reg.snapshot()
    assert snap["ps.shard.commit_repairs"]["value"] == 2  # budget spent
    assert snap["ps.shard.cut_incomplete"]["value"] == 1
    np.testing.assert_allclose(tree["params"][0]["w"], 0.0)  # dropped
    np.testing.assert_allclose(tree["params"][1]["w"], 1.0)  # applied


def test_full_drop_is_a_clean_lost_update():
    """Every shard dropping the commit is the single-server lost-update:
    report False, repair NOTHING (vectors never diverged)."""
    c = center_tree()
    reg = Registry()
    with ShardedParameterServer(c, 3, DeltaParameterServer, num_workers=1,
                                fault_injector=lambda a, m: a == "commit") \
            as sps:
        with ShardedPSClient(sps.addrs(), c, registry=reg) as cl:
            assert cl.commit(ones_like_center()) is False
            tree, n = cl.pull()
    assert reg.snapshot()["ps.shard.commit_repairs"]["value"] == 0
    assert n == 0
    for leaf in tree["params"]:
        np.testing.assert_allclose(leaf["w"], 0.0)


# -- fleet lifecycle through the facade --------------------------------------

def test_eviction_fans_out_and_tombstones_everywhere():
    c = center_tree((8, 4))
    with ShardedParameterServer(c, 2, DeltaParameterServer,
                                num_workers=1) as sps:
        with ShardedPSClient(sps.addrs(), c, worker_id=0) as cl:
            assert cl.commit(ones_like_center((8, 4)))
            window = sps.evict_worker(0)
            assert window == 1
            with pytest.raises(WorkerEvicted):
                cl.commit(ones_like_center((8, 4)))
        # the zombie's commit tombstoned on (at least) the first shard it
        # reached; no shard applied it
        assert sps.num_updates == 1
        for ps in sps.shards:
            assert ps.generations[0] == 1
        start, gen = sps.register_respawn(0)
        assert (start, gen) == (1, 1)
        with ShardedPSClient(sps.addrs(), c, worker_id=0,
                             generation=gen) as cl2:
            assert cl2.commit(ones_like_center((8, 4)))
        assert sps.commits_by_worker[0] == 2


def test_dead_shard_raises_named_fleet_error():
    c = center_tree((8, 4))
    sps = ShardedParameterServer(c, 2, DeltaParameterServer).start()
    try:
        sps.raise_if_unhealthy()  # healthy fleet: no-op
        sps.servers[1].stop()     # shard dies OUTSIDE the facade's stop()
        with pytest.raises(ShardFleetError) as ei:
            sps.raise_if_unhealthy()
        msg = str(ei.value)
        assert "shard 1/2" in msg and "last commit counter" in msg
    finally:
        sps.stop()
    # an intentional facade stop is not an incident
    sps.raise_if_unhealthy()


def test_dead_shard_fails_the_training_run(monkeypatch):
    """ISSUE 10 satellite: a shard dying mid-run is a fatal,
    clearly-reported fleet error — the supervisor's shard watch raises
    with the shard id instead of workers hanging in reconnect backoff."""
    monkeypatch.setenv("DKTPU_WINDOW_DELAY_S", "0.1")
    ds = toy_problem()
    t = dk.DOWNPOUR(make_model(), "sgd", num_workers=2, mode="async",
                    communication_window=4, ps_shards=2, **COMMON)
    out: dict = {}

    def run():
        try:
            t.train(ds)
        except BaseException as e:
            out["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    _wait(lambda: t._supervisor is not None, 120, "the supervisor")
    sup = t._supervisor
    _wait(lambda: sup.ps.commits_by_worker.get(0, 0) >= 1, 120,
          "worker 0's first commit")
    sup.ps.servers[0].stop()  # the shard vanishes mid-run
    th.join(120)
    assert not th.is_alive(), "training never surfaced the dead shard"
    assert isinstance(out.get("err"), ShardFleetError), out.get("err")
    assert "shard 0/2" in str(out["err"])


def test_process_shard_fleet_end_to_end():
    """The deployment shape (ISSUE 10): one shard-server OS process per
    shard (``ps.shard.shard_main``), ports discovered via port files,
    plan agreement verified over the wire, stats pollable per shard."""
    from distkeras_tpu.ps.shard.server import ProcessShardFleet
    c = center_tree((512, 256))
    with ProcessShardFleet(c, 2) as fleet:
        with ShardedPSClient(fleet.addrs(), c, worker_id=0) as cl:
            cl.pull()
            assert cl.commit(ones_like_center((512, 256)))
            tree, updates = cl.pull()
            np.testing.assert_allclose(tree["params"][0]["w"][:3], 1.0)
            assert updates == 2  # one logical commit, once per shard
            st = cl.stats()
            assert st["num_updates"] == 1
            assert st["plan"]["digest"] == cl.plan.digest
            # the shard processes' lock-wait instrument rode the RPC
            assert "ps.lock_wait_seconds" in st["stats"]


# -- trainer integration ------------------------------------------------------

@pytest.fixture(scope="module")
def ds():
    return toy_problem()


def test_ps_shards_validation():
    with pytest.raises(ValueError, match="ps_shards"):
        dk.DOWNPOUR(make_model(), ps_shards=0)


def test_ps_shards_2_bit_identical_to_single_server(ds):
    """A single deterministic worker trains BIT-identical params whether
    the center lives on one server or two shards: the sharded path
    cannot have changed the numerics (``ps_shards=1`` IS the pre-shard
    code path, asserted by every existing PS test running unmodified)."""
    import jax

    def run(shards):
        t = dk.DOWNPOUR(make_model(), "sgd", num_workers=1, mode="async",
                        communication_window=4, ps_shards=shards, **COMMON)
        return t.train(ds)

    p1 = jax.tree_util.tree_leaves(run(1).variables["params"])
    p2 = jax.tree_util.tree_leaves(run(2).variables["params"])
    assert len(p1) == len(p2)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_dynsgd_converges_with_zero_retraces(ds):
    """ISSUE 10 acceptance: a 4-shard async DynSGD run converges at the
    existing gate with ``jit.retraces == 0`` drift-gated against the
    committed OBS_BASELINE.json (zero tolerance)."""
    from distkeras_tpu.obs import drift
    from distkeras_tpu.obs.registry import Registry as _Registry

    t = dk.DynSGD(make_model(), "sgd", num_workers=2, mode="async",
                  communication_window=4, ps_shards=4, **COMMON)
    reg = _Registry()
    t.tracer.registry = reg
    m = t.train(ds)
    acc = accuracy(m, ds)
    assert acc > 0.85, acc
    assert len(t.get_history()) == COMMON["num_epoch"]
    # per-shard lockstep: the logical update count is the per-worker sum
    assert t.ps_stats["num_updates"] == \
        sum(t.ps_stats["commits_by_worker"].values())
    snap = t.ps_stats["registry"]
    # merged across 4 shards: every logical commit landed once per shard
    assert snap["ps.commits"]["value"] == 4 * t.ps_stats["num_updates"]
    # jit.retraces == 0, drift-gated (the committed zero-tolerance rule)
    bl = drift.load_baseline(os.path.join(ROOT, "OBS_BASELINE.json"))
    reg.counter("jit.compiles")
    reg.counter("jit.retraces")
    doc = {"config": {"shards": 4}, "trainer": reg.snapshot()}
    rep = drift.diff_docs(doc, doc, baseline=bl)
    assert not rep.drifted
    assert reg.counter("jit.retraces").value == 0


# -- ISSUE 12: DOWN compression + shm across a shard fleet -------------------

def test_sharded_down_pulls_resync_per_link():
    """DOWN compression over a shard fleet: every shard connection owns
    its own reference epoch (one resync per link), assembled centers
    match raw pulls within quantization error, and the DOWN ledger shows
    the reduction."""
    center = center_tree()
    with ShardedParameterServer(center, 2, DeltaParameterServer,
                                num_workers=2) as fleet:
        reg = Registry()
        with ShardedPSClient(fleet.addrs(), center, 0, registry=reg,
                             down="int8") as down_c, \
                ShardedPSClient(fleet.addrs(), center, 1) as raw_c:
            down_c.pull()
            assert reg.counter("ps.down.resyncs").value == 2  # per link
            raw_c.commit(ones_like_center(v=0.5))
            got_raw, n_raw = raw_c.pull()
            got_down, n_down = down_c.pull()
            assert n_raw == n_down
            for a, b in zip(got_down["params"], got_raw["params"]):
                np.testing.assert_allclose(a["w"], b["w"], atol=1e-3)
            # still one resync per link: steady state is residual-only
            assert reg.counter("ps.down.resyncs").value == 2
            snap = reg.snapshot()
            assert snap["ps.down.bytes_raw"]["value"] > \
                snap["ps.down.bytes_encoded"]["value"]


def test_mixed_fleet_partial_shm_negotiation():
    """ISSUE 12 satellite: a fleet where only SOME shards can negotiate
    shm (here one shard is v1-pinned — legacy build emulation) runs the
    shm links on the ring and the refused links on TCP, with DOWN active
    only where acked; pulls still assemble exactly."""
    from distkeras_tpu.ps.shard.server import ShardFrontend
    center = center_tree()
    plan = ShardPlan.build(center, 2)
    slices = plan.split(center)
    shards = [DeltaParameterServer(s, num_workers=1) for s in slices]
    servers = [ShardFrontend(shards[0], plan, 0),
               ShardFrontend(shards[1], plan, 1, max_wire_version=1)]
    for s in servers:
        s.start()
    try:
        addrs = [("127.0.0.1", s.port) for s in servers]
        reg = Registry()
        with ShardedPSClient(addrs, center, 0, registry=reg,
                             down="int8", shm=True) as c:
            assert c.clients[0].shm_active and c.clients[0].down_enabled
            assert not c.clients[1].shm_active
            assert not c.clients[1].down_enabled  # v1: raw, no rings
            assert c.wire_version == 1  # fleet minimum, as negotiated
            c.commit(ones_like_center(v=1.0))
            got, n = c.pull()
            assert n == 2  # one logical commit, once per shard
            for leaf, ref in zip(got["params"],
                                 ones_like_center(v=1.0)["params"]):
                np.testing.assert_allclose(leaf["w"], ref["w"], atol=1e-3)
            assert reg.counter("net.bytes_shm").value > 0
    finally:
        for s in servers:
            s.stop()


def test_sharded_dynsgd_converges_with_down_and_shm(ds):
    """ISSUE 12 acceptance: async DynSGD over a sharded fleet with int8
    DOWN compression AND the shm transport converges at the existing
    gate with ``jit.retraces == 0`` — the full wire-round-2 stack under
    the tier-1 workload."""
    from distkeras_tpu.obs import drift
    from distkeras_tpu.obs.registry import Registry as _Registry

    t = dk.DynSGD(make_model(), "sgd", num_workers=2, mode="async",
                  communication_window=4, ps_shards=2, comm_down="int8",
                  ps_shm=True, **COMMON)
    reg = _Registry()
    t.tracer.registry = reg
    m = t.train(ds)
    acc = accuracy(m, ds)
    assert acc > 0.85, acc
    snap = t.ps_stats["registry"]
    # the DOWN ledger and the direction split made it into the stats
    assert snap["ps.down.bytes_raw"]["value"] > \
        snap["ps.down.bytes_encoded"]["value"]
    assert snap["ps.wire.bytes_down"]["value"] > 0
    assert snap["ps.wire.bytes_up"]["value"] > 0
    assert snap["net.bytes_shm"]["value"] > 0  # co-located: rings used
    reg.counter("jit.retraces")
    assert reg.counter("jit.retraces").value == 0
    bl = drift.load_baseline(os.path.join(ROOT, "OBS_BASELINE.json"))
    doc = {"config": {"shards": 2, "down": "int8"}, "trainer": reg.snapshot()}
    rep = drift.diff_docs(doc, doc, baseline=bl)
    assert not rep.drifted


# -- racecheck: write-after-publish (ISSUE 10 satellite) ---------------------

def test_racecheck_clean_on_sharded_traffic():
    """Replace-style commits through a shard fleet never trip the
    write-after-publish detector (the autouse fixture is already
    collecting; this block asserts the seeded-vs-clean distinction
    explicitly)."""
    with racecheck.enabled() as viol:
        c = center_tree((64, 32))
        with ShardedParameterServer(c, 2, DeltaParameterServer) as sps:
            with ShardedPSClient(sps.addrs(), c) as cl:
                cl.pull()
                cl.commit(ones_like_center((64, 32)))
                cl.pull()
                cl.commit(ones_like_center((64, 32)))
        assert not viol, viol


def test_racecheck_catches_write_after_publish():
    """A shard mutating a center tensor in place AFTER the pull cache
    captured its buffer (the lock-free pull-snapshot contract) is a
    recorded violation, caught on the next commit."""
    with racecheck.enabled() as viol:
        c = center_tree((64, 32))
        with ShardedParameterServer(c, 2, DeltaParameterServer) as sps:
            with ShardedPSClient(sps.addrs(), c) as cl:
                cl.pull()  # publishes every shard's center payload
                victim = sps.shards[0]
                for leaf in victim.get_model().values():
                    np.asarray(leaf)[0] = 99.0  # in-place, post-publish
                cl.commit(ones_like_center((64, 32)))
        found = [v for v in viol if v["op"] == "write_after_publish"]
        assert found, viol
        assert found[0]["dict"].endswith(".center")
        viol.clear()  # seeded deliberately: keep the autouse collector green


# -- obsview tooling ----------------------------------------------------------

def test_obsview_ps_fleet_targets_and_balance(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import obsview
    finally:
        sys.path.remove(os.path.join(ROOT, "scripts"))
    # comma list + plan file parsing
    assert obsview.parse_ps_targets("127.0.0.1:9,localhost:10") == \
        [("127.0.0.1", 9), ("localhost", 10)]
    plan_file = tmp_path / "plan.json"
    plan_file.write_text(json.dumps(ShardPlan.build(center_tree(), 2).doc(
        addresses=[("127.0.0.1", 7001), ("127.0.0.1", 7002)])))
    assert obsview.parse_ps_targets(str(plan_file)) == \
        [("127.0.0.1", 7001), ("127.0.0.1", 7002)]
    with pytest.raises(ValueError, match="HOST:PORT"):
        obsview.parse_ps_targets("nonsense")
    # merged fleet view over a LIVE 2-shard fleet
    c = center_tree((64, 32))
    with ShardedParameterServer(c, 2, DeltaParameterServer,
                                num_workers=1) as sps:
        with ShardedPSClient(sps.addrs(), c) as cl:
            cl.pull()
            cl.commit(ones_like_center((64, 32)))
        replies = [obsview.poll_stats(h, p) for h, p in sps.addrs()]
    out = obsview.summarize_ps_fleet(replies)
    assert "×2 shards" in out
    assert "Shard balance" in out
    assert sps.plan.digest in out
    # merged ground truth: ONE logical commit, seen fleet-wide
    assert "updates: 1" in out
    # per-shard commit share is visible (50% each under lockstep)
    assert out.count("50.0%") == 2
