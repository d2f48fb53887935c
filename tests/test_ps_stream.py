"""Wire round 3 (ISSUE 15): streamed pulls, dispatch-ahead overlap, and
the link-quality loop.

The acceptance criteria live here: the assembled center is BIT-IDENTICAL
across every negotiation cell (v1 peer, stream-refused peer,
``DKTPU_STREAM=0``, mixed shard fleet, streaming×shm×``comm_down``), a
mid-stream socket reset resumes through the standard reconnect backoff
with exact commit accounting, async DynSGD converges at the existing
gate with streaming + dispatch-ahead pulls on, and the link-degradation
edge downshifts the adaptive DOWN codec as a recorded
``ps.link.downshifts`` event.
"""

import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import chaos
from distkeras_tpu.obs import LinkQuality, Registry, StragglerDetector
from distkeras_tpu.obs import default_registry
from distkeras_tpu.obs.stragglers import detect_from_heartbeats
from distkeras_tpu.ps import codecs
from distkeras_tpu.ps import networking as net
from distkeras_tpu.ps import (DeltaParameterServer, PSClient,
                              ShardedParameterServer, ShardedPSClient,
                              SocketParameterServer)
from distkeras_tpu.ps.state import PullCache
from tests.test_trainers_sync import COMMON, make_model, toy_problem

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tree(v):
    return {"params": [{"w": np.asarray(v, dtype=np.float32)}], "state": [{}]}


def big_center(rng, mb=2.0, leaves=8):
    n = max(1, int(mb * (1 << 20) / 4 / leaves))
    return {"params": [{"w": rng.normal(size=n).astype(np.float32)}
                       for _ in range(leaves)],
            "state": [{} for _ in range(leaves)]}


def assert_trees_equal(a, b):
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _val(snap, name):
    return snap.get(name, {}).get("value", 0)


# -- frame/split units -------------------------------------------------------

def test_stream_split_groups_and_join_roundtrip(rng):
    doc = {"center": {"params": [{"w": rng.normal(size=64).astype(
        np.float32)} for _ in range(5)],
        "state": [{}], "empty": np.zeros((0, 4), np.float32),
        "zero_d": np.array(7, np.int64)},
        "updates": 3, "vv": {0: 2, 1: 1}}
    skeleton, groups = net.stream_split(doc, 2 * 64 * 4)
    # 5 fp32(64) leaves + the 0-d int64 leaf; the empty array stays
    # inline in the skeleton (nothing to chunk)
    nleaves = sum(len(arrs) for _, arrs in groups)
    assert nleaves == 6
    # the byte bound groups at most 2 of the 256-byte leaves per chunk
    assert all(sum(a.nbytes for a in arrs) <= 2 * 64 * 4 + 8
               for _, arrs in groups)
    flat = [a for _, arrs in groups for a in arrs]
    out = net.stream_join(skeleton, flat)
    assert_trees_equal(out["center"], doc["center"])
    assert out["updates"] == 3 and out["vv"] == {0: 2, 1: 1}


def test_pack_stream_frame_bytes_are_exact(rng):
    # the prologue is a normal v2 frame: decode it back and check the
    # announced per-chunk frame sizes match the packed payloads exactly
    from distkeras_tpu.utils import serde
    doc = {"center": big_center(rng, mb=1.0), "updates": 0}
    parts = net.pack_stream(doc, 256 * 1024, version=2)
    pre_bufs, _ = parts[0]
    prologue_doc = serde.tree_from_frames(bytes(pre_bufs[1]), [])
    assert prologue_doc["nchunks"] == len(parts) - 1
    assert prologue_doc["frame_bytes"] == [t for _, t in parts[1:]]


def test_oversized_leaf_is_its_own_chunk(rng):
    a = rng.normal(size=100_000).astype(np.float32)  # 400 KB leaf
    doc = {"center": {"w": a}, "updates": 0}
    skeleton, groups = net.stream_split(doc, 1024)  # bound << leaf
    assert len(groups) == 1 and len(groups[0][1]) == 1


# -- streamed pull end to end ------------------------------------------------

def test_streamed_pull_bit_identical_and_counted(rng):
    center = big_center(rng)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        mono_reg, s_reg = Registry(), Registry()
        with PSClient("127.0.0.1", server.port, registry=mono_reg,
                      stream=False) as mono, \
                PSClient("127.0.0.1", server.port, registry=s_reg,
                         stream_chunk_bytes=256 * 1024) as sc:
            assert sc.stream_enabled and not mono.stream_enabled
            cm, nm = mono.pull()
            cs, ns = sc.pull()
            assert nm == ns
            assert_trees_equal(cm, cs)
            # 2 MB center at a 256 KB bound: multiple chunks, counted on
            # BOTH ends
            assert s_reg.get("ps.pull.streams").value == 1
            assert s_reg.get("ps.pull.stream_chunks").value >= 4
            assert mono_reg.get("ps.pull.streams").value == 0
            assert s_reg.get("ps.pull.chunk_bytes").snapshot()["count"] \
                == s_reg.get("ps.pull.stream_chunks").value
        assert ps.registry.get("ps.pull.streams").value == 1


def test_unchanged_protocol_still_skips_payload(rng):
    ps = DeltaParameterServer(big_center(rng, mb=1.0), num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, registry=reg) as c:
            c1, _ = c.pull()
            b1 = reg.counter("net.bytes_recv").value
            c2, _ = c.pull()
            assert c2 is c1
            assert reg.counter("net.bytes_recv").value - b1 < 1024
            # an unchanged answer is not a stream
            assert reg.get("ps.pull.streams").value == 1


def test_negotiation_matrix_all_cells_bit_identical(rng, monkeypatch):
    """v1-pinned client, stream-refused client, DKTPU_STREAM=0 on either
    end, v1-pinned server: every cell answers the exact same center,
    monolithically (``ps.pull.streams`` stays 0 on both ends)."""
    center = big_center(rng, mb=0.5)
    ps_ref = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps_ref, stream=True) as server:
        with PSClient("127.0.0.1", server.port, stream=False) as c:
            reference, _ = c.pull()

    def run_cell(server_kw, client_kw, env=None):
        ps = DeltaParameterServer(center, num_workers=1)
        if env:
            monkeypatch.setenv(*env)
        try:
            with SocketParameterServer(ps, **server_kw) as server:
                reg = Registry()
                with PSClient("127.0.0.1", server.port, registry=reg,
                              **client_kw) as c:
                    out, _ = c.pull()
                assert reg.get("ps.pull.streams").value == 0, \
                    (server_kw, client_kw, env)
            assert ps.registry.get("ps.pull.streams").value == 0
            assert_trees_equal(out, reference)
        finally:
            if env:
                monkeypatch.delenv(env[0])

    run_cell({}, {"wire_version": 1})            # v1-pinned client
    run_cell({}, {"stream": False})              # v2, stream refused
    run_cell({"stream": False}, {})              # old/disabled server
    run_cell({"max_wire_version": 1}, {})        # v1-pinned server
    run_cell({}, {}, env=("DKTPU_STREAM", "0"))  # env pin (client side)


def test_stream_composes_with_shm_and_down(rng):
    """streaming × shm × comm_down: the chunk frames ride the shared-
    memory ring (whole stream fits) and decode to the same center a
    monolithic DOWN pull of the same epoch yields — bit-identical
    (the residual encode is deterministic per (center, reference))."""
    center = big_center(rng, mb=1.0)
    ps = DeltaParameterServer(center, num_workers=2)
    with SocketParameterServer(ps) as server:
        reg_m, reg_s = Registry(), Registry()
        with PSClient("127.0.0.1", server.port, 0, registry=reg_m,
                      down="int8", stream=False) as mono, \
                PSClient("127.0.0.1", server.port, 1, registry=reg_s,
                         down="int8", shm=True,
                         stream_chunk_bytes=256 * 1024) as sc:
            assert sc.stream_enabled and sc.shm_active and sc.down_enabled
            cm, _ = mono.pull()
            cs, _ = sc.pull()
            assert_trees_equal(cm, cs)
            assert reg_s.get("ps.pull.streams").value == 1
            # the stream's tensor segments went through the ring
            assert reg_s.get("net.bytes_shm").value > (1 << 20) * 0.9
            # and a RAW streamed client still matches the true center
        with PSClient("127.0.0.1", server.port, 0) as raw:
            craw, _ = raw.pull()
        assert_trees_equal(craw, center)


def test_stream_too_big_for_ring_falls_back_to_tcp(rng):
    """A streamed reply whose chunks exceed the ring stays entirely on
    TCP for that reply (a per-chunk ring fallback could wrap onto an
    unread chunk) — and still decodes exactly."""
    center = big_center(rng, mb=4.0)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        reg = Registry()
        with PSClient("127.0.0.1", server.port, registry=reg, shm=True,
                      shm_mb=1.0) as c:  # 1 MB ring << 4 MB center
            assert c.shm_active
            out, _ = c.pull()
            assert_trees_equal(out, center)
            assert reg.get("ps.pull.streams").value == 1
            assert reg.get("net.bytes_shm").value < (1 << 20)


def test_mixed_shard_fleet_one_non_streaming_shard(rng):
    center = big_center(rng, mb=1.0, leaves=8)
    sharded = ShardedParameterServer(center, 2, DeltaParameterServer,
                                     num_workers=1)
    # shard 1 emulates a pre-streaming peer: refuses the stream offer
    sharded.servers[1].stream = False
    with sharded:
        reg = Registry()
        with ShardedPSClient(sharded.addrs(), center, registry=reg,
                             stream_chunk_bytes=128 * 1024) as c:
            out, total = c.pull()
            assert c.clients[0].stream_enabled
            assert not c.clients[1].stream_enabled
        assert_trees_equal(out, center)
        assert sharded.servers[0].registry.get(
            "ps.pull.streams").value == 1
        assert sharded.servers[1].registry.get(
            "ps.pull.streams").value == 0


def test_arena_reuse_never_corrupts_a_held_center(rng):
    """The pooled receive arena is reused only when the previous pull's
    leaves all died — a center the caller still holds keeps its values
    through arbitrarily many later pulls."""
    center = big_center(rng, mb=1.0)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            held, _ = c.pull()
            snapshot = np.array(held["params"][0]["w"][:64])
            delta = {"params": [{"w": np.ones_like(np.asarray(l["w"]))}
                                for l in center["params"]],
                     "state": [{} for _ in center["state"]]}
            for _ in range(4):
                c.commit(delta)
                c.invalidate()
                fresh, _ = c.pull()
            np.testing.assert_array_equal(
                snapshot, np.asarray(held["params"][0]["w"][:64]))
            np.testing.assert_allclose(
                np.asarray(fresh["params"][0]["w"][:64]),
                snapshot + 4.0, rtol=1e-5)


# -- overlap (dispatch-ahead pulls) ------------------------------------------

def test_pull_begin_join_and_overlap_accounting(rng):
    center = big_center(rng, mb=1.0)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        reg = Registry()
        with PSClient("127.0.0.1", server.port, registry=reg) as c:
            c.pull()
            c.invalidate()
            c.pull_begin()
            time.sleep(0.005)  # the "device step"
            out, n, vv, epoch = c.pull_join()
            assert_trees_equal(out, center)
            h = reg.get("ps.pull.hidden_seconds").snapshot()
            assert h["count"] == 2
            # the overlapped pull hid ≥ the sleep behind "compute"
            frac = reg.get("ps.pull.overlap_fraction").value
            assert 0.0 < frac <= 1.0


def test_sharded_pull_begin_join_matches_pull(rng):
    center = big_center(rng, mb=0.5)
    sharded = ShardedParameterServer(center, 2, DeltaParameterServer,
                                     num_workers=1)
    with sharded:
        with ShardedPSClient(sharded.addrs(), center) as c:
            ref, total = c.pull()
            c.invalidate()
            c.pull_begin()
            out, total2, _, _ = c.pull_join()
            assert total2 == total
            assert_trees_equal(out, ref)


class _ThisThreadsFaults(chaos.SocketFaults):
    """Counts the seam's calls from the thread that built it alone.  The
    server's handler runs in this process too: once its one ``sendmsg``
    is in the loopback buffer its next ``recv_msg`` races the client's
    reads for an ordinal, and on a loaded host it wins — the reset then
    lands on the server's side of a stream the client already holds."""

    def __init__(self, schedule):
        super().__init__(schedule)
        self._thread = threading.current_thread()

    def __call__(self, stage, action=None):
        if threading.current_thread() is self._thread:
            super().__call__(stage, action)


def test_midstream_reset_resumes_via_reconnect_backoff(rng):
    """A connection reset while chunk k is on the wire: ``pull_join``
    reconnects through the standard backoff and re-pulls — an
    idempotent read, so the retried center is exact."""
    center = big_center(rng, mb=1.0)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        reg = Registry()
        with PSClient("127.0.0.1", server.port, registry=reg,
                      stream_chunk_bytes=128 * 1024) as c:
            # recv fault ordinal 3: (1) the pull reply's announce, (2)
            # the prologue frame, (3) the FIRST chunk — mid-stream
            with _ThisThreadsFaults({"recv": [3]}) as faults:
                c.pull_begin()
                out, n, _, _ = c.pull_join()
            assert faults.injected == 1
            assert_trees_equal(out, center)
            assert reg.get("ps.client.reconnects").value == 1
            # the aborted stream was abandoned, the retry streamed fully
            assert reg.get("ps.pull.streams").value == 1


def test_overlapped_dynsgd_with_midrun_reset_exact_accounting():
    """The chaos rung: streaming + dispatch-ahead workers, a socket
    reset injected into a mid-run streamed pull — the worker resumes via
    the reconnect backoff and the run's commit accounting stays exact
    (``requests == applied + dropped + tombstoned``, no tombstones: a
    pull retry can never double-apply)."""
    ds = toy_problem(n=512)
    t = dk.DynSGD(make_model(), "sgd", num_workers=2, mode="async",
                  communication_window=4, pull_overlap=True, **COMMON)
    with chaos.SocketFaults({"recv": [40]}) as faults:
        m = t.train(ds)
    assert faults.injected == 1
    assert m.variables is not None
    reg = t.ps_stats["registry"]
    assert _val(reg, "ps.commit_requests") == (
        _val(reg, "ps.commits") + _val(reg, "ps.commits_dropped")
        + _val(reg, "ps.commits_tombstoned"))
    assert _val(reg, "ps.commits_tombstoned") == 0


def test_overlapped_dynsgd_converges_at_existing_gate():
    """ISSUE 15 acceptance: async DynSGD with streamed, dispatch-ahead
    pulls converges at the existing async gate (the extra window of
    self-staleness needs a couple more epochs of the toy problem — the
    wall-clock win is the point, the MATH must stay inside what the
    staleness rule absorbs), overlap measurably recorded, zero
    retraces."""
    ds = toy_problem()
    reg = default_registry()
    r0 = reg.counter("jit.retraces").value
    kw = dict(COMMON)
    kw["num_epoch"] = 6
    t = dk.DynSGD(make_model(), "sgd", num_workers=4, mode="async",
                  communication_window=4, pull_overlap=True, **kw)
    m = t.train(ds)
    pred = dk.ModelPredictor(m, "features").predict(ds)
    acc = dk.AccuracyEvaluator("prediction", "label").evaluate(pred)
    assert acc > 0.85, acc
    assert reg.get("ps.pull.hidden_seconds").snapshot()["count"] > 0
    assert reg.get("ps.pull.overlap_fraction").value > 0.0
    assert reg.counter("jit.retraces").value == r0


# -- link quality loop -------------------------------------------------------

def test_link_quality_ewma_and_degradation_edge():
    link = LinkQuality(alpha=0.5, degrade_factor=2.0, min_rtt_s=1e-4)
    assert link.ewma is None and not link.degraded()
    for _ in range(8):
        link.observe_pull(0.010)
    assert abs(link.ewma - 0.010) < 1e-6
    assert not link.degraded()
    # hostile inputs never poison the EWMA
    link.observe_pull(float("nan"))
    link.observe_pull(-1.0)
    link.observe_commit("bogus")
    assert abs(link.ewma - 0.010) < 1e-6
    for _ in range(8):
        link.observe_pull(0.050)   # the link just got 5x slower
    assert link.degraded()
    link.rebase()                  # a consumer acted on the edge
    assert not link.degraded()


def test_adaptive_policy_downshifts_on_degraded_link():
    reg = Registry()
    link = LinkQuality(alpha=1.0, degrade_factor=2.0, min_rtt_s=1e-4)
    pol = codecs.AdaptiveDownPolicy(reg, warmup_samples=1, patience=2,
                                    link=link)
    # warmup: one request per candidate (request -> observe, like a pull)
    seen = []
    for _ in range(3):
        c = pol.next_codec()
        seen.append(c)
        pol.observe(c, 0.010)
    assert seen == ["none", "bf16", "int8"]
    link.observe_pull(0.010)                # healthy-link baseline
    assert pol.next_codec() == "none"       # healthy link: incumbent
    link.observe_pull(0.100)                # degradation edge
    shifted = pol.next_codec()
    assert shifted == "bf16"                # one step MORE compression
    assert pol.downshifts == 1
    assert reg.get("ps.link.downshifts").value == 1
    assert pol.trail[-1]["kind"] == "downshift"
    assert pol.trail[-1]["from"] == "none"
    # the rebase cooled the edge: no cascade on the next pull
    assert pol.next_codec() in ("bf16", "none", "int8")
    assert pol.downshifts == 1


def test_overlapped_pulls_do_not_poison_link_ewma(rng):
    """The link EWMA folds the VISIBLE pull wait, never the caller's
    compute between pull_begin and pull_join — a healthy link under
    dispatch-ahead pulls with a long device step must not read as
    degraded (which would downshift the adaptive codec for no wire
    reason and report compute time as link RTT)."""
    center = big_center(rng, mb=0.5)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            c.pull()  # sequential pull: seeds the baseline at wire RTT
            baseline = c.link.ewma
            for _ in range(6):
                c.invalidate()
                c.pull_begin()
                time.sleep(0.05)  # a device step ~10x the wire RTT
                c.pull_join()
            # the 50ms compute windows never entered the EWMA
            assert c.link.ewma < 0.04, c.link.ewma
            assert not c.link.degraded(), (c.link.snapshot(), baseline)


def test_detector_record_link_snapshot_and_hostile_inputs():
    det = StragglerDetector(registry=Registry())
    det.record_link(0, 0.012, downshifts=2)
    det.record_link(1, float("nan"))       # rejected
    det.record_link("bogus", 0.5)          # rejected
    snap = det.snapshot()
    assert snap["link_rtt_s"] == {"0": 0.012}
    assert snap["link_downshifts"] == {"0": 2}


def test_commit_ships_link_rtt_to_server(rng):
    ps = DeltaParameterServer(tree([0.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, 0) as c:
            c.pull()            # seeds the link's pull EWMA
            c.commit(tree([1.0]))
            c.commit(tree([1.0]))
            stats = c.stats()
    link = stats["stragglers"]["link_rtt_s"]
    assert "0" in link and link["0"] > 0


def test_heartbeat_link_replay():
    records = [
        {"event": "heartbeat", "worker_id": 0, "gap_s": 0.1,
         "link_rtt_s": 0.004},
        {"event": "heartbeat", "worker_id": 1, "gap_s": 0.1,
         "link_rtt_s": 0.020, "link_downshifts": 1},
    ]
    snap = detect_from_heartbeats(records)
    assert snap["link_rtt_s"] == {"0": 0.004, "1": 0.020}
    assert snap["link_downshifts"] == {"1": 1}


# -- pull cache parts --------------------------------------------------------

def test_pull_cache_payload_parts_single_flight_and_prune(rng):
    reg = Registry()
    cache = PullCache(reg)
    doc = {"center": big_center(rng, mb=0.25), "updates": 0}
    builds = []

    def builder():
        builds.append(1)
        return net.pack_stream(doc, 64 * 1024, version=2), doc["center"]

    p1 = cache.payload_parts((2, "stream", 64 * 1024), 0, builder)
    p2 = cache.payload_parts((2, "stream", 64 * 1024), 0, builder)
    assert p2 is p1 and len(builds) == 1
    assert reg.get("ps.pull_cache_hits").value == 1
    # a newer counter on another shape prunes the stale parts entry
    cache.payload(2, 1, lambda: {"center": doc["center"], "updates": 1})
    p3 = cache.payload_parts((2, "stream", 64 * 1024), 1, builder)
    assert p3 is not p1 and len(builds) == 2


# -- obsview + bench ---------------------------------------------------------

def _obsview():
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import obsview
    return obsview


def test_obsview_renders_stream_section_and_link_table(rng):
    obsview = _obsview()
    center = big_center(rng, mb=0.5)
    ps = DeltaParameterServer(center, num_workers=1)
    with SocketParameterServer(ps) as server:
        reg = Registry()
        with PSClient("127.0.0.1", server.port, registry=reg) as c:
            c.pull()
            c.commit({"params": [{"w": np.zeros_like(np.asarray(l["w"]))}
                                 for l in center["params"]],
                      "state": [{} for _ in center["state"]]})
            stats = c.stats()
    # snapshot mode: client registry carries the streaming instruments
    doc = {"config": {"windows": 1}, "client": reg.snapshot(),
           "server": ps.registry.snapshot()}
    text = obsview.summarize_snapshot(doc)
    assert "Pull streaming" in text
    assert "streamed pulls: 1" in text
    # live mode: the stats reply carries the link table
    live = obsview.summarize_stats(stats)
    assert "Pull streaming" in live
    assert "Link quality" in live
    # JSONL replay mode: heartbeat-borne link RTTs render too
    records = [{"event": "heartbeat", "worker_id": 0, "gap_s": 0.2,
                "link_rtt_s": 0.005},
               {"event": "heartbeat", "worker_id": 1, "gap_s": 0.2,
                "link_rtt_s": 0.006}]
    assert "Link quality" in "\n".join(
        obsview._link_lines(detect_from_heartbeats(records)))
