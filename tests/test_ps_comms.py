"""PS comms fast path (ISSUE 4): v2 zero-copy framing, wire negotiation,
pull caching, delta codecs, and the bench_ps/obsview tooling.

The acceptance criteria live here: int8 commits cut worker-side
``net.bytes_sent`` per communication window >= 3x vs uncompressed
(registry-snapshot asserted), ``comm_codec='none'`` keeps the trainer
numerics bit-identical across wire versions, and error-feedback
quantization converges within epsilon of the uncompressed run on the
tier-1 toy problem.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.obs import Registry, default_registry
from distkeras_tpu.ps import codecs
from distkeras_tpu.ps import networking as net
from distkeras_tpu.ps import (DeltaParameterServer, PSClient,
                              SocketParameterServer)
from distkeras_tpu.utils import serde
from tests.test_trainers_sync import COMMON, make_model, toy_problem

jnp = pytest.importorskip("jax.numpy")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tree(v):
    return {"params": [{"w": np.asarray(v, dtype=np.float32)}], "state": [{}]}


# -- v2 framing: round-trip property tests over dtypes -----------------------

@pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16",
                                   "float16", "int8", "int32", "int64",
                                   "uint16", "bool"])
def test_frames_roundtrip_dtypes(dtype, rng):
    if dtype == "bfloat16":
        arr = jnp.asarray(rng.normal(size=(3, 5)), jnp.bfloat16)
        arr = np.asarray(arr)
    elif dtype == "bool":
        arr = rng.normal(size=(3, 5)) > 0
    elif dtype.startswith(("int", "uint")):
        arr = rng.integers(0, 100, size=(3, 5)).astype(dtype)
    else:
        arr = rng.normal(size=(3, 5)).astype(dtype)
    tree_ = {"x": arr, "nested": [{"y": arr[:1]}], "scalar": 3, "s": "str"}
    header, segs = serde.tree_to_frames(tree_)
    # simulate the wire: segments arrive as plain byte buffers
    out = serde.tree_from_frames(header, [bytearray(bytes(net._flat_view(s)))
                                          for s in segs])
    assert np.asarray(out["x"]).dtype == arr.dtype
    np.testing.assert_array_equal(np.asarray(out["x"]), arr)
    np.testing.assert_array_equal(np.asarray(out["nested"][0]["y"]), arr[:1])
    assert out["scalar"] == 3 and out["s"] == "str"


def test_frames_roundtrip_edge_shapes(rng):
    tree_ = {"zero_d": np.array(7, np.int64),
             "empty": np.zeros((0, 4), np.float32),
             "noncontig": np.asarray(rng.normal(size=(4, 6)),
                                     np.float32).T,
             "big": rng.normal(size=(100, 100)).astype(np.float32)}
    out = serde.tree_from_frames(*serde.tree_to_frames(tree_))
    assert np.asarray(out["zero_d"]).shape == ()
    assert out["zero_d"] == 7
    assert np.asarray(out["empty"]).shape == (0, 4)
    np.testing.assert_array_equal(out["noncontig"], tree_["noncontig"])
    np.testing.assert_array_equal(out["big"], tree_["big"])


def test_frames_payload_is_zero_copy(rng):
    """The v2 segments ARE the source arrays' buffers, not copies."""
    a = rng.normal(size=(32, 32)).astype(np.float32)
    _, segs = serde.tree_to_frames({"a": a})
    assert len(segs) == 1
    assert np.shares_memory(np.asarray(segs[0]), a)


# -- version negotiation -----------------------------------------------------

def test_wire_negotiation_v2_and_v1_fallback():
    ps = DeltaParameterServer(tree([1.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            assert c.wire_version == 2
            assert c.commit(tree([1.0]))
            center, n = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [2.0])
    # a v1-pinned server (legacy emulation): the hello negotiates down
    ps1 = DeltaParameterServer(tree([0.0]), num_workers=1)
    with SocketParameterServer(ps1, max_wire_version=1) as server:
        with PSClient("127.0.0.1", server.port) as c:
            assert c.wire_version == 1
            assert c.commit(tree([3.0]))
            center, n = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [3.0])
    # a v1-pinned CLIENT against a current server (old worker emulation):
    # no handshake is sent, the server answers v1 frames as before
    ps2 = DeltaParameterServer(tree([0.0]), num_workers=1)
    with SocketParameterServer(ps2) as server:
        with PSClient("127.0.0.1", server.port, wire_version=1) as c:
            assert c.wire_version == 1
            assert c.commit(tree([5.0]))
            center, n = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [5.0])


def test_wire_env_pin(monkeypatch):
    monkeypatch.setenv("DKTPU_WIRE", "1")
    ps = DeltaParameterServer(tree([0.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            assert c.wire_version == 1
            c.commit(tree([1.0]))
            center, _ = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [1.0])


def test_mixed_wire_clients_share_a_server():
    """One server, one v1 worker + one v2 worker committing concurrently:
    the per-connection negotiation keeps them isolated."""
    ps = DeltaParameterServer(tree([0.0]), num_workers=2)
    n_commits = 20
    with SocketParameterServer(ps) as server:
        def hammer(pin):
            with PSClient("127.0.0.1", server.port,
                          wire_version=pin) as c:
                for _ in range(n_commits):
                    c.commit(tree([1.0]))
                    c.pull()
        ts = [threading.Thread(target=hammer, args=(pin,))
              for pin in (1, None)]
        [t.start() for t in ts]
        [t.join() for t in ts]
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"],
                               [2 * n_commits])


# -- pull caching ------------------------------------------------------------

def test_pull_unchanged_skips_center_payload():
    ps = DeltaParameterServer(tree(np.zeros(50_000)), num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, registry=reg) as c:
            c1, n1 = c.pull()          # cold: full center ships
            b1 = reg.counter("net.bytes_recv").value
            c2, n2 = c.pull()          # idle server: unchanged
            b2 = reg.counter("net.bytes_recv").value
            assert n1 == n2 == 0
            assert c2 is c1            # client-side cache identity
            assert b2 - b1 < 1024      # no 200 KB center re-ship
            c.commit(tree(np.ones(50_000)))
            c3, n3 = c.pull()          # invalidated by the commit
            b3 = reg.counter("net.bytes_recv").value
            assert n3 == 1 and c3 is not c1
            assert b3 - b2 > 50_000 * 4
            np.testing.assert_allclose(c3["params"][0]["w"][:3], 1.0)
    assert ps.registry.get("ps.pulls_unchanged").value == 1


def test_pull_cache_serves_many_workers():
    """P workers pulling the same center: the server encodes it once per
    commit (cache hits), not once per pull."""
    ps = DeltaParameterServer(tree(np.zeros(10_000)), num_workers=4)
    with SocketParameterServer(ps) as server:
        def puller(k):
            with PSClient("127.0.0.1", server.port, k) as c:
                for _ in range(5):
                    c.pull()
        ts = [threading.Thread(target=puller, args=(k,)) for k in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
    reg = ps.registry
    # 20 pulls total: each client's FIRST pull needs a payload (the rest
    # answer unchanged); at most one of those builds it, the others hit
    assert reg.get("ps.pulls").value == 20
    assert reg.get("ps.pulls_unchanged").value == 16
    assert reg.get("ps.pull_cache_hits").value >= 3


# -- codec unit behavior -----------------------------------------------------

def test_codec_none_is_identity():
    c = codecs.get_codec("none")
    t = tree([1.0, -2.0])
    assert c.encode(t) is t  # not a copy: bit-identical wire vs pre-PR


def test_codec_int8_error_bound(rng):
    c = codecs.get_codec("int8")
    a = rng.normal(size=(64,)).astype(np.float32)
    dec = codecs.decode_tree(c.encode({"w": a}))["w"]
    assert dec.dtype == np.float32
    assert np.max(np.abs(dec - a)) <= np.max(np.abs(a)) / 127 / 2 + 1e-7


def test_codec_topk_ships_fraction(rng):
    c = codecs.get_codec("topk0.1")
    a = rng.normal(size=(1000,)).astype(np.float32)
    enc = c.encode({"w": a})
    stub = enc["w"]
    assert stub["idx"].size == 100
    dec = codecs.decode_tree(enc)["w"]
    # the 100 largest-magnitude coordinates survive exactly
    keep = np.argsort(np.abs(a))[-100:]
    np.testing.assert_allclose(dec[keep], a[keep])
    assert np.count_nonzero(dec) == 100


@pytest.mark.parametrize("spec,bound_steps", [
    # EF bounds the drift to the RESIDUAL, i.e. at most a few steps'
    # worth of error: ~1 step for int8 (half-LSB residual), ~1/frac
    # steps for top-k (a coordinate ships once its residual wins a slot)
    ("int8", 1.0),
    ("topk0.05", 1.5 / 0.05),
])
def test_codec_error_feedback_accumulates(rng, spec, bound_steps):
    """EF property: the SUM of decoded commits tracks the sum of raw
    gradients (error is delayed — bounded by the residual — not lost;
    without EF the top-k drift would grow linearly, 60 steps' worth)."""
    g = rng.normal(size=(200,)).astype(np.float32)
    c = codecs.get_codec(spec)
    total = np.zeros_like(g)
    for _ in range(60):
        total += np.asarray(codecs.decode_tree(c.encode({"w": g}))["w"])
    drift = np.max(np.abs(total - 60 * g))
    assert drift < bound_steps * np.max(np.abs(g)), (spec, drift)


def test_codec_non_float_leaves_pass_through(rng):
    c = codecs.get_codec("int8")
    t = {"w": rng.normal(size=(8,)).astype(np.float32),
         "counter": np.array([3, 4], np.int64)}
    enc = c.encode(t)
    assert enc["counter"].dtype == np.int64
    dec = codecs.decode_tree(enc)
    np.testing.assert_array_equal(dec["counter"], t["counter"])


def test_codec_nonfinite_leaf_ships_verbatim():
    """A NaN/Inf delta leaf (diverging run) must ship raw — repeatedly —
    without crashing the encoder or poisoning the residual (inf - inf)."""
    c = codecs.get_codec("int8")
    a = np.array([1.0, np.nan, np.inf, -2.0], np.float32)
    for _ in range(3):
        dec = codecs.decode_tree(c.encode(
            {"w": a, "good": np.ones(4, np.float32)}))
        np.testing.assert_array_equal(dec["w"], a)
        np.testing.assert_allclose(dec["good"], 1.0, atol=1 / 127)


def test_reconnect_drops_pull_cache():
    """A restarted server's counter can coincide with the cached one; the
    client must re-ship after reconnect, never serve the old server's
    center from cache."""
    ps = DeltaParameterServer(tree([1.0]), num_workers=1)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port) as c:
            c.pull()
            assert c._last_pull is not None
            c.reconnect()
            assert c._last_pull is None
            center, n = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [1.0])
            # TRANSPARENT reconnect mid-pull: the retry resends a stale
            # ``have`` matching the server counter; the client must
            # recover the full center (not KeyError on the unchanged
            # reply it can no longer serve from cache)
            c.sock.close()
            center, n = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [1.0])


def test_codec_instance_spec_not_shared_by_workers(ds):
    """Passing a Codec INSTANCE as comm_codec must coerce to its spec
    string (per-worker EF residual state cannot be shared)."""
    t = dk.DOWNPOUR(make_model(), comm_codec=codecs.Int8Codec())
    assert t.comm_codec == "int8"


def test_codec_bad_spec_rejected():
    with pytest.raises(ValueError, match="comm_codec"):
        codecs.get_codec("gzip")
    with pytest.raises(ValueError):
        codecs.get_codec("topk0")
    with pytest.raises(ValueError, match="comm_codec"):
        dk.DOWNPOUR(make_model(), comm_codec="bogus")


# -- acceptance: bytes on the wire + numeric parity --------------------------

@pytest.fixture(scope="module")
def ds():
    return toy_problem()


def _async_run(ds, codec, seed=0, workers=2, model=None):
    t = dk.DOWNPOUR(model or make_model(), "sgd", num_workers=workers,
                    mode="async", communication_window=4, comm_codec=codec,
                    seed=seed, **COMMON)
    m = t.train(ds)
    return t, m


def test_int8_cuts_wire_bytes_3x(ds):
    """ISSUE 4 acceptance: comm_codec='int8' drops worker-side
    net.bytes_sent per communication window >= 3x vs 'none' on the tier-1
    async trainer workload, asserted via registry snapshots."""
    from distkeras_tpu.models.layers import Dense, Sequential
    reg = default_registry()

    def model():
        # wide enough that the delta payload dominates the per-message
        # envelope (action/worker_id keys, pull requests) — the regime
        # any real model is in
        return dk.Model(Sequential([Dense(256, "relu"),
                                    Dense(3, "softmax")]),
                        input_shape=(10,))

    def run(codec):
        b0 = reg.counter("net.bytes_sent").value
        t, _ = _async_run(ds, codec, model=model())
        windows = t.ps_stats["num_updates"]
        assert windows > 0
        return (reg.counter("net.bytes_sent").value - b0) / windows, t

    none_bpw, t_none = run("none")
    int8_bpw, t_int8 = run("int8")
    assert none_bpw / int8_bpw >= 3.0, (none_bpw, int8_bpw)
    # codec accounting made it into the server's persisted snapshot
    snap = t_int8.ps_stats["registry"]
    assert snap["ps.codec.bytes_saved"]["value"] > 0
    raw = snap["ps.codec.bytes_raw"]["value"]
    enc = snap["ps.codec.bytes_encoded"]["value"]
    assert raw / enc >= 3.0
    assert snap["ps.codec.decode_seconds"]["count"] == \
        t_int8.ps_stats["num_updates"]
    assert "ps.codec.bytes_saved" not in t_none.ps_stats["registry"] or \
        t_none.ps_stats["registry"].get(
            "ps.codec.bytes_saved", {}).get("value", 0) == 0


def test_codec_none_bit_identical_across_wire_versions(ds, monkeypatch):
    """comm_codec='none' + the v2 wire produce BIT-identical trained
    params to the legacy v1 wire (single worker: the async run is
    deterministic), so the fast path cannot have changed numerics."""
    import jax
    _, m2 = _async_run(ds, "none", workers=1)
    p2 = jax.tree_util.tree_leaves(m2.variables["params"])
    monkeypatch.setenv("DKTPU_WIRE", "1")
    _, m1 = _async_run(ds, "none", workers=1)
    p1 = jax.tree_util.tree_leaves(m1.variables["params"])
    assert len(p1) == len(p2)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("codec", ["int8", "bf16"])
def test_quantized_downpour_converges(ds, codec):
    """Error-feedback quantized DOWNPOUR reaches within epsilon of the
    uncompressed run's accuracy on the tier-1 toy problem."""
    _, m_none = _async_run(ds, "none", seed=3)
    _, m_q = _async_run(ds, codec, seed=3)

    def acc(m):
        pred = dk.ModelPredictor(m, "features").predict(ds)
        return dk.AccuracyEvaluator("prediction", "label").evaluate(pred)

    a_none, a_q = acc(m_none), acc(m_q)
    assert a_q > a_none - 0.08, (codec, a_q, a_none)
    assert a_q > 0.7, (codec, a_q)


# -- ISSUE 12: DOWN compression, adaptive per-link codecs, shm transport -----

def big_tree(n=20_000, seed=0):
    r = np.random.default_rng(seed)
    return {"params": [{"w": r.normal(size=n).astype(np.float32)},
                       {"b": r.normal(size=n // 4).astype(np.float32)}],
            "state": [{"step": np.int32(7)}, {}]}


def test_down_ref_delta_roundtrip(rng):
    """encode_ref_delta/apply_ref_delta: int8 residual error is bounded
    by the residual's scale, non-floating leaves pass through verbatim."""
    ref = big_tree(seed=1)
    center = big_tree(seed=1)
    center["params"][0]["w"] = center["params"][0]["w"] \
        + rng.normal(scale=0.1, size=20_000).astype(np.float32)
    center["state"][0]["step"] = np.int32(9)
    enc = codecs.encode_ref_delta(center, ref, "int8")
    # floating leaves became stubs, the int leaf passed through
    assert enc["params"][0]["w"]["__dkcodec__"] == "int8"
    assert enc["state"][0]["step"] == 9
    dec = codecs.apply_ref_delta(ref, enc)
    # error bound: scale = max|residual| / 127, round-off <= scale/2
    bound = float(np.max(np.abs(
        center["params"][0]["w"] - ref["params"][0]["w"]))) / 127.0
    np.testing.assert_allclose(dec["params"][0]["w"],
                               center["params"][0]["w"], atol=bound)
    # identical leaves (zero residual) reconstruct EXACTLY
    np.testing.assert_array_equal(dec["params"][1]["b"],
                                  center["params"][1]["b"])
    assert dec["state"][0]["step"] == 9
    # spec validation: unknown and degenerate specs are rejected up
    # front, identity specs must be spelled "none"
    with pytest.raises(ValueError, match="comm_codec"):
        codecs.validate_down_spec("gzip")
    with pytest.raises(ValueError):
        codecs.validate_down_spec("topk0")
    assert codecs.validate_down_spec(None) == "none"
    assert codecs.validate_down_spec("adaptive") == "adaptive"


def test_down_pull_resync_then_residual_cuts_bytes_3x():
    """The DOWN acceptance shape: first pull is a full reference resync,
    steady-state pulls ship int8 residuals — >= 3x fewer DOWN wire bytes
    than raw pulls of the same center."""
    def measure(down):
        ps = DeltaParameterServer(big_tree(), num_workers=1)
        reg = Registry()
        with SocketParameterServer(ps) as server:
            with PSClient("127.0.0.1", server.port, registry=reg,
                          down=down) as c:
                c.pull()  # cold (resync when down): not the steady state
                b0 = reg.counter("ps.wire.bytes_down").value
                for i in range(6):
                    c.commit({"params": [
                        {"w": np.full(20_000, 0.01, np.float32)},
                        {"b": np.full(5_000, 0.01, np.float32)}],
                        "state": [{"step": np.int32(7)}, {}]})
                    got, n = c.pull()
                steady = reg.counter("ps.wire.bytes_down").value - b0
                return got, steady, reg
    raw_got, raw_bytes, _ = measure(None)
    q_got, q_bytes, reg = measure("int8")
    assert raw_bytes / q_bytes >= 3.0, (raw_bytes, q_bytes)
    # numerics: residual-decoded center within quantization error of raw
    np.testing.assert_allclose(q_got["params"][0]["w"],
                               raw_got["params"][0]["w"], atol=1e-3)
    assert reg.counter("ps.down.resyncs").value == 1  # cold pull only
    # the cumulative codec ledger INCLUDES the cold resync's verbatim
    # reference (honest accounting), so its ratio trails the steady
    # state; it still shows a clear win and converges to ~4x as the
    # resync amortizes over the run
    snap = reg.snapshot()
    assert snap["ps.down.bytes_raw"]["value"] \
        / snap["ps.down.bytes_encoded"]["value"] >= 2.0


def test_down_v1_interop_matrix():
    """v1 peers never see the DOWN layer: a v1-pinned client sends no
    hello (nothing to advertise), a v1-pinned server never acks — both
    mixes serve raw centers and bit-exact numerics, and shm is never
    negotiated on a v1 connection."""
    for pin_client, pin_server in ((1, None), (None, 1), (1, 1)):
        ps = DeltaParameterServer(tree([0.0]), num_workers=1)
        kw = {"max_wire_version": 1} if pin_server else {}
        with SocketParameterServer(ps, **kw) as server:
            with PSClient("127.0.0.1", server.port,
                          wire_version=pin_client, down="int8",
                          shm=True) as c:
                assert c.wire_version == 1
                assert not c.down_enabled
                assert not c.shm_active
                assert c.commit(tree([2.0]))
                center, n = c.pull()
                # raw path: exact, no quantization anywhere
                np.testing.assert_array_equal(center["params"][0]["w"],
                                              [2.0])
        snap = ps.registry.snapshot()
        assert snap.get("ps.down.bytes_encoded", {}).get("value", 0) == 0


def test_pull_cache_codec_state_guard():
    """ISSUE 12 satellite: a codec-state change WITHOUT a counter bump
    can never serve a stale pre-serialized payload — the composite key
    carries codec/ref-epoch/resync, unit-level and through both server
    paths (plain + shard front-end)."""
    from distkeras_tpu.ps.state import PullCache
    builds = []

    def builder(tag):
        def build():
            builds.append(tag)
            return {"center": {"w": np.zeros(4, np.float32)}, "tag": tag}
        return build

    cache = PullCache(Registry())
    p_raw = cache.payload(2, 5, builder("raw"))
    # same counter, different codec state -> different key -> rebuilt
    p_down = cache.payload((2, "int8", 1, False), 5, builder("int8"))
    assert builds == ["raw", "int8"]
    assert p_raw is not p_down
    # same key again -> cached, NOT rebuilt
    assert cache.payload((2, "int8", 1, False), 5, builder("int8")) \
        is p_down
    # epoch roll without counter bump -> new key -> rebuilt
    cache.payload((2, "int8", 2, True), 5, builder("resync"))
    assert builds == ["raw", "int8", "resync"]

    # end to end, plain server: a raw puller and a down puller at the
    # SAME update counter must get different payload shapes
    ps = DeltaParameterServer(tree([3.0]), num_workers=2)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, 0) as raw_c, \
                PSClient("127.0.0.1", server.port, 1,
                         down="int8") as down_c:
            r, _ = raw_c.pull()
            d, _ = down_c.pull()
            np.testing.assert_array_equal(r["params"][0]["w"], [3.0])
            np.testing.assert_allclose(d["params"][0]["w"], [3.0],
                                       atol=1e-4)
            assert down_c._down_ref is not None  # decoded via reference

    # and through the shard front-end (its _pull_state override rides
    # the same cache protocol)
    from distkeras_tpu.ps.shard import ShardedParameterServer
    center = big_tree(n=64)
    with ShardedParameterServer(center, 2, DeltaParameterServer,
                                num_workers=2) as fleet:
        from distkeras_tpu.ps.shard import ShardedPSClient
        with ShardedPSClient(fleet.addrs(), center, 0) as raw_c, \
                ShardedPSClient(fleet.addrs(), center, 1,
                                down="int8") as down_c:
            r, _ = raw_c.pull()
            d, _ = down_c.pull()
            np.testing.assert_allclose(
                d["params"][0]["w"], r["params"][0]["w"], atol=1e-3)
            assert all(c._down_ref is not None for c in down_c.clients)


def test_adaptive_down_policy_hysteresis_and_trail():
    """AdaptiveDownPolicy: warmup samples every candidate, a challenger
    must beat the incumbent by the margin on `patience` consecutive
    evaluations (one switch, recorded), and RTT noise never flaps."""
    reg = Registry()
    pol = codecs.AdaptiveDownPolicy(reg, candidates=("none", "int8"),
                                    margin=0.2, patience=3,
                                    warmup_samples=2, reprobe_every=0)
    # warmup: the pull loop asks, pulls, observes — the policy walks
    # every candidate to warmup_samples before serving an incumbent
    seen = []
    for _ in range(4):
        c = pol.next_codec()
        seen.append(c)
        pol.observe(c, 0.010 if c == "none" else 0.002)
    assert seen.count("none") == 2 and seen.count("int8") == 2
    # int8 is 5x better: patience evaluations then ONE switch
    for _ in range(3):
        pol.observe("int8", 0.002)
    assert pol.current == "int8"
    assert reg.counter("ps.codec.switches").value == 1
    assert len(pol.trail) == 1
    assert pol.trail[0]["from"] == "none" and pol.trail[0]["to"] == "int8"
    # noise within the margin: no flapping back
    for _ in range(20):
        pol.observe("int8", 0.0021)
        pol.observe("none", 0.0022)
    assert pol.current == "int8"
    assert reg.counter("ps.codec.switches").value == 1
    # junk observations are ignored, not folded into the EWMAs
    pol.observe("int8", float("nan"))
    pol.observe("bogus", 0.001)
    assert pol.current == "int8"


def test_adaptive_down_end_to_end():
    """down='adaptive' drives real pulls: warmup cycles every candidate
    codec against the live link, every pull decodes exactly (within
    quantization error), and the policy's EWMAs get seeded."""
    ps = DeltaParameterServer(big_tree(), num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, registry=reg,
                      down="adaptive") as c:
            assert c.down_enabled and c._down_policy is not None
            ref = None
            for i in range(8):
                c.commit({"params": [
                    {"w": np.full(20_000, 0.01, np.float32)},
                    {"b": np.full(5_000, 0.01, np.float32)}],
                    "state": [{"step": np.int32(7)}, {}]})
                got, n = c.pull()
            pol = c._down_policy
            assert all(pol._samples[cand] >= pol.warmup_samples
                       for cand in pol.candidates if cand != "none"), \
                pol._samples
    expect = np.asarray(ps.center["params"][0]["w"])
    np.testing.assert_allclose(got["params"][0]["w"], expect, atol=1e-2)


def test_shm_negotiation_transport_and_cleanup():
    """shm=True against a same-host server: rings negotiated, tensor
    segments bypass TCP (net.bytes_shm), numerics exact, and the
    client-owned segments are unlinked from /dev/shm on close."""
    ps = DeltaParameterServer(big_tree(), num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        c = PSClient("127.0.0.1", server.port, registry=reg, shm=True)
        try:
            assert c.shm_active
            names = [c._chan.tx.name.strip("/"), c._chan.rx.name.strip("/")]
            got, _ = c.pull()
            np.testing.assert_array_equal(
                got["params"][0]["w"], np.asarray(ps.center["params"][0]["w"]))
            c.commit({"params": [{"w": np.ones(20_000, np.float32)},
                                 {"b": np.ones(5_000, np.float32)}],
                      "state": [{"step": np.int32(7)}, {}]})
            got2, n2 = c.pull()
            assert n2 == 1
            np.testing.assert_allclose(
                got2["params"][0]["w"],
                np.asarray(ps.center["params"][0]["w"]))
            assert reg.counter("net.bytes_shm").value > 0
        finally:
            c.close()
        if os.path.isdir("/dev/shm"):
            leftovers = [n for n in names
                         if os.path.exists(os.path.join("/dev/shm", n))]
            assert not leftovers, leftovers


def test_shm_oversized_message_falls_back_to_tcp():
    """A message whose segments exceed the ring transparently rides the
    TCP frame for that message — correctness never depends on capacity."""
    n = 600_000  # 2.4 MB center vs the 1 MB minimum ring
    center = {"params": [{"w": np.arange(n, dtype=np.float32)}],
              "state": [{}]}
    ps = DeltaParameterServer(center, num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, registry=reg, shm=True,
                      shm_mb=1.0) as c:
            assert c.shm_active
            got, _ = c.pull()  # 2.4 MB does not fit: TCP fallback
            np.testing.assert_array_equal(got["params"][0]["w"],
                                          center["params"][0]["w"])
            c.commit({"params": [{"w": np.zeros(n, np.float32)}],
                      "state": [{}]})
    # the big center payload was NOT shm-carried
    assert reg.counter("net.bytes_shm").value < n * 4


def test_killed_worker_respawn_resyncs_reference_and_tombstones():
    """ISSUE 12 satellite: a worker killed mid-run (connection torn, no
    teardown) and respawned starts reference-less — its first pull is a
    full resync — while the zombie's stale-generation commit tombstones
    with exact accounting."""
    ps = DeltaParameterServer(big_tree(), num_workers=1)
    with SocketParameterServer(ps) as server:
        reg1 = Registry()
        zombie = PSClient("127.0.0.1", server.port, worker_id=0,
                          registry=reg1, down="int8", generation=0)
        zombie.pull()
        zombie.commit({"params": [{"w": np.ones(20_000, np.float32)},
                                  {"b": np.ones(5_000, np.float32)}],
                       "state": [{"step": np.int32(7)}, {}]})
        assert reg1.counter("ps.down.resyncs").value == 1
        # the supervisor declares the incarnation dead (kill -9 has no
        # goodbye): generation bumps, socket just drops
        window = ps.evict_worker(0)
        assert window == 1
        # the respawned incarnation: a FRESH client under the bumped
        # generation — reference-less by construction
        start, gen = ps.register_respawn(0)
        assert (start, gen) == (1, 1)
        reg2 = Registry()
        with PSClient("127.0.0.1", server.port, worker_id=0,
                      registry=reg2, down="int8", generation=gen) as fresh:
            got, n = fresh.pull()
            assert reg2.counter("ps.down.resyncs").value == 1
            np.testing.assert_allclose(
                got["params"][0]["w"],
                np.asarray(ps.center["params"][0]["w"]), atol=1e-3)
            # the zombie wakes up (SIGCONT) and replays its commit: the
            # stale generation tombstones — never applied, exact books
            from distkeras_tpu.ps.client import WorkerEvicted
            with pytest.raises(WorkerEvicted):
                zombie.commit({"params": [
                    {"w": np.ones(20_000, np.float32)},
                    {"b": np.ones(5_000, np.float32)}],
                    "state": [{"step": np.int32(7)}, {}]})
            assert ps.tombstoned_by_worker == {0: 1}
            assert ps.commits_by_worker == {0: 1}
            assert ps.registry.get("ps.commits_tombstoned").value == 1
            # a fresh-generation commit lands normally
            fresh.commit({"params": [{"w": np.ones(20_000, np.float32)},
                                     {"b": np.ones(5_000, np.float32)}],
                          "state": [{"step": np.int32(7)}, {}]})
            assert ps.commits_by_worker == {0: 2}
        zombie.close()


def test_reconnect_resets_down_reference():
    """A reconnect (server restart, mid-pull connection loss) drops the
    held reference: the revenant connection's next pull resyncs instead
    of decoding against state the server may no longer have."""
    ps = DeltaParameterServer(big_tree(), num_workers=1)
    reg = Registry()
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, registry=reg,
                      down="int8") as c:
            c.pull()
            assert c._down_ref is not None
            c.reconnect()
            assert c._down_ref is None  # reference-less again
            got, _ = c.pull()           # full resync, decodes exactly
            assert reg.counter("ps.down.resyncs").value == 2
            np.testing.assert_allclose(
                got["params"][0]["w"],
                np.asarray(ps.center["params"][0]["w"]), atol=1e-3)


def test_obsview_prints_codec_accounting(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import obsview
    finally:
        sys.path.remove(os.path.join(ROOT, "scripts"))
    stats = {"ps.codec.bytes_raw": {"type": "counter", "value": 4000.0},
             "ps.codec.bytes_encoded": {"type": "counter", "value": 1000.0},
             "ps.codec.bytes_saved": {"type": "counter", "value": 3000.0},
             "ps.commits": {"type": "counter", "value": 7.0}}
    # JSONL mode: codec section rides the ps_stats record
    text = obsview.summarize([
        {"event": "epoch", "epoch": 0, "trainer": "DOWNPOUR",
         "mean_loss": 1.0, "epoch_seconds": 1.0, "samples_per_sec": 10.0},
        {"event": "ps_stats", "num_updates": 7, "stats": stats}])
    assert "bytes saved: 3,000" in text
    assert "compression: 4.00x" in text
    # snapshot-file mode (named registries beside a config)
    p = tmp_path / "snap.json"
    p.write_text(json.dumps({"config": {"codec": "int8"},
                             "server": stats}))
    doc = obsview.load_snapshot(str(p))
    assert doc is not None
    out = obsview.summarize_snapshot(doc)
    assert "compression: 4.00x" in out and "server registry" in out
    # live-poll rendering carries the section too
    live = obsview.summarize_stats({"stats": stats, "num_updates": 7})
    assert "bytes saved" in live
