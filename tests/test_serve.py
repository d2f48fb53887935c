"""Continuous-batching decode service (ISSUE 7): the engine's
offline-decode parity, mid-decode joins, admission control (queue-full
load shedding, draining rejections, hard-stop aborts — nothing drops
without a recorded rejection), the serve wire (v1<->v2 interop over the
shared hello seam), the steady-state ``jit.retraces == 0`` contract
drift-gated by the committed ``OBS_BASELINE.json``, and the
``obsview --serve`` rendering.

ISSUE 11 adds the decode accelerators: prefix-KV-cache warm joins
(parity, ttft split, LRU eviction under budget pressure, the
``promote()`` flush) and speculative decoding (greedy parity vs
``generate_tokens`` across bucket boundaries and eos-mid-window, at any
draft quality), their config-time knob validation, and their obsview
surface."""

import copy
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.models import zoo
from distkeras_tpu.models.generation import generate_tokens
from distkeras_tpu.obs import Registry, drift
from distkeras_tpu.serve import (DecodeEngine, ServeClient, ServeConfig,
                                 ServeRejected, ServeServer)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, SEQ = 32, 32


@pytest.fixture(scope="module")
def lm():
    model = zoo.gpt_lm(vocab_size=VOCAB, dim=16, num_heads=2,
                       num_blocks=1, seq_len=SEQ)
    return model, model.init(0)


def _engine(lm, registry=None, **kw):
    model, v = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 4)
    kw.setdefault("max_new_tokens", 12)
    return DecodeEngine(model, v, ServeConfig(**kw),
                        registry=registry if registry is not None
                        else Registry())


def _ref(lm, prompt, steps, **kw):
    """The offline decode's continuation for ``prompt`` — the ground
    truth a continuously-batched request must reproduce."""
    model, v = lm
    out = generate_tokens(model, v,
                          np.asarray(prompt, np.int32)[None, :],
                          int(steps), **kw)
    return np.asarray(out)[0, len(prompt):]


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

def test_config_buckets_and_validation():
    cfg = ServeConfig()
    assert cfg.resolved_buckets(256) == (32, 64, 128, 256)
    assert cfg.resolved_buckets(32) == (32,)
    assert cfg.bucket_for(5, 256) == 32
    assert cfg.bucket_for(65, 256) == 128
    explicit = ServeConfig(prefill_buckets=(8, 16))
    # the largest bucket is always topped up to seq_len
    assert explicit.resolved_buckets(32) == (8, 16, 32)
    with pytest.raises(ValueError):
        ServeConfig(slots=0)
    with pytest.raises(ValueError):
        # admission flows through the queue: a zero-length queue would
        # reject every request even with all slots idle
        ServeConfig(max_queue=0)
    with pytest.raises(ValueError):
        ServeConfig(prefill_buckets=(64,)).resolved_buckets(32)
    with pytest.raises(ValueError):
        ServeConfig(temperature=-1.0)


# ---------------------------------------------------------------------------
# engine: decode parity + continuous joins
# ---------------------------------------------------------------------------

def test_engine_matches_offline_decode(lm):
    rng = np.random.default_rng(0)
    with _engine(lm) as eng:
        for n, steps in ((5, 8), (1, 4), (17, 12)):
            prompt = _prompt(rng, n)
            got = eng.submit(prompt, steps).result(timeout=60)
            assert np.array_equal(got, _ref(lm, prompt, steps))


def test_engine_eos_finishes_row_early(lm):
    # pick a prompt whose greedy continuation's THIRD token is fresh, and
    # use it as the "eos" so the engine must stop exactly there
    prompt = full = eos = None
    for seed in range(8):
        rng = np.random.default_rng(seed)
        prompt = _prompt(rng, 6)
        full = _ref(lm, prompt, 8)
        eos = int(full[2])
        if eos not in (int(full[0]), int(full[1])):
            break
    else:
        pytest.skip("every probed continuation repeats its 3rd token")
    with _engine(lm, eos_id=eos) as eng:
        got = eng.submit(prompt, 8).result(timeout=60)
    assert list(got) == list(full[:3])  # stops AT the eos, inclusive


def test_continuous_join_mid_decode(lm):
    """The tentpole behavior: a request admitted while another is
    mid-decode joins the running batch (no wait for the batch to end)
    and completes — and the long request is unperturbed."""
    rng = np.random.default_rng(2)
    long_p, short_p = _prompt(rng, 4), _prompt(rng, 6)
    reg = Registry()
    with _engine(lm, registry=reg, max_new_tokens=24) as eng:
        req_a = eng.submit(long_p, 24)
        # wait until A is genuinely mid-decode (tokens flowing)
        deadline = time.monotonic() + 30
        while reg.counter("serve.tokens_out").value < 2:
            assert time.monotonic() < deadline, "decode never started"
            time.sleep(0.002)
        req_b = eng.submit(short_p, 4)
        got_b = req_b.result(timeout=60)
        got_a = req_a.result(timeout=60)
    assert not req_a.done or req_a.done_t >= req_b.admit_t  # B joined mid-A
    assert req_b.done_t < req_a.done_t  # B retired while A kept going
    assert np.array_equal(got_a, _ref(lm, long_p, 24))
    assert np.array_equal(got_b, _ref(lm, short_p, 4))
    assert reg.counter("serve.joins").value == 2
    assert reg.counter("jit.retraces").value == 0


def test_checkpoint_promotion_swaps_weights_without_retrace(lm):
    """The online-learning "deploy" seam: promote() swaps the serving
    weights between steps — subsequent requests decode under the new
    checkpoint, and nothing re-traces (same shapes, same programs)."""
    model, _ = lm
    v_new = model.init(1)  # a different checkpoint of the same model
    rng = np.random.default_rng(8)
    prompt = _prompt(rng, 6)
    reg = Registry()
    with _engine(lm, registry=reg) as eng:
        before = eng.submit(prompt, 8).result(timeout=60)
        eng.promote(v_new)
        after = eng.submit(prompt, 8).result(timeout=60)
    assert np.array_equal(before, _ref(lm, prompt, 8))
    ref_new = np.asarray(generate_tokens(
        model, v_new, prompt[None, :], 8))[0, len(prompt):]
    assert np.array_equal(after, ref_new)
    assert not np.array_equal(before, after), \
        "distinct checkpoints should decode differently"
    assert reg.counter("serve.promotions").value == 1
    assert reg.counter("jit.retraces").value == 0


# ---------------------------------------------------------------------------
# per-request sampling params (ISSUE 14 satellite)
# ---------------------------------------------------------------------------

def test_rowwise_filter_matches_batch_filter():
    """``filter_logits_rowwise`` with uniform traced params equals the
    Python-constant ``_filter_logits`` — the per-request path is the
    same filter, just value-parameterized."""
    import jax.numpy as jnp
    from distkeras_tpu.models.generation import (_filter_logits,
                                                 filter_logits_rowwise)
    rng = np.random.default_rng(30)
    logits = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    want = _filter_logits(logits, 5, 0.8)
    got = filter_logits_rowwise(logits, np.full(4, 5, np.int32),
                                np.full(4, 0.8, np.float32))
    assert np.allclose(np.asarray(want), np.asarray(got))
    # the disabled encodings: top_k=0 / top_p=1 pass logits through
    got = filter_logits_rowwise(logits, np.zeros(4, np.int32),
                                np.ones(4, np.float32))
    assert np.allclose(np.asarray(got), np.asarray(logits))


def test_per_request_sampling_rides_the_request(lm):
    """One fleet serves every temperature (ISSUE 14): a greedy request
    stays EXACTLY the offline reference while a sampled request shares
    its batch; a ``top_k=1`` request at any temperature is provably the
    argmax chain too (the per-row filter leaves one candidate); and the
    mixed traffic never re-traces — the params are traced values, not
    program constants."""
    rng = np.random.default_rng(31)
    greedy_p, hot_p, topk1_p = (_prompt(rng, n) for n in (5, 6, 7))
    reg = Registry()
    with _engine(lm, registry=reg, max_new_tokens=16) as eng:
        hot = eng.submit(hot_p, 12, temperature=1.2, top_p=0.9)
        greedy = eng.submit(greedy_p, 12)
        topk1 = eng.submit(topk1_p, 12, temperature=0.7, top_k=1)
        got_hot = hot.result(timeout=60)
        got_greedy = greedy.result(timeout=60)
        got_topk1 = topk1.result(timeout=60)
    assert np.array_equal(got_greedy, _ref(lm, greedy_p, 12))
    assert np.array_equal(got_topk1, _ref(lm, topk1_p, 12))
    assert got_hot.shape == (12,)
    assert ((0 <= got_hot) & (got_hot < VOCAB)).all()
    assert reg.counter("jit.retraces").value == 0
    # the resolved params ride the request handle
    assert (hot.temperature, hot.top_k, hot.top_p) == (1.2, 0, 0.9)
    assert (greedy.temperature, greedy.top_k, greedy.top_p) == \
        (0.0, 0, 1.0)


def test_per_request_sampling_over_the_wire(lm):
    """temperature/top_k/top_p ride the generate RPC as plain msgpack
    keys (old servers would ignore them — the wire extension
    contract)."""
    rng = np.random.default_rng(32)
    prompt = _prompt(rng, 6)
    with ServeServer(_engine(lm).warmup()) as srv:
        with ServeClient("127.0.0.1", srv.port) as c:
            r = c.generate(prompt, 8, temperature=0.7, top_k=1)
            assert r["ok"], r
            # top_k=1 at any temperature is the argmax chain
            assert np.array_equal(np.asarray(r["tokens"]),
                                  _ref(lm, prompt, 8))
            bad = c.generate(prompt, 8, temperature=-1.0)
            assert bad["ok"] is False and "temperature" in bad["error"]


def test_per_request_sampling_validation(lm):
    eng = _engine(lm)  # not started; submit validates before queueing
    with pytest.raises(ValueError, match="temperature"):
        eng.submit(np.arange(4), 4, temperature=-0.5)
    with pytest.raises(ValueError, match="temperature"):
        # NaN rides msgpack floats fine — it must fail validation, not
        # poison the row's logits in the compiled step
        eng.submit(np.arange(4), 4, temperature=float("nan"))
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(np.arange(4), 4, top_k=-2)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(np.arange(4), 4, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(np.arange(4), 4, top_p=1.5)
    eng.stop(drain=False)


# ---------------------------------------------------------------------------
# prefix KV cache (ISSUE 11 accelerator #1)
# ---------------------------------------------------------------------------

def test_config_accelerator_knob_validation(lm):
    """The new knobs reject at CONFIG time (the max_queue=0 precedent):
    an unbounded device cache, a nonsense block/k, and a draft the
    target cannot verify against are all caller errors, never
    decode-thread discoveries."""
    model, v = lm
    with pytest.raises(ValueError):
        ServeConfig(prefix_cache=True, prefix_cache_mb=0.0)
    with pytest.raises(ValueError):
        ServeConfig(prefix_cache=True, prefix_cache_mb=-1.0)
    with pytest.raises(ValueError):
        ServeConfig(prefix_block=0)
    with pytest.raises(ValueError):
        ServeConfig(spec_k=-1)
    # ISSUE 14: speculative decode COMPOSES with sampling now —
    # distribution-preserving accept/reject, no longer a config error
    ServeConfig(spec_k=2, temperature=0.7)
    # draft validation happens at ENGINE construction, same contract
    cfg = ServeConfig(spec_k=2, max_new_tokens=12)
    with pytest.raises(ValueError, match="draft"):
        DecodeEngine(model, v, cfg, registry=Registry())
    wrong_vocab = zoo.gpt_lm(vocab_size=VOCAB * 2, dim=8, num_heads=2,
                             num_blocks=1, seq_len=SEQ)
    with pytest.raises(ValueError, match="vocab"):
        DecodeEngine(model, v, cfg, registry=Registry(),
                     draft_model=wrong_vocab,
                     draft_variables=wrong_vocab.init(0))
    wrong_seq = zoo.gpt_lm(vocab_size=VOCAB, dim=8, num_heads=2,
                           num_blocks=1, seq_len=SEQ * 2)
    with pytest.raises(ValueError, match="seq_len"):
        DecodeEngine(model, v, cfg, registry=Registry(),
                     draft_model=wrong_seq,
                     draft_variables=wrong_seq.init(0))
    # zoo.draft_lm builds the compatible shape by construction
    draft = zoo.draft_lm(model, dim=8)
    assert int(draft.output_shape[-1]) == VOCAB
    assert int(draft.input_shape[0]) == SEQ
    # the converse mistake: a draft supplied with spec_k == 0 would
    # silently never speculate — rejected at construction too
    with pytest.raises(ValueError, match="spec_k"):
        DecodeEngine(model, v, ServeConfig(max_new_tokens=12),
                     registry=Registry(),
                     draft_model=draft, draft_variables=draft.init(0))


def test_prefix_cache_warm_join_parity_and_ttft_split(lm):
    """Prompts sharing a block-aligned system prefix warm-join over the
    cached KV: the decoded output is EXACTLY the cold path's (the
    offline reference), the hit/miss counters and the warm/cold ttft
    split record the outcome, and the pre-compiled suffix-join ladder
    holds ``jit.retraces == 0``."""
    rng = np.random.default_rng(20)
    reg = Registry()
    eng = _engine(lm, registry=reg, prefill_buckets=(8, SEQ),
                  prefix_cache=True, prefix_cache_mb=8.0,
                  prefix_block=8).warmup()
    snap0 = reg.snapshot()
    # full ladder: 2 joins + 2 suffix joins + 1 step
    assert snap0["jit.compiles"]["value"] == 5
    shared = _prompt(rng, 8)  # one block exactly
    prompts = [np.concatenate([shared, _prompt(rng, n)])
               for n in (3, 5, 9)]  # suffixes span both buckets
    with eng:
        for p in prompts:
            got = eng.submit(p, 6).result(timeout=60)
            assert np.array_equal(got, _ref(lm, p, 6))
        # resubmission of a fully cached prompt: longest-prefix match is
        # capped at len-1, the last token re-plays, output identical
        got = eng.submit(prompts[0], 6).result(timeout=60)
        assert np.array_equal(got, _ref(lm, prompts[0], 6))
    snap = reg.snapshot()
    assert snap["serve.prefix.misses"]["value"] == 1
    assert snap["serve.prefix.hits"]["value"] == 3
    # 3 distinct prompts inserted; the resubmission dedups by content
    assert snap["serve.prefix.inserts"]["value"] == 3
    assert snap["serve.ttft_cold_seconds"]["count"] == 1
    assert snap["serve.ttft_warm_seconds"]["count"] == 3
    assert snap["jit.compiles"]["value"] == 5  # nothing new compiled
    assert snap["jit.retraces"]["value"] == 0


def test_prefix_cache_lru_eviction_under_pressure(lm):
    """Fill the cache past its byte budget: LRU entries evict (recorded
    under ``serve.prefix.evictions``, bytes bounded by the budget) and
    every served output is unchanged — the cache only ever buys ttft,
    never correctness."""
    rng = np.random.default_rng(21)
    reg = Registry()
    budget_mb = 0.02  # a couple of entries' worth for this toy model
    eng = _engine(lm, registry=reg, prefix_cache=True,
                  prefix_cache_mb=budget_mb, prefix_block=8).warmup()
    prompts = [_prompt(rng, 10) for _ in range(6)]  # all distinct
    with eng:
        for p in prompts:
            got = eng.submit(p, 5).result(timeout=60)
            assert np.array_equal(got, _ref(lm, p, 5))
    snap = reg.snapshot()
    assert snap["serve.prefix.inserts"]["value"] == 6
    assert snap["serve.prefix.evictions"]["value"] >= 1
    assert snap["serve.prefix.bytes"]["value"] <= budget_mb * 1024 * 1024
    assert snap["serve.prefix.entries"]["value"] < 6
    assert snap["jit.retraces"]["value"] == 0


def test_prefix_eviction_repoints_shared_alias():
    """First-writer-wins aliasing survives eviction of the owner: when
    the entry that owns a shared-prefix lookup key is LRU-evicted while
    another live entry still holds those prefix bytes, the alias is
    re-pointed at the heir instead of dropped — the next prompt with
    that prefix still warm-hits."""
    from distkeras_tpu.serve.prefix import PrefixCache, PrefixEntry

    def entry(host):
        return PrefixEntry(np.asarray(host, np.int32),
                           np.zeros((1, SEQ), np.int32),
                           {"k": np.zeros((SEQ, 4), np.float32)})

    rng = np.random.default_rng(23)
    system = _prompt(rng, 8)  # exactly one block
    a = entry(np.concatenate([system, _prompt(rng, 3)]))
    b = entry(np.concatenate([system, _prompt(rng, 1)]))
    c = entry(_prompt(rng, 10))  # unrelated content
    reg = Registry()
    cache = PrefixCache(a.nbytes + b.nbytes + c.nbytes - 1, reg, block=8)
    cache.insert(a)  # first writer: owns the (8, sha1(system)) alias
    cache.insert(b)
    cache.insert(c)  # over budget -> evicts A (LRU)
    snap = reg.snapshot()
    assert snap["serve.prefix.evictions"]["value"] == 1
    assert len(cache) == 2
    hit = cache.lookup(np.concatenate([system, _prompt(rng, 2)]))
    assert hit is not None
    heir, matched = hit
    assert matched == 8
    assert np.array_equal(heir.host_tokens, b.host_tokens)
    assert reg.snapshot()["serve.prefix.hits"]["value"] == 1


def test_prefix_insert_of_covered_content_spends_no_budget():
    """Inserting content every lookup key of which is already owned (a
    block-aligned prompt fully covered by an older entry) must NOT
    store an unreachable duplicate: the covering owner is LRU-refreshed
    and no bytes/insert are accounted — budget is never spent on KV
    that could never be hit."""
    from distkeras_tpu.serve.prefix import PrefixCache, PrefixEntry

    def entry(host):
        return PrefixEntry(np.asarray(host, np.int32),
                           np.zeros((1, SEQ), np.int32),
                           {"k": np.zeros((SEQ, 4), np.float32)})

    rng = np.random.default_rng(25)
    system = _prompt(rng, 8)  # exactly one block
    a = entry(np.concatenate([system, _prompt(rng, 8)]))  # owns (8,) (16,)
    b = entry(system)  # fully covered: its only key (8,) is A's
    reg = Registry()
    cache = PrefixCache(10 * a.nbytes, reg, block=8)
    cache.insert(a)
    cache.insert(b)
    snap = reg.snapshot()
    assert len(cache) == 1
    assert cache.nbytes == a.nbytes
    assert snap["serve.prefix.inserts"]["value"] == 1
    hit = cache.lookup(np.concatenate([system, _prompt(rng, 2)]))
    assert hit is not None and hit[1] == 8
    assert np.array_equal(hit[0].host_tokens, a.host_tokens)


def test_drain_skips_wasted_lookahead_step(lm):
    """Dispatch-ahead skips the look-ahead step when the in-flight one
    is certain to drain the batch: a lone greedy request needing
    ``max_new`` tokens costs EXACTLY ``max_new`` device steps — no
    trailing step dispatched only to be discarded — and the output is
    still the offline reference."""
    rng = np.random.default_rng(24)
    reg = Registry()
    prompt = _prompt(rng, 7)
    with _engine(lm, registry=reg) as eng:
        got = eng.submit(prompt, 6).result(timeout=60)
    assert np.array_equal(got, _ref(lm, prompt, 6))
    snap = reg.snapshot()
    assert snap["serve.steps"]["value"] == 6
    assert snap["serve.tokens_out"]["value"] == 6


def test_promote_flushes_prefix_cache(lm):
    """A promoted checkpoint MUST flush the cache: cached KV is a pure
    function of (tokens, weights).  A prompt cached under the old
    weights decodes correctly under the new ones — served output equals
    the offline decode under the deployed checkpoint."""
    model, _ = lm
    v_new = model.init(42)
    rng = np.random.default_rng(22)
    prompt = _prompt(rng, 9)
    reg = Registry()
    with _engine(lm, registry=reg, prefix_cache=True,
                 prefix_cache_mb=8.0, prefix_block=4) as eng:
        before = eng.submit(prompt, 6).result(timeout=60)
        assert len(eng._prefix) == 1
        eng.promote(v_new)
        assert len(eng._prefix) == 0  # flushed with the swap
        # the SAME prompt again: no stale-KV hit is possible, and the
        # decode matches the offline reference under the NEW weights
        after = eng.submit(prompt, 6).result(timeout=60)
    assert np.array_equal(before, _ref(lm, prompt, 6))
    ref_new = np.asarray(generate_tokens(
        model, v_new, prompt[None, :], 6))[0, len(prompt):]
    assert np.array_equal(after, ref_new)
    assert reg.counter("jit.retraces").value == 0


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 11 accelerator #2)
# ---------------------------------------------------------------------------

def _spec_engine(lm, registry, draft, draft_v, **kw):
    model, v = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("max_new_tokens", 12)
    return DecodeEngine(model, v, ServeConfig(**kw), registry=registry,
                        draft_model=draft, draft_variables=draft_v)


def test_spec_greedy_parity_across_buckets(lm):
    """Speculative greedy output equals ``generate_tokens`` exactly, at
    BOTH ends of draft quality: a self-draft (accept rate 1 — every
    window fully accepted) and an independent random draft (accept rate
    ~0 — every window rejected at its first token).  Prompts span the
    bucket ladder; the whole run holds ``jit.retraces == 0``."""
    model, v = lm
    rng = np.random.default_rng(23)
    prompts = [_prompt(rng, n) for n in (3, 8, 17)]  # both buckets
    indep = zoo.draft_lm(model, dim=8, num_heads=2, num_blocks=1)
    for draft, draft_v, lo, hi in ((model, v, 0.99, 1.0),
                                   (indep, indep.init(7), 0.0, 0.5)):
        reg = Registry()
        eng = _spec_engine(lm, reg, draft, draft_v, spec_k=3,
                           prefill_buckets=(8, SEQ)).warmup()
        with eng:
            for p in prompts:
                got = eng.submit(p, 10).result(timeout=60)
                assert np.array_equal(got, _ref(lm, p, 10))
        snap = reg.snapshot()
        rate = snap["serve.spec.accept_rate"]["value"]
        assert lo <= rate <= hi, \
            f"accept rate {rate} outside [{lo}, {hi}]"
        assert snap["serve.spec.proposed"]["value"] > 0
        assert snap["jit.retraces"]["value"] == 0


def test_spec_eos_mid_window_stops_exactly(lm):
    """An eos sampled MID speculative window (the self-draft guarantees
    the window runs past it) stops the request exactly there, inclusive
    — tokens the window emitted past the stop are discarded."""
    model, v = lm
    prompt = full = eos = None
    for seed in range(16):
        rng = np.random.default_rng(seed)
        prompt = _prompt(rng, 5)
        full = _ref(lm, prompt, 8)
        eos = int(full[1])  # 2nd token: inside the first k=3 window
        if eos != int(full[0]):
            break
    else:
        pytest.skip("every probed continuation repeats its 2nd token")
    reg = Registry()
    eng = _spec_engine(lm, reg, model, v, spec_k=3, eos_id=eos).warmup()
    with eng:
        got = eng.submit(prompt, 8).result(timeout=60)
    assert list(got) == list(full[:2])
    assert reg.snapshot()["jit.retraces"]["value"] == 0


def test_spec_composes_with_prefix_cache(lm):
    """Both accelerators on one engine: a warm suffix join must prefill
    the DRAFT's cache alongside the target's, and the speculative decode
    that follows stays greedy-exact."""
    model, v = lm
    rng = np.random.default_rng(24)
    shared = _prompt(rng, 8)
    prompts = [np.concatenate([shared, _prompt(rng, n)]) for n in (3, 4)]
    reg = Registry()
    eng = _spec_engine(lm, reg, model, v, spec_k=2,
                       prefill_buckets=(8, SEQ), prefix_cache=True,
                       prefix_cache_mb=8.0, prefix_block=8).warmup()
    with eng:
        for p in prompts:
            got = eng.submit(p, 8).result(timeout=60)
            assert np.array_equal(got, _ref(lm, p, 8))
    snap = reg.snapshot()
    assert snap["serve.prefix.hits"]["value"] == 1
    assert snap["serve.spec.accept_rate"]["value"] > 0.99
    assert snap["jit.retraces"]["value"] == 0


def test_spec_sampling_topk1_is_greedy_exact(lm):
    """``spec_k`` composes with ``temperature > 0`` (ISSUE 14): with
    ``top_k=1`` the per-row filter leaves a single candidate, so the
    distribution-preserving accept/reject must reproduce the argmax
    chain EXACTLY — a deterministic end-to-end probe of the sampled
    acceptance path (draft proposes from q, target accepts against p,
    residual resample on rejection) through the live engine."""
    model, v = lm
    rng = np.random.default_rng(25)
    prompts = [_prompt(rng, n) for n in (4, 9)]
    indep = zoo.draft_lm(model, dim=8, num_heads=2, num_blocks=1)
    for draft, draft_v in ((model, v), (indep, indep.init(7))):
        reg = Registry()
        eng = _spec_engine(lm, reg, draft, draft_v, spec_k=3,
                           prefill_buckets=(8, SEQ)).warmup()
        with eng:
            for p in prompts:
                got = eng.submit(p, 8, temperature=0.9,
                                 top_k=1).result(timeout=60)
                assert np.array_equal(got, _ref(lm, p, 8))
        assert reg.snapshot()["jit.retraces"]["value"] == 0


def test_spec_sampling_self_draft_accepts_everything(lm):
    """With the draft == the target, q == p at every position, so the
    accept test ``u*q(x) <= p(x)`` passes for every proposal: accept
    rate 1.0 even at temperature > 0 — and a mixed greedy/sampled batch
    holds it while the greedy rows stay parity-exact."""
    model, v = lm
    rng = np.random.default_rng(26)
    greedy_p, hot_p = _prompt(rng, 5), _prompt(rng, 6)
    reg = Registry()
    eng = _spec_engine(lm, reg, model, v, spec_k=3).warmup()
    with eng:
        hot = eng.submit(hot_p, 9, temperature=1.0)
        greedy = eng.submit(greedy_p, 9)
        got_hot = hot.result(timeout=60)
        got_greedy = greedy.result(timeout=60)
    assert np.array_equal(got_greedy, _ref(lm, greedy_p, 9))
    assert got_hot.shape == (9,)
    snap = reg.snapshot()
    assert snap["serve.spec.accept_rate"]["value"] > 0.99
    assert snap["jit.retraces"]["value"] == 0


def test_spec_sampling_distribution_preserved(lm):
    """The core identity: the FIRST token emitted by the speculative
    sampling step is distributed as the target's own sampling
    distribution, at any draft quality — an independent (wrong) draft
    shifts speed, never the marginal.  Empirical TV distance against
    ``rowwise_dist`` of the target's carried logits over many rng
    draws, greedy row checked alongside."""
    import jax
    import jax.numpy as jnp
    from distkeras_tpu.models.generation import (_model_cache,
                                                 rowwise_dist)
    from distkeras_tpu.serve.spec import build_spec_step

    model, v = lm
    draft = zoo.draft_lm(model, dim=8, num_heads=2, num_blocks=1)
    dv = jax.tree_util.tree_map(jnp.asarray, draft.init(19))
    vv = jax.tree_util.tree_map(jnp.asarray, v)
    b, k, t, plen = 2, 3, SEQ, 4
    rng = np.random.default_rng(27)
    buf = np.zeros((b, t), np.int32)
    buf[:, :plen] = rng.integers(0, VOCAB, size=(b, plen))
    buf = jnp.asarray(buf)
    cache = _model_cache(model, b)
    dcache = _model_cache(draft, b)
    y, cache = model.layer.apply_prefill(vv["params"], vv["state"], buf,
                                         cache)
    dy, dcache = draft.layer.apply_prefill(dv["params"], dv["state"],
                                           buf, dcache)
    logits, dlogits = y[:, plen - 1], dy[:, plen - 1]
    pos = jnp.full((b,), plen, jnp.int32)
    active = np.ones((b,), bool)
    # row 0 samples at temperature 1 with nucleus filtering; row 1 is
    # greedy — both through the SAME compiled program
    temp = np.asarray([1.0, 0.0], np.float32)
    topk = np.zeros((b,), np.int32)
    topp = np.asarray([0.9, 1.0], np.float32)
    fn = jax.jit(build_spec_step(model, draft, k))
    counts = np.zeros(VOCAB)
    draws = 600
    for i in range(draws):
        outs = fn(vv, dv, buf, cache, dcache, pos, logits, dlogits,
                  active, temp, topk, topp, jax.random.PRNGKey(i))
        emitted = np.asarray(outs[7])
        counts[emitted[0, 0]] += 1
        # the greedy row emits the argmax regardless of rng
        assert emitted[1, 0] == int(np.argmax(np.asarray(logits)[1]))
    want = np.asarray(rowwise_dist(logits, temp, topk, topp))[0]
    tv = 0.5 * np.abs(counts / draws - want).sum()
    assert tv < 0.15, f"first-token TV distance {tv:.3f} vs target dist"


# ---------------------------------------------------------------------------
# admission control + drain
# ---------------------------------------------------------------------------

def test_queue_full_load_shedding_counters(lm):
    reg = Registry()
    eng = _engine(lm, registry=reg, slots=1, max_queue=1)
    # engine NOT started: the queue fills deterministically
    first = eng.submit(np.arange(3), 4)
    shed = 0
    for _ in range(3):
        with pytest.raises(ServeRejected) as ei:
            eng.submit(np.arange(3), 4)
        assert ei.value.reason == "queue full"
        shed += 1
    eng.start()
    assert np.array_equal(first.result(timeout=60),
                          _ref(lm, np.arange(3), 4))
    eng.stop()
    snap = reg.snapshot()
    assert snap["serve.rejected"]["value"] == shed
    assert snap["serve.rejected_queue_full"]["value"] == shed
    assert snap["serve.admitted"]["value"] == 1
    assert snap["serve.completed"]["value"] == 1
    # nothing vanished: every submit is accounted completed or rejected
    assert snap["serve.requests"]["value"] == \
        snap["serve.completed"]["value"] + snap["serve.rejected"]["value"]


def test_drain_completes_inflight_then_rejects(lm):
    rng = np.random.default_rng(3)
    prompt = _prompt(rng, 5)
    reg = Registry()
    eng = _engine(lm, registry=reg).start()
    req = eng.submit(prompt, 10)
    assert eng.drain(timeout=60)
    assert req.done
    assert np.array_equal(req.result(), _ref(lm, prompt, 10))
    with pytest.raises(ServeRejected) as ei:
        eng.submit(prompt, 4)
    assert ei.value.reason == "draining"
    eng.stop()
    snap = reg.snapshot()
    assert snap["serve.rejected_draining"]["value"] == 1
    assert snap["serve.requests"]["value"] == \
        snap["serve.completed"]["value"] + snap["serve.rejected"]["value"]


def test_hard_stop_aborts_with_recorded_rejection(lm):
    reg = Registry()
    eng = _engine(lm, registry=reg)  # never started: request stays queued
    req = eng.submit(np.arange(4), 8)
    eng.stop(drain=False)
    assert req.done and req.error is not None
    with pytest.raises(ServeRejected):
        req.result()
    snap = reg.snapshot()
    assert snap["serve.rejected_aborted"]["value"] == 1
    assert snap["serve.requests"]["value"] == \
        snap["serve.completed"]["value"] + snap["serve.rejected"]["value"]


# ---------------------------------------------------------------------------
# the serve wire
# ---------------------------------------------------------------------------

def test_server_v1_v2_interop(lm):
    rng = np.random.default_rng(4)
    p1, p2 = _prompt(rng, 5), _prompt(rng, 7)
    with ServeServer(_engine(lm).warmup()) as srv:
        with ServeClient("127.0.0.1", srv.port) as c2, \
                ServeClient("127.0.0.1", srv.port, wire_version=1) as c1:
            assert c2.wire_version == 2
            assert c1.wire_version == 1
            r2 = c2.generate(p1, 6)
            r1 = c1.generate(p2, 6)
            assert r2["ok"] and r1["ok"]
            assert np.array_equal(np.asarray(r2["tokens"]),
                                  _ref(lm, p1, 6))
            assert np.array_equal(np.asarray(r1["tokens"]),
                                  _ref(lm, p2, 6))
            assert "ttft_s" in r2 and "queue_wait_s" in r2
            st = c1.stats()
            assert st["stats"]["serve.completed"]["value"] == 2
    # a legacy v1-only SERVER: current clients fall back cleanly
    with ServeServer(_engine(lm).warmup(), max_wire_version=1) as srv:
        with ServeClient("127.0.0.1", srv.port) as c:
            assert c.wire_version == 1
            r = c.generate(p1, 4)
            assert r["ok"]
            assert np.array_equal(np.asarray(r["tokens"]),
                                  _ref(lm, p1, 4))


def test_server_burst_load_shedding(lm):
    """Acceptance: an over-capacity burst sheds load — every reply is
    either a completed generation or an explicit rejection, and the
    server's counter agrees with what clients saw."""
    rng = np.random.default_rng(5)
    reg = Registry()
    eng = _engine(lm, registry=reg, slots=1, max_queue=1,
                  max_new_tokens=16)
    prompts = [_prompt(rng, 4) for _ in range(6)]
    replies = [None] * 6
    with ServeServer(eng.warmup()) as srv:
        clients = [ServeClient("127.0.0.1", srv.port) for _ in range(6)]

        def go(k):
            replies[k] = clients[k].generate(prompts[k], 16)

        threads = [threading.Thread(target=go, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for c in clients:
            c.close()
    ok = [k for k, r in enumerate(replies) if r["ok"]]
    shed = [k for k, r in enumerate(replies)
            if not r["ok"] and r.get("rejected")]
    assert len(ok) + len(shed) == 6
    assert shed, "burst over a 1-slot/1-queue service must shed load"
    assert ok, "a shedding service must still complete admitted work"
    for k in ok:  # completed requests are CORRECT under the burst
        assert np.array_equal(np.asarray(replies[k]["tokens"]),
                              _ref(lm, prompts[k], 16))
    snap = reg.snapshot()
    assert snap["serve.rejected"]["value"] == len(shed)
    assert snap["serve.requests"]["value"] == \
        snap["serve.completed"]["value"] + snap["serve.rejected"]["value"]


def test_server_acceptance_continuous_join_steady_state(lm):
    """Acceptance: a real multi-request run THROUGH the server — a
    request admitted mid-decode of another joins the running batch and
    completes correctly, and the whole run holds ``jit.retraces == 0``."""
    rng = np.random.default_rng(9)
    long_p, short_p = _prompt(rng, 4), _prompt(rng, 9)
    reg = Registry()
    eng = _engine(lm, registry=reg, max_new_tokens=24).warmup()
    reply_a: dict = {}
    with ServeServer(eng) as srv:
        with ServeClient("127.0.0.1", srv.port, registry=Registry()) as ca, \
                ServeClient("127.0.0.1", srv.port,
                            registry=Registry()) as cb:
            t = threading.Thread(
                target=lambda: reply_a.update(ca.generate(long_p, 24)))
            t.start()
            deadline = time.monotonic() + 30
            while reg.counter("serve.tokens_out").value < 2:
                assert time.monotonic() < deadline, "decode never started"
                time.sleep(0.002)
            reply_b = cb.generate(short_p, 4)  # admitted mid-decode of A
            t.join(timeout=30)
            st = cb.stats()
    assert reply_a.get("ok") and reply_b.get("ok")
    assert np.array_equal(np.asarray(reply_a["tokens"]),
                          _ref(lm, long_p, 24))
    assert np.array_equal(np.asarray(reply_b["tokens"]),
                          _ref(lm, short_p, 4))
    assert st["stats"]["serve.joins"]["value"] == 2
    assert st["stats"]["serve.completed"]["value"] == 2
    assert st["stats"]["serve.rejected"]["value"] == 0
    assert st["stats"]["jit.retraces"]["value"] == 0
    # the load generator's side: its clients' registries merge into one
    # view that counts every request once
    mine = Registry.merge_snapshots(ca.registry.snapshot(),
                                    cb.registry.snapshot())
    assert mine["serve.client.requests"]["value"] == 2
    assert mine["serve.client.rejected"]["value"] == 0
    assert mine["serve.client.e2e_seconds"]["count"] == 2


def test_server_malformed_fields_answer_instead_of_dropping(lm):
    """A malformed FIELD (not just an unknown action) must get an error
    reply on the same connection, never a replyless disconnect."""
    from distkeras_tpu.ps.networking import connect, recv_msg, send_msg
    with ServeServer(_engine(lm).warmup()) as srv:
        sock = connect("127.0.0.1", srv.port)
        try:
            send_msg(sock, {"action": "hello", "versions": ["two"]})
            resp = recv_msg(sock)
            assert resp["ok"] is False and "error" in resp
            # the connection survived: a well-formed request still works
            send_msg(sock, {"action": "generate",
                            "prompt": np.arange(4, dtype=np.int32),
                            "max_new_tokens": 2})
            resp = recv_msg(sock)
            assert resp["ok"] is True and len(resp["tokens"]) == 2
        finally:
            sock.close()


def test_server_graceful_drain_over_wire(lm):
    rng = np.random.default_rng(6)
    prompt = _prompt(rng, 5)
    reg = Registry()
    srv = ServeServer(_engine(lm, registry=reg, max_new_tokens=24)
                      .warmup()).start()
    reply = {}
    with ServeClient("127.0.0.1", srv.port) as c:
        t = threading.Thread(
            target=lambda: reply.update(c.generate(prompt, 24)))
        t.start()
        deadline = time.monotonic() + 30
        while reg.counter("serve.tokens_out").value < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        srv.stop()  # graceful: drains the in-flight generate first
        t.join(timeout=30)
    assert reply.get("ok"), reply
    assert np.array_equal(np.asarray(reply["tokens"]),
                          _ref(lm, prompt, 24))
    snap = reg.snapshot()
    assert snap["serve.requests"]["value"] == \
        snap["serve.completed"]["value"] + snap["serve.rejected"]["value"]


# ---------------------------------------------------------------------------
# retrace contract (acceptance) + drift gate
# ---------------------------------------------------------------------------

def test_steady_state_retraces_zero_drift_gated(lm):
    """Bucketed shapes mean the whole service compiles once per program
    and NEVER re-traces under mixed traffic; the committed
    OBS_BASELINE.json gates any increase as drift."""
    rng = np.random.default_rng(7)
    reg = Registry()
    eng = _engine(lm, registry=reg, prefill_buckets=(8, SEQ),
                  max_queue=8).warmup()
    compiles_after_warmup = reg.counter("jit.compiles").value
    assert compiles_after_warmup == 3  # 2 bucket joins + 1 step
    with eng:
        reqs = [eng.submit(_prompt(rng, n), 4)
                for n in (3, 8, 12, 2, 20, 7)]  # spans both buckets
        for r in reqs:
            assert r.result(timeout=60).shape == (4,)
    snap = reg.snapshot()
    assert snap["jit.compiles"]["value"] == compiles_after_warmup
    assert snap["jit.retraces"]["value"] == 0

    # the drift gate: identical steady-state snapshots are clean, and a
    # single retrace over the committed zero-tolerance rule is DRIFT
    baseline = drift.load_baseline(os.path.join(_ROOT,
                                                "OBS_BASELINE.json"))
    doc = {"config": {"mode": "serve"}, "server": snap}
    report = drift.diff_docs(doc, copy.deepcopy(doc), baseline=baseline)
    assert not report.drifted
    bumped = copy.deepcopy(doc)
    bumped["server"]["jit.retraces"]["value"] += 1
    report = drift.diff_docs(doc, bumped, baseline=baseline)
    assert any(m.endswith("jit.retraces")
               for m in report.drifted_metrics)


# ---------------------------------------------------------------------------
# obsview --serve
# ---------------------------------------------------------------------------

def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obsview_serve_poll_renders_slo_table(lm):
    obsview = _load_obsview()
    with ServeServer(_engine(lm).warmup()) as srv:
        eng = srv.engine
        eng.submit(np.arange(4), 6).result(timeout=60)
        out = obsview.summarize_serve(
            obsview.poll_serve("127.0.0.1", srv.port))
    assert "Live decode service" in out
    assert "first token" in out and "end-to-end" in out
    assert "retraces 0" in out
    assert "RETRACING" not in out
    # the accelerator panel renders from the pre-created zeros
    assert "prefix cache" in out and "spec decode" in out
    assert "LOW-ACCEPT" not in out  # no proposals -> no alarm
    # the alarm renders when the sentinel fired
    reply = {"stats": {"jit.retraces": {"type": "counter", "value": 2},
                       "jit.compiles": {"type": "counter", "value": 3}}}
    assert "RETRACING" in obsview.summarize_serve(reply)


def test_obsview_serve_accelerator_columns_and_low_accept_alarm():
    """The ISSUE 11 panel: prefix hit-rate and draft accept-rate render
    from a stats reply, and a collapsed accept rate (proposals flowing,
    almost none accepted) raises the LOW-ACCEPT alarm — a healthy rate
    must not."""
    obsview = _load_obsview()

    def reply(rate):
        return {"stats": {
            "serve.prefix.hits": {"type": "counter", "value": 30},
            "serve.prefix.misses": {"type": "counter", "value": 10},
            "serve.prefix.entries": {"type": "gauge", "value": 4},
            "serve.prefix.bytes": {"type": "gauge", "value": 4096},
            "serve.prefix.evictions": {"type": "counter", "value": 2},
            "serve.spec.proposed": {"type": "counter", "value": 300},
            "serve.spec.accepted": {"type": "counter",
                                    "value": int(300 * rate)},
            "serve.spec.accept_rate": {"type": "gauge", "value": rate},
        }}

    healthy = obsview.summarize_serve(reply(0.8))
    assert "hit rate 75%" in healthy
    assert "accept rate 80%" in healthy
    assert "LOW-ACCEPT" not in healthy
    collapsed = obsview.summarize_serve(reply(0.05))
    assert "LOW-ACCEPT" in collapsed
