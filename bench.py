"""Headline benchmark: samples/sec/chip, ResNet-20 on CIFAR-10.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no numbers (BASELINE.md): the baseline is this
repo's own recorded anchor (BENCH_ANCHOR.json, written on first run), so
``vs_baseline`` tracks our progress against the first measured
implementation — exactly the "beat your own SingleTrainer anchor"
methodology SURVEY.md §6 prescribes.

Measured through the PUBLIC trainer API: ``SingleTrainer(...,
compute_dtype="bfloat16")`` — the same path a user reaches, not a
bench-only harness.  Timing is honest: the trainer pipelines epochs
(epoch k's loss readback happens after epoch k+1 is dispatched) but every
epoch's wall time is marked at the completion of its own device->host
loss readback, and the final epoch is fully drained before the clock
stops — so sum(epoch_seconds) spans dispatch start → last epoch's compute
actually done.  (``jax.block_until_ready`` is an equally honest fence on
this machine — ``chip_smoke.py``'s fence phase measures both; the trainer
reads the losses back because the host needs them anyway.)

The anchor value is the round-1 first-measured throughput on this same
workload+metric (end-to-end samples/sec with a hard final sync); the
harness version that produced each number is recorded alongside so
methodology changes are visible (HARNESS below).

``python bench.py --ps [--codec C] [--windows N] [--mb M]
[--ps-workers N,M,...]`` runs the **PS-comms microbenchmark** instead
(ISSUE 4): a localhost SocketParameterServer + N concurrent clients doing
pull/commit windows over an M-MB synthetic center, printing one JSON line
per sweep point with the commit RTT and wire bytes per communication
window, and persisting one MERGED client+server obs registry snapshot per
sweep point at the repo root (the ROADMAP telemetry item)
so runs can diff distributions, not just wall numbers.

``python bench.py --serve [--requests N] [--concurrency C]
[--prompt-len P] [--max-new K] [--slots B] [--queue Q] [--spec K]
[--no-prefix] [--engines N]`` runs the **decode-service load bench**
(ISSUE 7): a localhost continuous-batching ``ServeServer`` over a small
gpt_lm, driven by C closed-loop client threads, printing one JSON row
with p50/p99 end-to-end + time-to-first-token latency, tokens/sec and
the load-shed count, and persisting the service registry snapshot (SLO
histograms + admission counters + the zero-pinned ``jit.retraces``
sentinel) to ``BENCH_SERVE_OBS.json``.  ISSUE 11 folds the two decode
accelerators into the same row + snapshot: a warm-vs-cold **prefix
phase** (ttft p50 with a shared cached prefix vs a cold prefill) and a
**spec phase** (tokens/sec with and without speculative decoding, at
exact greedy parity vs ``generate_tokens``) — both drift-gated, so a
hit-rate or accept-rate regression fails like any perf regression.
ISSUE 14 adds the **router phase** (``--engines N``): the
``ServeRouter`` fleet scaling sweep — aggregate tokens/sec + client
p99 e2e vs fleet size over a shared-prefix workload with
prefix-affinity routing, one merged fleet snapshot per point
(``router_n<n>``), same drift gate.  ISSUE 16 adds the **KV-fabric
phase** (same ``--engines``): forced overflow on the fleet with
hot-prefix replication and planned-drain migration, certifying the
warm-vs-cold spill ttft split (snapshot part ``fabric``).

All benches self-check against the committed baseline snapshot named in
``OBS_BASELINE.json`` (ISSUE 5): the fresh run's registry snapshot is
drift-diffed (``distkeras_tpu/obs/drift.py`` — counter ratios, bucket-wise
PSI, p50/p99 shift) against the previous committed one BEFORE overwriting
it; the drift report goes to stderr (the stdout JSON row contract is
untouched) and the row carries ``obs_drift``.  ``scripts/obsview.py
--diff`` exposes the same comparison standalone.
"""

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from distkeras_tpu.data.dataset import Dataset  # noqa: E402
from distkeras_tpu.models import zoo  # noqa: E402
from distkeras_tpu.trainers import SingleTrainer  # noqa: E402

BATCH = int(os.environ.get("BENCH_BATCH", 1024))
#: ResNet-20 base width; 16 = the standard He et al. model (the recorded
#: headline).  Wider variants (scripts/mfu.py ladder) lift MFU toward MXU
#: granularity — keyed into the anchor so widths never cross-compare.
WIDTH = int(os.environ.get("BENCH_WIDTH", 16))
STEPS_PER_EPOCH = 32
WARMUP_EPOCHS = 2
TIMED_EPOCHS = int(os.environ.get("BENCH_CALLS", 4))
ANCHOR_PATH = os.path.join(ROOT, "BENCH_ANCHOR.json")
#: bench methodology version (ADVICE r2: record it so a harness change can
#: never masquerade as a perf change): v1 = raw window-fn timing (r1),
#: v2 = SingleTrainer with per-epoch blocking readback (r2),
#: v3 = SingleTrainer with pipelined epochs + final drain (r3).
HARNESS = "trainer_pipelined_v3"

#: samples/sec buckets for the trainer-bench throughput histogram —
#: log-spaced 100..50M; the top must clear every machine's plausible
#: reading (dispatch-dominated toy runs report several M), else the
#: drift gate's quantiles pin at the last bound and regressions shrink
RATE_BUCKETS = (100, 250, 500, 1000, 2500, 5000, 10000, 25000, 50000,
                100000, 250000, 500000, 1000000, 2500000, 5000000,
                10000000, 25000000, 50000000)


def _load_doc(path):
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        # a corrupt committed snapshot must degrade LOUDLY: treating it
        # as "no baseline" would let the drift gate pass green
        from distkeras_tpu.obs.logging import emit
        emit(f"bench: cannot read snapshot {path}: {e}", err=True)
        return None


_BASELINE_CFG_CACHE: dict = {}


def _baseline_cfg():
    """The committed ``OBS_BASELINE.json`` drift config, parsed+validated
    ONCE per process per path (a multi-point sweep must not re-read it —
    or re-warn about it — per point); None (with a stderr note — silently
    dropping the tuned thresholds would cause spurious DRIFT reports)
    when invalid."""
    from distkeras_tpu.obs import drift
    from distkeras_tpu.obs.logging import emit
    bl = os.path.join(ROOT, "OBS_BASELINE.json")
    if bl in _BASELINE_CFG_CACHE:
        return _BASELINE_CFG_CACHE[bl]
    cfg = None
    if os.path.exists(bl):
        try:
            cfg = drift.load_baseline(bl)
        except (OSError, ValueError) as e:
            emit(f"bench: ignoring invalid OBS_BASELINE.json ({e}); "
                 "drift checks fall back to default thresholds", err=True)
    _BASELINE_CFG_CACHE[bl] = cfg
    return cfg


def _baseline_snapshot_path(cfg, key: str, default_name: str) -> str:
    """The committed baseline snapshot file for bench mode ``key``, as
    named by the baseline config's ``snapshots`` map."""
    name = ((cfg or {}).get("snapshots") or {}).get(key, default_name)
    return os.path.join(ROOT, name)


def _obs_self_check(prev_doc, new_doc, label: str, baseline) -> dict:
    """Drift-gate a fresh obs snapshot against the previous committed one
    (ISSUE 5): the report goes to stderr — stdout keeps the one-JSON-row
    contract — and the returned dict rides in the row as ``obs_drift``.
    Skipped (never a false alarm) when there is no baseline yet or the
    configs differ (a diff across workloads measures the workload)."""
    from distkeras_tpu.obs import drift
    from distkeras_tpu.obs.logging import emit
    if prev_doc is None:
        return {"checked": False, "reason": "no baseline snapshot"}
    if prev_doc.get("config") != new_doc.get("config"):
        return {"checked": False, "reason": "baseline config differs"}
    report = drift.diff_docs(prev_doc, new_doc, baseline=baseline,
                             base_name=f"{label} (committed)",
                             cand_name="this run")
    emit(report.render(), err=True)
    return {"checked": True, "drifted": report.drifted_metrics}


def _persist_obs_snapshot(snap_path: str, obs_doc: dict, bl_cfg,
                          base_path: str = None, check: bool = True):
    """Self-check + clobber-guarded write, shared by both benches:
    drift-check ``obs_doc`` against the committed baseline (``base_path``,
    defaulting to the destination itself; ``check=False`` skips it for
    snapshots with no designated baseline), divert a config-incompatible
    run to a ``.variant.json`` sidecar instead of voiding the existing
    file in place, then write.  The sidecar itself is per-run scratch —
    only the baseline file is guarded; a later incompatible run replaces
    the previous variant like any other bench output.  Returns
    ``(obs_drift_row, final_path)``."""
    drift_row = None
    if check:
        check_path = base_path if base_path is not None else snap_path
        prev_base = _load_doc(check_path)
        if prev_base is None and os.path.exists(check_path):
            # distinct machine-readable reason: a CORRUPT committed
            # baseline must not look like a genuinely absent one to CI
            drift_row = {"checked": False, "reason": "baseline unreadable"}
        else:
            drift_row = _obs_self_check(prev_base, obs_doc,
                                        os.path.basename(check_path),
                                        bl_cfg)
        prev_dest = prev_base if check_path == snap_path \
            else _load_doc(snap_path)
    else:
        prev_dest = _load_doc(snap_path)
    # divert when the destination exists but is incomparable — config
    # mismatch OR unreadable; overwriting a corrupt committed baseline in
    # place would quietly green the gate
    if os.path.exists(snap_path) and (
            prev_dest is None or
            prev_dest.get("config") != obs_doc["config"]):
        snap_path = os.path.splitext(snap_path)[0] + ".variant.json"
    with open(snap_path, "w") as f:
        json.dump(obs_doc, f, indent=1)
    return drift_row, snap_path


def main():
    rng = np.random.default_rng(0)
    n_rows = STEPS_PER_EPOCH * BATCH
    labels = rng.integers(0, 10, size=n_rows)
    ds = Dataset({
        "features": rng.random((n_rows, 32, 32, 3), dtype=np.float32),
        "label": np.eye(10, dtype=np.float32)[labels],
    })

    from distkeras_tpu.obs import Registry, TIME_BUCKETS

    trainer = SingleTrainer(
        zoo.resnet20(width=WIDTH), "sgd", "categorical_crossentropy",
        features_col="features", label_col="label",
        num_epoch=WARMUP_EPOCHS + TIMED_EPOCHS, batch_size=BATCH,
        learning_rate=0.1, compute_dtype="bfloat16")
    # bench-scoped registry: the trainer's span durations (jit_compile /
    # train) histogram into it, per-epoch wall/throughput observations are
    # folded in below — the distribution snapshot the ROADMAP telemetry
    # item wants persisted beside the wall-clock row (ISSUE 5).  The
    # profiling layer (ISSUE 6) lands here too: the retrace sentinel's
    # jit.compiles/jit.retraces and the per-epoch mem.* watermark gauges
    # all resolve to the tracer's registry.  Pre-create the jit counters
    # so the snapshot carries them even at zero — a missing metric is
    # only a drift-gate NOTE; a present 0 -> 1 jump is drift (the
    # OBS_BASELINE.json jit.retraces rule: any increase fails).
    breg = Registry()
    breg.counter("jit.compiles")
    breg.counter("jit.retraces")
    trainer.tracer.registry = breg
    trainer.train(ds)

    epochs = [r for r in trainer.metrics.records if r["event"] == "epoch"]
    timed = epochs[WARMUP_EPOCHS:]
    samples = STEPS_PER_EPOCH * BATCH * len(timed)
    # the epoch program is a plain single-device jit: per-chip == total here
    sps_chip = samples / sum(r["epoch_seconds"] for r in timed)

    h_sec = breg.histogram("bench.epoch_seconds", TIME_BUCKETS)
    h_rate = breg.histogram("bench.samples_per_sec", RATE_BUCKETS)
    for r in timed:
        h_sec.observe(r["epoch_seconds"])
        h_rate.observe(r["samples_per_sec"])
    breg.counter("bench.epochs").inc(len(timed))
    breg.counter("bench.samples").inc(samples)

    # anchor is keyed by config so overriding BENCH_BATCH can't masquerade
    # as a regression against an incompatible workload
    cfg_key = f"b{BATCH}_s{STEPS_PER_EPOCH}" + \
        (f"_w{WIDTH}" if WIDTH != 16 else "")
    anchors = {}
    if os.path.exists(ANCHOR_PATH):
        with open(ANCHOR_PATH) as f:
            anchors = json.load(f)
    if cfg_key not in anchors:
        anchors[cfg_key] = {"value": sps_chip, "harness": HARNESS}
        with open(ANCHOR_PATH, "w") as f:
            json.dump(anchors, f, indent=1)
    entry = anchors[cfg_key]  # legacy anchors are bare floats
    anchor = entry["value"] if isinstance(entry, dict) else entry

    # persist the headline bench's registry snapshot at the repo root
    # (same document schema as BENCH_PS_OBS.json — obsview's snapshot-file
    # mode reads both unchanged) and self-check against the committed one
    obs_doc = {"config": {"mode": "trainer_bench", "batch": BATCH,
                          "steps_per_epoch": STEPS_PER_EPOCH,
                          "width": WIDTH, "warmup_epochs": WARMUP_EPOCHS,
                          "timed_epochs": TIMED_EPOCHS,
                          "harness": HARNESS},
               "trainer": breg.snapshot()}
    bl_cfg = _baseline_cfg()
    snap_path = _baseline_snapshot_path(bl_cfg, "trainer_bench",
                                        "BENCH_TRAINER_OBS.json")
    obs_drift, snap_path = _persist_obs_snapshot(snap_path, obs_doc, bl_cfg)

    print(json.dumps({
        "metric": "samples/sec/chip (CIFAR-10 ResNet-20)",
        "value": round(sps_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / anchor, 4),
        "harness": HARNESS,
        "obs_snapshot": os.path.relpath(snap_path, ROOT),
        "obs_drift": obs_drift,
    }))


#: committed config of the warm-vs-cold prefix phase (ISSUE 11): a model
#: big enough that prefill COMPUTE dominates the join (long seq_len, the
#: O(T²) attention term) against a short suffix replay — the regime the
#: prefix cache exists for.  ``shared`` is the system-prompt stand-in
#: (a ``block`` multiple, so later prompts alias into the first entry);
#: request 1 is the cold prefill, every later request warm-joins.
SERVE_PREFIX_PHASE = dict(requests=6, vocab=128, dim=128, heads=4,
                          blocks=2, seq_len=768, shared=744, tail=6,
                          max_new=4, slots=2, suffix_bucket=8,
                          cache_mb=512.0, block=8)

#: committed config of the speculative-decode phase (ISSUE 11): a model
#: small enough that per-dispatch overhead dominates decode compute —
#: the regime where emitting k+1 tokens per dispatch pays on this host
#: (on a real TPU the same mechanism amortizes the target's HBM weight
#: read instead).  The draft is the TARGET ITSELF (accept rate 1.0):
#: that measures the verify machinery's dispatch-amortization ceiling
#: at guaranteed parity; a distilled smaller draft lands below it in
#: accept rate but above it in per-proposal cost.
SERVE_SPEC_PHASE = dict(k=4, requests=8, prompt_len=8, max_new=32,
                        vocab=64, dim=32, heads=2, blocks=1, seq_len=64,
                        slots=2)

#: committed config of the router scaling phase (ISSUE 14): an N-engine
#: fleet behind one ``ServeRouter``, swept n = 1..engines over a
#: shared-prefix workload.  Sized so the fleet actually scales on a CPU
#: host: the decode step must be COMPUTE-bound (dim 256 — a
#: dispatch-bound toy step lets one engine's continuous batching absorb
#: any concurrency, and splitting it across engines only adds hops) and
#: the offered concurrency must OVERSUBSCRIBE a single engine's slots
#: (concurrency 12 vs slots 2: one engine runs at occupancy 2, the
#: 3-engine fleet at 6) — that gap is exactly what the front door
#: exists to harvest.  The cold pass is SERIALIZED (one request per
#: group) so affinity registration and every prefix counter are
#: deterministic under the drift gate's exact serve.prefix.* rule; the
#: storm that follows is all warm, affinity-routed traffic.
SERVE_ROUTER_PHASE = dict(engines=3, groups=12, per_group=5,
                          concurrency=12, shared=48, tail=6, max_new=16,
                          block=16, slots=2, queue=256, cache_mb=64.0,
                          vocab=256, dim=256, heads=4, blocks=2,
                          seq_len=128)

#: Committed config for the KV-fabric phase (ISSUE 16): forced overflow
#: on an N-engine fleet, warm-vs-cold spill TTFT split.  Every request
#: is SERIALIZED and each spill is forced by pinning the affine owner
#: at its in-flight bound, so routing, the prefix counters, and the
#: replication/migration tallies are all deterministic under the drift
#: gate's exact ``serve.prefix.*`` rule.  Sized like the ISSUE 11
#: prefix phase: a long shared prefix whose cold prefill (the O(T²)
#: attention term in the 256-token bucket) DOMINATES ttft, against a
#: short-suffix warm join replayed in the 8-token bucket — the speedup
#: the phase certifies is prefill avoided by moving KV across engines,
#: not scheduler noise.
SERVE_FABRIC_PHASE = dict(engines=3, groups=3, rounds=3, shared=504,
                          tail=6, max_new=4, suffix_bucket=8,
                          prefill_bucket=512, block=8, slots=2,
                          queue=16, cache_mb=16.0, vocab=128, dim=128,
                          heads=4, blocks=2, seq_len=544)


def _serve_prefix_phase(phase: dict):
    """The warm-vs-cold ttft probe: serialized requests sharing a long
    prefix through a prefix-cached engine — request 1 cold-prefills (and
    populates the cache), the rest warm-join over the cached KV.
    Returns the row fields + the engine registry snapshot (the
    ``serve.ttft_{warm,cold}_seconds`` split and ``serve.prefix.*``
    counters live there)."""
    from distkeras_tpu.obs import Registry
    from distkeras_tpu.serve import DecodeEngine, ServeConfig

    model = zoo.gpt_lm(vocab_size=phase["vocab"], dim=phase["dim"],
                       num_heads=phase["heads"],
                       num_blocks=phase["blocks"],
                       seq_len=phase["seq_len"])
    registry = Registry()
    cfg = ServeConfig(slots=phase["slots"], max_queue=phase["requests"],
                      max_new_tokens=phase["max_new"],
                      prefill_buckets=(phase["suffix_bucket"],
                                       phase["seq_len"]),
                      prefix_cache=True, prefix_cache_mb=phase["cache_mb"],
                      prefix_block=phase["block"])
    engine = DecodeEngine(model, model.init(0), cfg, registry=registry)
    engine.warmup()
    rng = np.random.default_rng(11)
    shared = rng.integers(0, phase["vocab"],
                          size=(phase["shared"],)).astype(np.int32)
    done = []
    with engine:
        for _ in range(phase["requests"]):
            tail = rng.integers(0, phase["vocab"],
                                size=(phase["tail"],)).astype(np.int32)
            # serialized: each request completes before the next is
            # submitted, so warm/cold attribution is deterministic
            req = engine.submit(np.concatenate([shared, tail]),
                                phase["max_new"])
            req.result(timeout=600)
            done.append(req)
    snap = registry.snapshot()
    # the ROW p50s come from the exact per-request timestamps (the
    # requests are driven right here) — the histogram quantile would
    # interpolate a handful of observations across coarse bucket
    # bounds, quantizing warm_speedup run to run; the histograms still
    # ride in the snapshot for the drift gate's distribution check
    warm = float(np.median([r.first_token_t - r.submit_t
                            for r in done if r.warm]))
    cold = float(np.median([r.first_token_t - r.submit_t
                            for r in done if r.warm is False]))
    hits = snap["serve.prefix.hits"]["value"]
    misses = snap["serve.prefix.misses"]["value"]
    fields = {
        "ttft_warm_ms_p50": round(warm * 1e3, 3),
        "ttft_cold_ms_p50": round(cold * 1e3, 3),
        "warm_speedup": round(cold / warm, 2) if warm > 0 else None,
        "prefix_hit_rate": round(hits / (hits + misses), 3)
        if hits + misses else 0.0,
    }
    return fields, snap


def _serve_spec_phase(phase: dict):
    """The speculative-decode probe: the same prompts through a plain
    engine and a ``spec_k`` engine (draft = the target checkpoint, see
    ``SERVE_SPEC_PHASE``), tokens/sec each way, exact-parity check of
    every output against the offline ``generate_tokens`` reference.
    Returns the row fields + both engine registry snapshots."""
    from distkeras_tpu.models.generation import generate_tokens
    from distkeras_tpu.obs import Registry
    from distkeras_tpu.serve import DecodeEngine, ServeConfig

    model = zoo.gpt_lm(vocab_size=phase["vocab"], dim=phase["dim"],
                       num_heads=phase["heads"],
                       num_blocks=phase["blocks"],
                       seq_len=phase["seq_len"])
    variables = model.init(0)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, phase["vocab"],
                            size=(phase["prompt_len"],)).astype(np.int32)
               for _ in range(phase["requests"])]

    def drive(spec_k: int):
        registry = Registry()
        kw = {}
        if spec_k > 0:
            kw = dict(draft_model=model, draft_variables=variables)
        engine = DecodeEngine(
            model, variables,
            ServeConfig(slots=phase["slots"],
                        max_queue=phase["requests"],
                        max_new_tokens=phase["max_new"], spec_k=spec_k),
            registry=registry, **kw)
        engine.warmup()
        with engine:
            t0 = time.perf_counter()
            reqs = [engine.submit(p, phase["max_new"]) for p in prompts]
            outs = [r.result(timeout=600) for r in reqs]
            wall = time.perf_counter() - t0
        snap = registry.snapshot()
        return snap["serve.tokens_out"]["value"] / wall, snap, outs

    tps_base, snap_base, outs_base = drive(0)
    tps_spec, snap_spec, outs_spec = drive(phase["k"])
    parity = all(
        np.array_equal(b, s) and np.array_equal(
            s, np.asarray(generate_tokens(
                model, variables, p[None, :],
                phase["max_new"]))[0, len(p):])
        for p, b, s in zip(prompts, outs_base, outs_spec))
    fields = {
        "spec_k": phase["k"],
        "tokens_per_sec_base": round(tps_base, 1),
        "tokens_per_sec_spec": round(tps_spec, 1),
        "spec_uplift": round(tps_spec / tps_base, 2) if tps_base else None,
        "spec_accept_rate": round(
            snap_spec["serve.spec.accept_rate"]["value"], 3),
        "spec_parity": parity,
    }
    return fields, snap_base, snap_spec


def _serve_router_phase(phase: dict):
    """The ISSUE 14 fleet scaling sweep: for each fleet size
    n = 1..engines, build n prefix-cached engines behind one
    ``ServeRouter`` and drive the SAME shared-prefix workload through
    the front door — a serialized cold pass (one request per group:
    registers affinity, populates each engine's prefix cache,
    deterministic counters) followed by a concurrent closed-loop storm
    of the remaining warm requests.  Returns the row fields (the
    scaling curve: tokens/sec, client p99 e2e, prefix/affinity hit
    rates per n) plus one MERGED fleet registry snapshot per point
    (``router_n<n>`` — router + every engine, the
    ``Registry.merge_snapshots`` SLO view) for the drift gate."""
    import threading

    from distkeras_tpu.serve import (DecodeEngine, RouterConfig,
                                     ServeClient, ServeConfig,
                                     ServeRouter, ServeServer)
    from distkeras_tpu.obs import Registry

    model = zoo.gpt_lm(vocab_size=phase["vocab"], dim=phase["dim"],
                       num_heads=phase["heads"],
                       num_blocks=phase["blocks"],
                       seq_len=phase["seq_len"])
    variables = model.init(0)
    rng = np.random.default_rng(13)
    groups, per_group = int(phase["groups"]), int(phase["per_group"])
    conc = int(phase["concurrency"])
    max_new, block = int(phase["max_new"]), int(phase["block"])
    gshared = [rng.integers(0, phase["vocab"],
                            size=(phase["shared"],)).astype(np.int32)
               for _ in range(groups)]
    tails = [[rng.integers(0, phase["vocab"],
                           size=(phase["tail"],)).astype(np.int32)
              for _ in range(per_group)] for _ in range(groups)]

    scaling, parts = [], {}
    for n in range(1, int(phase["engines"]) + 1):
        servers = []
        router = None
        try:
            for _ in range(n):
                cfg = ServeConfig(
                    slots=phase["slots"], max_queue=phase["queue"],
                    max_new_tokens=max_new,
                    prefill_buckets=(block * 2, phase["seq_len"]),
                    prefix_cache=True, prefix_cache_mb=phase["cache_mb"],
                    prefix_block=block)
                eng = DecodeEngine(model, variables, cfg,
                                   registry=Registry()).warmup()
                servers.append(ServeServer(eng).start())
            # fabric OFF: this phase measures front-door ROUTING
            # scaling, and its exact serve.prefix.* drift contract
            # needs the storm's warm/miss split deterministic — the
            # fabric's async spill transfers would add scheduling-
            # dependent cold prefills.  The fabric phase below is the
            # fabric's own (serialized, deterministic) proof.
            router = ServeRouter(
                [("127.0.0.1", s.port) for s in servers],
                config=RouterConfig(affinity_block=block,
                                    stats_interval_s=0.2,
                                    kv_fabric=False)).start()
            with ServeClient("127.0.0.1", router.port) as client:
                for g in range(groups):
                    reply = client.generate(
                        np.concatenate([gshared[g], tails[g][0]]),
                        max_new)
                    if not reply.get("ok"):
                        raise RuntimeError(
                            f"router cold pass failed: {reply}")
            work = [(g, i) for g in range(groups)
                    for i in range(1, per_group)]
            shares = [work[k::conc] for k in range(conc)]
            e2e = [[] for _ in range(conc)]
            errors: list = []

            def drive(k: int) -> None:
                try:
                    with ServeClient("127.0.0.1",
                                     router.port) as client:
                        for g, i in shares[k]:
                            t0 = time.perf_counter()
                            reply = client.generate(
                                np.concatenate([gshared[g],
                                                tails[g][i]]), max_new)
                            if not reply.get("ok"):
                                raise RuntimeError(
                                    f"router storm failed: {reply}")
                            e2e[k].append(time.perf_counter() - t0)
                except BaseException as e:
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=drive, args=(k,),
                                        name=f"bench-router-{k}")
                       for k in range(conc)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            with ServeClient("127.0.0.1", router.port) as client:
                reply = client.stats()
        finally:
            if router is not None:
                router.stop()
            for s in servers:
                s.stop()
        merged = reply["stats"]

        def _v(name):
            return merged.get(name, {}).get("value", 0)

        hits, misses = _v("serve.prefix.hits"), _v("serve.prefix.misses")
        all_e2e = np.asarray(sorted(v for part in e2e for v in part))
        routed_aff = _v("serve.router.affinity_hits")
        routed = routed_aff + _v("serve.router.affinity_misses")
        scaling.append({
            "engines": n,
            "tokens_per_sec": round(len(work) * max_new / wall, 1),
            "e2e_ms_p99": round(
                float(np.quantile(all_e2e, 0.99)) * 1e3, 3),
            "prefix_hit_rate": round(hits / (hits + misses), 3)
            if hits + misses else 0.0,
            "affinity_route_share": round(routed_aff / routed, 3)
            if routed else 0.0,
            "per_engine_requests": [e.get("requests")
                                    for e in reply.get("engines", [])],
            "requeues": _v("serve.router.requeues"),
            "evictions": _v("serve.router.evictions"),
            "jit_retraces": _v("jit.retraces"),
        })
        parts[f"router_n{n}"] = merged
    fields = {
        "router_engines": int(phase["engines"]),
        "router_scaling": scaling,
        "router_speedup": round(scaling[-1]["tokens_per_sec"]
                                / scaling[0]["tokens_per_sec"], 2),
        "router_affinity_hit_rate": scaling[-1]["prefix_hit_rate"],
    }
    return fields, parts


def _serve_fabric_phase(phase: dict):
    """The ISSUE 16 KV-fabric probe: N prefix-cached engines behind one
    ``ServeRouter`` with the fabric on, every overflow FORCED (the
    affine owner pinned at its in-flight bound) and every request
    serialized so the run is deterministic end to end.

    Pass 1 registers one hot prefix per group and warms its owner.
    Pass 2 overflows each group once: the spill lands COLD on a
    least-loaded survivor and seeds a fabric replication; the phase
    then waits for every transfer to land.  Passes 3..rounds overflow
    again: the router's secondary-owner hit routes each spill WARM onto
    the replica.  Finally one owner takes a PLANNED drain — its hot KV
    migrates to survivors, a follow-up request of its group must still
    land warm — and the merged fleet snapshot (part ``"fabric"``) plus
    the row fields certify the split: replicated-spill ttft p50 beats
    cold-spill p50, transfers moved real bytes, ZERO stale refusals."""
    import threading

    from distkeras_tpu.serve import (DecodeEngine, RouterConfig,
                                     ServeClient, ServeConfig,
                                     ServeRouter, ServeServer)
    from distkeras_tpu.obs import Registry

    model = zoo.gpt_lm(vocab_size=phase["vocab"], dim=phase["dim"],
                       num_heads=phase["heads"],
                       num_blocks=phase["blocks"],
                       seq_len=phase["seq_len"])
    variables = model.init(0)
    rng = np.random.default_rng(17)
    engines, groups = int(phase["engines"]), int(phase["groups"])
    rounds, block = int(phase["rounds"]), int(phase["block"])
    max_new = int(phase["max_new"])
    gshared = [rng.integers(0, phase["vocab"],
                            size=(phase["shared"],)).astype(np.int32)
               for _ in range(groups)]

    def prompt(g):
        tail = rng.integers(0, phase["vocab"],
                            size=(phase["tail"],)).astype(np.int32)
        return np.concatenate([gshared[g], tail])

    servers, router = [], None
    warm_ts, cold_ts = [], []
    try:
        for _ in range(engines):
            cfg = ServeConfig(
                slots=phase["slots"], max_queue=phase["queue"],
                max_new_tokens=max_new,
                prefill_buckets=(phase["suffix_bucket"],
                                 phase["prefill_bucket"]),
                prefix_cache=True, prefix_cache_mb=phase["cache_mb"],
                prefix_block=block)
            servers.append(ServeServer(DecodeEngine(
                model, variables, cfg,
                registry=Registry()).warmup()).start())
        router = ServeRouter(
            [("127.0.0.1", s.port) for s in servers],
            config=RouterConfig(affinity_block=block,
                                max_inflight=phase["slots"],
                                stats_interval_s=30.0)).start()
        fabric = router._kv_fabric

        def spill(client, g):
            """One forced overflow of group g: pin the affine owner at
            the in-flight bound for exactly this request."""
            owner = next(b for b in router.backends
                         if b.addr == owners[g])
            with router._lock:
                owner.inflight = int(phase["slots"])
            try:
                reply = client.generate(prompt(g), max_new)
            finally:
                with router._lock:
                    owner.inflight = 0
            if not reply.get("ok"):
                raise RuntimeError(f"fabric spill failed: {reply}")
            return reply

        with ServeClient("127.0.0.1", router.port) as client:
            owners = []
            for g in range(groups):  # pass 1: register + warm owners
                reply = client.generate(prompt(g), max_new)
                if not reply.get("ok"):
                    raise RuntimeError(f"fabric warm pass: {reply}")
                owners.append(reply["engine"])
            for g in range(groups):  # pass 2: forced COLD spills
                reply = spill(client, g)
                if reply.get("warm") is not False:
                    raise RuntimeError(
                        f"first overflow of group {g} must cold-"
                        f"prefill, got warm={reply.get('warm')!r}")
                cold_ts.append(float(reply["ttft_s"]))
            repl = router.registry.counter("serve.router.kv_replications")
            deadline = time.monotonic() + 60.0
            while (repl.value < groups or fabric._jobs
                   or fabric._inflight):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"fabric replication stalled: "
                        f"{repl.value}/{groups} landed")
                time.sleep(0.02)
            for _ in range(1, rounds):  # passes 3..: WARM spills
                for g in range(groups):
                    reply = spill(client, g)
                    if reply.get("warm") is not True:
                        raise RuntimeError(
                            f"replicated overflow of group {g} must "
                            f"land warm, got warm={reply.get('warm')!r}")
                    warm_ts.append(float(reply["ttft_s"]))
            # planned drain: group 0's owner leaves, its KV migrates
            dr = client.drain(engine=owners[0])
            if not dr.get("ok") or not dr.get("drained"):
                raise RuntimeError(f"planned drain failed: {dr}")
            reply = client.generate(prompt(0), max_new)
            if not reply.get("ok") or reply.get("warm") is not True:
                raise RuntimeError(
                    f"post-drain request must land warm on the "
                    f"migration recipient, got {reply}")
            st = client.stats()
    finally:
        if router is not None:
            router.stop()
        for s in servers:
            s.stop()
    merged = st["stats"]

    def _v(name):
        return merged.get(name, {}).get("value", 0)

    warm_p50 = float(np.median(warm_ts))
    cold_p50 = float(np.median(cold_ts))
    fields = {
        "fabric_engines": engines,
        "fabric_ttft_spill_cold_ms_p50": round(cold_p50 * 1e3, 3),
        "fabric_ttft_spill_warm_ms_p50": round(warm_p50 * 1e3, 3),
        "fabric_spill_speedup": round(cold_p50 / warm_p50, 2)
        if warm_p50 > 0 else None,
        "fabric_kv_replications": int(_v("serve.router.kv_replications")),
        "fabric_kv_migrations": int(_v("serve.router.kv_migrations")),
        "fabric_kv_push_bytes": int(_v("serve.router.kv_push_bytes")),
        "fabric_kv_refused_stale": int(
            _v("serve.router.kv_refused_stale")),
        "fabric_secondary_hits": int(
            _v("serve.router.affinity_secondary_hits")),
    }
    return fields, merged


def bench_serve(requests: int = 32, concurrency: int = 4,
                prompt_len: int = 12, max_new: int = 16, slots: int = 4,
                queue: int = 8, out_dir: str = ROOT, wire_version=None,
                vocab: int = 64, dim: int = 32, heads: int = 2,
                blocks: int = 1, seq_len: int = 64, prefix_phase=None,
                spec_phase=None, router_phase=None,
                fabric_phase=None) -> dict:
    """Decode-service load bench (ISSUE 7 acceptance): a localhost
    ``ServeServer`` over a small ``gpt_lm`` and ``concurrency``
    closed-loop client threads driving ``requests`` generations through
    the continuous batcher.  One JSON row: p50/p99 end-to-end and
    time-to-first-token latency, tokens/sec, rejected count.

    The service registry snapshot (SLO histograms, admission counters,
    and the PRE-CREATED ``jit.compiles``/``jit.retraces`` sentinels — 0
    must be present, not missing) plus the merged per-client registries
    persist to ``BENCH_SERVE_OBS.json`` at the repo root,
    drift-checked against the committed baseline BEFORE overwriting it
    (the same ``OBS_BASELINE.json`` contract as the trainer/PS benches;
    config-incompatible runs divert to a ``.variant.json`` sidecar).

    ISSUE 11 adds two accelerator phases to the same row + snapshot
    (each a dict of overrides onto ``SERVE_PREFIX_PHASE`` /
    ``SERVE_SPEC_PHASE``; ``False`` skips the phase, leaving its row
    fields ``None`` — explicitly absent, not missing):

    * **prefix phase** — warm-vs-cold ttft over a long shared prefix
      (``ttft_warm_ms_p50`` / ``ttft_cold_ms_p50`` / ``warm_speedup`` /
      ``prefix_hit_rate``; snapshot part ``"prefix"``).
    * **spec phase** — tokens/sec with and without speculative decoding
      at exact greedy parity vs ``generate_tokens``
      (``tokens_per_sec_base`` / ``tokens_per_sec_spec`` /
      ``spec_uplift`` / ``spec_accept_rate`` / ``spec_parity``;
      snapshot parts ``"spec_base"`` / ``"spec"``).

    ISSUE 14 adds the **router phase** (``SERVE_ROUTER_PHASE``
    overrides; the ``bench.py --serve --engines N`` entry point): the
    N-engine fleet scaling sweep behind one ``ServeRouter`` —
    ``router_scaling`` (tokens/sec + client p99 e2e + prefix/affinity
    hit rates per fleet size), ``router_speedup`` (n=max vs n=1),
    ``router_affinity_hit_rate``; one merged fleet snapshot part
    ``router_n<n>`` per point.

    ISSUE 16 adds the **KV-fabric phase** (``SERVE_FABRIC_PHASE``
    overrides, sharing ``--engines`` with the router phase): forced
    overflow on an N-engine fleet — first overflow cold-prefills and
    seeds a fabric replication, later overflows land warm on the
    replica, one owner takes a planned drain with KV migration —
    certifying ``fabric_spill_speedup`` (cold-spill vs replicated-spill
    ttft p50), the transfer tallies, and ZERO stale refusals; merged
    fleet snapshot part ``"fabric"``.

    All phases' registry snapshots ride in the SAME drift-gated
    ``BENCH_SERVE_OBS.json``, so a future hit-rate, accept-rate, or
    spill-warmth regression fails the gate like any perf regression."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.obs import Registry, snapshot_quantile
    from distkeras_tpu.serve import (DecodeEngine, ServeClient,
                                     ServeConfig, ServeServer)

    requests, concurrency = int(requests), int(concurrency)
    if requests < 1 or concurrency < 1:
        raise ValueError(f"bench_serve needs requests >= 1 and "
                         f"concurrency >= 1 (got {requests}, "
                         f"{concurrency})")
    model = zoo.gpt_lm(vocab_size=vocab, dim=dim, num_heads=heads,
                       num_blocks=blocks, seq_len=seq_len)
    variables = model.init(0)
    cfg = ServeConfig(slots=slots, max_queue=queue,
                      max_new_tokens=max_new)
    registry = Registry()
    engine = DecodeEngine(model, variables, cfg, registry=registry)
    # compile the whole bucket ladder up front: the measured window is
    # steady-state serving, and jit.retraces must stay 0 through it
    engine.warmup()

    regs = [Registry() for _ in range(concurrency)]
    e2e = [[] for _ in range(concurrency)]
    ttft = [[] for _ in range(concurrency)]
    rejected = [0] * concurrency
    negotiated = [1] * concurrency
    errors: list = []
    share = [requests // concurrency + (1 if k < requests % concurrency
                                        else 0)
             for k in range(concurrency)]

    def drive(k: int) -> None:
        try:
            rng = np.random.default_rng(1000 + k)
            with ServeClient("127.0.0.1", server.port, registry=regs[k],
                             wire_version=wire_version) as client:
                negotiated[k] = client.wire_version
                for _ in range(share[k]):
                    prompt = rng.integers(0, vocab, size=(prompt_len,))
                    t0 = time.perf_counter()
                    reply = client.generate(prompt, max_new)
                    if reply.get("ok"):
                        e2e[k].append(time.perf_counter() - t0)
                        ttft[k].append(float(reply.get("ttft_s", 0.0)))
                    elif reply.get("rejected"):
                        # closed-loop at <= slots+queue outstanding never
                        # sheds; counted anyway so an open-loop variant
                        # (concurrency > capacity) reports honestly
                        rejected[k] += 1
                    else:
                        raise RuntimeError(f"generate failed: {reply}")
        except BaseException as e:  # surfaced after join — never hang
            errors.append(e)

    t_load0 = time.perf_counter()
    with ServeServer(engine) as server:
        threads = [threading.Thread(target=drive, args=(k,),
                                    name=f"bench-serve-{k}")
                   for k in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_load0
    if errors:
        raise errors[0]

    merged = Registry.merge_snapshots(*[r.snapshot() for r in regs])
    snap = registry.snapshot()
    all_e2e = np.asarray([v for part in e2e for v in part])
    all_ttft = np.asarray([v for part in ttft for v in part])
    tokens_out = snap["serve.tokens_out"]["value"]
    row = {
        "metric": f"serve e2e latency (localhost, gpt_lm d{dim} "
                  f"T{seq_len}, slots={slots}, conc={concurrency})",
        "mode": "bench_serve",
        "requests": requests, "concurrency": concurrency,
        "prompt_len": prompt_len, "max_new_tokens": max_new,
        "slots": slots, "max_queue": queue,
        "e2e_ms_p50": round(float(np.median(all_e2e)) * 1e3, 3)
        if all_e2e.size else None,
        "e2e_ms_p99": round(float(np.quantile(all_e2e, 0.99)) * 1e3, 3)
        if all_e2e.size else None,
        "ttft_ms_p50": round(float(np.median(all_ttft)) * 1e3, 3)
        if all_ttft.size else None,
        "ttft_ms_p99": round(float(np.quantile(all_ttft, 0.99)) * 1e3, 3)
        if all_ttft.size else None,
        "queue_wait_ms_p50": round(snapshot_quantile(
            snap["serve.queue_wait_seconds"], 0.5) * 1e3, 3),
        "tokens_per_sec": round(tokens_out / wall, 1),
        "rejected": sum(rejected),
        "jit_retraces": snap["jit.retraces"]["value"],
        "wire_version": min(negotiated),
        # the fleet scaling curve is only meaningful when the recording
        # host had cores to give each engine — committed-artifact
        # contracts gate on this instead of asserting scale-up a
        # single-core container cannot express
        "host_cpus": os.cpu_count(),
    }

    # -- accelerator phases (ISSUE 11): row fields are ALWAYS present
    # (None when a phase is skipped), snapshot parts only when run
    prefix_cfg = None if prefix_phase is False \
        else {**SERVE_PREFIX_PHASE, **(prefix_phase or {})}
    spec_cfg = None if spec_phase is False \
        else {**SERVE_SPEC_PHASE, **(spec_phase or {})}
    router_cfg = None if router_phase is False \
        else {**SERVE_ROUTER_PHASE, **(router_phase or {})}
    fabric_cfg = None if fabric_phase is False \
        else {**SERVE_FABRIC_PHASE, **(fabric_phase or {})}
    row.update(dict.fromkeys(
        ("ttft_warm_ms_p50", "ttft_cold_ms_p50", "warm_speedup",
         "prefix_hit_rate", "spec_k", "tokens_per_sec_base",
         "tokens_per_sec_spec", "spec_uplift", "spec_accept_rate",
         "spec_parity", "router_engines", "router_scaling",
         "router_speedup", "router_affinity_hit_rate",
         "fabric_engines", "fabric_ttft_spill_cold_ms_p50",
         "fabric_ttft_spill_warm_ms_p50", "fabric_spill_speedup",
         "fabric_kv_replications", "fabric_kv_migrations",
         "fabric_kv_push_bytes", "fabric_kv_refused_stale",
         "fabric_secondary_hits")))
    parts = {}
    if prefix_cfg is not None:
        fields, parts["prefix"] = _serve_prefix_phase(prefix_cfg)
        row.update(fields)
    if spec_cfg is not None:
        fields, parts["spec_base"], parts["spec"] = \
            _serve_spec_phase(spec_cfg)
        row.update(fields)
    if router_cfg is not None:
        fields, router_parts = _serve_router_phase(router_cfg)
        row.update(fields)
        parts.update(router_parts)
    if fabric_cfg is not None:
        fields, parts["fabric"] = _serve_fabric_phase(fabric_cfg)
        row.update(fields)

    bl_cfg = _baseline_cfg()
    base_path = _baseline_snapshot_path(bl_cfg, "serve_bench",
                                        "BENCH_SERVE_OBS.json")
    obs_doc = {"config": {"mode": "bench_serve",
                          "requests": requests,
                          "concurrency": concurrency,
                          "prompt_len": prompt_len,
                          "wire_version": min(negotiated),
                          "model": {"vocab": vocab, "dim": dim,
                                    "heads": heads, "blocks": blocks,
                                    "seq_len": seq_len},
                          "prefix_phase": prefix_cfg,
                          "spec_phase": spec_cfg,
                          "router_phase": router_cfg,
                          "fabric_phase": fabric_cfg,
                          **cfg.config_row(seq_len)},
               # the wall-clock row rides in the committed artifact too:
               # the acceptance numbers (warm_speedup, spec_uplift,
               # spec_parity) are then inspectable from the snapshot
               # alone.  Not a registry part — diff_docs skips it; the
               # drift gate works on the distributions above instead
               "row": dict(row),
               "client": merged,
               "server": snap,
               **parts}
    snap_path = os.path.join(out_dir, os.path.basename(base_path))
    row["obs_drift"], snap_path = _persist_obs_snapshot(
        snap_path, obs_doc, bl_cfg, base_path=base_path)
    row["snapshot"] = os.path.relpath(snap_path, ROOT)
    return row


def bench_continual(intervals: int = 16, snapshot_every: int = 4,
                    window: int = 4, batch: int = 16, history: int = 3,
                    min_history: int = 2, drift_interval=10,
                    out_dir: str = ROOT, vocab: int = 16, dim: int = 16,
                    heads: int = 2, blocks: int = 1, seq_len: int = 16,
                    lr: float = 1e-2, slots: int = 2,
                    max_new: int = 8) -> dict:
    """Continual-learning bench (ISSUE 8 acceptance): a bounded-duration
    ``ContinualTrainer`` run over a simulated unbounded LM feed with a
    LIVE ``DecodeEngine`` as the deploy target — training, windowed
    drift gating, rolling checkpoints-in-registry, and gated promotes
    all in one loop.  ``drift_interval`` injects an abrupt distribution
    change into the feed at that interval boundary, so the committed run
    records BOTH behaviors: drift-clean deploys before it and a
    drift-dirty rejection (not a silent skip) at it.

    One JSON row: deploy/rejection counts, verdict tally, stream-lag +
    window-wall quantiles, the zero-pinned ``jit.retraces``.  The shared
    trainer+engine registry snapshot AND the gate's window-verdict log
    persist to ``BENCH_CONTINUAL_OBS.json``, drift-checked against the
    committed baseline BEFORE overwriting it (the standard
    ``OBS_BASELINE.json`` contract; config-incompatible runs divert to a
    ``.variant.json`` sidecar)."""
    from distkeras_tpu.continual import (ContinualConfig, ContinualTrainer,
                                         synthetic_lm_feed)
    from distkeras_tpu.models import zoo
    from distkeras_tpu.obs import Registry, snapshot_quantile
    from distkeras_tpu.serve import DecodeEngine, ServeConfig

    intervals = int(intervals)
    if intervals < 1:
        raise ValueError(f"bench_continual needs intervals >= 1 "
                         f"(got {intervals})")
    model = zoo.gpt_lm(vocab_size=vocab, dim=dim, num_heads=heads,
                       num_blocks=blocks, seq_len=seq_len)
    reg = Registry()  # ONE registry: trainer + gate + engine + wire
    engine = DecodeEngine(model, model.init(0),
                          ServeConfig(slots=slots, max_new_tokens=max_new),
                          registry=reg)
    engine.warmup()
    engine.start()
    bl_cfg = _baseline_cfg()
    cfg = ContinualConfig(batch_size=batch, window_steps=window,
                          snapshot_every=snapshot_every, history=history,
                          min_history=min_history)
    # NOTE: the deploy gate runs on the built-in WITHIN-RUN thresholds
    # (baseline=None).  OBS_BASELINE.json's continual.* entries tune the
    # CROSS-run bench-vs-committed comparison below — its loosened
    # continual.loss PSI would silently weaken the live gate
    trainer = ContinualTrainer(model, "adam",
                               "sparse_categorical_crossentropy",
                               config=cfg, learning_rate=lr, registry=reg,
                               deploy_to=engine)
    drift_after = None if drift_interval is None else \
        int(drift_interval) * snapshot_every * window
    feed = synthetic_lm_feed(vocab, seq_len, batch, seed=0,
                             drift_after=drift_after)
    t0 = time.perf_counter()
    try:
        trainer.run(feed, intervals=intervals)
    finally:
        engine.stop()
    wall = time.perf_counter() - t0

    snap = reg.snapshot()

    def _c(name):
        return snap.get(name, {}).get("value", 0.0)

    row = {
        "metric": f"continual train+deploy loop (gpt_lm d{dim} "
                  f"T{seq_len}, {intervals} intervals)",
        "mode": "bench_continual",
        "intervals": intervals,
        "windows": _c("continual.windows"),
        "samples_per_sec": round(_c("continual.samples") / wall, 1),
        "deploys": _c("continual.deploys"),
        "deploys_rejected": _c("continual.deploys_rejected"),
        "rejected_dirty": _c("continual.rejected_dirty"),
        "rejected_warmup": _c("continual.rejected_warmup"),
        "verdicts": {k: _c(f"continual.verdicts_{k}")
                     for k in ("stable", "step", "trend")},
        "stream_lag_ms_p50": round(snapshot_quantile(
            snap["continual.stream_lag_seconds"], 0.5) * 1e3, 3),
        "window_ms_p50": round(snapshot_quantile(
            snap["continual.window_seconds"], 0.5) * 1e3, 3),
        "jit_retraces": snap["jit.retraces"]["value"],
        "promotions": _c("serve.promotions"),
    }
    base_path = _baseline_snapshot_path(bl_cfg, "continual_bench",
                                        "BENCH_CONTINUAL_OBS.json")
    obs_doc = {"config": {"mode": "bench_continual",
                          "intervals": intervals,
                          "drift_interval": drift_interval,
                          "lr": lr,
                          "model": {"vocab": vocab, "dim": dim,
                                    "heads": heads, "blocks": blocks,
                                    "seq_len": seq_len},
                          **cfg.config_row()},
               "continual": snap,
               "verdicts": trainer.gate.history_log()}
    snap_path = os.path.join(out_dir, os.path.basename(base_path))
    row["obs_drift"], snap_path = _persist_obs_snapshot(
        snap_path, obs_doc, bl_cfg, base_path=base_path)
    row["snapshot"] = os.path.relpath(snap_path, ROOT)
    return row


def bench_ps(codec: str = "none", windows: int = 50, mb: float = 4.0,
             out_dir: str = ROOT, wire_version=None,
             ps_workers: int = 1, ps_shards: int = 1,
             ps_shard_placement: str = "threads",
             down: str = "none", pull_ratio: int = 1,
             shm: bool = False) -> dict:
    """PS-comms microbenchmark (ISSUE 4 acceptance): N pull+commit windows
    against a localhost PS over an ``mb``-megabyte synthetic center, from
    ``ps_workers`` concurrent clients (ISSUE 5: the contention sweep point
    — lock/accept-thread contention is exactly what single-client RTTs
    cannot see).  ``ps_shards > 1`` (ISSUE 10) partitions the center
    across a shard fleet and drives it with ``ShardedPSClient`` fan-out —
    the sweep that shows whether sharding flattens the single-lock
    commit-RTT pileup.

    ISSUE 12 (wire round 2): ``down`` selects the DOWN pull-compression
    spec ("int8"/"bf16"/"topk<frac>"/"adaptive"), ``pull_ratio`` makes
    each window **pull-heavy** — ``pull_ratio`` timed FRESH pulls (the
    client cache is invalidated per pull so every one ships a center,
    the regime a busy async fleet's pulls are in) per commit — and
    ``shm=True`` negotiates the same-host shared-memory transport.  Pull
    RTTs land in their own ``bench.ps.pull_seconds`` histogram (committed
    evidence for the shm-vs-TCP comparison), and the row carries
    DOWN-direction bytes/window plus the reference-residual compression
    ratio.

    ISSUE 15 (wire round 3): the single-worker point additionally runs a
    **streaming A/B phase** — a monolithic reference pass (streaming
    refused per client, fresh pull == full blocking RTT) then a streamed
    dispatch-ahead pass (``pull_begin`` before a simulated compute window
    sized at the monolithic p50, ``pull_join`` after) — reporting
    ``pull_hidden_fraction`` (share of fresh-pull wall time hidden behind
    the compute window) and fresh-pull-to-first-dispatch p50 for both
    sides in one committed snapshot.

    Returns (and the CLI prints) one JSON row: median/p99 commit AND pull
    RTT across all workers, wire bytes per window (direction-tagged),
    compression ratios.  One MERGED registry snapshot per sweep point is
    written at the repo root — ``BENCH_PS_OBS.json`` for
    the single-worker point (the committed baseline),
    ``BENCH_PS_OBS_shm.json`` for the single-worker shm point, and
    ``BENCH_PS_OBS_w<N>.json`` for contention points (self-checked when
    ``OBS_BASELINE.json`` maps a ``ps_bench_w<N>`` / ``ps_bench_shm``
    snapshot) — all in the same document schema obsview and the drift
    gate read.
    """
    from distkeras_tpu.obs import Registry, TIME_BUCKETS
    from distkeras_tpu.ps import (PSClient, ShardedParameterServer,
                                  ShardedPSClient, SocketParameterServer)
    from distkeras_tpu.ps.servers import DeltaParameterServer
    from distkeras_tpu.ps.shard.server import ProcessShardFleet

    from distkeras_tpu.ps.codecs import validate_down_spec

    ps_workers = int(ps_workers)
    windows = int(windows)
    ps_shards = int(ps_shards)
    pull_ratio = int(pull_ratio)
    down = validate_down_spec(down)
    if ps_workers < 1 or windows < 1 or ps_shards < 1 or pull_ratio < 1:
        raise ValueError(f"bench_ps needs ps_workers, windows, ps_shards "
                         f"and pull_ratio >= 1 (got {ps_workers}, "
                         f"{windows}, {ps_shards}, {pull_ratio})")
    if ps_shard_placement not in ("threads", "processes"):
        raise ValueError(f"ps_shard_placement must be 'threads' or "
                         f"'processes', got {ps_shard_placement!r}")
    rng = np.random.default_rng(0)
    # 8 equal fp32 leaves totalling ~mb MB — tensor-shaped like a model,
    # not one giant blob, so framing/segment overhead is realistic
    n = max(1, int(mb * (1 << 20) / 4 / 8))
    center = {"params": [{"w": rng.normal(size=n).astype(np.float32)}
                         for _ in range(8)], "state": [{} for _ in range(8)]}
    delta = {"params": [{"w": (0.01 * rng.normal(size=n)).astype(np.float32)}
                        for _ in range(8)], "state": [{} for _ in range(8)]}

    sharded = None
    if ps_shards > 1 and ps_shard_placement == "processes":
        # the deployment shape: one shard-server process per shard (the
        # fleet stops sharing the bench interpreter's GIL — on a real
        # deployment, one per host).  Per-shard server registries live in
        # the shard processes; their counters are pollable via the stats
        # RPC, so the persisted server snapshot is the merged RPC view.
        sharded = ProcessShardFleet(center, ps_shards,
                                    num_workers=ps_workers)
    elif ps_shards > 1:
        sharded = ShardedParameterServer(center, ps_shards,
                                         DeltaParameterServer,
                                         num_workers=ps_workers)
    else:
        ps = DeltaParameterServer(center, num_workers=ps_workers)
    regs = [Registry() for _ in range(ps_workers)]  # one per client thread
    rtts = [[] for _ in range(ps_workers)]
    pull_rtts = [[] for _ in range(ps_workers)]
    tcp_pull_rtts = [[] for _ in range(ps_workers)]
    wire_bytes = [0.0] * ps_workers
    down_bytes = [0.0] * ps_workers
    shm_active = [False] * ps_workers
    negotiated = [1] * ps_workers
    errors: list = []

    stream_ab: dict = {}

    def make_client(k: int, use_shm: bool, use_stream=None):
        # explicit bool: False must DISABLE shm even under DKTPU_SHM=1,
        # or the TCP reference phase of an --shm A/B silently negotiates
        # rings and measures shm against itself (use_stream likewise for
        # the streaming A/B's monolithic reference phase — ISSUE 15)
        if sharded is not None:
            return ShardedPSClient(sharded.addrs(), center, k,
                                   registry=regs[k], codec=codec,
                                   wire_version=wire_version, down=down,
                                   shm=use_shm, stream=use_stream)
        return PSClient("127.0.0.1", server.port, k, registry=regs[k],
                        codec=codec, wire_version=wire_version, down=down,
                        shm=use_shm, stream=use_stream)

    def drive_stream_ab(k: int, creg) -> None:
        """Streaming A/B (ISSUE 15), single-worker point only: a
        monolithic reference pass (stream refused client, fresh pulls,
        pull == dispatch wait), then a streamed dispatch-ahead pass —
        ``pull_begin`` before a simulated compute window sized at the
        monolithic pull p50, ``pull_join`` after — so ONE committed
        snapshot carries both sides of pull-to-first-dispatch and the
        measured hidden fraction."""
        h_mono = creg.histogram("bench.ps.pull_to_dispatch_seconds_mono",
                                TIME_BUCKETS)
        h_stream = creg.histogram(
            "bench.ps.pull_to_dispatch_seconds_stream", TIME_BUCKETS)
        mono_rtts = []
        with make_client(k, use_shm=False, use_stream=False) as mono:
            mono.pull()  # connection + first transfer warm
            for _ in range(max(8, windows // 4)):
                # calibration: the simulated compute window is sized at
                # the monolithic pull p50, so "hidden behind compute"
                # means hidden behind a window the pull itself would fill
                mono.invalidate()
                t0 = time.perf_counter()
                mono.pull()
                mono_rtts.append(time.perf_counter() - t0)
            compute_s = float(np.median(mono_rtts))
            mono_rtts = []
            hidden_s = wall_s = 0.0
            waits = []
            with make_client(k, use_shm=False, use_stream=True) as sc:
                sc.pull()
                subs = getattr(sc, "clients", None)
                active = all(c.stream_enabled for c in subs) if subs \
                    else bool(getattr(sc, "stream_enabled", False))
                # the two sides run INTERLEAVED (not pass-after-pass):
                # localhost RTTs drift with host load over a pass, and a
                # sequential A then B would measure the drift, not the
                # streaming
                for _ in range(windows):
                    mono.invalidate()
                    t0 = time.perf_counter()
                    mono.pull()
                    dt = time.perf_counter() - t0
                    mono_rtts.append(dt)
                    h_mono.observe(dt)
                    sc.invalidate()
                    t0 = time.perf_counter()
                    sc.pull_begin()
                    time.sleep(compute_s)  # the simulated device window
                    t1 = time.perf_counter()
                    sc.pull_join()
                    t2 = time.perf_counter()
                    hidden_s += t1 - t0
                    wall_s += t2 - t0
                    waits.append(t2 - t1)
                    h_stream.observe(t2 - t1)
        mono_p50 = float(np.median(mono_rtts))
        stream_p50 = float(np.median(waits))
        stream_ab.update({
            "stream": active,
            "pull_hidden_fraction": round(hidden_s / max(wall_s, 1e-12),
                                          3),
            "pull_to_dispatch_ms_p50_mono": round(mono_p50 * 1e3, 3),
            "pull_to_dispatch_ms_p50_stream": round(stream_p50 * 1e3, 3),
            "stream_speedup": round(mono_p50 / max(stream_p50, 1e-12), 2),
        })

    def drive(k: int) -> None:
        try:
            creg = regs[k]
            # dedicated pull/commit RTT histograms ride the committed
            # snapshot — the shm-vs-TCP pull-p50 comparison's evidence
            h_pull = creg.histogram("bench.ps.pull_seconds", TIME_BUCKETS)
            h_commit = creg.histogram("bench.ps.commit_seconds",
                                      TIME_BUCKETS)
            # pre-created so 0 is present even when no link downshifts
            # (or no adaptive policy) ever fire
            creg.counter("ps.link.downshifts")
            if ps_workers == 1 and not shm:
                drive_stream_ab(k, creg)
            if shm:
                # A/B reference phase (ISSUE 12): the SAME pull-heavy
                # workload over plain TCP first, into its own histogram,
                # so ONE committed snapshot carries both sides of the
                # shm-vs-TCP-loopback comparison
                h_tcp = creg.histogram("bench.ps.pull_seconds_tcp",
                                       TIME_BUCKETS)
                with make_client(k, use_shm=False) as ref:
                    ref.pull()  # connection + first center transfer warm
                    for _ in range(windows * pull_ratio):
                        ref.invalidate()
                        t0 = time.perf_counter()
                        ref.pull()
                        dt = time.perf_counter() - t0
                        tcp_pull_rtts[k].append(dt)
                        h_tcp.observe(dt)
            with make_client(k, use_shm=shm) as client:
                negotiated[k] = client.wire_version
                client.pull()  # connection + first center transfer warm
                b0 = creg.counter("net.bytes_sent").value \
                    + creg.counter("net.bytes_recv").value
                d0 = creg.counter("ps.wire.bytes_down").value
                for _ in range(windows):
                    # pull-heavy window (ISSUE 12): ``pull_ratio`` fresh
                    # pulls per commit — each invalidated so a center
                    # actually ships, the regime a busy fleet's pulls
                    # are in (some OTHER worker committed since)
                    for _ in range(pull_ratio):
                        client.invalidate()
                        t0 = time.perf_counter()
                        client.pull()
                        dt = time.perf_counter() - t0
                        pull_rtts[k].append(dt)
                        h_pull.observe(dt)
                    t0 = time.perf_counter()
                    client.commit(delta)
                    dt = time.perf_counter() - t0
                    rtts[k].append(dt)
                    h_commit.observe(dt)
                wire_bytes[k] = creg.counter("net.bytes_sent").value \
                    + creg.counter("net.bytes_recv").value - b0
                down_bytes[k] = creg.counter("ps.wire.bytes_down").value \
                    - d0
                subs = getattr(client, "clients", None)
                # a sharded link counts only when EVERY shard connection
                # negotiated rings — a partial fleet is a TCP-mixed
                # measurement, not an shm one
                shm_active[k] = all(c.shm_active for c in subs) if subs \
                    else bool(getattr(client, "shm_active", False))
        except BaseException as e:  # surfaced after join — never hang
            errors.append(e)

    server = sharded if sharded is not None \
        else SocketParameterServer(ps)
    server_snap = None
    with server:
        threads = [threading.Thread(target=drive, args=(k,),
                                    name=f"bench-ps-{k}")
                   for k in range(ps_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            # surface the drive threads' own failures BEFORE the stats
            # poll: a dead shard would otherwise mask the recorded root
            # cause with the poller's unrelated ConnectionError
            raise errors[0]
        if isinstance(sharded, ProcessShardFleet):
            # shard-process registries live across a process boundary:
            # the merged stats-RPC view IS the server snapshot, polled
            # while the fleet still serves
            replies = []
            for h, p in sharded.addrs():
                with PSClient(h, p) as poller:
                    replies.append(poller.stats())
            server_snap = Registry.merge_snapshots(
                *[r.get("stats", {}) for r in replies])
    if server_snap is None:
        server_snap = (sharded.registry if sharded is not None
                       else ps.registry).snapshot()

    merged = Registry.merge_snapshots(*[r.snapshot() for r in regs])

    def _counter(snap, name):
        return snap.get(name, {}).get("value", 0.0)

    raw = _counter(merged, "ps.codec.bytes_raw")
    enc = _counter(merged, "ps.codec.bytes_encoded")
    down_raw = _counter(merged, "ps.down.bytes_raw")
    down_enc = _counter(merged, "ps.down.bytes_encoded")
    all_rtts = np.concatenate([np.asarray(r) for r in rtts])
    all_pulls = np.concatenate([np.asarray(r) for r in pull_rtts])
    total_windows = ps_workers * windows
    total_pulls = total_windows * pull_ratio
    row = {
        "metric": "ps commit RTT (localhost, "
                  f"{mb:g} MB center, codec={codec}, "
                  f"workers={ps_workers}"
                  + (f", shards={ps_shards}" if ps_shards > 1 else "")
                  + (f", down={down}" if down != "none" else "")
                  + (", shm" if all(shm_active) and shm else "")
                  + ")",
        "mode": "bench_ps", "codec": codec, "windows": windows,
        "ps_workers": ps_workers,
        "ps_shards": ps_shards,
        "ps_shard_placement": ps_shard_placement,
        "center_mb": round(mb, 3),
        "down": down, "pull_ratio": pull_ratio,
        #: True only when EVERY client negotiated the same-host rings —
        #: a refused offer (cross-host, old server) silently staying on
        #: TCP must not be read as an shm measurement
        "shm": bool(shm and all(shm_active)),
        "commit_rtt_ms_p50": round(float(np.median(all_rtts)) * 1e3, 3),
        "commit_rtt_ms_p99": round(float(np.quantile(all_rtts, 0.99)) * 1e3,
                                   3),
        "pull_rtt_ms_p50": round(float(np.median(all_pulls)) * 1e3, 3),
        "pull_rtt_ms_p99": round(float(np.quantile(all_pulls, 0.99)) * 1e3,
                                 3),
        **({"pull_rtt_ms_p50_tcp_ref": round(float(np.median(
            np.concatenate([np.asarray(r) for r in tcp_pull_rtts])))
            * 1e3, 3)} if shm else {}),
        "wire_bytes_per_window": round(sum(wire_bytes)
                                       / max(1, total_windows)),
        #: DOWN direction (ISSUE 12): bytes the pulled centers took per
        #: fresh pull — the number reference-residual compression cuts
        "wire_bytes_down_per_pull": round(sum(down_bytes)
                                          / max(1, total_pulls)),
        #: as NEGOTIATED on the live connections (env pins like
        #: DKTPU_WIRE=1 and server refusals included) — benchmark
        #: provenance must name the frame format that carried the traffic
        "wire_version": min(negotiated),
        "compression_ratio": round(raw / enc, 3) if enc else 1.0,
        "down_compression_ratio": round(down_raw / down_enc, 3)
        if down_enc else 1.0,
        "bytes_saved": _counter(merged, "ps.codec.bytes_saved"),
        #: streaming A/B (ISSUE 15), single-worker point: hidden fraction
        #: + pull-to-first-dispatch p50 both sides, from drive_stream_ab
        **stream_ab,
        **({"stream_chunks": _counter(merged, "ps.pull.stream_chunks")}
           if stream_ab else {}),
    }
    # the single-worker snapshot name follows OBS_BASELINE.json's
    # ``snapshots.ps_bench`` mapping so a remapped baseline is both
    # checked against AND refreshed (the trainer bench does the same)
    bl_cfg = _baseline_cfg()
    if ps_workers == 1 and row["shm"]:
        # the single-worker shm point is its own committed baseline —
        # the pull-p50 shm-vs-TCP comparison needs BOTH files stable
        base_path = _baseline_snapshot_path(bl_cfg, "ps_bench_shm",
                                            "BENCH_PS_OBS_shm.json")
    else:
        base_path = _baseline_snapshot_path(bl_cfg, "ps_bench",
                                            "BENCH_PS_OBS.json")
    name = os.path.basename(base_path) if ps_workers == 1 \
        else f"BENCH_PS_OBS_w{ps_workers}.json"
    snap_path = os.path.join(out_dir, name)
    # config carries the shard/down/shm keys only when active: committed
    # baselines of the plain workload must keep matching plain reruns
    cfg_keys = ("codec", "windows", "center_mb", "ps_workers",
                "wire_version") \
        + (("ps_shards", "ps_shard_placement") if ps_shards > 1 else ()) \
        + (("down",) if down != "none" else ()) \
        + (("pull_ratio",) if pull_ratio != 1 else ()) \
        + (("shm",) if row["shm"] else ())
    obs_doc = {"config": {k: row[k] for k in cfg_keys},
               "client": merged,
               "server": server_snap}
    if sharded is not None:
        obs_doc["plan"] = sharded.plan.doc()
    # self-check + clobber guard for the single-worker baseline point and
    # for contention points with a designated ``ps_bench_w<N>`` mapping
    # (ISSUE 10: the committed sharded w8/w16 points); unmapped contention
    # points get the clobber guard only — a committed w<N> snapshot must
    # not be silently replaced by a config-incompatible run either way
    if ps_workers == 1:
        row["obs_drift"], snap_path = _persist_obs_snapshot(
            snap_path, obs_doc, bl_cfg, base_path=base_path)
    elif ((bl_cfg or {}).get("snapshots") or {}).get(
            f"ps_bench_w{ps_workers}"):
        wbase = _baseline_snapshot_path(bl_cfg, f"ps_bench_w{ps_workers}",
                                        name)
        row["obs_drift"], snap_path = _persist_obs_snapshot(
            snap_path, obs_doc, bl_cfg, base_path=wbase)
    else:
        row["obs_drift"] = {"checked": False,
                            "reason": "no designated baseline"}
        _, snap_path = _persist_obs_snapshot(snap_path, obs_doc, bl_cfg,
                                             check=False)
    row["snapshot"] = os.path.relpath(snap_path, ROOT)
    return row


# ---------------------------------------------------------------------------
# scenario bench (ISSUE 17): trace-driven open-loop load + autoscaler
# ---------------------------------------------------------------------------

#: committed scenario-fleet config (ISSUE 17): one small-but-real gpt_lm
#: shared by every named scenario.  slots=1 keeps per-engine service
#: visibly bounded so the diurnal peak genuinely saturates a one-engine
#: fleet and the autoscaler has something to track.
SCENARIO_MODEL = dict(vocab=64, dim=64, heads=2, blocks=2, seq_len=96)
SCENARIO_FLEET = dict(engines=3, slots=1, queue=12, max_new=24, block=8,
                      cache_mb=16.0, prefill_buckets=(16, 48))
#: heavy-tail lognormal request sizes, clamped inside the seq budget
#: (prompt_max + new_max <= seq_len - slack)
SCENARIO_LENGTHS = dict(prompt_median=12, new_median=8, prompt_sigma=0.5,
                        new_sigma=0.4, prompt_min=4, prompt_max=40,
                        new_min=2, new_max=20)
SCENARIO_MIX = dict(groups=6, share=0.7)
#: the committed SLO — targets sit exactly on TIME_BUCKETS bounds so
#: attainment-from-histograms is exact, not interpolated.  0.5 s ttft /
#: 2.5 s e2e leaves room for the bounded queue wait a request absorbs
#: while the autoscaler is mid-reaction — the gate catches waits past
#: the queue bound, not the transient the policy exists to absorb.
SCENARIO_SLO = dict(ttft_s=0.5, e2e_s=2.5, attainment=0.95)
#: ``down_after`` is short because each tick costs a synchronous fleet
#: stats poll — under load the effective cadence stretches well past
#: ``interval_s``, and the diurnal trace's quiet tail is only ~2.5 s
SCENARIO_POLICY = dict(min_engines=1, max_engines=3, interval_s=0.1,
                       queue_high=2.0, queue_low=0.5,
                       attainment_low=0.92, attainment_high=0.96,
                       up_after=2, down_after=4, cooldown_s=0.5,
                       min_samples=12)
#: named scenarios.  ``smoke`` is the tier-1/CI deterministic tiny run;
#: the committed BENCH_SCENARIO_OBS.json holds the other three.
SCENARIO_TRACES = dict(
    smoke=dict(kind="poisson", rate=25.0, duration_s=1.5, seed=5,
               engines=1, start_engines=1, autoscale=False, workers=6),
    # base_rate 10/s leaves the night/evening troughs genuinely idle
    # (queue/engine reliably under queue_low) so the evening
    # scale-downs fire every run, not only on lucky scheduling
    diurnal=dict(kind="diurnal", base_rate=10.0, peak_rate=220.0,
                 period_s=12.0, seed=17, engines=3, start_engines=1,
                 autoscale=True, workers=24),
    spike=dict(kind="spike", base_rate=40.0, spike_rate=300.0,
               duration_s=9.0, spike_start=3.0, spike_duration=2.0,
               seed=23, engines=3, start_engines=2, autoscale=True,
               workers=24),
    chaos=dict(kind="poisson", rate=60.0, duration_s=6.0, seed=29,
               engines=3, start_engines=3, autoscale=False,
               kill_at=2.5, workers=16),
)
#: the trio the committed snapshot is built from (in this order)
SCENARIO_COMMITTED = ("diurnal", "spike", "chaos")


def _scenario_spec(name: str, sc: dict, lengths, mix):
    from distkeras_tpu.scenario import (diurnal_trace, poisson_trace,
                                        spike_trace)
    kind = sc["kind"]
    if kind == "poisson":
        return poisson_trace(sc["rate"], sc["duration_s"], seed=sc["seed"],
                             lengths=lengths, mix=mix, name=name)
    if kind == "diurnal":
        return diurnal_trace(sc["base_rate"], sc["peak_rate"],
                             sc["period_s"], seed=sc["seed"],
                             lengths=lengths, mix=mix, name=name)
    if kind == "spike":
        return spike_trace(sc["base_rate"], sc["spike_rate"],
                           sc["duration_s"], spike_start=sc["spike_start"],
                           spike_duration=sc["spike_duration"],
                           seed=sc["seed"], lengths=lengths, mix=mix,
                           name=name)
    raise ValueError(f"unknown trace kind {kind!r}")


def _scenario_run(name: str, sc: dict, spec, model, variables, target,
                  events):
    """One named scenario end to end: fresh fleet, parked spares,
    open-loop storm (autoscaler on when the scenario says so, one
    in-process engine kill when it is the chaos one), then ONE merged
    part snapshot with every reachable engine re-admitted first — so
    the part's ``jit.compiles`` covers a deterministic engine set no
    matter what the scaling history was."""
    import threading as _threading

    from distkeras_tpu.obs import Registry, snapshot_quantile
    from distkeras_tpu.scenario import (AutoScaler, AutoscalePolicy,
                                        ScenarioRunner)
    from distkeras_tpu.serve import (DecodeEngine, RouterConfig,
                                     ServeClient, ServeConfig,
                                     ServeRouter, ServeServer)

    f = SCENARIO_FLEET
    servers, router, scaler, killer = [], None, None, None
    stats_client = None
    try:
        for _ in range(int(sc["engines"])):
            cfg = ServeConfig(
                slots=f["slots"], max_queue=f["queue"],
                max_new_tokens=f["max_new"],
                prefill_buckets=tuple(f["prefill_buckets"]),
                prefix_cache=True, prefix_cache_mb=f["cache_mb"],
                prefix_block=f["block"])
            servers.append(ServeServer(DecodeEngine(
                model, variables, cfg, registry=Registry()
            ).warmup()).start())
        # fabric OFF: the scenario gate reads scenario.*/serve.* deltas;
        # async spill transfers would add scheduling-dependent cold
        # prefills (same reasoning as the router phase)
        router = ServeRouter(
            [("127.0.0.1", s.port) for s in servers],
            config=RouterConfig(affinity_block=f["block"],
                                stats_interval_s=0.5,
                                kv_fabric=False)).start()
        # park the spares: scale-ups during the run are the POLICY's
        start_n = int(sc.get("start_engines", sc["engines"]))
        for be in router.backends[start_n:]:
            parked = router.scale_down(be.addr)
            if not parked.get("ok"):
                raise RuntimeError(f"scenario setup park failed: {parked}")
        # live alerting (ISSUE 20): the router evaluates the committed
        # OBS_BASELINE threshold + SLO burn-rate rules over its
        # telemetry aggregator (fed by its own health poller); fired
        # counts land in the part snapshot, where the drift gate holds
        # obs.alerts.* to exactly zero — a clean bench must end quiet
        bl_alerts = (_baseline_cfg() or {}).get("alerts")
        if bl_alerts:
            router.enable_alerts(bl_alerts, events=events)
        sreg = Registry()
        if sc.get("autoscale"):
            scaler = AutoScaler(router, AutoscalePolicy(**SCENARIO_POLICY),
                                target=target, registry=sreg,
                                events=events, alerts=router.alerts)
        stats_client = ServeClient("127.0.0.1", router.port, registry=sreg)
        runner = ScenarioRunner(
            spec,
            make_client=lambda: ServeClient("127.0.0.1", router.port,
                                            registry=sreg),
            snap=lambda: stats_client.stats()["stats"],
            registry=sreg, target=target, workers=int(sc["workers"]),
            deadline_s=10.0, vocab=int(SCENARIO_MODEL["vocab"]),
            prefix_len=int(f["block"]) * 2, events=events)
        if sc.get("kill_at") is not None:
            victim = servers[-1]

            def _kill():
                # abrupt in-process death: outstanding requests abort
                # with recorded rejections, pooled router connections
                # die, the next forward re-queues to a survivor and
                # evicts the corpse — the PR 13 path, now timed
                runner.mark_eviction()
                victim.stop(drain=False)

            killer = _threading.Timer(float(sc["kill_at"]), _kill)
            killer.daemon = True
            killer.start()
        if scaler is not None:
            scaler.start()
        row = runner.run()
    finally:
        if killer is not None:
            killer.cancel()
        if scaler is not None:
            scaler.stop()
        if router is not None and stats_client is not None:
            # re-admit every reachable parked engine BEFORE the part
            # snapshot: the merged doc must cover a deterministic
            # engine set (all of them, minus the chaos corpse) or
            # jit.compiles would depend on where the scaler stopped
            for be in router.backends:
                if not be.alive:
                    router.scale_up(be.addr)
            st = stats_client.stats()
            stats_client.close()
        else:
            st = None
        if router is not None:
            router.stop()
        for s in servers:
            s.stop()
    from distkeras_tpu.obs import Registry as _R
    part = _R.merge_snapshots(st["stats"], sreg.snapshot())

    def _v(metric):
        return part.get(metric, {}).get("value", 0)

    h_rec = part.get("scenario.recovery_seconds", {})
    row.update(
        engines=int(sc["engines"]), engines_alive_end=st["engines_alive"],
        scale_up=int(_v("scenario.scale_up")),
        scale_down=int(_v("scenario.scale_down")),
        scale_events=scaler.history if scaler is not None else [],
        shed=int(_v("serve.router.rejected_no_backend")),
        jit_retraces=int(_v("jit.retraces")),
        recovery_s_p50=round(snapshot_quantile(h_rec, 0.5), 6)
        if h_rec.get("count") else None,
        alerts=(router.alerts.counts()
                if router is not None and router.alerts is not None
                else None),
    )
    return row, part


def bench_scenario(names=None, out_dir: str = ROOT) -> dict:
    """ISSUE 17 entry point: run the named scenarios (default: the
    committed diurnal + spike + chaos trio) through the open-loop
    harness and persist ONE drift-self-checked ``BENCH_SCENARIO_OBS.json``
    with a part per scenario.  Any other selection (e.g. ``smoke``)
    runs and reports but never touches the committed snapshot."""
    from distkeras_tpu.scenario import (LengthModel, PrefixMix, SLOTarget)
    from distkeras_tpu.utils.metrics import MetricsLogger

    names = tuple(names) if names else SCENARIO_COMMITTED
    for n in names:
        if n not in SCENARIO_TRACES:
            raise ValueError(
                f"unknown scenario {n!r} (have "
                f"{', '.join(sorted(SCENARIO_TRACES))})")
    model = zoo.gpt_lm(vocab_size=SCENARIO_MODEL["vocab"],
                       dim=SCENARIO_MODEL["dim"],
                       num_heads=SCENARIO_MODEL["heads"],
                       num_blocks=SCENARIO_MODEL["blocks"],
                       seq_len=SCENARIO_MODEL["seq_len"])
    variables = model.init(0)
    target = SLOTarget(**SCENARIO_SLO)
    lengths = LengthModel(**SCENARIO_LENGTHS)
    mix = PrefixMix(**SCENARIO_MIX)
    events_path = os.path.join(out_dir, "bench_scenario_events.jsonl")
    events = MetricsLogger(events_path)
    scenarios, parts = {}, {}
    try:
        for name in names:
            sc = SCENARIO_TRACES[name]
            spec = _scenario_spec(name, sc, lengths, mix)
            srow, part = _scenario_run(name, sc, spec, model, variables,
                                       target, events)
            scenarios[name] = srow
            parts[f"scenario_{name}"] = part
    finally:
        events.close()

    def _phase_ok(srow, skip=()):
        return all(p["attainment"] is None or p["phase"] in skip
                   or p["attainment"] >= target.attainment
                   for p in srow["phases"])

    row = {
        "metric": "scenario harness (open-loop SLO attainment)",
        "slo": dict(SCENARIO_SLO),
        "scenarios": scenarios,
        # the acceptance verdicts, machine-checkable in the row:
        # attainment holds everywhere except inside a spike window,
        # the autoscaler moved (both directions) on the diurnal trace,
        # and nothing retraced anywhere
        "attainment_ok": all(
            _phase_ok(s, skip=("spike",)) for s in scenarios.values()),
        "autoscaler_tracked": (
            scenarios.get("diurnal", {}).get("scale_up", 0) > 0
            and scenarios.get("diurnal", {}).get("scale_down", 0) > 0),
        "jit_retraces": sum(s["jit_retraces"] for s in scenarios.values()),
        "events_jsonl": os.path.relpath(events_path, ROOT),
    }
    obs_doc = {"config": {"mode": "scenario_bench",
                          "model": dict(SCENARIO_MODEL),
                          "fleet": {k: list(v) if isinstance(v, tuple)
                                    else v
                                    for k, v in SCENARIO_FLEET.items()},
                          "lengths": dict(SCENARIO_LENGTHS),
                          "mix": dict(SCENARIO_MIX),
                          "slo": dict(SCENARIO_SLO),
                          "policy": dict(SCENARIO_POLICY),
                          "traces": {n: dict(SCENARIO_TRACES[n])
                                     for n in names}},
               "row": {k: v for k, v in row.items() if k != "obs_drift"}}
    obs_doc.update(parts)
    if tuple(names) == SCENARIO_COMMITTED:
        bl_cfg = _baseline_cfg()
        snap_path = _baseline_snapshot_path(bl_cfg, "scenario_bench",
                                            "BENCH_SCENARIO_OBS.json")
        row["obs_drift"], snap_path = _persist_obs_snapshot(
            snap_path, obs_doc, bl_cfg)
        row["obs_snapshot"] = os.path.relpath(snap_path, ROOT)
    else:
        row["obs_drift"] = {"checked": False,
                            "reason": "non-committed scenario selection"}
    return row


# ---------------------------------------------------------------------------
# self-heal bench (ISSUE 20 satellite): eviction -> first replacement commit
# ---------------------------------------------------------------------------

#: committed self-heal workload: a 2-worker thread-placement async fleet
#: on the toy regression problem, worker 1 virtually SIGSTOPped after its
#: first window so the supervisor's detect -> evict -> respawn pipeline
#: runs exactly once.  ``heartbeat_hard_s`` bounds (and dominates) the
#: measured recovery latency: detection IS the budget, the respawn and
#: its first commit are milliseconds on top.
SELFHEAL_CFG = dict(workers=2, window=4, n=512, d=10, k=3, seed=0,
                    num_epoch=3, batch_size=32, heartbeat_hard_s=2.0)


def bench_selfheal(out_dir: str = ROOT) -> dict:
    """Self-healing latency point (ISSUE 20): one injected thread stall
    through the live supervisor, reporting the ``ps.recovery_seconds``
    window (eviction -> the replacement's first PS-applied commit) that
    :class:`FleetSupervisor` now times.  Persists the committed
    ``BENCH_SELFHEAL_OBS.json`` evidence snapshot, drift-self-checked
    like every other bench mode."""
    import distkeras_tpu as dk
    from distkeras_tpu import chaos
    from distkeras_tpu.data.transformers import OneHotTransformer
    from distkeras_tpu.models.layers import Dense, Sequential
    from distkeras_tpu.obs import snapshot_quantile
    from distkeras_tpu.ps import workers as workers_mod

    c = SELFHEAL_CFG
    rng = np.random.default_rng(c["seed"])
    x = rng.normal(size=(c["n"], c["d"])).astype(np.float32)
    w = rng.normal(size=(c["d"], c["k"])).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.normal(size=(c["n"], c["k"])),
                  axis=-1)
    ds = OneHotTransformer(c["k"], "label", "label_onehot").transform(
        Dataset({"features": x, "label": y}))
    model = dk.Model(Sequential([Dense(32, "relu"),
                                 Dense(c["k"], "softmax")]),
                     input_shape=(c["d"],))
    trainer = dk.DOWNPOUR(
        model, "sgd", loss="categorical_crossentropy",
        features_col="features", label_col="label_onehot",
        num_workers=c["workers"], mode="async",
        communication_window=c["window"], num_epoch=c["num_epoch"],
        batch_size=c["batch_size"], learning_rate=0.05,
        heartbeat_hard_s=c["heartbeat_hard_s"], startup_grace_s=60.0)
    t0 = time.monotonic()
    with chaos.ThreadStall(workers_mod.PullCommitWorker, worker_id=1,
                           stall_after=1) as stall:
        out = {}
        th = threading.Thread(target=lambda: out.update(m=trainer.train(ds)),
                              daemon=True)
        th.start()
        if not stall.wait_stalled(90):
            raise RuntimeError("selfheal bench: worker 1 never stalled")

        def _evicted():
            sup = trainer._supervisor
            return sup is not None and \
                sup.ps.registry.counter("ps.evictions").value >= 1

        deadline = time.monotonic() + 120
        while not _evicted():
            if time.monotonic() > deadline:
                raise RuntimeError("selfheal bench: the stalled worker "
                                   "was never evicted")
            time.sleep(0.05)
        stall.resume()  # the SIGCONT: its late commit tombstones
        th.join(240)
    if th.is_alive() or out.get("m") is None:
        raise RuntimeError("selfheal bench: supervised run never finished")
    wall_s = time.monotonic() - t0
    snap = trainer.ps_stats["registry"]

    def _v(name):
        return snap.get(name, {}).get("value", 0)

    h_rec = snap.get("ps.recovery_seconds", {})
    if not h_rec.get("count"):
        raise RuntimeError("selfheal bench: no ps.recovery_seconds "
                           "observation (eviction or respawn never "
                           "happened)")
    row = {
        "metric": "self-heal latency (thread stall -> evict -> respawn "
                  "-> first replacement commit)",
        "mode": "bench_selfheal",
        "wall_s": round(wall_s, 3),
        "evictions": int(_v("ps.evictions")),
        "respawns": int(_v("ps.respawns")),
        "commits_tombstoned": int(_v("ps.commits_tombstoned")),
        "recoveries": int(h_rec.get("count", 0)),
        "recovery_s_p50": round(snapshot_quantile(h_rec, 0.5), 6),
        "heartbeat_hard_s": c["heartbeat_hard_s"],
        #: the invariant the chaos suite gates: every commit request is
        #: applied, dropped, or tombstoned — nothing vanishes
        "accounting_exact": _v("ps.commit_requests") == (
            _v("ps.commits") + _v("ps.commits_dropped")
            + _v("ps.commits_tombstoned")),
    }
    bl_cfg = _baseline_cfg()
    snap_path = _baseline_snapshot_path(bl_cfg, "ps_selfheal",
                                        "BENCH_SELFHEAL_OBS.json")
    # persist ONLY the metrics this mode certifies: supervisor/recovery
    # accounting (deterministic under the single injected stall) plus
    # the telemetry-plane tallies (informational in the baseline).  A
    # 3-second chaos run's latency spans and EWMA gauges are pure
    # scheduling noise — committing them would make the self-check flap.
    certified = ("ps.commit_requests", "ps.commits", "ps.commits_dropped",
                 "ps.commits_tombstoned", "ps.evictions", "ps.respawns",
                 "ps.joins", "ps.recovery_seconds")
    obs_doc = {"config": {"mode": "bench_selfheal", **SELFHEAL_CFG},
               "server": {k: v for k, v in snap.items()
                          if k in certified
                          or k.startswith("obs.telemetry.")}}
    row["obs_drift"], snap_path = _persist_obs_snapshot(
        snap_path, obs_doc, bl_cfg)
    row["obs_snapshot"] = os.path.relpath(snap_path, ROOT)
    return row


def _cli(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ps", action="store_true",
                    help="run the PS-comms microbenchmark instead of the "
                         "trainer headline")
    ap.add_argument("--serve", action="store_true",
                    help="run the decode-service load bench instead of "
                         "the trainer headline")
    ap.add_argument("--continual", action="store_true",
                    help="run the continual-learning train+deploy loop "
                         "bench instead of the trainer headline")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="run the trace-driven open-loop scenario "
                         "harness (ISSUE 17) instead of the trainer "
                         "headline: a named scenario (smoke|diurnal|"
                         "spike|chaos), a comma-separated list, or "
                         "'all' for the committed diurnal+spike+chaos "
                         "trio (the only selection that overwrites "
                         "BENCH_SCENARIO_OBS.json)")
    ap.add_argument("--selfheal", action="store_true",
                    help="run the self-heal latency bench (ISSUE 20): "
                         "one injected thread stall through the live "
                         "supervisor, reporting the ps.recovery_seconds "
                         "eviction -> first-replacement-commit window "
                         "and refreshing BENCH_SELFHEAL_OBS.json")
    ap.add_argument("--intervals", type=int, default=16,
                    help="bench_continual: obs intervals to run")
    ap.add_argument("--drift-interval", type=int, default=10,
                    help="bench_continual: interval at which the feed's "
                         "distribution step-changes (-1 disables)")
    ap.add_argument("--requests", type=int, default=32,
                    help="bench_serve: total generation requests")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="bench_serve: closed-loop client threads")
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="bench_serve: prompt length per request")
    ap.add_argument("--max-new", type=int, default=16,
                    help="bench_serve: generated tokens per request")
    ap.add_argument("--slots", type=int, default=4,
                    help="bench_serve: continuous-batch width")
    ap.add_argument("--queue", type=int, default=8,
                    help="bench_serve: admission queue bound")
    ap.add_argument("--spec", type=int, default=None, metavar="K",
                    help="bench_serve: draft tokens per speculative "
                         "step for the spec phase (default: the "
                         "committed SERVE_SPEC_PHASE k; 0 skips the "
                         "phase)")
    ap.add_argument("--no-prefix", action="store_true",
                    help="bench_serve: skip the warm-vs-cold prefix "
                         "phase")
    ap.add_argument("--engines", type=int, default=None, metavar="N",
                    help="bench_serve: sweep the ServeRouter fleet "
                         "scaling phase over 1..N engines (ISSUE 14) "
                         "and run the N-engine KV-fabric phase "
                         "(ISSUE 16; default: the committed fleet of "
                         "3; 0 skips both phases)")
    ap.add_argument("--codec", default="none",
                    help="bench_ps commit codec: none|int8|bf16|topk<frac>")
    ap.add_argument("--down", default="none",
                    help="bench_ps DOWN pull-compression spec (ISSUE 12): "
                         "none|int8|bf16|topk<frac>|adaptive")
    ap.add_argument("--pull-ratio", type=int, default=1,
                    help="bench_ps: fresh pulls per commit window — the "
                         "pull-heavy phase; DOWN bytes and pull RTT "
                         "p50/p99 get their own row fields")
    ap.add_argument("--shm", action="store_true",
                    help="bench_ps: negotiate the same-host shared-memory "
                         "transport (tensor segments skip TCP)")
    ap.add_argument("--windows", type=int, default=50,
                    help="bench_ps pull+commit windows")
    ap.add_argument("--mb", type=float, default=4.0,
                    help="bench_ps synthetic center size in MB")
    ap.add_argument("--wire", type=int, default=None, choices=(1, 2),
                    help="bench_ps / bench_serve: pin the frame format "
                         "(default: negotiate v2)")
    ap.add_argument("--ps-workers", default="1",
                    help="bench_ps: comma-separated concurrent-client "
                         "sweep points (e.g. 1,2,4); one JSON row and one "
                         "merged registry snapshot per point")
    ap.add_argument("--ps-shards", type=int, default=1,
                    help="bench_ps: partition the center across N PS "
                         "shards (ISSUE 10) — workers fan commits/pulls "
                         "out with consistent-cut assembly; 1 = the "
                         "single-server star")
    ap.add_argument("--ps-shard-placement", default="threads",
                    choices=("threads", "processes"),
                    help="bench_ps: host shard servers in this process "
                         "(threads) or one OS process each (processes — "
                         "the deployment shape; shards stop sharing the "
                         "bench interpreter's GIL)")
    args = ap.parse_args(argv)
    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if sum(map(bool, (args.ps, args.serve, args.continual,
                      args.scenario, args.selfheal))) > 1:
        ap.error("--ps, --serve, --continual, --scenario and --selfheal "
                 "are mutually exclusive")
    if args.selfheal:
        print(json.dumps(bench_selfheal()))
        return 0
    if args.scenario:
        names = None if args.scenario == "all" else tuple(
            n.strip() for n in args.scenario.split(",") if n.strip())
        try:
            print(json.dumps(bench_scenario(names=names)))
        except ValueError as e:
            ap.error(str(e))
        return 0
    if args.continual:
        if args.intervals < 1:
            ap.error("--intervals must be >= 1")
        print(json.dumps(bench_continual(
            intervals=args.intervals,
            drift_interval=None if args.drift_interval is not None
            and args.drift_interval < 0 else args.drift_interval)))
        return 0
    if args.serve:
        if args.requests < 1 or args.concurrency < 1:
            ap.error("--requests and --concurrency must be >= 1")
        if args.spec is not None and args.spec < 0:
            ap.error("--spec must be >= 0 (0 skips the spec phase)")
        if args.engines is not None and args.engines < 0:
            ap.error("--engines must be >= 0 (0 skips the router phase)")
        print(json.dumps(bench_serve(
            requests=args.requests, concurrency=args.concurrency,
            prompt_len=args.prompt_len, max_new=args.max_new,
            slots=args.slots, queue=args.queue,
            wire_version=args.wire,
            prefix_phase=False if args.no_prefix else None,
            spec_phase=False if args.spec == 0
            else None if args.spec is None else {"k": args.spec},
            router_phase=False if args.engines == 0
            else None if args.engines is None
            else {"engines": args.engines},
            fabric_phase=False if args.engines == 0
            else None if args.engines is None
            else {"engines": args.engines})))
        return 0
    if args.ps:
        try:
            points = [int(p) for p in str(args.ps_workers).split(",") if p]
        except ValueError:
            ap.error(f"--ps-workers expects ints, got {args.ps_workers!r}")
        if not points or any(p < 1 for p in points):
            ap.error(f"--ps-workers needs positive sweep points "
                     f"(got {args.ps_workers!r})")
        if args.windows < 1:
            ap.error(f"--windows must be >= 1 (got {args.windows})")
        if args.ps_shards < 1:
            ap.error(f"--ps-shards must be >= 1 (got {args.ps_shards})")
        if args.pull_ratio < 1:
            ap.error(f"--pull-ratio must be >= 1 (got {args.pull_ratio})")
        for n in points:
            print(json.dumps(bench_ps(
                codec=args.codec, windows=args.windows, mb=args.mb,
                wire_version=args.wire, ps_workers=n,
                ps_shards=args.ps_shards,
                ps_shard_placement=args.ps_shard_placement,
                down=args.down, pull_ratio=args.pull_ratio,
                shm=args.shm)))
        return 0
    main()
    return 0


if __name__ == "__main__":
    sys.exit(_cli())
