"""Run-inspection CLI for the telemetry stream (ISSUE 2).

Three modes:

* ``python scripts/obsview.py RUN.jsonl`` — read a JSONL metrics file (the
  ``MetricsLogger`` sink a trainer wrote: epoch records, spans, async
  heartbeats, the final ``ps_stats`` registry snapshot) and print a run
  summary: per-epoch table, throughput timeline, staleness distribution,
  straggler analysis over the heartbeat gaps, the per-worker cross-process
  timeline (worker commit spans linked to the server apply spans they
  caused — ISSUE 5 trace propagation), top spans by cumulative time,
  per-worker heartbeat coverage.
* ``python scripts/obsview.py --ps HOST:PORT`` — poll a LIVE
  ``SocketParameterServer`` via its ``stats`` RPC and print the registry
  snapshot + straggler state (``--prometheus`` renders Prometheus text
  instead — pipe it anywhere that scrapes the standard format).
* ``python scripts/obsview.py --serve TARGET`` — poll a LIVE decode
  service (``distkeras_tpu/serve``) via its ``stats`` RPC: the SLO
  latency table (queue-wait / time-to-first-token / per-token /
  end-to-end p50/p99), admission-control counters (requests, rejected by
  reason), queue/slot occupancy, and the retrace sentinel — the serving
  health check (ISSUE 7).  A ``ServeRouter`` target — or a
  comma-separated engine fleet, like ``--ps`` shard fleets — renders
  the MERGED fleet SLO view plus a per-engine balance table
  (requests/occupancy/prefix-hit share) and a MISROUTED alarm when the
  fleet's affinity hit rate trails the single-engine baseline
  (ISSUE 14).
* ``python scripts/obsview.py --diff BASE CAND`` — drift-gate two
  persisted registry-snapshot files (``obs.drift``): counter ratio deltas,
  bucket-wise PSI + p50/p99 shift per histogram, thresholds from the
  committed ``OBS_BASELINE.json`` (or ``--thresholds FILE``).
  CI-friendly exit codes: 0 clean, 1 drift detected, 2 usage error.

The file mode takes ``--export-trace OUT.json`` (ISSUE 6) to write the
stream as a Chrome Trace Event Format document instead of printing the
summary: open it at ui.perfetto.dev (or chrome://tracing) and a
multi-worker async run reads as one linked timeline — one process row
per worker, server applies nested under the worker commits that caused
them (the PR 5 wire-carried trace context drawn as flow arrows),
heartbeats as instants, ``live_bytes`` watermarks as counter tracks.

The file mode also accepts a persisted registry-snapshot JSON (named
``Registry.snapshot()`` parts beside an optional ``config``):
per-registry instrument tables plus the commit-codec accounting
(compression ratio, bytes saved — ISSUE 4).

Everything renders through pure functions over plain records
(``summarize`` / ``summarize_stats``) so tests — and notebooks — can call
them directly on synthetic data.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, ROOT)

from distkeras_tpu.obs import (  # noqa: E402
    detect_from_heartbeats, emit, snapshot_quantile, to_prometheus_text)
from distkeras_tpu.obs import drift  # noqa: E402

_BLOCKS = " ▁▂▃▄▅▆▇█"

#: MetricsLogger's json_safe coerces non-finite floats to these strings so
#: the JSONL stays valid JSON; map them back when reading numbers
_NONFINITE = {"NaN": float("nan"), "Infinity": float("inf"),
              "-Infinity": float("-inf")}


def _num(v, default=float("nan")) -> float:
    """Record field -> float, tolerating the json_safe string coercions
    and anything else hostile (a diagnostic tool must not crash on the
    pathological runs it exists to inspect)."""
    if isinstance(v, str):
        v = _NONFINITE.get(v, v)
    try:
        return float(v)
    except (TypeError, ValueError):
        return default


def load_records(path: str) -> list:
    """JSONL file -> list of record dicts (blank lines skipped)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def load_snapshot(path: str):
    """Registry-snapshot JSON file -> dict, or None if the file is a
    JSONL record stream (record streams have an ``event`` key per line;
    snapshot files never do).  Classifies from the FIRST line alone —
    a metrics JSONL can be hundreds of MB and every line of it parses,
    so only a multi-line pretty-printed document (whose first line is
    not valid JSON) pays a whole-file parse."""
    with open(path) as f:
        first = f.readline().strip()
        if first:
            try:
                doc = json.loads(first)
            except ValueError:
                pass  # pretty-printed JSON: fall through to a full parse
            else:
                if not isinstance(doc, dict) or "event" in doc:
                    return None  # a JSONL record stream
                # single-line dict: snapshot iff nothing follows it
                return None if f.read().strip() else doc
    try:
        with open(path) as f:
            doc = json.load(f)
    except ValueError:
        return None
    return doc if isinstance(doc, dict) and "event" not in doc else None


def _sparkline(values) -> str:
    """Tiny unicode bar chart — the throughput timeline at a glance."""
    vals = [_num(v, 0.0) for v in values]
    vals = [0.0 if math.isnan(v) or math.isinf(v) else v for v in vals]
    if not vals:
        return ""
    hi = max(vals)
    if hi <= 0:
        return _BLOCKS[0] * len(vals)
    return "".join(_BLOCKS[min(8, int(round(v / hi * 8)))] for v in vals)


def _median(sorted_vals: list) -> float:
    """True median of a pre-sorted list (even length averages the middle
    pair — the upper-element shortcut overstates small samples)."""
    n = len(sorted_vals)
    if not n:
        return 0.0
    if n % 2:
        return sorted_vals[n // 2]
    return (sorted_vals[n // 2 - 1] + sorted_vals[n // 2]) / 2.0


def _fmt_seconds(s: float) -> str:
    if s >= 1.0:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.1f}ms"
    return f"{s * 1e6:.0f}µs"


def _epoch_table(epochs: list) -> list:
    lines = ["== Per-epoch ==",
             f"{'epoch':>5}  {'trainer':<22} {'mean_loss':>10}  "
             f"{'seconds':>8}  {'samples/sec':>12}"]
    for r in epochs:
        loss = _num(r.get("mean_loss"))
        rate = _num(r.get("samples_per_sec"), 0.0)
        rate_s = f"{rate:>12,.0f}" if math.isfinite(rate) else f"{rate:>12}"
        lines.append(
            f"{r.get('epoch', '?'):>5}  {r.get('trainer', '?'):<22} "
            f"{loss:>10.4f}  "
            f"{_num(r.get('epoch_seconds'), 0.0):>8.2f}  " + rate_s)
    return lines


def _staleness_lines(hist: dict) -> list:
    lines = ["== Staleness distribution =="]
    count = hist.get("count", 0)
    if not count:
        return lines + ["(no staleness observations)"]
    lines.append(f"commits: {count}   mean: "
                 f"{hist['sum'] / count:.2f}   p50: "
                 f"{snapshot_quantile(hist, 0.5):.1f}   p90: "
                 f"{snapshot_quantile(hist, 0.9):.1f}   p99: "
                 f"{snapshot_quantile(hist, 0.99):.1f}")
    bounds = list(hist["bounds"]) + [float("inf")]
    width = max(1, max(hist["counts"]))
    for bound, c in zip(bounds, hist["counts"]):
        if c:
            label = f"<= {bound:g}" if bound != float("inf") \
                else f"> {bounds[-2]:g}"
            bar = "#" * max(1, round(c / width * 40))
            lines.append(f"{label:>10}  {c:>8}  {bar}")
    return lines


def _wire_direction_lines(stats: dict) -> list:
    """Direction-tagged wire byte split (ISSUE 12): UP (commits/requests)
    vs DOWN (pulled centers) next to the codec accounting, so DOWN
    savings are directly observable.  Empty on pre-split snapshots."""
    up = stats.get("ps.wire.bytes_up", {}).get("value", 0)
    dn = stats.get("ps.wire.bytes_down", {}).get("value", 0)
    if not up and not dn:
        return []
    shm = stats.get("net.bytes_shm", {}).get("value", 0)
    line = f"wire bytes: {up:,.0f} up / {dn:,.0f} down"
    if shm:
        line += f"   ({shm:,.0f} via shared memory)"
    return [line]


def _codec_lines(stats: dict) -> list:
    """Codec accounting from a registry snapshot: bytes saved,
    compression ratio, encode/decode latency per direction — UP commit
    codecs (ISSUE 4) and DOWN reference-residual pulls with the adaptive
    switch trail (ISSUE 12) — plus the up/down wire byte split."""
    lines = []
    raw = stats.get("ps.codec.bytes_raw", {}).get("value", 0)
    enc = stats.get("ps.codec.bytes_encoded", {}).get("value", 0)
    if enc:
        saved = stats.get("ps.codec.bytes_saved", {}).get("value", 0)
        lines += ["== Commit codec (UP) ==",
                  f"bytes saved: {saved:,.0f}   compression: "
                  f"{raw / enc:.2f}x "
                  f"({raw:,.0f} raw -> {enc:,.0f} encoded)"]
        for key, label in (("ps.codec.encode_seconds", "encode"),
                           ("ps.codec.decode_seconds", "decode")):
            h = stats.get(key)
            if h and h.get("count"):
                lines.append(f"{label:>12}: n={h['count']} mean "
                             f"{_fmt_seconds(h['sum'] / h['count'])}  p99 "
                             f"{_fmt_seconds(snapshot_quantile(h, 0.99))}")
    draw = stats.get("ps.down.bytes_raw", {}).get("value", 0)
    denc = stats.get("ps.down.bytes_encoded", {}).get("value", 0)
    if denc:
        lines += ["== Pull codec (DOWN, reference-residual) ==",
                  f"bytes saved: "
                  f"{stats.get('ps.down.bytes_saved', {}).get('value', 0):,.0f}"
                  f"   compression: {draw / denc:.2f}x "
                  f"({draw:,.0f} raw -> {denc:,.0f} encoded)"]
        detail = []
        for key, label in (("ps.down.resyncs", "resyncs"),
                           ("ps.down.resyncs_served", "resyncs served"),
                           ("ps.codec.switches", "codec switches")):
            v = stats.get(key, {}).get("value")
            if v:
                detail.append(f"{label}: {v:,.0f}")
        epoch = stats.get("ps.down.ref_epoch", {}).get("value")
        if epoch is not None:
            detail.append(f"ref epoch: {epoch:g}")
        if detail:
            lines.append("   ".join(detail))
        for key, label in (("ps.down.encode_seconds", "encode"),
                           ("ps.down.decode_seconds", "decode")):
            h = stats.get(key)
            if h and h.get("count"):
                lines.append(f"{label:>12}: n={h['count']} mean "
                             f"{_fmt_seconds(h['sum'] / h['count'])}  p99 "
                             f"{_fmt_seconds(snapshot_quantile(h, 0.99))}")
    # the direction split renders even codec-free (raw + shm) runs —
    # the counters are always tagged once both ends are current
    wire = _wire_direction_lines(stats)
    if wire and not lines:
        lines.append("== Wire directions ==")
    lines.extend(wire)
    return lines


def _stream_lines(stats: dict) -> list:
    """Pull streaming (ISSUE 15): streamed-pull and chunk counters plus —
    on client-side registries — the overlap accounting (how much of each
    fresh pull's wall time hid behind compute) and chunk-size quantiles.
    Empty on pre-streaming snapshots."""
    streams = stats.get("ps.pull.streams", {}).get("value", 0)
    hidden = stats.get("ps.pull.hidden_seconds")
    if not streams and not (hidden and hidden.get("count")):
        return []
    lines = ["== Pull streaming =="]
    chunks = stats.get("ps.pull.stream_chunks", {}).get("value", 0)
    line = f"streamed pulls: {streams:,.0f}   chunks: {chunks:,.0f}"
    if streams:
        line += f"   chunks/pull: {chunks / streams:.1f}"
    frac = stats.get("ps.pull.overlap_fraction", {}).get("value")
    if frac is not None:
        line += f"   overlap: {100 * _num(frac, 0.0):.0f}% hidden"
    lines.append(line)
    h = stats.get("ps.pull.chunk_bytes")
    if h and h.get("count"):
        lines.append(
            f"{'chunk bytes':>12}: n={h['count']} "
            f"p50 {snapshot_quantile(h, 0.5):,.0f}  "
            f"p99 {snapshot_quantile(h, 0.99):,.0f}")
    if hidden and hidden.get("count"):
        lines.append(
            f"{'hidden':>12}: n={hidden['count']} mean "
            f"{_fmt_seconds(hidden['sum'] / hidden['count'])}  p99 "
            f"{_fmt_seconds(snapshot_quantile(hidden, 0.99))} per pull")
    downshifts = stats.get("ps.link.downshifts", {}).get("value")
    if downshifts:
        lines.append(f"{'downshifts':>12}: {downshifts:,.0f} "
                     "(link-degradation codec downshifts)")
    return lines


def _link_lines(snap: dict) -> list:
    """Link-quality table (ISSUE 15): per-worker link RTT EWMAs (shipped
    on the commit RPC) next to the codec-downshift trail — the numbers
    that tell a wire-degraded worker from a compute-stuck one.  Empty
    when no worker reported a link RTT."""
    link = (snap or {}).get("link_rtt_s") or {}
    if not link:
        return []

    def _wkey(w):
        try:
            return (0, int(w))
        except (TypeError, ValueError):
            return (1, str(w))

    downs = snap.get("link_downshifts") or {}
    lines = ["== Link quality ==",
             f"{'worker':>6}  {'link RTT EWMA':>14}  downshifts"]
    for w in sorted(link, key=_wkey):
        lines.append(f"{w:>6}  "
                     f"{_fmt_seconds(_num(link[w], 0.0)):>14}  "
                     f"{_num(downs.get(w), 0):>10,.0f}")
    return lines


def _timeline_lines(spans: list) -> list:
    """Per-worker cross-process timeline (ISSUE 5): worker ``ps.commit``
    spans matched to the server ``ps.apply`` spans that adopted their
    span id as ``parent_span`` — the trace the PS wire carried."""
    commits = [s for s in spans if s.get("name") == "ps.commit"]
    applies = [s for s in spans if s.get("name") == "ps.apply"]
    if not commits and not applies:
        return []
    apply_by_parent = {a["parent_span"]: a for a in applies
                       if a.get("parent_span") is not None}
    lines = ["== Cross-process timeline (per worker) ==",
             f"{'worker':>6}  {'trace':<10} {'commits':>8}  "
             f"{'applies':>8}  {'commit p50':>10}  {'apply p50':>10}  "
             "commit seconds"]
    by_trace: dict = {}
    for c in commits:
        by_trace.setdefault(c.get("trace_id", "?"), []).append(c)
    linked_total = 0
    for trace in sorted(by_trace):
        group = sorted(by_trace[trace], key=lambda s: _num(s.get("ts"), 0.0))
        linked = [apply_by_parent[c["span_id"]] for c in group
                  if c.get("span_id") in apply_by_parent]
        linked_total += len(linked)
        secs = sorted(_num(c.get("seconds"), 0.0) for c in group)
        apply_secs = sorted(_num(a.get("seconds"), 0.0) for a in linked)
        p50 = _median(secs)
        a50 = _median(apply_secs)
        lines.append(
            f"{group[0].get('worker', '?'):>6}  {trace:<10} "
            f"{len(group):>8}  {len(linked):>8}  {_fmt_seconds(p50):>10}  "
            f"{(_fmt_seconds(a50) if apply_secs else '-'):>10}  "
            f"[{_sparkline([_num(c.get('seconds'), 0.0) for c in group])}]")
    orphans = len(applies) - linked_total
    if orphans > 0:
        lines.append(f"({orphans} apply span(s) without a linked commit "
                     "span — v1 peers or spans outside this stream)")
    return lines


def _straggler_lines(snap: dict, source: str) -> list:
    """Straggler state — live (``stats`` RPC reply) or replayed from the
    recorded heartbeat gaps (``obs.stragglers.detect_from_heartbeats``)."""
    ewma = (snap or {}).get("gap_ewma_s") or {}
    if not ewma:
        return []
    def _wkey(w):  # numeric-aware: '10' sorts after '2', not before
        try:
            return (0, int(w))
        except (TypeError, ValueError):
            return (1, str(w))

    flagged = set(str(w) for w in snap.get("stragglers", []))
    peer = snap.get("peer_median_s") or {}
    floor = _num(snap.get("min_gap_s"), 0.0)
    lines = [f"== Stragglers ({source}) ==",
             f"threshold: {snap.get('k', '?')}x leave-one-out peer median"
             + (f" (floored at {_fmt_seconds(floor)})" if floor else "")
             + "   flagged: "
             + (str(sorted(flagged, key=_wkey)) if flagged else "none")]
    for w in sorted(ewma, key=lambda k: -_num(ewma[k], 0.0)):
        mark = "  << STRAGGLER" if w in flagged else ""
        pm = _num(peer.get(w), 0.0)
        lines.append(f"  worker {w:>3}  gap EWMA "
                     f"{_fmt_seconds(_num(ewma[w], 0.0)):>8}  "
                     f"(peers {_fmt_seconds(pm)}){mark}")
    return lines


def _fleet_lines(fleet: dict, stats: dict) -> list:
    """Per-worker fleet liveness (ISSUE 9): last-seen age, generation,
    eviction/respawn/join/tombstone tallies — the live view that makes a
    stalled or self-healing fleet visible while it runs (the old
    end-of-run-only retry path had no such window)."""
    fleet = fleet or {}
    ages = fleet.get("last_seen_age_s") or {}
    gens = fleet.get("generations") or {}
    ev = fleet.get("evictions_by_worker") or {}
    rs = fleet.get("respawns_by_worker") or {}
    jn = fleet.get("joins_by_worker") or {}
    tb = fleet.get("tombstoned_by_worker") or {}
    workers = sorted({int(w) for d in (ages, gens, ev, rs, jn, tb)
                      for w in d}, key=int)
    if not workers:
        return []

    def _cval(name):
        return stats.get(name, {}).get("value", 0)

    def _get(d, w):
        return d.get(w, d.get(str(w), 0))

    lines = ["== Fleet liveness ==",
             f"evictions {_cval('ps.evictions'):.0f}   "
             f"respawns {_cval('ps.respawns'):.0f}   "
             f"joins {_cval('ps.joins'):.0f}   "
             f"tombstoned commits {_cval('ps.commits_tombstoned'):.0f}",
             f"{'worker':>6}  {'last seen':>10}  {'gen':>4}  "
             f"{'evict':>5}  {'respawn':>7}  {'join':>4}  {'tombst':>6}"]
    for w in workers:
        age = _get(ages, w)
        age_s = f"{_num(age, 0.0):.1f}s ago" if w in ages or str(w) in ages \
            else "never"
        lines.append(f"{w:>6}  {age_s:>10}  {_get(gens, w):>4}  "
                     f"{_get(ev, w):>5}  {_get(rs, w):>7}  "
                     f"{_get(jn, w):>4}  {_get(tb, w):>6}")
    return lines


def _top_spans(spans: list, top: int = 10) -> list:
    lines = ["== Top spans by cumulative time ==",
             f"{'span':<24} {'count':>6}  {'total':>10}  {'mean':>10}"]
    agg: dict = {}
    for s in spans:
        name = s.get("name", "?")
        tot, n = agg.get(name, (0.0, 0))
        agg[name] = (tot + float(s.get("seconds", 0.0)), n + 1)
    for name, (tot, n) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]:
        lines.append(f"{name:<24} {n:>6}  {_fmt_seconds(tot):>10}  "
                     f"{_fmt_seconds(tot / n):>10}")
    return lines


def _heartbeat_lines(heartbeats: list) -> list:
    by_worker: dict = {}
    for h in heartbeats:
        w = h.get("worker_id", h.get("worker", "?"))
        cur = by_worker.setdefault(w, {"n": 0, "last_window": -1,
                                       "last_ts": 0.0})
        cur["n"] += 1
        cur["last_window"] = max(cur["last_window"], h.get("window", -1))
        cur["last_ts"] = max(cur["last_ts"], h.get("ts", 0.0))
    lines = ["== Worker heartbeats ==",
             f"{'worker':>6}  {'beats':>6}  {'last window':>12}"]
    for w in sorted(by_worker):
        cur = by_worker[w]
        lines.append(f"{w:>6}  {cur['n']:>6}  {cur['last_window']:>12}")
    return lines


def summarize(records: list) -> str:
    """Full-run summary from a JSONL record list — the file mode's body."""
    epochs = [r for r in records if r.get("event") == "epoch"]
    spans = [r for r in records if r.get("event") == "span"]
    heartbeats = [r for r in records if r.get("event") == "heartbeat"]
    ps_stats = [r for r in records if r.get("event") == "ps_stats"]

    sections = []
    if epochs:
        sections.append(_epoch_table(epochs))
        rates = [_num(r.get("samples_per_sec"), 0.0) for r in epochs]
        finite = [r for r in rates if math.isfinite(r)] or [0.0]
        sections.append(["== Throughput timeline ==",
                         f"[{_sparkline(rates)}]  "
                         f"min {min(finite):,.0f}  max {max(finite):,.0f} "
                         f"samples/sec over {len(rates)} epochs"])
    else:
        sections.append(["== Per-epoch ==", "(no epoch records)"])

    # staleness: prefer the final ps_stats registry snapshot (complete,
    # bounded-memory histogram) — the PS path's defining distribution
    stats = ps_stats[-1].get("stats", {}) if ps_stats else {}
    if "ps.staleness" in stats:
        sections.append(_staleness_lines(stats["ps.staleness"]))
        per_worker = {k: v for k, v in stats.items()
                      if k.startswith("ps.staleness.worker")}
        if per_worker:
            lines = ["== Per-worker staleness (mean) =="]
            for k in sorted(per_worker):
                h = per_worker[k]
                mean = h["sum"] / h["count"] if h["count"] else 0.0
                lines.append(f"{k.rsplit('worker', 1)[1]:>6}  "
                             f"n={h['count']:<6}  mean {mean:.2f}")
            sections.append(lines)
    if ps_stats:
        last = ps_stats[-1]
        lines = ["== Parameter server =="]
        lines.append(f"updates: {last.get('num_updates')}   "
                     f"commits_by_worker: {last.get('commits_by_worker')}")
        for key, label in (("ps.commits", "commits"), ("ps.pulls", "pulls"),
                           ("ps.pulls_unchanged", "unchanged"),
                           ("ps.pull_cache_hits", "cache_hits"),
                           ("ps.commits_dropped", "dropped"),
                           ("net.bytes_sent", "bytes_sent"),
                           ("net.bytes_recv", "bytes_recv"),
                           ("ps.wire.bytes_up", "bytes_up"),
                           ("ps.wire.bytes_down", "bytes_down"),
                           ("net.bytes_shm", "bytes_shm")):
            if key in stats:
                lines.append(f"{label:>12}: {stats[key]['value']:,.0f}")
        if "ps.apply_seconds" in stats:
            h = stats["ps.apply_seconds"]
            if h["count"]:
                lines.append(
                    f"{'apply':>12}: mean "
                    f"{_fmt_seconds(h['sum'] / h['count'])}  p99 "
                    f"{_fmt_seconds(snapshot_quantile(h, 0.99))}")
        sections.append(lines)
        sections.append(_codec_lines(stats))
        sections.append(_stream_lines(stats))
    if heartbeats:
        # replay the recorded gaps through the same detector the live PS
        # runs — post-mortem straggler analysis (ISSUE 5); the replayed
        # snapshot also carries the heartbeat-borne link RTTs (ISSUE 15)
        replayed = detect_from_heartbeats(records)
        sections.append(_straggler_lines(replayed,
                                         "replayed from heartbeats"))
        sections.append(_link_lines(replayed))
    if spans:
        sections.append(_timeline_lines(spans))
        sections.append(_top_spans(spans))
    if heartbeats:
        sections.append(_heartbeat_lines(heartbeats))

    return "\n".join("\n".join(s) for s in sections if s)


def _host_lines(stats: dict) -> list:
    """Host-side health: the input-pipeline counters
    (``stream.batches`` / prefetch occupancy / the PR 7 producer-leak
    tally) and the profiler's memory watermarks (``mem.*`` gauges).
    Neither family had a panel before ISSUE 18 — a stalled producer or a
    climbing live-bytes watermark was invisible unless someone read the
    raw instrument table."""
    batches = stats.get("stream.batches", {}).get("value", 0)
    stall = stats.get("stream.stall_seconds")
    live = stats.get("mem.live_bytes", {}).get("value")
    if not batches and not (stall and stall.get("count")) \
            and live is None:
        return []
    lines = ["== Host (input pipeline / memory) =="]
    if batches or (stall and stall.get("count")):
        line = f"batches: {batches:,.0f}"
        occ = stats.get("stream.prefetch_occupancy", {}).get("value")
        if occ is not None:
            line += f"   prefetch occupancy: {_num(occ, 0.0):.1f}"
        if stall and stall.get("count"):
            line += (f"   stalls: n={stall['count']} p99 "
                     f"{_fmt_seconds(snapshot_quantile(stall, 0.99))}")
        leaks = stats.get("stream.producer_leaks", {}).get("value", 0)
        if leaks:
            line += f"   PRODUCER LEAKS: {leaks:,.0f}"
        lines.append(line)
    if live is not None:
        mb = 1024.0 * 1024.0
        line = (f"host live: {_num(live, 0.0) / mb:,.1f} MiB "
                f"({stats.get('mem.live_arrays', {}).get('value', 0):,.0f} "
                f"arrays)")
        peak = stats.get("mem.peak_live_bytes", {}).get("value")
        if peak is not None:
            line += f"   peak: {_num(peak, 0.0) / mb:,.1f} MiB"
        dev = stats.get("mem.device_peak_bytes", {}).get("value")
        if dev is not None:
            line += f"   device peak: {_num(dev, 0.0) / mb:,.1f} MiB"
        lines.append(line)
    return lines


def _instrument_lines(stats: dict) -> list:
    """One line per instrument in a registry snapshot."""
    lines = []
    for name in sorted(stats):
        s = stats[name]
        if s["type"] == "histogram":
            if s["count"]:
                lines.append(
                    f"{name}: n={s['count']} mean="
                    f"{s['sum'] / s['count']:.4g} "
                    f"p50={snapshot_quantile(s, 0.5):.4g} "
                    f"p99={snapshot_quantile(s, 0.99):.4g}")
            else:
                lines.append(f"{name}: n=0")
        else:
            lines.append(f"{name}: {s['value']:g}")
    return lines


#: registry-snapshot detection shared with the drift gate (obs.drift owns
#: the definition; the alias keeps this module's call sites readable)
_is_registry_snapshot = drift.is_registry_snapshot


def summarize_snapshot(doc: dict) -> str:
    """Summary of a persisted registry-snapshot file: one section per
    component registry, codec accounting surfaced."""
    sections = []
    if isinstance(doc.get("config"), dict):
        sections.append(["== Config ==",
                         "  ".join(f"{k}={v}" for k, v in
                                   sorted(doc["config"].items()))])
    named = {k: v for k, v in doc.items() if _is_registry_snapshot(v)}
    if not named and _is_registry_snapshot(doc):
        named = {"registry": doc}
    for name, snap in sorted(named.items()):
        sections.append([f"== {name} registry =="] + _instrument_lines(snap))
        sections.append(_codec_lines(snap))
        sections.append(_stream_lines(snap))
        sections.append(_host_lines(snap))
        if "serve.router.kv_replications" in snap:
            # drop the leading blank: sections are already newline-joined
            sections.append(_kvfabric_lines(snap)[1:])
    return "\n".join("\n".join(s) for s in sections if s)


def summarize_stats(reply: dict) -> str:
    """Live-poll summary from a ``stats`` RPC reply."""
    stats = reply.get("stats", {})
    lines = [f"== Live PS ({reply.get('server', '?')}, "
             f"{reply.get('num_workers', '?')} workers) ==",
             f"updates: {reply.get('num_updates')}   commits_by_worker: "
             f"{reply.get('commits_by_worker')}"]
    lines.extend(_instrument_lines(stats))
    codec = _codec_lines(stats)
    if codec:
        lines.append("")
        lines.extend(codec)
    stream = _stream_lines(stats)
    if stream:
        lines.append("")
        lines.extend(stream)
    fleet = _fleet_lines(reply.get("fleet") or {}, stats)
    if fleet:
        lines.append("")
        lines.extend(fleet)
    stragglers = _straggler_lines(reply.get("stragglers") or {}, "live")
    if stragglers:
        lines.append("")
        lines.extend(stragglers)
    link = _link_lines(reply.get("stragglers") or {})
    if link:
        lines.append("")
        lines.extend(link)
    if "ps.staleness" in stats:
        lines.append("")
        lines.extend(_staleness_lines(stats["ps.staleness"]))
    return "\n".join(lines)


def poll_stats(host: str, port: int) -> dict:
    from distkeras_tpu.ps.client import PSClient
    with PSClient(host, int(port)) as client:
        return client.stats()


def parse_ps_targets(arg: str) -> list:
    """``--ps`` target(s) -> [(host, port), ...]: a single HOST:PORT, a
    comma-separated shard fleet, or a shard PLAN FILE path (the JSON a
    ``ShardedParameterServer.write_plan`` emits — ISSUE 10)."""
    if os.path.exists(arg):
        with open(arg) as f:
            doc = json.load(f)
        targets = [(s["host"], int(s["port"]))
                   for s in (doc.get("shards") or []) if "host" in s]
        if not targets:
            raise ValueError(f"plan file {arg} carries no shard addresses")
        return targets
    targets = []
    for part in str(arg).split(","):
        host, _, port = part.strip().rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(f"--ps expects HOST:PORT (single, "
                             f"comma-separated fleet, or a plan file), "
                             f"got {part.strip()!r}")
        targets.append((host, int(port)))
    return targets


def summarize_ps_fleet(replies: list) -> str:
    """ONE merged view over a shard fleet's ``stats`` replies (ISSUE 10):
    the consistent merge itself is ``ps.shard``'s ``merge_fleet_stats``
    (one definition, shared with ``ShardedPSClient.stats``); this adds
    the per-shard balance table that makes placement skew visible —
    commits/bytes per shard."""
    from distkeras_tpu.ps.shard.client import merge_fleet_stats
    head = {
        **merge_fleet_stats(replies),
        "server": f"{replies[0].get('server', '?')} "
                  f"×{len(replies)} shards",
        "num_workers": replies[0].get("num_workers", "?"),
        # every shard's detector sees the same gap_s stream; one
        # representative suffices for the merged view
        "stragglers": replies[0].get("stragglers"),
        "fleet": replies[0].get("fleet"),
    }
    lines = [summarize_stats(head)]
    plan = replies[0].get("shard") or {}
    lines += ["", "== Shard balance =="]
    if plan:
        lines.append(f"plan: shards={plan.get('num_shards', '?')}  "
                     f"epoch={plan.get('epoch', '?')}  "
                     f"digest={plan.get('digest', '?')}")
    lines.append(f"{'shard':>5}  {'updates':>8}  {'commits':>8}  "
                 f"{'share':>6}  {'bytes in':>12}  {'bytes out':>12}")
    total = sum(_num(r.get("stats", {}).get("ps.commits", {})
                     .get("value"), 0) for r in replies) or 1.0
    for i, r in enumerate(replies):
        s = r.get("stats", {})
        commits = _num(s.get("ps.commits", {}).get("value"), 0)
        idx = (r.get("shard") or {}).get("index", i)
        lines.append(
            f"{idx:>5}  {_num(r.get('num_updates'), 0):>8,.0f}  "
            f"{commits:>8,.0f}  {100 * commits / total:>5.1f}%  "
            f"{_num(s.get('net.bytes_recv', {}).get('value'), 0):>12,.0f}  "
            f"{_num(s.get('net.bytes_sent', {}).get('value'), 0):>12,.0f}")
    return "\n".join(lines)


#: the serving SLO surface, rendered in this order (ISSUE 7; ISSUE 11
#: adds the warm/cold ttft split and the dispatch-ahead host component)
_SLO_HISTS = (("serve.queue_wait_seconds", "queue wait"),
              ("serve.ttft_seconds", "first token"),
              ("serve.ttft_warm_seconds", "  ttft (warm)"),
              ("serve.ttft_cold_seconds", "  ttft (cold)"),
              ("serve.per_token_seconds", "per token"),
              ("serve.e2e_seconds", "end-to-end"),
              ("serve.step_seconds", "batch step"),
              ("serve.host_seconds", "  host (hidden)"),
              ("serve.join_seconds", "join (prefill)"))

#: draft accept rate below this (with proposals flowing) renders the
#: LOW-ACCEPT alarm: the draft has diverged from the target and the
#: speculative speedup is gone (correctness never depends on it)
_LOW_ACCEPT = 0.25

#: fleet prefix hit rate below this (with lookups flowing, >1 engine)
#: renders the MISROUTED alarm (ISSUE 14): on a shared-prefix workload a
#: correctly affinity-routed fleet holds the single-engine warm baseline
#: (the committed bench's single-engine point), so a rate trailing it
#: means requests are landing on engines that don't hold their prefix
_MISROUTE_RATE = 0.5

#: spill-warm fraction below this (with spill traffic flowing) renders
#: the COLD-SPILL alarm (ISSUE 16): a working KV fabric replicates a
#: hot prefix to its spill target after the FIRST overflow, so repeat
#: overflow should mostly land warm — a trailing fraction means
#: transfers are failing, being budget-skipped, or arriving stale
_COLD_SPILL = 0.5


def _accel_lines(stats: dict) -> list:
    """The ISSUE 11 accelerator panel: prefix-cache hit rate + LRU
    level, draft accept rate + the LOW-ACCEPT alarm.  Metrics are
    pre-created by the engine, so zeros mean 'enabled but idle / off' —
    never 'missing'."""

    def _v(name):
        return stats.get(name, {}).get("value", 0)

    lines = []
    hits, misses = _v("serve.prefix.hits"), _v("serve.prefix.misses")
    looked = hits + misses
    lines.append(
        f"prefix cache: hits {hits:,.0f}  misses {misses:,.0f}"
        + (f"  (hit rate {hits / looked:.0%})" if looked else "")
        + f"  entries {_v('serve.prefix.entries'):,.0f}"
          f"  bytes {_v('serve.prefix.bytes'):,.0f}"
          f"  evictions {_v('serve.prefix.evictions'):,.0f}")
    proposed = _v("serve.spec.proposed")
    rate = _v("serve.spec.accept_rate")
    lines.append(
        f"spec decode: proposed {proposed:,.0f}  accepted "
        f"{_v('serve.spec.accepted'):,.0f}  accept rate {rate:.0%}"
        + ("  << LOW-ACCEPT (draft diverged from target; speculative "
           "speedup lost)"
           if proposed and rate < _LOW_ACCEPT else ""))
    return lines


def _router_lines(stats: dict) -> list:
    """The ISSUE 14 front-door panel (rendered when the polled stats
    carry ``serve.router.*`` — i.e. the target is a ``ServeRouter`` or a
    fleet list that includes one): routing split, failure handling, and
    the fleet promote trail."""

    def _v(name):
        return stats.get(name, {}).get("value", 0)

    lines = ["", "== Router =="]
    lines.append(
        f"routed: {_v('serve.router.requests'):,.0f}  (affinity "
        f"{_v('serve.router.affinity_hits'):,.0f}, least-loaded "
        f"{_v('serve.router.affinity_misses'):,.0f}, decays "
        f"{_v('serve.router.affinity_decays'):,.0f})   engines alive: "
        f"{_v('serve.router.engines_alive'):,.0f}")
    lines.append(
        f"failures: evictions {_v('serve.router.evictions'):,.0f}  "
        f"requeues {_v('serve.router.requeues'):,.0f}  rejoins "
        f"{_v('serve.router.rejoins'):,.0f}   promotes "
        f"{_v('serve.router.promotes'):,.0f}  (failed "
        f"{_v('serve.router.promote_failures'):,.0f}, rolled forward "
        f"{_v('serve.router.promote_rollforwards'):,.0f})")
    return lines


def _kvfabric_lines(stats: dict) -> list:
    """The ISSUE 16 fleet-KV-fabric panel (rendered when the stats
    carry ``serve.router.kv_*`` — a fabric-enabled ``ServeRouter``):
    replication/migration trail, push bytes, stale refusals, and the
    warm-vs-cold spill TTFT split with the COLD-SPILL alarm."""

    def _v(name):
        return _num(stats.get(name, {}).get("value"), 0)

    lines = ["", "== KV fabric =="]
    lines.append(
        f"transfers: replications {_v('serve.router.kv_replications'):,.0f}"
        f"  migrations {_v('serve.router.kv_migrations'):,.0f}  "
        f"push bytes {_v('serve.router.kv_push_bytes'):,.0f}  "
        f"refused stale {_v('serve.router.kv_refused_stale'):,.0f}  "
        f"secondary hits "
        f"{_v('serve.router.affinity_secondary_hits'):,.0f}")
    warm = stats.get("serve.router.ttft_spill_warm_seconds") or {}
    cold = stats.get("serve.router.ttft_spill_cold_seconds") or {}
    n_warm = int(warm.get("count") or 0)
    n_cold = int(cold.get("count") or 0)
    for label, h, n in (("spill ttft warm", warm, n_warm),
                        ("spill ttft cold", cold, n_cold)):
        if not n:
            lines.append(f"{label}: n=0")
            continue
        lines.append(
            f"{label}: n={n}  mean "
            f"{_fmt_seconds(h['sum'] / n)}  p50 "
            f"{_fmt_seconds(snapshot_quantile(h, 0.5))}  p99 "
            f"{_fmt_seconds(snapshot_quantile(h, 0.99))}")
    if n_warm + n_cold:
        frac = n_warm / (n_warm + n_cold)
        lines.append(
            f"spill warm fraction: {frac:.0%}"
            + (f"  << COLD-SPILL (spill traffic is mostly cold-"
               f"prefilling; KV replication is not landing — check "
               f"kv_refused_stale / the kv_fabric_mb budget)"
               if frac < _COLD_SPILL else ""))
    return lines


def _engine_balance_lines(engines: list, stats: dict) -> list:
    """Per-engine balance table (ISSUE 14): request/occupancy/prefix-hit
    share per engine, plus the MISROUTED alarm when the fleet's prefix
    hit rate trails the single-engine baseline."""
    lines = ["", "== Engine balance ==",
             f"{'engine':<22} {'alive':<6} {'reqs':>7} {'share':>6}  "
             f"{'active':>6} {'queue':>5}  {'hit rate':>8}"]
    total = sum(_num(e.get("requests"), 0) for e in engines) or 1.0
    for e in engines:
        hits = _num(e.get("prefix_hits"), 0)
        misses = _num(e.get("prefix_misses"), 0)
        looked = hits + misses
        reqs = _num(e.get("requests"), 0)
        lines.append(
            f"{str(e.get('addr', '?')):<22} "
            f"{('yes' if e.get('alive', True) else 'NO'):<6} "
            f"{reqs:>7,.0f} {100 * reqs / total:>5.1f}%  "
            f"{_num(e.get('active_slots'), 0):>6,.0f} "
            f"{_num(e.get('queue_depth'), 0):>5,.0f}  "
            + (f"{hits / looked:>8.0%}" if looked else f"{'-':>8}"))
    hits = _num(stats.get("serve.prefix.hits", {}).get("value"), 0)
    misses = _num(stats.get("serve.prefix.misses", {}).get("value"), 0)
    looked = hits + misses
    if len(engines) > 1 and looked and hits / looked < _MISROUTE_RATE:
        lines.append(
            f"<< MISROUTED (fleet prefix hit rate {hits / looked:.0%} "
            f"trails the single-engine warm baseline; affinity routing "
            f"is not landing requests on the engines that hold their "
            f"prefixes)")
    return lines


def merge_serve_replies(replies: list) -> dict:
    """N per-engine ``stats`` replies -> ONE router-reply-shaped view
    (ISSUE 14): merged registry via ``Registry.merge_snapshots`` (the
    shard-fleet primitive), summed occupancy, and a synthesized
    per-engine balance list — so ``--serve a:1,b:2,c:3`` renders like a
    ``ServeRouter`` poll."""
    from distkeras_tpu.obs import Registry
    merged = Registry.merge_snapshots(*[r.get("stats", {})
                                        for r in replies])
    engines = []
    for i, r in enumerate(replies):
        s = r.get("stats", {})

        def _v(name):
            return s.get(name, {}).get("value", 0)

        engines.append({"addr": r.get("addr", f"engine {i}"),
                        "alive": True,
                        "requests": _v("serve.requests"),
                        "completed": _v("serve.completed"),
                        "queue_depth": r.get("queue_depth"),
                        "active_slots": r.get("active_slots"),
                        "slots": r.get("slots"),
                        "prefix_hits": _v("serve.prefix.hits"),
                        "prefix_misses": _v("serve.prefix.misses")})
    return {"stats": merged,
            "server": f"{replies[0].get('server', '?')} "
                      f"×{len(replies)} engines",
            "model": replies[0].get("model"),
            "seq_len": replies[0].get("seq_len"),
            "prefill_buckets": replies[0].get("prefill_buckets"),
            "slots": sum(int(r.get("slots", 0) or 0) for r in replies),
            "queue_depth": sum(int(r.get("queue_depth", 0) or 0)
                               for r in replies),
            "active_slots": sum(int(r.get("active_slots", 0) or 0)
                                for r in replies),
            "draining": any(r.get("draining") for r in replies),
            "engines": engines}


def summarize_serve(reply: dict) -> str:
    """Live-poll summary from a decode service's ``stats`` RPC reply:
    SLO latency table, admission counters, occupancy, retrace health.
    A fleet-shaped reply (a ``ServeRouter`` poll, or
    :func:`merge_serve_replies` over an engine list) additionally
    renders the router panel and the per-engine balance table with the
    MISROUTED alarm (ISSUE 14)."""
    stats = reply.get("stats", {})

    def _cval(name):
        return stats.get(name, {}).get("value", 0)

    lines = [f"== Live decode service ({reply.get('server', '?')}, "
             f"model {reply.get('model', '?')}, "
             f"{reply.get('slots', '?')} slots) ==",
             f"buckets: {reply.get('prefill_buckets', '?')}   "
             f"seq_len: {reply.get('seq_len', '?')}   "
             f"queue: {reply.get('queue_depth', '?')}   active: "
             f"{reply.get('active_slots', '?')}   draining: "
             f"{reply.get('draining', '?')}",
             "", "== SLO latency ==",
             f"{'metric':<16} {'n':>8}  {'mean':>9}  {'p50':>9}  "
             f"{'p99':>9}"]
    for key, label in _SLO_HISTS:
        h = stats.get(key)
        if not h or not h.get("count"):
            lines.append(f"{label:<16} {0:>8}")
            continue
        lines.append(
            f"{label:<16} {h['count']:>8}  "
            f"{_fmt_seconds(h['sum'] / h['count']):>9}  "
            f"{_fmt_seconds(snapshot_quantile(h, 0.5)):>9}  "
            f"{_fmt_seconds(snapshot_quantile(h, 0.99)):>9}")
    lines += ["", "== Admission =="]
    lines.append(f"requests: {_cval('serve.requests'):,.0f}   admitted: "
                 f"{_cval('serve.admitted'):,.0f}   completed: "
                 f"{_cval('serve.completed'):,.0f}   tokens_out: "
                 f"{_cval('serve.tokens_out'):,.0f}")
    lines.append(f"rejected: {_cval('serve.rejected'):,.0f}  "
                 f"(queue_full {_cval('serve.rejected_queue_full'):,.0f}, "
                 f"draining {_cval('serve.rejected_draining'):,.0f}, "
                 f"aborted {_cval('serve.rejected_aborted'):,.0f})")
    retraces = _cval("jit.retraces")
    lines.append(f"jit: compiles {_cval('jit.compiles'):,.0f}  retraces "
                 f"{retraces:,.0f}"
                 + ("  << RETRACING (bucket instability)"
                    if retraces else ""))
    lines += ["", "== Accelerators =="]
    lines.extend(_accel_lines(stats))
    if "serve.router.requests" in stats:
        lines.extend(_router_lines(stats))
        if "serve.router.kv_replications" in stats:
            lines.extend(_kvfabric_lines(stats))
    engines = reply.get("engines")
    if engines:
        lines.extend(_engine_balance_lines(engines, stats))
    lines += ["", "== Instruments =="]
    lines.extend(_instrument_lines(stats))
    return "\n".join(lines)


def poll_serve(host: str, port: int) -> dict:
    from distkeras_tpu.serve import ServeClient
    with ServeClient(host, int(port)) as client:
        reply = client.stats()
    if isinstance(reply, dict):
        reply.setdefault("addr", f"{host}:{port}")
    return reply


def parse_serve_targets(arg: str) -> list:
    """``--serve`` target(s) -> [(host, port), ...]: a single HOST:PORT
    (an engine or a ``ServeRouter``) or a comma-separated engine fleet
    (ISSUE 14, like ``--ps`` shard fleets)."""
    targets = []
    for part in str(arg).split(","):
        host, _, port = part.strip().rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(f"--serve expects HOST:PORT (single or "
                             f"comma-separated fleet), got "
                             f"{part.strip()!r}")
        targets.append((host, int(port)))
    return targets


#: the continual-training health surface, rendered in this order (ISSUE 8)
_CONTINUAL_HISTS = (("continual.loss", "loss"),
                    ("continual.window_seconds", "window wall"),
                    ("continual.stream_lag_seconds", "stream lag"))


def summarize_continual(stats: dict, verdicts=None,
                        source: str = "live") -> str:
    """Continual-loop summary (ISSUE 8): deploy history, window-verdict
    tally (with the per-interval table when the decision log is
    available — a persisted document carries it under ``verdicts``),
    training-health histograms, and the two alarms: DRIFT-DIRTY (the
    current window classifies step/trend — deploys blocked) and
    RETRACING (the serve health check's sentinel rule)."""

    def _cval(name):
        return stats.get(name, {}).get("value", 0)

    lines = [f"== Continual training ({source}) ==",
             f"intervals: {_cval('continual.intervals'):,.0f}   windows: "
             f"{_cval('continual.windows'):,.0f}   samples: "
             f"{_cval('continual.samples'):,.0f}   checkpoints: "
             f"{_cval('continual.checkpoints'):,.0f}"]
    dirty_now = _cval("continual.window_dirty") > 0
    lines.append(
        f"deploys: {_cval('continual.deploys'):,.0f}   rejected: "
        f"{_cval('continual.deploys_rejected'):,.0f}  (dirty "
        f"{_cval('continual.rejected_dirty'):,.0f}, warmup "
        f"{_cval('continual.rejected_warmup'):,.0f})   errors: "
        f"{_cval('continual.deploy_errors'):,.0f}"
        + ("  << DRIFT-DIRTY (deploys blocked)" if dirty_now else ""))
    lines.append(f"verdicts: stable {_cval('continual.verdicts_stable'):,.0f}"
                 f"  step {_cval('continual.verdicts_step'):,.0f}"
                 f"  trend {_cval('continual.verdicts_trend'):,.0f}")
    retraces = _cval("jit.retraces")
    lines.append(f"jit: compiles {_cval('jit.compiles'):,.0f}  retraces "
                 f"{retraces:,.0f}"
                 + ("  << RETRACING (shape instability)" if retraces
                    else ""))
    lines += ["", "== Training health ==",
              f"{'metric':<14} {'n':>8}  {'mean':>9}  {'p50':>9}  "
              f"{'p99':>9}"]
    for key, label in _CONTINUAL_HISTS:
        h = stats.get(key)
        if not h or not h.get("count"):
            lines.append(f"{label:<14} {0:>8}")
            continue
        if key == "continual.loss":  # loss is unitless, not seconds
            lines.append(f"{label:<14} {h['count']:>8}  "
                         f"{h['sum'] / h['count']:>9.4f}  "
                         f"{snapshot_quantile(h, 0.5):>9.4f}  "
                         f"{snapshot_quantile(h, 0.99):>9.4f}")
        else:
            lines.append(
                f"{label:<14} {h['count']:>8}  "
                f"{_fmt_seconds(h['sum'] / h['count']):>9}  "
                f"{_fmt_seconds(snapshot_quantile(h, 0.5)):>9}  "
                f"{_fmt_seconds(snapshot_quantile(h, 0.99)):>9}")
    if verdicts:
        lines += ["", "== Window verdicts ==",
                  f"{'interval':>8}  {'kind':<7} {'deployed':<9} reason"]
        for e in verdicts:
            mark = "DEPLOYED" if e.get("deployed") else \
                ("accepted" if e.get("deploy") else "-")
            lines.append(f"{e.get('interval', '?'):>8}  "
                         f"{e.get('kind', '?'):<7} {mark:<9} "
                         f"{e.get('reason', '')}")
    serving = [k for k in stats if k.startswith("serve.")]
    if serving:
        lines += ["", "== Serving (same process) =="]
        lines.append(f"promotions: {_cval('serve.promotions'):,.0f}   "
                     f"completed: {_cval('serve.completed'):,.0f}   "
                     f"rejected: {_cval('serve.rejected'):,.0f}")
    return "\n".join(lines)


def run_continual(target: str) -> int:
    """``--continual`` body: live HOST:PORT (the decode service's
    ``stats`` RPC — a trainer sharing the engine's registry shows up in
    the same snapshot) or a persisted registry-snapshot document."""
    host, _, port = target.rpartition(":")
    if host and port.isdigit():
        reply = poll_serve(host, int(port))
        emit(summarize_continual(reply.get("stats", {}),
                                 source=f"live {target}"))
        return 0
    try:
        doc = load_snapshot(target)
    except OSError as e:
        emit(f"obsview --continual: cannot read {target}: {e}", err=True)
        return 2
    if doc is None:
        emit(f"obsview --continual: {target} is neither HOST:PORT nor a "
             "registry-snapshot file", err=True)
        return 2
    regs = list(drift.named_registries(doc).values())
    if not regs:
        emit(f"obsview --continual: no registry snapshot in {target}",
             err=True)
        return 2
    from distkeras_tpu.obs import Registry
    stats = regs[0] if len(regs) == 1 else Registry.merge_snapshots(*regs)
    emit(summarize_continual(stats, verdicts=doc.get("verdicts"),
                             source=os.path.basename(target)))
    return 0


def summarize_scenario(doc: dict, source: str) -> str:
    """Scenario-harness panel (ISSUE 17) over a persisted document
    with a ``row.scenarios`` table: one per-phase SLO table + scale-event
    trail per scenario, the open-loop accounting identity, and the
    SLO-MISS alarm for any phase whose attainment landed under the
    committed target."""
    row = doc.get("row", {})
    slo = row.get("slo", {})
    target = float(slo.get("attainment", 0.95) or 0.95)
    lines = [f"== Scenario harness ({source}) ==",
             f"SLO: ttft<={slo.get('ttft_s', '?')}s  "
             f"e2e<={slo.get('e2e_s', '?')}s  "
             f"target attainment {target:.2f}"]
    misses = []
    for name, s in (row.get("scenarios") or {}).items():
        counts = s.get("counts", {})
        lines += ["",
                  f"-- {name} (seed {s.get('seed', '?')}, "
                  f"{s.get('arrivals', '?')} arrivals, "
                  f"{s.get('wall_s', 0):.1f}s wall, "
                  f"{s.get('engines', '?')} engines) --",
                  f"{'phase':<12} {'offered':>8} {'done':>7} {'shed%':>6} "
                  f"{'attain':>7}  {'goodput':>9}  {'ttft p99':>9}  "
                  f"{'e2e p99':>9}"]
        for p in s.get("phases", []):
            att = p.get("attainment")
            miss = att is not None and att < target
            if miss:
                misses.append(f"{name}/{p['phase']}")
            lines.append(
                f"{p.get('phase', '?'):<12} {p.get('offered', 0):>8} "
                f"{p.get('completed', 0):>7} "
                f"{p.get('shed_rate', 0) * 100:>5.1f}% "
                f"{'n/a' if att is None else f'{att:.3f}':>7}  "
                f"{p.get('goodput_tps', 0):>7.1f}/s  "
                f"{_fmt_seconds(_num(p.get('ttft_p99'), 0.0)):>9}  "
                f"{_fmt_seconds(_num(p.get('e2e_p99'), 0.0)):>9}"
                + ("  << SLO-MISS" if miss else ""))
        settled = (counts.get("completed", 0) + counts.get("rejected", 0)
                   + counts.get("timeouts", 0))
        lines.append(
            f"open loop: dispatched {counts.get('dispatched', 0)} = "
            f"completed {counts.get('completed', 0)} + rejected "
            f"{counts.get('rejected', 0)} + timeouts "
            f"{counts.get('timeouts', 0)}"
            + ("" if counts.get("dispatched", 0) == settled
               else "  << ACCOUNTING LEAK"))
        if s.get("recovery_s_p50") is not None:
            lines.append(f"recovery p50: "
                         f"{_fmt_seconds(s['recovery_s_p50'])} "
                         f"(engines alive at end: "
                         f"{s.get('engines_alive_end', '?')})")
        events = s.get("scale_events") or []
        if events:
            lines.append(f"scale events ({s.get('scale_up', 0)} up / "
                         f"{s.get('scale_down', 0)} down):")
            for e in events:
                lines.append(
                    f"  t={e.get('t', 0):>7.3f}s  "
                    f"{e.get('action', '?'):<5} -> "
                    f"{e.get('alive', '?')} alive  "
                    f"[{e.get('engine', '?')}]  {e.get('reason', '')}"
                    + ("" if e.get("ok") else "  FAILED"))
    lines += ["", "== Verdicts =="]
    lines.append("SLO-MISS phases: " + (", ".join(misses) if misses
                                        else "none")
                 + ("  << SLO-MISS" if misses else ""))
    lines.append(
        f"attainment_ok: {row.get('attainment_ok', '?')}   "
        f"autoscaler_tracked: {row.get('autoscaler_tracked', '?')}   "
        f"jit_retraces: {row.get('jit_retraces', '?')}")
    return "\n".join(lines)


def summarize_scenario_live(reply: dict, target: str) -> str:
    """Live ``--scenario HOST:PORT`` view: the signals an
    :class:`~distkeras_tpu.scenario.AutoScaler` folds each tick —
    cumulative SLO attainment straight from the merged serve
    histograms, fleet queue pressure, and any ``scenario.*`` counters
    a co-resident harness publishes — over the SAME merged-stats poll
    ``--serve`` uses."""
    from distkeras_tpu.scenario import SLOTarget, hist_fraction_le
    stats = reply.get("stats", {})
    slo = SLOTarget()
    fr_ttft = hist_fraction_le(stats.get("serve.ttft_seconds"), slo.ttft_s)
    fr_e2e = hist_fraction_le(stats.get("serve.e2e_seconds"), slo.e2e_s)
    cands = [f for f in (fr_ttft, fr_e2e) if f is not None]
    att = min(cands) if cands else None
    alive = reply.get("engines_alive", reply.get("num_engines", 1)) or 1
    qd = _num(reply.get("queue_depth"), 0.0)
    miss = att is not None and att < slo.attainment
    lines = [f"== Scenario signals (live {target}) ==",
             f"SLO: ttft<={slo.ttft_s}s  e2e<={slo.e2e_s}s  "
             f"target attainment {slo.attainment:.2f}",
             f"attainment (cumulative): "
             f"{'n/a (no traffic)' if att is None else f'{att:.3f}'}"
             + ("  << SLO-MISS" if miss else ""),
             f"engines alive: {alive}   fleet queue: {qd:.0f}   "
             f"queue/engine: {qd / max(int(alive), 1):.1f}   "
             f"active slots: {reply.get('active_slots', '?')}"]
    scen = {k: v.get("value", 0) for k, v in stats.items()
            if k.startswith("scenario.") and "value" in v}
    if scen:
        lines += ["", "== Scenario counters =="]
        for k in sorted(scen):
            lines.append(f"{k:<32} {scen[k]:>10,.0f}")
    return "\n".join(lines)


def run_scenario(target: str) -> int:
    """``--scenario`` body: live HOST:PORT (a ``ServeRouter`` or engine
    stats RPC) or a persisted ``row.scenarios`` document."""
    host, _, port = target.rpartition(":")
    if host and port.isdigit():
        reply = poll_serve(host, int(port))
        emit(summarize_scenario_live(reply, target))
        return 0
    try:
        doc = load_snapshot(target)
    except OSError as e:
        emit(f"obsview --scenario: cannot read {target}: {e}", err=True)
        return 2
    if doc is None or "scenarios" not in (doc.get("row") or {}):
        emit(f"obsview --scenario: {target} is neither HOST:PORT nor a "
             "scenario-bench snapshot (expected a row.scenarios table)",
             err=True)
        return 2
    emit(summarize_scenario(doc, os.path.basename(target)))
    return 0


def poll_alerts(host: str, port: int) -> dict:
    """One ``alerts`` RPC against any FrameServer front-end (PS, shard,
    engine, router — ISSUE 20): hello handshake, ask, read."""
    import socket as _socket
    from distkeras_tpu.ps.networking import (client_handshake, recv_msg,
                                             send_msg)
    sock = _socket.create_connection((host, int(port)), timeout=10)
    try:
        ver = client_handshake(sock)
        send_msg(sock, {"action": "alerts"}, version=ver)
        return recv_msg(sock)
    finally:
        sock.close()


def _burn_gauge(measure: dict) -> str:
    """Compact burn-rate gauge cell for one burn_rate rule."""
    bs, bl = measure.get("burn_short"), measure.get("burn_long")
    if bs is None or bl is None:
        return "no data yet"
    att = measure.get("attainment_short")
    return (f"burn {_num(bs):.2f}/{_num(bl):.2f} "
            f"(max {_num(measure.get('max_burn')):.1f})  "
            f"attain {'n/a' if att is None else f'{_num(att):.3f}'}")


def summarize_alerts(alerts, telemetry, source: str) -> str:
    """Live/engine-state alerts panel over an ``alerts`` RPC reply (or a
    persisted engine ``state_doc``): per-rule firing state + burn
    gauges, transition tallies, the ALERT-FLAP warning, and the
    aggregator's source ages."""
    lines = [f"== Alerts ({source}) =="]
    if not alerts:
        lines.append("no alert engine attached (enable_alerts() was "
                     "never called on this server)")
    else:
        counts = alerts.get("counts", {})
        lines.append(f"fired {counts.get('fired', 0)}  "
                     f"resolved {counts.get('resolved', 0)}  "
                     f"firing now {counts.get('firing', 0)}")
        lines.append(f"{'rule':<20} {'kind':<10} {'metric':<30} "
                     f"{'state':<9} {'fired':>5} {'rsvd':>5}")
        flapping = []
        for r in alerts.get("rules", []):
            if r.get("flapping"):
                flapping.append(r.get("name", "?"))
            state = "FIRING" if r.get("firing") else "ok"
            lines.append(
                f"{r.get('name', '?'):<20} {r.get('kind', '?'):<10} "
                f"{r.get('metric', '?'):<30} {state:<9} "
                f"{r.get('fired', 0):>5} {r.get('resolved', 0):>5}"
                + ("  << ALERT" if r.get("firing") else ""))
            m = r.get("measure") or {}
            if r.get("kind") == "burn_rate":
                lines.append(f"  {_burn_gauge(m)}")
            elif "value" in m:
                lines.append(f"  value {_num(m['value']):g} "
                             f"(max {_num(m.get('max_value')):g})")
            elif "rate" in m:
                lines.append(f"  rate {_num(m['rate']):.3f}/s "
                             f"(max {_num(m.get('max_rate')):g}/s)")
        if flapping:
            lines.append(f"ALERT-FLAP: {', '.join(sorted(flapping))} "
                         f"(rapid fire/resolve churn — widen for_s/"
                         f"clear_s or fix the thresholds)")
    store = telemetry if telemetry else (alerts or {}).get("store")
    if store:
        lines += ["", f"telemetry: {store.get('series', 0)} series, "
                      f"{store.get('points', 0)} ring points"]
        for src, age in sorted((store.get("sources") or {}).items()):
            lines.append(f"  source {src:<24} last frame "
                         f"{_num(age):.1f}s ago")
    return "\n".join(lines)


def summarize_alert_records(records: list, source: str) -> str:
    """JSONL-replay alerts panel: the ``alert`` transition trail a run's
    events stream recorded, with the same flap math the live engine
    applies (>= 4 transitions of one rule inside 60s)."""
    lines = [f"== Alert trail ({source}) =="]
    if not records:
        lines.append("no alert records in stream")
        return "\n".join(lines)
    t0 = _num(records[0].get("ts"), 0.0)
    by_rule: dict = {}
    firing: set = set()
    for r in records:
        name = r.get("rule", "?")
        ts = _num(r.get("ts"), 0.0)
        by_rule.setdefault(name, []).append(ts)
        state = str(r.get("state", "?")).upper()
        if r.get("state") == "firing":
            firing.add(name)
        else:
            firing.discard(name)
        detail = _burn_gauge(r) if "burn_short" in r else (
            f"value {_num(r.get('value')):g}" if "value" in r else "")
        lines.append(f"  t={ts - t0:>8.3f}s  {state:<9} {name:<20} "
                     f"({r.get('metric', '?')})  {detail}")
    lines.append("firing at end: "
                 + (", ".join(sorted(firing)) if firing else "none"))
    flappers = sorted(
        name for name, tss in by_rule.items()
        if any(sum(1 for t in tss if 0 <= t2 - t <= 60.0) >= 4
               for t2 in tss))
    if flappers:
        lines.append(f"ALERT-FLAP: {', '.join(flappers)} (rapid "
                     f"fire/resolve churn in the recorded trail)")
    return "\n".join(lines)


def summarize_alert_metrics(stats: dict, doc: dict, source: str) -> str:
    """Snapshot-file alerts panel: the ``obs.alerts.*`` tallies a
    persisted registry snapshot carries (labeled per-rule counters
    flatten to ``obs.alerts.{fired,resolved}.rule<name>``), plus the
    persisted engine state when the bench stored one."""
    alerts_doc = (doc.get("row") or {}).get("alerts") \
        if isinstance(doc.get("row"), dict) else None
    if isinstance(alerts_doc, dict) and alerts_doc.get("rules"):
        return summarize_alerts(alerts_doc, None, source)
    lines = [f"== Alerts ({source}) =="]
    fired = stats.get("obs.alerts.fired", {}).get("value")
    resolved = stats.get("obs.alerts.resolved", {}).get("value")
    flaps = stats.get("obs.alerts.flaps", {}).get("value")
    if fired is None:
        lines.append("no obs.alerts.* metrics in snapshot (run had no "
                     "alert engine)")
        return "\n".join(lines)
    lines.append(f"fired {fired:g}  resolved {_num(resolved, 0):g}  "
                 f"flaps {_num(flaps, 0):g}"
                 + ("  << ALERT-FLAP" if _num(flaps, 0) > 0 else ""))
    per_rule = {k: v for k, v in stats.items()
                if k.startswith(("obs.alerts.fired.rule",
                                 "obs.alerts.resolved.rule"))}
    for k in sorted(per_rule):
        lines.append(f"  {k:<44} {per_rule[k].get('value', 0):g}")
    tel = {k: v.get("value") for k, v in stats.items()
           if k.startswith("obs.telemetry.") and "value" in v}
    if tel:
        lines.append("telemetry: " + "  ".join(
            f"{k.rsplit('.', 1)[-1]} {v:g}" for k, v in sorted(tel.items())))
    return "\n".join(lines)


def run_alerts(target: str) -> int:
    """``--alerts`` body: live HOST:PORT (any FrameServer's ``alerts``
    RPC), a persisted registry-snapshot file, or a JSONL events stream
    (replays its ``alert`` records)."""
    host, _, port = target.rpartition(":")
    if host and port.isdigit():
        reply = poll_alerts(host, int(port))
        if not isinstance(reply, dict) or not reply.get("ok", False):
            emit(f"obsview --alerts: {target} answered "
                 f"{reply.get('error', reply) if isinstance(reply, dict) else reply!r}",
                 err=True)
            return 2
        emit(summarize_alerts(reply.get("alerts"), reply.get("telemetry"),
                              f"live {target}"))
        return 0
    try:
        snap = load_snapshot(target)
    except OSError as e:
        emit(f"obsview --alerts: cannot read {target}: {e}", err=True)
        return 2
    if snap is None:
        alerts = [r for r in load_records(target)
                  if r.get("event") == "alert"]
        emit(summarize_alert_records(alerts, os.path.basename(target)))
        return 0
    from distkeras_tpu.obs import Registry
    regs = list(drift.named_registries(snap).values())
    stats = regs[0] if len(regs) == 1 else (
        Registry.merge_snapshots(*regs) if regs else {})
    emit(summarize_alert_metrics(stats, snap, os.path.basename(target)))
    return 0


def run_diff(base: str, cand: str, thresholds=None) -> int:
    """``--diff`` body: drift-gate two snapshot files.  Exit codes are the
    CI contract — 0 clean, 1 drift, 2 unreadable/invalid input."""
    try:
        if thresholds:
            # an EXPLICITLY named config failing to parse is a usage error
            baseline = drift.load_baseline(thresholds)
        else:
            found = drift.find_baseline(
                os.path.dirname(os.path.abspath(base))) \
                or drift.find_baseline(ROOT)
            baseline = None
            if found:
                try:
                    baseline = drift.load_baseline(found)
                except (OSError, ValueError) as e:
                    # auto-discovered config: degrade to defaults with a
                    # note — an unrelated bad file must not fail every
                    # diff of valid snapshots
                    emit(f"obsview --diff: ignoring invalid {found} "
                         f"({e}); using default thresholds", err=True)
        report = drift.diff_files(base, cand, baseline=baseline)
    except (OSError, ValueError) as e:
        emit(f"obsview --diff: {e}", err=True)
        return 2
    emit(report.render())
    if all(f.get("skipped") for f in report.findings):
        # disjoint registries, wrong file pairing, or everything skipped
        # (gauges / too-thin histograms): a gate that COMPARED nothing
        # must not report green — exit-0 is reserved for "compared and
        # clean"
        emit("obsview --diff: no comparable metrics between the two "
             "snapshots (wrong file pairing?)", err=True)
        return 2
    return 1 if report.drifted else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="inspect a telemetry JSONL file, poll a live PS, or "
                    "drift-gate two registry snapshots")
    ap.add_argument("jsonl", nargs="?",
                    help="JSONL metrics file written by MetricsLogger")
    ap.add_argument("--ps", metavar="TARGET",
                    help="poll a live SocketParameterServer's stats RPC; "
                         "a comma-separated HOST:PORT list or a shard "
                         "plan file polls every shard of a sharded PS "
                         "and renders ONE merged view with a per-shard "
                         "balance table (ISSUE 10)")
    ap.add_argument("--serve", metavar="TARGET",
                    help="poll a live decode service's stats RPC (SLO "
                         "latency table, admission counters, retrace "
                         "health); a ServeRouter target or a comma-"
                         "separated engine fleet additionally renders "
                         "the merged fleet view with a per-engine "
                         "balance table and the MISROUTED alarm "
                         "(ISSUE 14)")
    ap.add_argument("--continual", metavar="TARGET",
                    help="continual-loop view (ISSUE 8): HOST:PORT polls "
                         "a live decode service whose registry the "
                         "continual trainer shares; a file path reads a "
                         "persisted registry-snapshot document (window "
                         "verdicts, deploy history, stream lag, "
                         "DRIFT-DIRTY/RETRACING alarms)")
    ap.add_argument("--scenario", metavar="TARGET",
                    help="scenario-harness view (ISSUE 17): a file path "
                         "reads a persisted row.scenarios document "
                         "(per-phase SLO table, scale-event trail, "
                         "SLO-MISS alarm); HOST:PORT polls a live "
                         "decode service and renders the autoscaler's "
                         "signal view over the same merged-stats path "
                         "as --serve")
    ap.add_argument("--alerts", metavar="TARGET",
                    help="alerts panel (ISSUE 20): HOST:PORT polls any "
                         "telemetry-plane front-end's alerts RPC (PS, "
                         "shard, engine, router) and renders the live "
                         "rule table with burn-rate gauges and the "
                         "ALERT-FLAP warning; a snapshot file renders "
                         "its obs.alerts.* tallies; a JSONL file "
                         "replays the recorded alert transition trail")
    ap.add_argument("--diff", nargs=2, metavar=("BASE", "CAND"),
                    help="compare two registry-snapshot files for "
                         "distribution drift (exit 0 clean / 1 drift / "
                         "2 error)")
    ap.add_argument("--thresholds", metavar="OBS_BASELINE",
                    help="with --diff: threshold config file (default: "
                         "the committed OBS_BASELINE.json, discovered "
                         "upward from BASE, then from the repo root)")
    ap.add_argument("--prometheus", action="store_true",
                    help="with --ps (or a ps_stats record): render the "
                         "registry snapshot as Prometheus text")
    ap.add_argument("--export-trace", metavar="OUT",
                    help="with a JSONL file: write the stream as a "
                         "Chrome Trace Event Format JSON (open at "
                         "ui.perfetto.dev) instead of printing the "
                         "summary")
    args = ap.parse_args(argv)

    if sum(map(bool, (args.jsonl, args.ps, args.serve, args.continual,
                      args.scenario, args.alerts, args.diff))) != 1:
        ap.error("need exactly one of JSONL, --ps, --serve, --continual, "
                 "--scenario, --alerts or --diff")
    if args.export_trace and not args.jsonl:
        ap.error("--export-trace needs a JSONL metrics file")

    if args.diff:
        return run_diff(args.diff[0], args.diff[1], args.thresholds)

    if args.continual:
        return run_continual(args.continual)

    if args.scenario:
        return run_scenario(args.scenario)

    if args.alerts:
        return run_alerts(args.alerts)

    if args.ps:
        try:
            targets = parse_ps_targets(args.ps)
        except (ValueError, OSError) as e:
            ap.error(str(e))
        replies = [poll_stats(h, p) for h, p in targets]
        if args.prometheus:
            from distkeras_tpu.obs import Registry
            emit(to_prometheus_text(Registry.merge_snapshots(
                *[r.get("stats", {}) for r in replies])))
        elif len(replies) == 1:
            emit(summarize_stats(replies[0]))
        else:
            emit(summarize_ps_fleet(replies))
        return 0

    if args.serve:
        try:
            targets = parse_serve_targets(args.serve)
        except ValueError as e:
            ap.error(str(e))
        replies = [poll_serve(h, p) for h, p in targets]
        reply = replies[0] if len(replies) == 1 \
            else merge_serve_replies(replies)
        emit(to_prometheus_text(reply.get("stats", {})) if args.prometheus
             else summarize_serve(reply))
        return 0

    snap = load_snapshot(args.jsonl)
    if args.export_trace:
        if snap is not None:
            emit(f"obsview --export-trace: {args.jsonl} is a registry "
                 "snapshot, not a JSONL record stream (nothing to put on "
                 "a timeline)", err=True)
            return 2
        from distkeras_tpu.obs import export as obs_export
        doc = obs_export.write_chrome_trace(load_records(args.jsonl),
                                            args.export_trace)
        emit(f"wrote {len(doc['traceEvents'])} trace events -> "
             f"{args.export_trace} (open at ui.perfetto.dev)")
        return 0
    if snap is not None:
        if args.prometheus:
            # a snapshot file may hold several component registries;
            # fold them with the registry merge semantics (counters/
            # histograms add, gauges last-write) so the exposition has
            # no duplicate metric names
            from distkeras_tpu.obs import Registry
            regs = [v for v in snap.values() if _is_registry_snapshot(v)]
            if not regs and _is_registry_snapshot(snap):
                regs = [snap]
            if not regs:
                emit("no registry snapshot in file", err=True)
                return 1
            emit(to_prometheus_text(Registry.merge_snapshots(*regs)))
            return 0
        emit(summarize_snapshot(snap))
        return 0
    records = load_records(args.jsonl)
    if args.prometheus:
        ps_stats = [r for r in records if r.get("event") == "ps_stats"]
        if not ps_stats:
            emit("no ps_stats record in stream", err=True)
            return 1
        emit(to_prometheus_text(ps_stats[-1].get("stats", {})))
        return 0
    emit(summarize(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())
