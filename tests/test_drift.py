"""Cross-run drift guard (ISSUE 5 tentpole): ``obs.drift`` snapshot
diffing — counter ratio deltas, bucket-wise PSI + p50/p99 shift, the
three-layer threshold config, the committed ``OBS_BASELINE.json`` schema
— and the ``obsview --diff`` CLI exit-code contract (0 clean / 1 drift /
2 error) against golden snapshot pairs."""

import copy
import importlib.util
import json
import os

import pytest

from distkeras_tpu.obs import drift
from distkeras_tpu.obs.drift import (DEFAULT_THRESHOLDS, diff_docs,
                                     diff_files, load_baseline, psi)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_obsview():
    spec = importlib.util.spec_from_file_location(
        "obsview", os.path.join(_ROOT, "scripts", "obsview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


obsview = _load_obsview()


# -- golden snapshot pairs ---------------------------------------------------

def golden_doc():
    """A small but representative snapshot document: two registries,
    every instrument kind, histogram mass clustered low."""
    hist = {"type": "histogram", "bounds": [0.001, 0.01, 0.1, 1.0],
            "counts": [40, 50, 10, 0, 0], "sum": 0.9, "count": 100}
    return {
        "config": {"codec": "none", "windows": 50},
        "client": {
            "ps.commits": {"type": "counter", "value": 50},
            "net.bytes_sent": {"type": "counter", "value": 1_000_000},
            "ps.inflight": {"type": "gauge", "value": 0},
            "ps.client.rtt_seconds": copy.deepcopy(hist),
        },
        "server": {
            "ps.commits": {"type": "counter", "value": 50},
            "ps.apply_seconds": copy.deepcopy(hist),
        },
    }


def golden_counter_drift():
    """Counter-only drift: triple the byte counter, distributions equal."""
    doc = golden_doc()
    doc["client"]["net.bytes_sent"]["value"] = 3_000_000
    return doc


def golden_hist_shift():
    """Histogram-shift drift: same total count, mass pushed to the tail
    (the latency-regression shape); counters untouched."""
    doc = golden_doc()
    h = doc["client"]["ps.client.rtt_seconds"]
    h["counts"] = [0, 0, 10, 50, 40]
    h["sum"] = 60.0
    return doc


def test_self_diff_is_clean():
    rep = diff_docs(golden_doc(), golden_doc())
    assert not rep.drifted and rep.drifted_metrics == []
    # every non-skipped comparison is rendered
    out = rep.render()
    assert "0 drifted" in out and "DRIFT" not in out


def test_counter_only_drift_detected_and_named():
    rep = diff_docs(golden_doc(), golden_counter_drift())
    assert rep.drifted
    assert rep.drifted_metrics == ["client/net.bytes_sent"]
    line = [l for l in rep.lines() if l.startswith("DRIFT")][0]
    assert "client/net.bytes_sent" in line


def test_histogram_shift_detected_and_named():
    rep = diff_docs(golden_doc(), golden_hist_shift())
    assert rep.drifted_metrics == ["client/ps.client.rtt_seconds"]
    finding = [f for f in rep.findings if f.drifted][0]
    assert finding["psi"] > DEFAULT_THRESHOLDS["psi"]
    assert finding["p50_factor"] > 1.0
    # the report names the offending histogram AND the reason
    assert "psi" in finding["detail"]


def test_psi_properties():
    a = {"counts": [40, 50, 10, 0, 0], "count": 100}
    b = {"counts": [0, 0, 10, 50, 40], "count": 100}
    assert psi(a, a) == 0.0
    assert psi(a, b) > 1.0          # gross shift scores high
    # smoothing: disjoint support stays finite
    c = {"counts": [100, 0, 0, 0, 0], "count": 100}
    d = {"counts": [0, 0, 0, 0, 100], "count": 100}
    import math
    assert math.isfinite(psi(c, d))


def test_thin_histograms_are_skipped():
    base, cand = golden_doc(), golden_hist_shift()
    for doc in (base, cand):
        h = doc["client"]["ps.client.rtt_seconds"]
        h["counts"] = [c // 10 for c in h["counts"]]
        h["count"] = 10  # below min_count=16
    rep = diff_docs(base, cand)
    assert not rep.drifted
    f = [x for x in rep.findings
         if x["metric"] == "client/ps.client.rtt_seconds"][0]
    assert f.get("skipped")


def test_counter_abs_floor_tolerates_change_from_zero():
    """A counter at 0 in the baseline has an infinite relative delta for
    ANY increase; counter_abs is the only way to tolerate small absolute
    movement (e.g. one reconnect-induced cache miss)."""
    base, cand = golden_doc(), golden_doc()
    base["client"]["ps.cache_hits"] = {"type": "counter", "value": 0}
    cand["client"]["ps.cache_hits"] = {"type": "counter", "value": 1}
    assert diff_docs(base, cand).drifted_metrics == ["client/ps.cache_hits"]
    cfg = {"metrics": {"ps.cache_hits": {"counter_abs": 2}}}
    assert not diff_docs(base, cand, baseline=cfg).drifted
    cand["client"]["ps.cache_hits"]["value"] = 5  # beyond the floor
    assert diff_docs(base, cand, baseline=cfg).drifted


def test_gauges_skipped_by_default_and_opt_in():
    base, cand = golden_doc(), golden_doc()
    cand["client"]["ps.inflight"]["value"] = 50
    assert not diff_docs(base, cand).drifted
    rep = diff_docs(base, cand, baseline={
        "metrics": {"ps.inflight": {"gauge_abs": 5}}})
    assert rep.drifted_metrics == ["client/ps.inflight"]


def test_threshold_override_config():
    base, cand = golden_doc(), golden_counter_drift()
    # global loosening clears the gate
    rep = diff_docs(base, cand, baseline={"thresholds": {"counter_rel": 5.0}})
    assert not rep.drifted
    # per-metric fnmatch override beats the global
    rep = diff_docs(base, cand, baseline={
        "thresholds": {"counter_rel": 5.0},
        "metrics": {"net.bytes_*": {"counter_rel": 0.1}}})
    assert rep.drifted_metrics == ["client/net.bytes_sent"]
    # ignore drops the metric entirely
    rep = diff_docs(base, cand, baseline={"ignore": ["net.bytes_sent"]})
    assert not rep.drifted
    assert not any(f["metric"] == "client/net.bytes_sent"
                   for f in rep.findings)


def test_config_mismatch_and_schema_evolution_are_notes():
    base, cand = golden_doc(), golden_doc()
    cand["config"]["codec"] = "int8"
    cand["client"]["ps.stragglers"] = {"type": "gauge", "value": 0}
    del cand["server"]["ps.apply_seconds"]
    rep = diff_docs(base, cand)
    assert not rep.drifted  # notes never fail the gate
    joined = "\n".join(rep.notes)
    assert "config differs" in joined
    assert "ps.stragglers" in joined and "new" in joined
    assert "ps.apply_seconds" in joined and "missing" in joined


def test_bounds_change_is_drift():
    base, cand = golden_doc(), golden_doc()
    cand["server"]["ps.apply_seconds"]["bounds"] = [0.1, 1.0, 10.0, 100.0]
    rep = diff_docs(base, cand)
    assert "server/ps.apply_seconds" in rep.drifted_metrics


def test_baseline_schema_validation(tmp_path):
    good = tmp_path / "ok.json"
    good.write_text(json.dumps({"schema": drift.BASELINE_SCHEMA,
                                "thresholds": {"psi": 1.0}}))
    assert load_baseline(str(good))["thresholds"]["psi"] == 1.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"thresholds": {}}))
    with pytest.raises(ValueError):
        load_baseline(str(bad))


def test_committed_baseline_is_valid():
    """The repo's OBS_BASELINE.json parses under the schema, and the
    zero-tolerance rules tier-1's fleet tests and the continual gate
    lean on are in it: exact counts stay exact, the two opted-in gauges
    stay tight."""
    cfg = load_baseline(os.path.join(_ROOT, "OBS_BASELINE.json"))
    assert cfg["schema"] == drift.BASELINE_SCHEMA
    exact = {"counter_rel": 0.0, "counter_abs": 0.0}
    for name in ("jit.retraces", "jit.compiles", "serve.joins",
                 "serve.prefix.*", "serve.router.requests",
                 "serve.router.completed", "serve.router.evictions",
                 "serve.router.requeues", "serve.router.kv_refused_stale",
                 "ps.shard.cut_incomplete", "scenario.dispatched",
                 "obs.alerts.*"):
        rule = cfg["metrics"][name]
        assert {k: rule[k] for k in exact} == exact, name
    assert cfg["metrics"]["serve.spec.accept_rate"]["gauge_abs"] <= 0.2
    assert cfg["metrics"]["serve.router.affinity_hit_rate"][
        "gauge_abs"] <= 0.2


# -- obsview --diff exit-code contract (acceptance) --------------------------

def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_obsview_diff_exit_codes(tmp_path, capsys):
    base = _write(tmp_path, "base.json", golden_doc())
    same = _write(tmp_path, "same.json", golden_doc())
    shifted = _write(tmp_path, "shifted.json", golden_hist_shift())

    assert obsview.main(["--diff", base, same]) == 0
    capsys.readouterr()
    assert obsview.main(["--diff", base, shifted]) == 1
    out = capsys.readouterr().out
    assert "DRIFT" in out and "ps.client.rtt_seconds" in out

    # unreadable / non-snapshot inputs: exit 2, error on stderr
    assert obsview.main(["--diff", base, str(tmp_path / "nope.json")]) == 2
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text('{"event": "epoch"}\n')
    assert obsview.main(["--diff", base, str(jsonl)]) == 2

    # disjoint registries (wrong file pairing): a gate that compared
    # nothing must not report green
    capsys.readouterr()
    other = _write(tmp_path, "other.json",
                   {"elsewhere": {"x.y": {"type": "counter", "value": 1}}})
    assert obsview.main(["--diff", base, other]) == 2
    assert "no comparable metrics" in capsys.readouterr().err


def test_obsview_diff_tolerates_corrupt_discovered_baseline(tmp_path,
                                                            capsys):
    """An invalid auto-discovered OBS_BASELINE.json degrades to default
    thresholds with a stderr note — it must not turn every diff of
    valid snapshots into a usage error.  An EXPLICIT --thresholds file
    still hard-fails."""
    (tmp_path / "OBS_BASELINE.json").write_text("{broken")
    base = _write(tmp_path, "base.json", golden_doc())
    same = _write(tmp_path, "same.json", golden_doc())
    assert obsview.main(["--diff", base, same]) == 0
    assert "ignoring invalid" in capsys.readouterr().err
    assert obsview.main(["--diff", base, same, "--thresholds",
                         str(tmp_path / "OBS_BASELINE.json")]) == 2


def test_obsview_diff_thresholds_flag(tmp_path, capsys):
    base = _write(tmp_path, "base.json", golden_doc())
    cand = _write(tmp_path, "cand.json", golden_counter_drift())
    cfg = _write(tmp_path, "baseline.json", {
        "schema": drift.BASELINE_SCHEMA,
        "thresholds": {"counter_rel": 5.0}})
    assert obsview.main(["--diff", base, cand]) == 1
    capsys.readouterr()
    assert obsview.main(["--diff", base, cand, "--thresholds", cfg]) == 0
