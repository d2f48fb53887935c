"""Benchmark harness: the five BASELINE.json configs, one table —
plus the scenario-harness smoke (ISSUE 17) and the dklint gate
(ISSUE 18).

Usage: ``python scripts/bench_all.py [--quick]``.

The trainer configs live as DATA in ``configs/bench_all.yaml``
(SURVEY.md §5.6: one checked-in file reproduces the whole table); that
part is a thin alias for ``python -m distkeras_tpu.config
configs/bench_all.yaml``.  The scenario smoke is ``bench.py``'s
``bench_scenario(("smoke",))`` called IN THIS PROCESS — the yaml schema
is trainer-only, and the trainers above already hold the accelerator,
which belongs to one process at a time: a child that needed it would
fail or hang — appended so the nightly table also proves the open-loop
serve path end to end.  The dklint gate runs ``dklint --format json``
repo-wide and fails the nightly on findings or IO errors, and
round-trips the committed ``dklint_baseline.json`` in the same run so
serializer drift surfaces the night it lands.  ``--job`` (a packaging
mode) skips both.
"""

import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distkeras_tpu import config  # noqa: E402
from distkeras_tpu.obs.logging import emit  # noqa: E402


def run_scenario_smoke() -> int:
    """``bench.py``'s scenario smoke, in this process (the one that
    holds the device — see the module docstring); renders the row's
    headline as one more table-ish line."""
    import bench
    row = bench.bench_scenario(names=("smoke",))
    s = row["scenarios"]["smoke"]
    counts = s.get("counts", {})
    alerts = s.get("alerts") or {}
    emit(f"| scenario smoke | {counts.get('dispatched', 0)} dispatched "
         f"({counts.get('completed', 0)} ok, "
         f"{counts.get('rejected', 0)} shed, "
         f"{counts.get('timeouts', 0)} timeout) "
         f"| attainment_ok {row.get('attainment_ok')} "
         f"| retraces {row.get('jit_retraces')} "
         f"| alerts {alerts.get('fired', 'n/a')} "
         f"| {s.get('wall_s', 0):.1f}s |")
    # ISSUE 20: the smoke runs with the committed alert rules LIVE on
    # the router — a healthy toy fleet must end the storm quiet.  A
    # missing alerts block means the wiring regressed (rules no longer
    # reach the router), which must fail just as loudly as a firing.
    if alerts.get("fired") != 0 or alerts.get("firing") != 0:
        emit(f"scenario smoke: alert self-check FAILED — expected zero "
             f"fired/firing alerts, got {alerts or 'no alerts block'}",
             err=True)
        return 1
    return 0


def run_alert_injection() -> int:
    """In-process alert-engine self-check (ISSUE 20): feed the committed
    OBS_BASELINE rules a gross injected SLO breach (every e2e sample at
    4x the bound) and assert EXACTLY the e2e burn-rate rule fires —
    proof the live plane both fires on real breaches and stays quiet on
    rules whose metrics carry no evidence."""
    from distkeras_tpu.obs import Registry
    from distkeras_tpu.obs.alerts import AlertEngine, parse_rules
    from distkeras_tpu.obs.drift import load_baseline
    from distkeras_tpu.obs.timeseries import TimeSeriesStore
    try:
        doc = load_baseline(os.path.join(ROOT, "OBS_BASELINE.json"))
        rules = parse_rules(doc.get("alerts") or [])
    except (OSError, ValueError) as e:
        emit(f"alert self-check: unusable OBS_BASELINE alerts ({e})",
             err=True)
        return 1
    e2e = [r for r in rules
           if r.kind == "burn_rate" and r.metric == "serve.e2e_seconds"]
    if len(e2e) != 1:
        emit(f"alert self-check: want exactly one committed e2e burn "
             f"rule, found {len(e2e)}", err=True)
        return 1
    rule = e2e[0]
    clock = [0.0]
    store = TimeSeriesStore(clock=lambda: clock[0])
    engine = AlertEngine(store, rules, eval_interval_s=0.0,
                         clock=lambda: clock[0])
    src = Registry()
    h = src.histogram("serve.e2e_seconds")
    # breach spread across ticks so BOTH burn windows hold >= min_samples
    for _ in range(max(3, rule.min_samples)):
        clock[0] += rule.short_s / max(3, rule.min_samples)
        h.observe(rule.bound_s * 4)
        store.ingest_total("inject", src.snapshot())
        engine.evaluate(force=True)
    clock[0] += rule.for_s + 0.001  # ride out any for_s hysteresis
    engine.evaluate(force=True)
    fired = sorted(r["name"] for r in engine.state_doc()["rules"]
                   if r["firing"])
    if fired != [rule.name]:
        emit(f"alert self-check FAILED: injected 4x-SLO breach should "
             f"fire exactly [{rule.name}], got {fired}", err=True)
        return 1
    emit(f"| alert self-check | injected 4x e2e breach fired exactly "
         f"[{rule.name}] |")
    return 0


def _baseline_round_trip(path: str) -> int:
    """load -> write(tmp) -> reload the committed baseline and compare
    fingerprint sets: any writer/loader asymmetry would silently grow or
    shed accepted debt on the next ``--write-baseline``."""
    from distkeras_tpu.analysis import core as lint_core
    try:
        with open(path, encoding="utf-8") as f:
            entries = json.load(f)["findings"]
        fps = lint_core.load_baseline(path)
    except (OSError, ValueError, KeyError) as e:
        emit(f"dklint baseline: unreadable {path} ({e})", err=True)
        return 1
    findings = [
        lint_core.Finding(rule=e["rule"], path=e["path"], rel=e["path"],
                          line=0, col=0, message=e["message"],
                          snippet=e.get("snippet", ""),
                          fingerprint=e["fingerprint"])
        for e in entries]
    fd, tmp = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        lint_core.write_baseline(tmp, findings)
        if lint_core.load_baseline(tmp) != fps:
            emit("dklint baseline: round-trip mismatch — load -> write -> "
                 "reload changed the fingerprint set", err=True)
            return 1
    finally:
        os.unlink(tmp)
    return 0


def run_dklint_gate() -> int:
    """Repo-wide ``dklint --format json`` in a subprocess (same
    invocation a contributor would run); exit 1 (findings) or 2 (IO /
    usage) fails the nightly.  The baseline round-trip rides in the
    same run."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "dklint.py"),
         "--format", "json"],
        capture_output=True, text=True, cwd=ROOT, timeout=600)
    if proc.returncode != 0:
        emit(f"dklint gate FAILED (rc={proc.returncode}):\n"
             f"{(proc.stdout + proc.stderr).strip()[-2000:]}", err=True)
        return proc.returncode
    try:
        doc = json.loads(proc.stdout)
        n = len(doc["findings"])
        supp = doc["suppressed"]
    except (ValueError, KeyError, TypeError) as e:
        emit(f"dklint gate: unparseable report ({e})", err=True)
        return 1
    emit(f"| dklint | {n} finding(s) "
         f"| {supp.get('inline', 0)} inline "
         f"+ {supp.get('baseline', 0)} baseline suppressed |")
    return _baseline_round_trip(os.path.join(ROOT, "dklint_baseline.json"))


if __name__ == "__main__":
    rc = config.main(
        [os.path.join(ROOT, "configs", "bench_all.yaml"), *sys.argv[1:]])
    if rc == 0 and "--job" not in sys.argv[1:]:
        rc = run_scenario_smoke()
    if rc == 0 and "--job" not in sys.argv[1:]:
        rc = run_alert_injection()
    if rc == 0 and "--job" not in sys.argv[1:]:
        rc = run_dklint_gate()
    sys.exit(rc)
