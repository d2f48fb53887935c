"""dklint v2 tests (ISSUE 18): the interprocedural core — lock-order
deadlock detection (static graph + runtime recorder), the
metric-contract gate over OBS_BASELINE.json/obsview, handoff-protocol,
the fleet-wide racecheck install, and the ``--changed``/``--jobs`` CLI
satellites."""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from distkeras_tpu.analysis import analyze_source, racecheck, run_paths
from distkeras_tpu.analysis.cli import main as dklint_main
from distkeras_tpu.analysis.rules import RULES_BY_ID

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, rule=None):
    rules = [RULES_BY_ID[rule]] if rule else None
    report = analyze_source(textwrap.dedent(src), rules=rules)
    assert not report.errors, report.errors
    return report.findings


def _tree(v):
    return {"params": [{"w": np.asarray(v, dtype=np.float32)}], "state": [{}]}


# ---------------------------------------------------------------------------
# lock-order-cycle (static)
# ---------------------------------------------------------------------------

def test_lock_order_cycle_flags_two_lock_inversion():
    """The acceptance fixture: two methods acquiring the same pair of
    locks in opposite orders is a deadlock waiting for its interleave."""
    found = lint("""
        import threading

        class Pool:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def backward(self):
                with self._b:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert len(found) == 1
    msg = found[0].message
    assert "lock-order cycle" in msg
    assert "Pool._a" in msg and "Pool._b" in msg


def test_lock_order_cycle_through_one_call_edge():
    # forward: A held, calls _commit which takes B (one call-edge level,
    # the jit-purity precedent); backward inverts lexically -> cycle
    found = lint("""
        import threading

        class Pool:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def _commit(self):
                with self._b:
                    pass

            def forward(self):
                with self._a:
                    self._commit()

            def backward(self):
                with self._b:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert len(found) == 1
    assert "Pool._commit" in found[0].message


def test_lock_order_consistent_order_is_clean():
    found = lint("""
        import threading

        class Pool:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def drain(self):
                with self._a:
                    with self._b:
                        pass
        """, rule="lock-order-cycle")
    assert found == []


def test_lock_order_rlock_reentry_silent_lock_reentry_fatal():
    # RLock re-entry is legal: no 1-cycle, no finding
    found = lint("""
        import threading

        class R:
            def __init__(self):
                self._a = threading.RLock()

            def f(self):
                with self._a:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert found == []
    # the same shape over a non-reentrant Lock ALWAYS deadlocks
    found = lint("""
        import threading

        class L:
            def __init__(self):
                self._a = threading.Lock()

            def f(self):
                with self._a:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert len(found) == 1
    assert "self-deadlock" in found[0].message


def test_lock_order_holds_pragma_on_subclass_resolves_base_lock():
    """A subclass method's ``holds=`` contract names a BASE-class lock;
    the edge it contributes must connect with the base's own lexical
    acquisitions (same LockNode identity) to close the cycle."""
    found = lint("""
        import threading

        class Base:
            def __init__(self):
                self._lock = threading.Lock()
                self._aux = threading.Lock()

            def outer(self):
                with self._aux:
                    with self._lock:
                        pass

        class Child(Base):
            def _flush(self):  # dklint: holds=_lock
                with self._aux:
                    pass
        """, rule="lock-order-cycle")
    assert len(found) == 1
    msg = found[0].message
    assert "Base._lock" in msg and "Base._aux" in msg


def test_lock_order_sees_finally_block_acquisition():
    found = lint("""
        import threading

        class F:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    try:
                        pass
                    finally:
                        with self._b:
                            pass

            def backward(self):
                with self._b:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert len(found) == 1
    assert "lock-order cycle" in found[0].message


def test_lock_order_inline_disable_pragma():
    found = lint("""
        import threading

        class Pool:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:  # dklint: disable=lock-order-cycle
                        pass

            def backward(self):
                with self._b:
                    with self._a:
                        pass
        """, rule="lock-order-cycle")
    assert found == []


def test_lock_order_repo_is_clean():
    """The whole library under the static lock-order graph: zero cycles
    (there is exactly one cross-lock edge in the repo — the router's
    promote->routing nesting — and nothing inverts it)."""
    rule = RULES_BY_ID["lock-order-cycle"]
    report = run_paths([os.path.join(_ROOT, "distkeras_tpu")], rules=[rule])
    assert not report.errors, report.errors
    assert report.findings == []


# ---------------------------------------------------------------------------
# metric-contract
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, baseline, pkg_src, obsview_src=None):
    root = tmp_path / "repo"
    (root / "pkg").mkdir(parents=True)
    (root / "pyproject.toml").write_text("[project]\nname='x'\n")
    (root / "OBS_BASELINE.json").write_text(json.dumps(baseline, indent=1))
    (root / "pkg" / "mod.py").write_text(textwrap.dedent(pkg_src))
    if obsview_src is not None:
        (root / "scripts").mkdir()
        (root / "scripts" / "obsview.py").write_text(
            textwrap.dedent(obsview_src))
    return root


def _metric_findings(root):
    report = run_paths([str(root / "pkg")],
                       rules=[RULES_BY_ID["metric-contract"]])
    assert not report.errors, report.errors
    return report.findings


def test_metric_contract_flags_dead_threshold(tmp_path):
    root = _mini_repo(
        tmp_path,
        {"metrics": {"pkg.live": {"counter_abs": 1},
                     "pkg.dead": {"counter_abs": 1}}},
        """
        def build(registry):
            c = registry.counter("pkg.live")
            return c
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "dead threshold" in found[0].message
    assert "pkg.dead" in found[0].message
    assert found[0].rel == "OBS_BASELINE.json"
    assert found[0].line > 1  # anchored at the pattern's own line


def test_metric_contract_flags_dead_ignore(tmp_path):
    root = _mini_repo(
        tmp_path,
        {"metrics": {}, "ignore": ["pkg.ghost"]},
        """
        def build(registry):
            return registry.counter("pkg.live")
        """)
    msgs = [f.message for f in _metric_findings(root)]
    assert len(msgs) == 1
    assert "dead ignore entry" in msgs[0] and "pkg.ghost" in msgs[0]


def test_metric_contract_flags_dead_renderer_read(tmp_path):
    root = _mini_repo(
        tmp_path,
        {"metrics": {}},
        """
        def build(registry):
            return registry.counter("pkg.live")
        """,
        obsview_src="""
        def render(stats):
            ok = stats.get("pkg.live", 0)        # created: fine
            ghost = stats.get("pkg.ghost", 0)    # nobody emits this
            return ok, ghost
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "pkg.ghost" in found[0].message
    assert found[0].rel == "scripts/obsview.py"


def test_metric_contract_glob_sites_match_with_shared_fragment(tmp_path):
    # f-string creation -> glob site; a suffix threshold with a shared
    # literal fragment matches, an unrelated glob threshold does not
    root = _mini_repo(
        tmp_path,
        {"metrics": {"*pull_cache_hits": {"counter_abs": 3},
                     "continual.verdicts_*": {"counter_rel": 1.0}}},
        """
        def build(registry, prefix):
            return registry.counter(f"{prefix}.pull_cache_hits")
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "continual.verdicts_*" in found[0].message


def test_metric_contract_gated_counter_must_be_precreated(tmp_path):
    # exactly-gated + ONLY created on first use -> a run that never
    # fires the path omits the metric and the gate silently skips
    root = _mini_repo(
        tmp_path,
        {"metrics": {"pkg.evictions": {"counter_abs": 0}}},
        """
        def evict(registry):
            registry.counter("pkg.evictions").inc()
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "pre-create" in found[0].message
    assert found[0].rel == "pkg/mod.py"

    # a pre-creation site anywhere satisfies the contract
    root2 = _mini_repo(
        tmp_path / "b",
        {"metrics": {"pkg.evictions": {"counter_abs": 0}}},
        """
        def init(registry):
            registry.counter("pkg.evictions")

        def evict(registry):
            registry.counter("pkg.evictions").inc()
        """)
    assert _metric_findings(root2) == []


def test_metric_contract_flags_dead_alert_rule(tmp_path):
    # an alert rule on a metric nobody creates can never fire — silently
    root = _mini_repo(
        tmp_path,
        {"metrics": {},
         "alerts": [{"name": "r", "kind": "threshold",
                     "metric": "pkg.ghost", "max_value": 0}]},
        """
        def build(registry):
            return registry.counter("pkg.live")
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "dead alert rule 'r'" in found[0].message
    assert "pkg.ghost" in found[0].message
    assert found[0].rel == "OBS_BASELINE.json"


def test_metric_contract_flags_malformed_alerts_doc(tmp_path):
    # structural problems surface through the SAME strict parser the
    # live engine uses — one finding anchored at the alerts block
    root = _mini_repo(
        tmp_path,
        {"metrics": {},
         "alerts": [{"name": "r", "kind": "threshold",
                     "metric": "pkg.live", "max_valu": 0}]},
        """
        def build(registry):
            return registry.counter("pkg.live")
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "malformed alert rules" in found[0].message
    assert "max_valu" in found[0].message


def test_metric_contract_alert_rule_matches_labeled_site(tmp_path):
    # a labeled creation site registers the glob family; a rule on the
    # flattened member matches it, and a rule with a label key the site
    # never uses is a typo finding
    root = _mini_repo(
        tmp_path,
        {"metrics": {},
         "alerts": [
             {"name": "ok", "kind": "threshold",
              "metric": "pkg.lag", "labels": {"worker": 3},
              "max_value": 5},
             {"name": "typo", "kind": "threshold",
              "metric": "pkg.lag", "labels": {"shard": 3},
              "max_value": 5}]},
        """
        def build(registry, i):
            return registry.gauge("pkg.lag", labels={"worker": i})
        """)
    found = _metric_findings(root)
    assert len(found) == 1
    assert "alert rule 'typo'" in found[0].message
    assert "'shard'" in found[0].message and "typo" in found[0].message


def test_metric_contract_repo_contract_holds():
    """Acceptance: every OBS_BASELINE.json threshold/ignore pattern
    matches a real creation site, every obsview read is emitted
    somewhere, every exactly-gated counter is pre-created."""
    rule = RULES_BY_ID["metric-contract"]
    report = run_paths([os.path.join(_ROOT, "distkeras_tpu")], rules=[rule])
    assert not report.errors, report.errors
    pretty = "\n".join(f"{f.location()}: {f.message}"
                       for f in report.findings)
    assert report.findings == [], f"metric contract broken:\n{pretty}"


# ---------------------------------------------------------------------------
# handoff-protocol
# ---------------------------------------------------------------------------

def test_handoff_flags_bare_mutable_object_to_thread():
    found = lint("""
        import threading

        class Stats:
            def __init__(self):
                self.counts = {}

        def run(work):
            s = Stats()
            t = threading.Thread(target=work, args=(s,))
            t.start()
            return s
        """, rule="handoff-protocol")
    assert len(found) == 1
    assert "Stats" in found[0].message and "counts" in found[0].message


def test_handoff_queue_put_and_callback_registration():
    found = lint("""
        class Job:
            def __init__(self):
                self.parts = []

        class Pool:
            def __init__(self, q, bus):
                self._q = q
                self._bus = bus

            def submit(self):
                j = Job()
                self._q.put(j)
                self._bus.add_callback(j)
        """, rule="handoff-protocol")
    assert len(found) == 2
    assert all("Job" in f.message for f in found)


def test_handoff_negatives():
    # owning a lock, or carrying no mutable containers: both clean
    found = lint("""
        import threading

        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self.counts = {}

        class Frozen:
            def __init__(self, n):
                self.n = n

        def run(work, q):
            g = Guarded()
            f = Frozen(3)
            threading.Thread(target=work, args=(g, f)).start()
            q.put(g)
            q.put(f)
        """, rule="handoff-protocol")
    assert found == []


# ---------------------------------------------------------------------------
# runtime lock-order recorder
# ---------------------------------------------------------------------------

def test_racecheck_runtime_records_inversion_cycle():
    """Acceptance, dynamic half: an A->B then B->A acquisition order is
    flagged the moment the closing edge lands (no deadlock required —
    the recorder sees the ORDER, not the collision)."""
    with racecheck.enabled() as violations:
        a = racecheck.TrackedLock(threading.RLock(), name="A")
        b = racecheck.TrackedLock(threading.RLock(), name="B")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        assert racecheck.lock_order_edges() == {("A", "B"): 1,
                                               ("B", "A"): 1}
        cyc = [v for v in violations if v["dict"] == "lock-order"]
        assert len(cyc) == 1
        assert cyc[0]["op"] == "cycle"
        assert cyc[0]["key"] == "A -> B -> A"


def test_racecheck_runtime_rlock_reentry_and_consistent_order_silent():
    with racecheck.enabled() as violations:
        a = racecheck.TrackedLock(threading.RLock(), name="A")
        b = racecheck.TrackedLock(threading.RLock(), name="B")
        with a:
            with a:  # re-entry: depth bookkeeping, no self-edge
                with b:
                    pass
        with a:      # same order again: same edge, still no cycle
            with b:
                pass
        assert racecheck.lock_order_edges() == {("A", "B"): 2}
        assert [v for v in violations if v["dict"] == "lock-order"] == []


def test_racecheck_runtime_cycle_spanning_threads():
    # thread 1 observes A->B, thread 2 observes B->A sequentially (no
    # actual contention): the edge graph is global, so the cycle reports
    with racecheck.enabled() as violations:
        a = racecheck.TrackedLock(threading.RLock(), name="A")
        b = racecheck.TrackedLock(threading.RLock(), name="B")

        def order(first, second):
            with first:
                with second:
                    pass

        t1 = threading.Thread(target=order, args=(a, b))
        t1.start()
        t1.join()
        t2 = threading.Thread(target=order, args=(b, a))
        t2.start()
        t2.join()
        cyc = [v for v in violations if v["dict"] == "lock-order"]
        assert len(cyc) == 1 and cyc[0]["key"] == "A -> B -> A"


# ---------------------------------------------------------------------------
# fleet racecheck (install beyond the PS)
# ---------------------------------------------------------------------------

def test_racecheck_wraps_serve_router_and_fabric():
    from distkeras_tpu.serve import RouterConfig, ServeRouter
    with racecheck.enabled():
        r = ServeRouter([("127.0.0.1", 1)],
                        config=RouterConfig(stats_interval_s=30.0))
        assert isinstance(r._lock, racecheck.TrackedLock)
        assert isinstance(r._promote_lock, racecheck.TrackedLock)
        assert isinstance(r._affinity, racecheck.GuardedOrderedDict)
        assert r._kv_fabric is not None
        assert isinstance(r._kv_fabric._lock, racecheck.TrackedLock)
        assert isinstance(r._kv_fabric._inflight, racecheck.GuardedSet)
        assert isinstance(r._kv_fabric._link_jobs, racecheck.GuardedDict)
        # the fabric's condition must ride the proxy, not the raw lock
        assert r._kv_fabric._work._lock is r._kv_fabric._lock


def test_racecheck_wraps_fleet_supervisor():
    from distkeras_tpu.ps.runner import FleetSupervisor
    from distkeras_tpu.ps.servers import DeltaParameterServer
    with racecheck.enabled():
        ps = DeltaParameterServer(_tree([0.0]), num_workers=1)
        sup = FleetSupervisor(ps, None, lambda *a: None)
        assert isinstance(sup._lock, racecheck.TrackedLock)
        for attr in ("live", "attempts", "finished"):
            assert isinstance(getattr(sup, attr), racecheck.GuardedDict), attr


def test_guarded_containers_flag_unguarded_cross_thread_writes():
    with racecheck.enabled() as violations:
        guard = racecheck.TrackedLock(threading.RLock())
        od = racecheck.GuardedOrderedDict(guard, "T.od")
        ss = racecheck.GuardedSet(guard, "T.ss")
        with guard:
            od["a"] = 1
            ss.add("a")
        assert list(od) == ["a"] and "a" in ss

        def rogue():
            od.move_to_end("a")
            ss.add("b")

        t = threading.Thread(target=rogue)
        t.start()
        t.join()
        names = {v["dict"] for v in violations}
        assert "T.od" in names and "T.ss" in names


def test_racecheck_fleet_install_idempotent_and_uninstall_exact():
    """Class-keyed registry: a second install() is a no-op, the inner
    uninstall is a no-op, and the outermost uninstall restores every
    fleet class's ORIGINAL __init__ (run opted-out + subprocess so the
    autouse fixture's own install doesn't mask a regression)."""
    code = (
        "from distkeras_tpu.analysis import racecheck\n"
        "from distkeras_tpu.serve.router import ServeRouter\n"
        "from distkeras_tpu.serve.engine import DecodeEngine\n"
        "from distkeras_tpu.serve.kvfabric import KVFabric\n"
        "from distkeras_tpu.ps.runner import FleetSupervisor\n"
        "from distkeras_tpu.ps.servers import ParameterServer\n"
        "fleet = (ServeRouter, DecodeEngine, KVFabric, FleetSupervisor,\n"
        "         ParameterServer)\n"
        "orig = {c: c.__init__ for c in fleet}\n"
        "with racecheck.enabled():\n"
        "    assert all(c.__init__ is not orig[c] for c in fleet)\n"
        "    patched = {c: c.__init__ for c in fleet}\n"
        "    undo = racecheck.install()  # nested: must not re-wrap\n"
        "    assert all(c.__init__ is patched[c] for c in fleet)\n"
        "    undo()                      # nested undo: must not restore\n"
        "    assert all(c.__init__ is patched[c] for c in fleet)\n"
        "assert not racecheck.installed()\n"
        "assert all(c.__init__ is orig[c] for c in fleet)\n"
        "print('FLEET_RESTORE_OK')\n")
    env = {**os.environ, "DKLINT_RACECHECK": "0", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    assert "FLEET_RESTORE_OK" in out.stdout


# ---------------------------------------------------------------------------
# CLI satellites: --changed and --jobs
# ---------------------------------------------------------------------------

def _git(root, *args):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *args], cwd=root, check=True, capture_output=True)


def test_cli_changed_lints_only_changed_files(tmp_path, capsys,
                                              monkeypatch):
    root = tmp_path / "repo"
    (root / "pkg").mkdir(parents=True)
    (root / "pyproject.toml").write_text("[project]\nname='x'\n")
    a, b = root / "pkg" / "a.py", root / "pkg" / "b.py"
    a.write_text("def f():\n    print('a')\n")
    b.write_text("def f():\n    print('b')\n")
    _git(root, "init", "-q")
    _git(root, "add", ".")
    _git(root, "commit", "-qm", "seed")
    monkeypatch.chdir(root)

    # nothing changed -> clean exit without scanning anything
    assert dklint_main(["pkg", "--changed"]) == 0
    assert "no changed" in capsys.readouterr().out

    # touch ONE file: only its findings surface
    a.write_text("def f():\n    print('a2')\n")
    rc = dklint_main(["pkg", "--changed", "HEAD", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["path"] for f in doc["findings"]} == {"pkg/a.py"}

    # a partial scan must never be allowed to overwrite the baseline
    assert dklint_main(["pkg", "--changed", "--write-baseline"]) == 2
    capsys.readouterr()


def test_cli_changed_bad_ref_is_usage_error(tmp_path, capsys, monkeypatch):
    root = tmp_path / "repo"
    (root / "pkg").mkdir(parents=True)
    (root / "pkg" / "a.py").write_text("x = 1\n")
    _git(root, "init", "-q")
    monkeypatch.chdir(root)
    assert dklint_main(["pkg", "--changed", "no-such-ref"]) == 2
    capsys.readouterr()


def test_run_paths_parallel_matches_serial(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    for i in range(6):
        (pkg / f"m{i}.py").write_text(
            f"def f():\n    print('m{i}')\n")
    serial = run_paths([str(pkg)])
    parallel = run_paths([str(pkg)], jobs=4)
    assert [f.fingerprint for f in serial.findings] == \
        [f.fingerprint for f in parallel.findings]
    assert len(serial.findings) == 6
    assert serial.errors == parallel.errors == []


def test_cli_jobs_flag_repo_subtree(capsys):
    pkg = os.path.join(_ROOT, "distkeras_tpu", "analysis")
    assert dklint_main([pkg, "--jobs", "4"]) == 0
    capsys.readouterr()
