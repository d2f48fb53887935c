"""Async parameter-server path: protocol, update rules, end-to-end training.

Mirrors the reference's only multi-worker test story (Spark ``local[*]``,
SURVEY.md §4): N worker threads against a localhost PS, plus the unit tests
the reference never had (PS update-rule math, staleness arithmetic,
commit-drop fault injection per SURVEY.md §5.3).
"""

import threading

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.ps import (ADAGParameterServer, DeltaParameterServer,
                              DynSGDParameterServer, PSClient,
                              SocketParameterServer)
from tests.test_trainers_sync import COMMON, make_model, toy_problem


@pytest.fixture(scope="module")
def ds():
    return toy_problem()


# -- update-rule math (pure, no sockets) ------------------------------------

def tree(v):
    return {"params": [{"w": np.asarray(v, dtype=np.float32)}], "state": [{}]}


def test_delta_ps_rule():
    ps = DeltaParameterServer(tree([1.0, 2.0]), num_workers=4)
    ps.handle_commit(tree([0.5, -0.5]), {})
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [1.5, 1.5])
    assert ps.num_updates == 1


def test_adag_ps_rule_normalizes():
    ps = ADAGParameterServer(tree([0.0, 0.0]), num_workers=4)
    ps.handle_commit(tree([4.0, 8.0]), {})
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [1.0, 2.0])


def test_dynsgd_staleness_scaling():
    ps = DynSGDParameterServer(tree([0.0]), num_workers=2)
    # fresh commit: staleness 0 -> full delta
    ps.handle_commit(tree([1.0]), {"last_update": 0})
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [1.0])
    # stale commit: pulled at update 0, but server is now at 1 -> delta/2
    ps.handle_commit(tree([1.0]), {"last_update": 0})
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [1.5])
    # staleness 2 -> delta/3
    ps.handle_commit(tree([3.0]), {"last_update": 0})
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [2.5])


# -- socket protocol --------------------------------------------------------

def test_socket_pull_commit_roundtrip():
    ps = DeltaParameterServer(tree([1.0, 1.0]), num_workers=2)
    with SocketParameterServer(ps) as server:
        with PSClient("127.0.0.1", server.port, 0) as c:
            center, updates = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [1.0, 1.0])
            assert updates == 0
            assert c.commit(tree([1.0, 0.0]))
            center, updates = c.pull()
            np.testing.assert_allclose(center["params"][0]["w"], [2.0, 1.0])
            assert updates == 1


def test_concurrent_commits_are_not_lost():
    """Stress the commit mutex (SURVEY.md §5.2: the reference's single-lock
    discipline, tested the way TSan would)."""
    ps = DeltaParameterServer(tree([0.0]), num_workers=8)
    n_threads, n_commits = 8, 25
    with SocketParameterServer(ps) as server:
        def hammer():
            with PSClient("127.0.0.1", server.port) as c:
                for _ in range(n_commits):
                    c.commit(tree([1.0]))
        ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
        [t.start() for t in ts]
        [t.join() for t in ts]
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"],
                               [n_threads * n_commits])
    assert ps.num_updates == n_threads * n_commits


def test_fault_injection_drops_commits():
    ps = DeltaParameterServer(tree([0.0]), num_workers=1)
    drop_every_other = {"n": 0}

    def injector(action, msg):
        drop_every_other["n"] += 1
        return drop_every_other["n"] % 2 == 0

    with SocketParameterServer(ps, fault_injector=injector) as server:
        with PSClient("127.0.0.1", server.port) as c:
            results = [c.commit(tree([1.0])) for _ in range(4)]
    assert results == [True, False, True, False]
    np.testing.assert_allclose(ps.get_model()["params"][0]["w"], [2.0])


# -- end-to-end async training ----------------------------------------------

@pytest.mark.parametrize("cls,kw,floor", [
    (dk.DOWNPOUR, dict(communication_window=4), 0.85),
    (dk.ADAG, dict(communication_window=4), 0.55),
    (dk.DynSGD, dict(communication_window=4), 0.85),
    (dk.AEASGD, dict(communication_window=4, rho=1.0), 0.5),
    (dk.EAMSGD, dict(communication_window=4, rho=1.0, momentum=0.9), 0.7),
])
def test_async_trainers_converge(ds, cls, kw, floor):
    t = cls(make_model(), "sgd", num_workers=4, mode="async", **COMMON, **kw)
    m = t.train(ds)
    pred = dk.ModelPredictor(m, "features").predict(ds)
    acc = dk.AccuracyEvaluator("prediction", "label").evaluate(pred)
    assert acc > floor, acc
    assert len(t.get_history()) == COMMON["num_epoch"]
    assert t.get_history()[0].shape[0] == 4
    # every worker states ONCE where its carry sits: thread workers are
    # placed one per device (8 virtual CPU devices here)
    placed = [r for r in t.metrics.records
              if r.get("event") == "worker_platform"]
    assert sorted(p["worker_id"] for p in placed) == [0, 1, 2, 3]
    assert len({p["device"] for p in placed}) == 4
    assert {p["platform"] for p in placed} == {"cpu"}
