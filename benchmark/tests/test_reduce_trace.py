"""``reduce_trace.py``: the interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on the chip."""

import os

import pytest

import reduce_trace

RECORDED = os.path.join(os.path.dirname(__file__), "data", "small_trace")


def test_union_counts_overlap_once_and_finds_the_gaps():
    busy, gaps = reduce_trace.union_seconds(
        [(0, 10), (2, 5), (8, 12), (20, 30), (30, 31), (40, 41)])
    assert busy == 12 + 11 + 1
    assert gaps == [(12, 20), (31, 40)]


def test_self_time_takes_nested_events_off_their_parent():
    events = [("while", 0, 100), ("fusion", 10, 30), ("fusion", 30, 50),
              ("pallas:_fwd_kernel", 60, 90), ("copy", 100, 110)]
    own, leaves = reduce_trace.self_times(events)
    assert own == {"while": 30.0, "fusion": 40.0,
                   "pallas:_fwd_kernel": 30.0, "copy": 10.0}
    # the while is no leaf: the gaps inside its loop stay gaps
    assert leaves == [(10, 30), (30, 50), (60, 90), (100, 110)]
    assert reduce_trace.union_seconds(leaves) == (80, [(50, 60), (90, 100)])


def test_gaps_are_named_by_the_innermost_annotation():
    host = [("bench:traced_call", 0, 1000), ("bench:pause", 100, 200),
            ("PjitFunction(run)", 150, 160)]
    assert reduce_trace.innermost(host, 155, "bench:") == "bench:pause"
    assert reduce_trace.innermost(host, 155) == "PjitFunction(run)"
    assert reduce_trace.innermost(host, 500, "bench:") == "bench:traced_call"
    assert reduce_trace.innermost(host, 5000, "bench:") == ""


def test_a_trace_with_no_device_plane_reduces_to_nothing(tmp_path):
    assert reduce_trace.reduce(str(tmp_path)) is None
    assert reduce_trace.reduce_loaded({"devices": {}, "host": []}) is None


def test_the_recorded_trace_reduces_to_busy_idle_names_and_gaps():
    """A trace recorded on a v5e chip (PR 24): three runs of a tiny jitted
    3-step scan over flash attention forward and backward, with a 2 ms
    ``bench:pause`` sleep after each, all under ``bench:traced_call``."""
    got = reduce_trace.reduce(RECORDED)
    assert got["window_module"] == "jit_small_epoch"
    assert got["module_runs"] == 3
    # the program runs for tens of microseconds; the host sleeps between
    assert 0 < got["busy_s"] < 1e-3 < got["window_s"] < 0.05
    assert got["idle_share"] == pytest.approx(
        1 - got["busy_s"] / got["window_s"])
    assert got["idle_share"] > 0.9
    table = dict(got["device_ops"])
    # the Mosaic calls are found and named by their transform; a while's
    # own time is what is left of it beside its body
    assert table["tpu_custom_call:jvp__"] > 0
    assert table["tpu_custom_call:transpose_jvp___"] \
        > table["tpu_custom_call:jvp__"]
    assert 0 < table["while"] < got["busy_s"]
    assert sum(table.values()) == pytest.approx(got["busy_s"], rel=0.2)
    gaps = dict(got["idle_gaps"])
    assert any(name.startswith("pause") for name in gaps)
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert got["longest_gap_s"] > 1e-3
