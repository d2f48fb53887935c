"""The four readers of the memory account (PR 38) on made-up ``sources``:
the ``program_*`` bytes that a cold call's ``jit_compile`` record carries
and the recompute plan's ``remat_bytes_*`` beside them."""

import pytest

from run import in_cell, load_module

ACCOUNT_READERS = ("program_memory_gb", "program_temp_share",
                   "program_donated_share")
READERS = ACCOUNT_READERS + ("remat_estimate_error",)

#: a planned program's cold record, as the trainers write it now
RECORD = {
    "name": "jit_compile", "seconds": 9.5, "cache_hits": 1,
    "program_argument_bytes": 8_000_000_000,
    "program_output_bytes": 7_500_000_000,
    "program_alias_bytes": 7_400_000_000,
    "program_temp_bytes": 4_400_000_000, "program_code_bytes": 0,
    "program_bytes": 12_500_000_000,
    "device_bytes_limit": 16_900_000_000, "device_bytes_in_use": 8_100_000_000,
    "remat_children_kept": 21, "remat_children_recomputed": 33,
    "remat_bytes_estimated": 15_000_000_000,
    "remat_bytes_compiled": 12_500_000_000,
}
#: a program without a plan: the account alone
UNPLANNED = {k: v for k, v in RECORD.items() if not k.startswith("remat_")}
#: a cold record from before the account (an unplanned cell's at the parent)
PARENT = {"name": "jit_compile", "seconds": 9.5, "cache_hits": 1}


def read(name, sources):
    return load_module("layer_metrics", name).read(sources)


def test_the_readers_read_the_account():
    sources = {"setup_compile_spans": [RECORD]}
    assert read("program_memory_gb", sources) == 12.5
    assert read("program_temp_share", sources) == pytest.approx(35.2)
    assert read("program_donated_share", sources) == pytest.approx(92.5)
    assert read("remat_estimate_error", sources) == pytest.approx(20.0)


def test_an_estimate_under_the_compiled_size_is_as_far_off():
    low = dict(RECORD, remat_bytes_estimated=10_000_000_000)
    assert read("remat_estimate_error",
                {"setup_compile_spans": [low]}) == pytest.approx(20.0)


def test_nothing_estimated_reads_a_hundred():
    off_chip = dict(RECORD, remat_bytes_estimated=0)
    assert read("remat_estimate_error",
                {"setup_compile_spans": [off_chip]}) == 100.0


def test_a_program_that_donates_nothing_reads_zero_not_nothing():
    bare = dict(UNPLANNED, program_alias_bytes=0)
    assert read("program_donated_share",
                {"setup_compile_spans": [bare]}) == 0.0


def test_the_last_record_with_the_fields_wins():
    """A second cold call (or a judge that compiled twice) writes the
    account again: the window runs the last program.  A later record
    without the fields hides nothing."""
    again = dict(RECORD, program_bytes=14_000_000_000,
                 program_temp_bytes=7_000_000_000,
                 program_alias_bytes=4_000_000_000,
                 remat_bytes_compiled=14_000_000_000,
                 remat_bytes_estimated=14_700_000_000)
    sources = {"setup_compile_spans": [RECORD, again, PARENT]}
    assert read("program_memory_gb", sources) == 14.0
    assert read("program_temp_share", sources) == 50.0
    assert read("program_donated_share", sources) == 50.0
    assert read("remat_estimate_error", sources) == pytest.approx(5.0)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("sources", [
    {}, {"setup_compile_spans": None}, {"setup_compile_spans": []},
    {"setup_compile_spans": [PARENT]}, {"config": {}, "trace": None},
], ids=["no-key", "none", "no-span", "parent-style", "bare"])
def test_the_readers_find_nothing_without_the_fields(name, sources):
    assert read(name, sources) is None


def test_a_program_without_a_plan_has_no_estimate_to_miss():
    sources = {"setup_compile_spans": [UNPLANNED]}
    assert read("remat_estimate_error", sources) is None
    assert read("program_memory_gb", sources) == 12.5


@pytest.mark.parametrize("field, name", [
    ("program_bytes", "program_temp_share"),
    ("program_argument_bytes", "program_donated_share"),
    ("remat_bytes_compiled", "remat_estimate_error")])
def test_no_reader_divides_by_nothing(field, name):
    empty = dict(RECORD, **{field: 0})
    assert read(name, {"setup_compile_spans": [empty]}) is None


def test_the_entries_name_cells_the_readers_find_something_in(bench):
    """Every cell an entry lists trains (its runner passes the set-up's
    ``jit_compile`` records on), and ``remat_estimate_error``'s train under
    a recompute plan."""
    import json
    import os

    from conftest import ROOT
    entries = {m["name"]: m for m in bench["per_layer"]}
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_samples_per_s")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    for name in READERS:
        assert entries[name]["workloads"]
        for cell in entries[name]["workloads"]:
            assert in_cell(rate, cell)
    for cell in entries["remat_estimate_error"]["workloads"]:
        with open(os.path.join(ROOT, configs[cells[cell]["config"]])) as f:
            assert json.load(f)["train"]["remat"] is True
