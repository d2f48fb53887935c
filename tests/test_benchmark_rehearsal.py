"""Tier-1's eye on the yardstick: every cell of ``BENCHMARK.json`` is
rehearsed on the CPU by the command the driver starts on the chip.

``benchmark/run.py --rehearse`` runs a cell's whole control flow at the
tiny ``rehearse`` sizes of its files (exit 3, never a result).  The
runner stamps the trainers' ``epoch`` records and ``jit_compile`` spans,
the traffic files pass trainer keywords, the readers read registry
counters: a PR that renames one of them fails here and not on the chip.
Nothing is imported from ``benchmark/``; the cells come from
``BENCHMARK.json`` where the tests are collected, so a later cell is
rehearsed without an edit to this file.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

# what the program itself emits; a device trace and a chip's peak exist
# only on the chip
PROGRAM_SOURCES = ("program_span", "program_counter")


def run_py(cell, tmp_path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed",
         "2147483999", "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def names_of(metrics, cell, sources=None):
    return {m["name"] for m in metrics
            if cell in m.get("workloads", [cell])
            and (sources is None or m["source"] in sources)}


@pytest.mark.parametrize("trace", (0, 1), ids=("trace0", "trace1"))
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_rehearses(cell, trace, tmp_path):
    done = run_py(cell, tmp_path, "--trace", str(trace), "--rehearse")
    assert done.returncode == 3, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    values = {k: v["value"] for k, v in line["rehearsal_values"].items()}
    if trace == 0:
        assert set(values) == names_of(BENCH["end_to_end"], cell)
        return
    emitted = names_of(BENCH["per_layer"], cell, PROGRAM_SOURCES)
    assert emitted and emitted <= set(values), emitted - set(values)
    assert not names_of(BENCH["per_layer"], cell, ("device_trace",)) \
        & set(values)
    assert "model_flops_util" not in values  # needs the chip's peak
    assert values.get("train_retraces", 0) == 0


def test_off_the_chip_run_py_measures_nothing(tmp_path):
    done = run_py(BENCH["workloads"][0]["name"], tmp_path, "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr
