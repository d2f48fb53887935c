"""``run.py`` end to end: a rehearsal of one training cell on the CPU, and
the refusal to measure off the chip."""

import json
import os
import subprocess
import sys

from conftest import ROOT


def run_py(bench, *extra):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483999", "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_off_the_chip_it_exits_non_zero_and_prints_no_result(bench):
    done = run_py(bench, "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_rehearsal_runs_the_whole_control_flow_and_is_no_result(bench):
    for trace, names in (("0", {"train_samples_per_s", "setup_s"}),
                         ("1", {"train_compile_s", "train_retraces",
                                "train_call_overhead_s"})):
        done = run_py(bench, "--trace", trace, "--rehearse")
        assert done.returncode == 3, done.stderr[-2000:]
        line = json.loads(done.stdout.strip().splitlines()[-1])
        assert line["rehearsal"] is True and "metrics" not in line
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 0
        assert names <= set(line["rehearsal_values"])
        # no device trace off the chip: nothing stands under those names
        assert "flash_roofline" not in line["rehearsal_values"]
        assert line["device"]["platform"] == "cpu"
