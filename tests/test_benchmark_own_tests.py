"""The benchmark's own tests under the driver's run.

``benchmark/tests`` holds the yardstick's file contract, FLOP and
parameter counts, the plain references, the tie-settling comparison, the
trace reduction and the readers; only a ``benchmark`` PR may edit them and
they stand outside ``tests/``.  Each file is run here as
``benchmark/README.md`` says to run it, in a process of its own (their
``conftest.py`` and this suite's differ).  ``test_run.py`` is left out:
``tests/test_benchmark_rehearsal.py`` rehearses every cell where it
rehearses the first.
"""

import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py"))
    if os.path.basename(p) != "test_run.py")


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_the_benchmarks_own_tests_pass(path):
    done = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
