"""Test config: run everything on a virtual 8-device CPU mesh.

This is our equivalent of the reference's Spark ``local[*]`` trick
(multi-worker semantics on one machine, SURVEY.md §4): 8 fake XLA devices
exercise the real psum/mesh code paths without a TPU pod.

The suite never touches an accelerator, whatever the environment says:
``jax.config.update`` pins the CPU even if a pytest plugin imported jax
before this file ran.  XLA_FLAGS must be in the environment before the
CPU client spins up.
"""

import os

os.environ.setdefault("KERAS_BACKEND", "jax")  # Keras-3 ingestion adapter

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 fake devices, got {len(d)}"
    return d


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _dklint_racecheck():
    """Runtime race detector (ISSUE 3): wraps every ParameterServer's
    mutex + shared dicts in tracking proxies and fails any test whose
    threads performed an unguarded concurrent write.

    ON by default for the tier-1 suite (ISSUE 5 satellite — measured
    overhead on the multiprocess tests is ~1% mean / <7% worst-case over
    three timed pairs, see README "Static analysis"); set
    ``DKLINT_RACECHECK=0`` to opt out."""
    from distkeras_tpu.analysis import racecheck
    if not racecheck.enabled_by_env():
        yield
        return
    with racecheck.enabled() as violations:
        try:
            yield
        finally:
            # snapshot before the context exit clears the scoped list
            found = list(violations)
    assert not found, (
        "dklint racecheck: unguarded concurrent write(s) to PS shared "
        "state:\n" + "\n".join(
            f"  {v['dict']}[{v['key']!r}] via {v['op']} on thread "
            f"{v['thread']}\n{v['stack']}" for v in found))
