"""What the runners and readers share."""

import importlib

#: every seed the driver may send (a little over 2**31) folds into what
#: ``jax.random.PRNGKey`` and the trainers' ``seed + k`` take
SEED_MOD = 2 ** 31 - 2 ** 16


def resolve(path: str):
    """``"module:name"`` -> the object; a module of ``benchmark/`` goes by
    its bare name (``flops:gpt_lm_train``)."""
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def trace_options():
    """The profiler as every runner starts it: the host's own spans and the
    runner's annotations, no Python call tracing."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options
