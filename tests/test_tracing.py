"""One clock and real names (ISSUE 26).

The four parts of the tracing the trainers carry: every span holds a
``jax.profiler.TraceAnnotation`` (so the program's spans lie in a
profiler trace's host plane), ``train()``'s work is split into the five
``train.*`` spans, the ``jit_compile`` record says what the cold call
spent (trace / lower / backend, persistent-cache hit or miss), and the
compiled program carries names: the three flash kernels and the step
program's layer scopes — metadata only, the program's operations do not
change."""

import collections
import contextlib
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import zoo
from distkeras_tpu.obs import SpanTracer
from distkeras_tpu.obs import profile as obs_profile
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.ops.pallas_attention import flash_attention
from distkeras_tpu.parallel.sync import make_window_fn
from distkeras_tpu.utils.metrics import MetricsLogger
from tests.test_trainers_sync import COMMON, make_model, toy_problem

COMPILE_FIELDS = ("trace_s", "lower_s", "backend_s", "cache_hits",
                  "cache_misses")


@pytest.fixture(scope="module")
def ds():
    return toy_problem(n=256)


def _spans(trainer, name=None):
    return [r for r in trainer.metrics.records if r["event"] == "span"
            and (name is None or r["name"] == name)]


# -- 1. one clock: a span is a TraceAnnotation too ---------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))


def test_span_holds_an_annotation_of_its_name_in_nesting_order(monkeypatch):
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    tracer = SpanTracer(MetricsLogger(None))
    with tracer.span("train"):
        with tracer.span("train.dispatch", epoch=0):
            pass
        with pytest.raises(KeyError):
            with tracer.span("train.readback"):
                raise KeyError("a failing span closes its annotation too")
    assert _Recorder.log == [
        ("open", "train"), ("open", "train.dispatch"),
        ("close", "train.dispatch"), ("open", "train.readback"),
        ("close", "train.readback"), ("close", "train")]


@pytest.mark.parametrize("where", ["__init__", "__enter__", "__exit__"])
def test_span_emits_its_record_when_the_annotation_raises(monkeypatch,
                                                          where):
    class Broken(_Recorder):
        log: list = []

    def boom(self, *a):
        raise RuntimeError(f"profiler refused in {where}")

    setattr(Broken, where, boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Broken)
    sink = MetricsLogger(None)
    tracer = SpanTracer(sink)
    with tracer.span("train"):
        with tracer.span("train.stage"):
            pass
    assert [(r["name"], r["path"]) for r in sink.records] == [
        ("train.stage", "train/train.stage"), ("train", "train")]
    assert all(r["seconds"] >= 0 for r in sink.records)
    assert tracer.depth == 0


def test_span_is_not_what_imports_jax(monkeypatch):
    """A process that never imported JAX (a PS shard server) gets spans
    and no annotation: the tracer never imports JAX itself."""
    monkeypatch.delitem(sys.modules, "jax")
    sink = MetricsLogger(None)
    with SpanTracer(sink).span("ps.apply"):
        pass
    assert "jax" not in sys.modules
    assert sink.records[0]["name"] == "ps.apply"


def test_span_yields_its_record_for_what_the_scope_learns():
    sink = MetricsLogger(None)
    tracer = SpanTracer(sink)
    with tracer.span("outer"):
        with tracer.span("inner", kind="window") as record:
            record.update(trace_s=0.5, seconds=99.0)
    inner, outer = sink.records
    assert inner["trace_s"] == 0.5 and inner["kind"] == "window"
    assert inner["seconds"] < 99.0  # the measured duration stays
    assert "trace_s" not in outer


# -- 2. the spans of train() in the profiler's host plane --------------------

TRAIN_SPANS = {"train", "train.stage", "train.init", "train.dispatch",
               "train.readback", "train.to_host"}


def _host_event_names(trace_dir) -> set:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}


def test_single_trainer_spans_enter_the_profiler_trace(ds, tmp_path):
    t = dk.SingleTrainer(make_model(), "sgd", **COMMON)
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.train(ds)
    finally:
        jax.profiler.stop_trace()
    assert TRAIN_SPANS | {"jit_compile"} <= _host_event_names(str(tmp_path))
    # and the same spans in the JSONL stream, one dispatch and one
    # readback an epoch, all under the train span
    by_name = collections.Counter(r["name"] for r in _spans(t))
    assert by_name["train.dispatch"] == by_name["train.readback"] \
        == COMMON["num_epoch"]
    assert by_name["train.stage"] == by_name["train.init"] \
        == by_name["train.to_host"] == by_name["train"] == 1
    assert {r["path"].split("/")[0] for r in _spans(t)} == {"train"}
    assert sorted(r["epoch"] for r in _spans(t, "train.readback")) \
        == list(range(COMMON["num_epoch"]))


def test_sync_trainer_carries_the_same_spans(ds):
    t = dk.ADAG(make_model(), "sgd", num_workers=2, communication_window=2,
                **COMMON)
    t.train(ds)
    assert TRAIN_SPANS <= {r["name"] for r in _spans(t)}


# -- 3. the jit_compile span says what it spent ------------------------------

def test_jit_compile_record_carries_the_compile_split(ds):
    t = dk.SingleTrainer(make_model(), "sgd", **COMMON)
    t.train(ds)
    cold, = _spans(t, "jit_compile")
    assert all(f in cold for f in COMPILE_FIELDS)
    assert cold["trace_s"] > 0 and cold["lower_s"] > 0 \
        and cold["backend_s"] > 0
    # JAX's timers nest; one that holds others replaces them, so the
    # three never pass the span that holds them
    assert cold["trace_s"] + cold["lower_s"] + cold["backend_s"] \
        <= cold["seconds"]
    assert cold["path"] == "train/train.dispatch/jit_compile"
    t.train(ds)  # warm: the same trainer compiles nothing more
    assert len(_spans(t, "jit_compile")) == 1


def test_compile_ledger_counts_nested_timers_once():
    """JAX's stage timers nest and report as they close, inner first: a
    timer that holds others replaces them, whatever their stage."""
    import jax.monitoring as monitoring
    trace, lower, backend = obs_profile._STAGE_FIELD
    before = obs_profile.compile_totals()
    for event, start, end in [
            (trace, 10.5, 11.0),    # a jitted callee, traced inside ...
            (trace, 11.5, 11.75),   # ... and another ...
            (trace, 10.0, 12.0),    # ... the program's own trace
            (trace, 12.25, 12.5),   # a helper traced while lowering
            (lower, 12.0, 13.0),
            (backend, 13.0, 17.0),
            ("/jax/some/other_duration", 0.0, 100.0)]:
        monitoring.record_event_time_span(event, start, end)
    spent = obs_profile.compile_spent(before)
    assert (spent["trace_s"], spent["lower_s"], spent["backend_s"]) \
        == (2.0, 1.0, 4.0)
    # a reading closes the books: a later timer takes nothing back
    monitoring.record_event_time_span(trace, 0.0, 1.0)
    assert obs_profile.compile_spent(before)["lower_s"] == 1.0


@pytest.fixture()
def fresh_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of this test's
    own, keeping every program however small."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (str(tmp_path / "cache"), 0.0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in zip(names, was):
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_first_trainer_counts_a_miss_and_its_twin_a_hit(ds, fresh_cache):
    counts = []
    for _ in range(2):
        t = dk.SingleTrainer(make_model(), "sgd", **COMMON)
        t.train(ds)
        cold, = _spans(t, "jit_compile")
        counts.append((cold["cache_hits"], cold["cache_misses"]))
    assert counts == [(0, 1), (1, 0)]


# -- 4. names in the compiled program ----------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _pallas_names(fn, *args) -> list:
    return sorted(e.params["name"]
                  for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                  if e.primitive.name == "pallas_call")


def test_the_three_flash_kernels_are_named():
    q = jnp.ones((1, 128, 2, 32), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True).sum()

    assert _pallas_names(flash_attention, q, q, q) == ["flash_fwd"]
    assert _pallas_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def _tiny_gpt_window():
    """(window program, its arguments) of a 2-block ``gpt_lm`` with the
    flash kernels, bf16 compute, adam: the cells' program at toy size."""
    model = zoo.gpt_lm(vocab_size=64, dim=32, num_heads=2, num_blocks=2,
                       seq_len=128, attention_impl="flash")
    optimizer = get_optimizer("adam", 1e-3)
    run = make_window_fn(model, get_loss("sparse_categorical_crossentropy"),
                         optimizer, compute_dtype=jnp.bfloat16)
    variables = model.init(0)
    tokens = jnp.zeros((2, 2, 128), jnp.int32)
    return run, (variables, optimizer.init(variables["params"]),
                 jax.random.PRNGKey(0), tokens, tokens)


def test_window_program_carries_layer_scopes_and_no_layer_index():
    run, args = _tiny_gpt_window()
    text = run.lower(*args).as_text(debug_info=True)
    # a named location prints as loc("<op_name>"(<where>)), a file's as
    # loc("<file>":<line>:<col>)
    op_names = set(re.findall(r'loc\("([^"]+)"\(', text))
    scopes = {part for name in op_names for part in name.split("/")[:-1]}
    # transforms wrap a path's first scope: jvp(residual), transpose(jvp(loss))
    bare = {re.sub(r"^(?:\w+\()*|\)*$", "", s) for s in scopes}
    assert {"loss", "optimizer", "cast_params", "layout", "qkv", "out_proj",
            "multiheadattention", "layernorm", "dense", "embedding",
            "residual", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} \
        <= bare
    # twelve blocks must share one path: no scope carries an index
    assert not [s for s in bare if re.search(r"\d$", s)]
    assert any("multiheadattention/layout/" in n for n in op_names)


def _opcode_counts(compiled) -> collections.Counter:
    return collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(",
            compiled.as_text(), re.M))


def test_scopes_are_metadata_the_compiled_operations_do_not_change(
        monkeypatch):
    run, args = _tiny_gpt_window()
    named = _opcode_counts(run.lower(*args).compile())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    run, args = _tiny_gpt_window()
    lowered = run.lower(*args)
    assert "multiheadattention" not in lowered.as_text(debug_info=True)
    bare = _opcode_counts(lowered.compile())
    assert sum(named.values()) > 100
    assert named == bare
