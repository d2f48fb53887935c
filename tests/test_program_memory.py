"""The bytes of a step, told by the program (ISSUE 38).

Every cold call compiles its program first and puts the executable's
memory account (``obs.profile.program_memory``: the compiler's own bytes
of arguments, outputs, aliased outputs, temporaries and code, and their
sum ``program_bytes``) on the ``jit_compile`` record; the recompute plan's
record says what its estimate is made of, and ``train.remat_plan`` lists
the applications by kind.  Nothing of it runs on a warm call, and the
cold call makes the backend-compile events it made before.  CPU, toy
widths."""

import jax
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import trainers
from distkeras_tpu.data.datasets import load_lm_corpus
from distkeras_tpu.models import remat, zoo
from distkeras_tpu.obs import Registry, SpanTracer
from distkeras_tpu.obs import profile as obs_profile
from distkeras_tpu.utils.metrics import MetricsLogger
from tests.test_remat_plan import SIZES, tokens
from tests.test_tracing import fresh_cache  # noqa: F401  (a fixture)
from tests.test_trainers_sync import COMMON, make_model, toy_problem

PROGRAM_FIELDS = ("program_argument_bytes", "program_output_bytes",
                  "program_alias_bytes", "program_temp_bytes",
                  "program_code_bytes", "program_bytes",
                  "device_bytes_limit", "device_bytes_in_use")
LM_SIZES = dict(SIZES, num_hidden_layers=2, seq_len=64, vocab_size=64)


@pytest.fixture(scope="module")
def ds():
    return toy_problem(n=256)


def unplanned():
    return dk.SingleTrainer(make_model(), "sgd", **COMMON)


def sync_adag():
    return dk.ADAG(make_model(), "sgd", num_workers=4,
                   communication_window=2, mode="sync", **COMMON)


def planned():
    return dk.SingleTrainer(
        zoo.decoder_lm(**LM_SIZES), "adam",
        "sparse_categorical_crossentropy", num_epoch=2, batch_size=2,
        learning_rate=1e-3, compute_dtype="bfloat16", remat=True)


def lm_rows():
    return load_lm_corpus(n_train=4, seq_len=64, vocab_size=64, seed=1)[0]


def compiles(trainer):
    return [r for r in trainer.metrics.records
            if r["event"] == "span" and r["name"] == "jit_compile"]


def one_device_bytes(args) -> int:
    """What the call's arguments take on ONE device: an array over a mesh
    counts at its shard's size."""
    return sum(a.addressable_shards[0].data.nbytes
               for a in jax.tree_util.tree_leaves(args))


def seeing_the_cold_arguments(trainer, seen: list):
    """``trainer``, its instrumented programs noting the bytes of their
    first call's arguments (before the call donates them)."""
    instrumented = trainer._instrumented

    def noting(run, *kind):
        wrapped = instrumented(run, *kind)

        def call(*args):
            if not seen:
                seen.append(one_device_bytes(args))
            return wrapped(*args)
        return call
    trainer._instrumented = noting
    return trainer


# -- the account on every cold record ----------------------------------------

@pytest.mark.parametrize("make", (unplanned, sync_adag),
                         ids=("single", "sync_adag"))
def test_the_cold_record_carries_the_programs_account(ds, make):
    seen = []
    trainer = seeing_the_cold_arguments(make(), seen)
    trainer.train(ds)
    cold, = compiles(trainer)
    assert all(f in cold for f in PROGRAM_FIELDS)
    assert cold["program_bytes"] == (
        cold["program_argument_bytes"] + cold["program_output_bytes"]
        - cold["program_alias_bytes"] + cold["program_temp_bytes"])
    # of a mesh program one device's share: 4 workers' rows and replicas
    # count once
    assert cold["program_argument_bytes"] == seen[0]
    assert cold["program_temp_bytes"] > 0
    # the CPU's allocator reports neither
    assert cold["device_bytes_limit"] is None
    assert cold["device_bytes_in_use"] is None
    # the window program donates its carry; SyncEngine.epoch_fn nothing
    donated = cold["program_alias_bytes"] / cold["program_argument_bytes"]
    assert donated == 0 if make is sync_adag else 0.05 < donated < 1


def test_a_warm_train_call_compiles_and_records_nothing(ds, monkeypatch):
    trainer = unplanned()
    registry = trainer.tracer.registry = Registry()
    trainer.train(ds)
    assert len(compiles(trainer)) == 1
    assert registry.counter("jit.compiles").value == 1
    calls = []
    monkeypatch.setattr(
        obs_profile, "program_memory",
        lambda compiled: calls.append(compiled) or {})
    trainer.train(ds)
    assert len(compiles(trainer)) == 1 and calls == []
    assert registry.counter("jit.compiles").value == 1
    assert registry.counter("jit.retraces").value == 0


@pytest.mark.parametrize("make, data", ((unplanned, None), (sync_adag, None),
                                        (planned, lm_rows)),
                         ids=("single", "sync_adag", "planned"))
def test_compiling_first_makes_the_cache_events_the_call_made(
        ds, fresh_cache, make, data):  # noqa: F811
    """(hits, misses) of a first trainer and of its twin, as the parent
    commit's cold calls counted them (e6fbf94, the same three trainers):
    compiling before the call adds no backend compile and no cache
    event, with a plan or without."""
    rows = ds if data is None else data()
    counts = []
    for _ in range(2):
        trainer = make()
        trainer.train(rows)
        cold, = compiles(trainer)
        counts.append((cold["cache_hits"], cold["cache_misses"]))
        assert cold["program_bytes"] > 0
    assert counts == [(0, 1), (1, 0)]


def test_a_planned_program_is_judged_by_the_same_account():
    trainer = planned()
    trainer.train(lm_rows())
    cold, = compiles(trainer)
    assert cold["remat_bytes_compiled"] == cold["program_bytes"] > 0
    assert trainer._run_cache[1].remat_plan.bytes_compiled \
        == cold["program_bytes"]


def test_a_run_that_cannot_be_lowered_trains_and_records_no_account(
        ds, monkeypatch):
    make_window_fn = trainers.make_window_fn

    def plain(*args, **kwargs):
        run = make_window_fn(*args, **kwargs)
        return lambda *call: run(*call)  # no .lower, no .remat_plan

    monkeypatch.setattr(trainers, "make_window_fn", plain)
    trainer = unplanned()
    trainer.train(ds)
    cold, = compiles(trainer)
    assert not any(k.startswith(("program_", "device_")) for k in cold)
    assert cold["backend_s"] > 0  # the compile split stays
    assert np.mean(trainer.history[-1]) < np.mean(trainer.history[0])


class _Executable:
    def __init__(self, analysis):
        self.analysis = analysis

    def memory_analysis(self):
        if isinstance(self.analysis, Exception):
            raise self.analysis
        return self.analysis


@pytest.mark.parametrize("analysis", (None, NotImplementedError("no"),
                                      RuntimeError("UNIMPLEMENTED")),
                         ids=("none", "not_implemented", "runtime_error"))
def test_an_executable_without_an_analysis_gives_an_empty_account(analysis):
    assert obs_profile.program_memory(_Executable(analysis)) == {}
    assert obs_profile.program_memory(object()) == {}


def test_the_account_of_an_executable_is_the_compilers_own():
    x = np.ones((64, 64), np.float32)
    compiled = jax.jit(lambda a, b: a @ b + 1.0,
                       donate_argnums=0).lower(x, x).compile()
    account = obs_profile.program_memory(compiled)
    analysis = compiled.memory_analysis()
    assert tuple(account) == PROGRAM_FIELDS
    assert account["program_argument_bytes"] == 2 * x.nbytes \
        == analysis.argument_size_in_bytes
    assert account["program_output_bytes"] == x.nbytes
    assert account["program_alias_bytes"] == x.nbytes  # the donated one
    assert account["program_bytes"] == 2 * x.nbytes \
        + analysis.temp_size_in_bytes


def test_the_fullest_device_is_a_maximum_never_a_sum(monkeypatch):
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devices = [Device({"bytes_in_use": 5, "bytes_limit": 100}),
               Device({"bytes_in_use": 9, "bytes_limit": 100}),
               Device(None)]
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    x = np.ones((8,), np.float32)
    account = obs_profile.program_memory(
        jax.jit(lambda a: a + 1).lower(x).compile())
    assert account["device_bytes_in_use"] == 9
    assert account["device_bytes_limit"] == 100


# -- the plan's estimate in its terms ----------------------------------------

def decided(model, budget, batch):
    plan = remat.Plan(budget=budget)
    plan.tracer = SpanTracer(MetricsLogger(None))
    shapes = jax.eval_shape(model.init)
    jax.eval_shape(lambda p: model.layer.apply(
        p, shapes["state"], batch, train=True, remat=plan)[0],
        shapes["params"])
    span, = (r for r in plan.tracer.sink.records
             if r.get("name") == "train.remat_plan")
    return plan, span


@pytest.mark.parametrize("budget", (0, 2e6, 1e12),
                         ids=("frugal", "mixed", "every_child_kept"))
def test_the_estimates_parts_sum_to_it_and_the_kinds_to_the_children(budget):
    plan, span = decided(zoo.decoder_lm(**SIZES, attention_impl="flash"),
                         budget, tokens(1))
    record = plan.record()
    assert record["remat_held_bytes"] + record["remat_residual_bytes"] \
        + record["remat_grads_bytes"] == record["remat_bytes_estimated"] > 0
    assert record["remat_bytes_estimated"] \
        == plan.held + remat.peak(plan.sizes, plan.first_kept)
    by_kind = span["by_kind"]
    assert sum(row["kept"] for row in by_kind) \
        == record["remat_children_kept"] == span["remat_children_kept"]
    assert sum(row["count"] for row in by_kind) == plan.children == 9
    assert {k: span[k] for k in record} == record
    # embedding, full and window attention, dense and sparse FF, norm, head
    assert len(by_kind) == 7
    assert [row["first"] for row in by_kind] == [0, 1, 2, 3, 4, 7, 8]
    assert by_kind[1]["kind"] == by_kind[3]["kind"] \
        == "residual/sequential/rmsnorm/multiheadattention"
    assert (by_kind[1]["count"], by_kind[4]["count"]) == (2, 2)
    for row in by_kind:
        assert row["whole"] >= row["saved"] > 0
    kept = {0: 1, 2e6: None, 1e12: 9}[budget]
    assert kept is None or record["remat_children_kept"] == kept
    assert 1 <= record["remat_children_kept"] <= 9


def test_the_parts_are_read_where_the_peak_is_reached():
    sizes = [{"saved": 1, "whole": 10, "grads": 100},
             {"saved": 2, "whole": 50, "grads": 5},
             {"saved": 3, "whole": 20, "grads": 7}]
    # the early child's gradients are the heaviest: the most is where the
    # backward ends, whatever was kept (the later residuals are freed)
    assert remat.peak_parts(sizes, 2) == (10, 112)
    assert remat.peak_parts(sizes, 0) == (10, 112)
    # heavy late gradients: all kept, the most is where the backward
    # begins; the last alone kept, at the child that is run again whole
    late = [dict(s, grads=g) for s, g in zip(sizes, (0, 0, 1000))]
    assert remat.peak_parts(late, 0) == (10 + 50 + 20, 1000)
    assert remat.peak_parts(late, 2) == (1 + 50, 1000)
    for first in range(3):
        assert sum(remat.peak_parts(sizes, first)) == remat.peak(sizes, first)
    assert remat.peak_parts([], 0) == (0, 0) and remat.peak([], 0) == 0


def test_nothing_estimated_is_three_zero_parts_and_no_kinds():
    plan, span = decided(zoo.decoder_lm(**SIZES, attention_impl="flash"),
                         None, tokens(1))
    record = plan.record()
    assert [record[f"remat_{p}_bytes"] for p in remat.ESTIMATE_PARTS] \
        == [0, 0, 0]
    assert record["remat_bytes_estimated"] == 0 and span["by_kind"] == []
    assert (record["remat_children_kept"],
            record["remat_children_recomputed"]) == (1, 8)


def test_a_mixers_kept_states_are_a_row_of_by_kind():
    """What the counter ``ssm.state_bytes`` said: a checkpointed mixer
    holds its input, the scan's output and the chunks' incoming states."""
    from tests.test_hybrid_lm import SIZES as HYBRID
    batch = np.zeros((2, 64), np.int32)
    plan, span = decided(
        zoo.hybrid_lm(**HYBRID, attention_impl="flash", ssm_impl="pallas"),
        1e12, batch)
    rows = [r for r in span["by_kind"] if "mamba2mixer" in r["kind"]]
    assert len(rows) == 1 and rows[0]["count"] == 2  # ME*-EM
    b, t, d = 2, 64, HYBRID["hidden_size"]
    h, p, n = (HYBRID[k] for k in ("mamba_num_heads", "mamba_head_dim",
                                   "ssm_state_size"))
    chunks = t // HYBRID["chunk_size"]
    carried = b * t * d * 4                       # its input, float32
    y, states = b * t * h * p * 4, b * chunks * h * p * n * 4
    assert rows[0]["saved"] == carried + y + states
