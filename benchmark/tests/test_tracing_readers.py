"""The six readers of what the program's tracing names (PR 26), on made-up
``sources``: the compile split on the ``jit_compile`` records, and the
three named flash kernels' shares of busy time."""

import pytest

from run import load_module

COMPILE_READERS = ("train_trace_lower_s", "train_backend_compile_s",
                   "train_cache_misses")
SHARES = ("flash_fwd_time_share", "flash_bwd_dq_time_share",
          "flash_bwd_dkv_time_share")

#: two cold calls before the window, as the trainers record them now
RECORDS = [
    {"name": "jit_compile", "seconds": 9.5, "trace_s": 2.0, "lower_s": 1.5,
     "backend_s": 5.5, "cache_hits": 1, "cache_misses": 0},
    {"name": "jit_compile", "seconds": 1.0, "trace_s": 0.25, "lower_s": 0.25,
     "backend_s": 0.25, "cache_hits": 0, "cache_misses": 1},
]
#: the same from a program that does not split its compiles (the parent)
PARENT_RECORDS = [{"name": "jit_compile", "seconds": 9.5}]


def read(name, sources):
    return load_module("layer_metrics", name).read(sources)


def test_compile_readers_sum_their_fields_over_the_set_up_spans():
    sources = {"setup_compile_spans": RECORDS}
    assert read("train_trace_lower_s", sources) == 4.0
    assert read("train_backend_compile_s", sources) == 5.75
    assert read("train_cache_misses", sources) == 1
    # the parts stay under the accepted span metric they split
    assert read("train_trace_lower_s", sources) \
        + read("train_backend_compile_s", sources) \
        <= read("train_compile_s", sources)


def test_a_warm_run_reads_no_miss_not_nothing():
    assert read("train_cache_misses",
                {"setup_compile_spans": RECORDS[:1]}) == 0


@pytest.mark.parametrize("name", COMPILE_READERS)
@pytest.mark.parametrize("sources", [
    {}, {"setup_compile_spans": None}, {"setup_compile_spans": []},
    {"setup_compile_spans": PARENT_RECORDS},
    {"setup_compile_spans": RECORDS + PARENT_RECORDS},
], ids=["no-key", "none", "no-span", "parent-style", "mixed"])
def test_compile_readers_find_nothing_without_the_fields(name, sources):
    assert read(name, sources) is None


#: a reduced trace whose Mosaic rows carry the kernels' names, once bare
#: (as XLA names them today) and once inside a scope path
def trace_with(rows):
    return {"busy_s": 10.0, "device_ops": rows + [
        ["fusion", 4.0], ["copy", 1.0],
        ["flash_fwd_lookalike_fusion", 0.5]]}


BARE = [["tpu_custom_call:flash_bwd_dkv", 1.0],
        ["tpu_custom_call:flash_fwd", 0.75],
        ["tpu_custom_call:flash_bwd_dq", 0.5]]
SCOPED = [
    ["tpu_custom_call:transpose_jvp_residual__multiheadattention_"
     "flash_bwd_dkv_", 1.0],
    ["tpu_custom_call:jvp_residual__sequential_flash_fwd_", 0.75],
    ["tpu_custom_call:transpose_jvp_residual__flash_bwd_dq_", 0.5]]
CONFIG = {"kernels": {"flash": {"match": ["tpu_custom_call:"]}}}


@pytest.mark.parametrize("rows", [BARE, SCOPED], ids=["bare", "scoped"])
def test_each_share_reads_its_own_kernel(rows):
    sources = {"trace": trace_with(rows), "config": CONFIG}
    assert [read(name, sources) for name in SHARES] == [7.5, 5.0, 10.0]


@pytest.mark.parametrize("rows", [BARE, SCOPED], ids=["bare", "scoped"])
def test_the_three_shares_sum_to_flash_time_share(rows):
    sources = {"trace": trace_with(rows), "config": CONFIG}
    assert sum(read(name, sources) for name in SHARES) \
        == pytest.approx(read("flash_time_share", sources))


def test_two_rows_of_one_kernel_are_summed():
    rows = BARE + [["tpu_custom_call:jvp_remat__flash_fwd_", 0.25]]
    assert read("flash_fwd_time_share", {"trace": trace_with(rows)}) == 10.0


@pytest.mark.parametrize("name", SHARES)
@pytest.mark.parametrize("sources", [
    {}, {"trace": None},
    {"trace": trace_with([["tpu_custom_call:jvp__", 0.75],
                          ["tpu_custom_call:transpose_jvp___", 1.5]])},
    {"trace": trace_with([])},
], ids=["no-key", "no-trace", "parent-names", "no-kernel"])
def test_shares_find_nothing_without_named_rows(name, sources):
    assert read(name, sources) is None
