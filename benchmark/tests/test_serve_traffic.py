"""The serving traffic generator: the same work for every seed, in another
order, and every token from the seed."""

import json
import os

import numpy as np

from conftest import BENCH
from run import load_module


def mix():
    with open(os.path.join(BENCH, "traffic", "closed-c64.json")) as f:
        return json.load(f)


def test_every_seed_offers_the_same_shapes_in_another_order():
    serve = load_module("runners", "serve")
    m = mix()
    a = serve.Offered(m, 1, 50257)
    b = serve.Offered(m, 2147483999, 50257)
    assert a.shapes == b.shapes and len(a.shapes) == m["pool"]
    assert list(a.order) != list(b.order)
    assert sorted(a.order) == sorted(b.order) == list(range(m["pool"]))
    # one walk of the pool is the same multiset of work
    work = [sorted(o.shapes[i] for i in o.order) for o in (a, b)]
    assert work[0] == work[1]


def test_shapes_keep_the_limits_and_the_prefix_share():
    serve = load_module("runners", "serve")
    m = mix()
    shapes = serve.request_shapes(m)
    prompts = np.array([s[0] for s in shapes])
    news = np.array([s[1] for s in shapes])
    shared = np.array([s[2] >= 0 for s in shapes])
    assert prompts.min() >= m["prompt"]["min"]
    assert prompts.max() <= m["prompt"]["max"]
    assert news.min() >= m["new_tokens"]["min"]
    assert news.max() <= m["engine"]["max_new_tokens"]
    assert (prompts + news).max() <= 1024  # admission: prompt + new <= T
    assert abs(shared.mean() - m["prefix"]["share"]) < 0.1
    assert prompts[shared].min() >= m["prefix"]["tokens"] \
        + m["prefix"]["min_tail"]
    assert 150 < np.median(prompts) < 260 and 70 < np.median(news) < 125


def test_tokens_come_from_the_seed_and_shared_prompts_share_a_prefix():
    serve = load_module("runners", "serve")
    m = mix()
    a, again, other = (serve.Offered(m, s, 50257) for s in (5, 5, 6))
    n = m["prefix"]["tokens"]
    for i in range(40):
        p, new, shared = a.request(i)
        q, _, _ = again.request(i)
        assert np.array_equal(p, q) and p.dtype == np.int32
        if shared:
            assert any(np.array_equal(p[:n], row) for row in a.prefixes)
    assert not np.array_equal(a.prefixes, other.prefixes)
    # the pool is walked round and round with fresh tokens each time
    first, _, _ = a.request(0)
    lap, _, _ = a.request(m["pool"])
    assert first.size == lap.size and not np.array_equal(first, lap)


def test_the_runner_rehearses_end_to_end_on_the_cpu(tmp_path):
    """No cell uses ``runners/serve.py`` yet (PERF.md, open questions), so
    ``run.py --rehearse`` cannot reach it: drive it directly at the tiny
    sizes of the two files' ``rehearse`` blocks."""
    import time

    from run import merged
    serve = load_module("runners", "serve")
    with open(os.path.join(BENCH, "configs", "gpt2-small.json")) as f:
        config = json.load(f)
    m = mix()
    out = serve.run({
        "t0": time.time(), "config": merged(config, config["rehearse"]),
        "traffic": merged(m, m["rehearse"]), "seed": 2147483999,
        "seconds": 2.0, "chips": 1, "trace": False, "rehearse": True,
        "trace_dir": str(tmp_path)})
    assert out["not_correct"] == []
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["end_to_end"]) == {"setup_s", "serve_tokens_per_s",
                                      "ttft_p95_ms", "tpot_p95_ms"}
    for name in ("serve_step_ms", "serve_join_ms", "queue_wait_p95_ms",
                 "prefix_hit_share"):
        value = load_module("layer_metrics", name).read(out["sources"])
        assert value is not None and value >= 0
