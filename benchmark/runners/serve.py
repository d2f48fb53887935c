"""Runner for serving cells: ``ServeServer(DecodeEngine(...).warmup())``
under a closed loop of callers.

Everything about the traffic is data in the traffic file: the engine's
``ServeConfig`` fields, the number of callers, the two lognormal length
distributions, the shared prefixes and how long the service is filled
before the window.  The generator below is the one general generator:
it draws a fixed pool of request *shapes* (prompt length, new tokens,
which shared prefix if any) from the traffic file's own ``shape_seed``,
so every ``--seed`` offers the same work; ``--seed`` orders the pool and
makes every token.  Callers are threads of this process, each with its
own ``ServeClient`` over TCP, each sending its next request when the
reply to the last has arrived; they walk the pool round and round.

The window's rows are the replies that arrive inside it.  ``attempted``
counts the requests sent inside it, ``failed`` those rejected, errored
or still unanswered when the drain's time is up.
"""

import importlib
import itertools
import sys
import threading
import time

import numpy as np

from common import SEED_MOD, resolve, trace_options


def lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    draws = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(draws), spec["min"], spec["max"]).astype(int)


def request_shapes(mix: dict) -> list:
    """The pool: (prompt length, new tokens, prefix group or -1), the same
    for every seed."""
    rng = np.random.default_rng(mix["shape_seed"])
    n, prefix = mix["pool"], mix["prefix"]
    prompts = lognormal_lengths(rng, mix["prompt"], n)
    news = lognormal_lengths(rng, mix["new_tokens"], n)
    weights = 1.0 / np.arange(1, prefix["groups"] + 1)  # popularity 1/rank
    groups = rng.choice(prefix["groups"], size=n, p=weights / weights.sum())
    shared = rng.random(n) < prefix["share"]
    least = prefix["tokens"] + prefix["min_tail"]
    return [(max(int(p), least) if s else int(p), int(k),
             int(g) if s else -1)
            for p, k, g, s in zip(prompts, news, groups, shared)]


class Offered:
    """The stream of requests: the pool in the seed's order, round and
    round, every token from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.shapes = request_shapes(mix)
        self.seed, self.vocab = seed, vocab
        self.order = np.random.default_rng([seed, 1]).permutation(
            len(self.shapes))
        prefix = mix["prefix"]
        self.prefixes = np.random.default_rng([seed, 2]).integers(
            0, vocab, size=(prefix["groups"], prefix["tokens"]),
            dtype=np.int32)

    def request(self, i: int) -> tuple:
        """(prompt, new tokens, shared) of the stream's i-th request."""
        length, new, group = self.shapes[self.order[i % len(self.order)]]
        rng = np.random.default_rng([self.seed, 3, i])
        prompt = rng.integers(0, self.vocab, size=length, dtype=np.int32)
        if group >= 0:
            prompt[:self.prefixes.shape[1]] = self.prefixes[group]
        return prompt, new, group >= 0


def caller(port, offered, counter, rows, stop, lock):
    import distkeras_tpu as dk
    with dk.ServeClient("127.0.0.1", port) as client:
        while not stop.is_set():
            with lock:
                i = next(counter)
            prompt, new, shared = offered.request(i)
            row = {"i": i, "prompt": prompt, "new": new, "shared": shared,
                   "sent": time.perf_counter(), "reply": None}
            with lock:
                rows.append(row)
            try:
                reply = client.generate(prompt, max_new_tokens=new)
            except Exception as e:  # a dead connection is a failed request
                reply = {"ok": False, "error": repr(e)}
            row["done"] = time.perf_counter()
            row["reply"] = reply


def histogram_delta(before: dict, after: dict, name: str):
    count = after[name]["count"] - before[name]["count"]
    if count <= 0:
        return None
    return (after[name]["sum"] - before[name]["sum"]) / count


def check_against_reference(config, variables, sample, not_correct):
    """Prefill and cached decode must agree with the reference's full
    forward pass: at every served position the served token's reference
    logit is within ``tie`` of the top one (a served token may differ from
    the reference's argmax only at a numerical tie: the service batches
    and pads differently, and the TPU rounds float32 matmul inputs to
    bfloat16 unless told otherwise; a wrong cache row or position gives a
    token far below the top)."""
    reference = importlib.import_module("reference." + config["reference"])
    sizes, tie = config["sizes"], config["serve"]["tie_tolerance"]
    tokens = np.zeros((len(sample), sizes["seq_len"]), np.int32)
    for b, row in enumerate(sample):
        full = np.concatenate([row["prompt"], row["reply"]["tokens"]])
        tokens[b, :full.size] = full
    logits = np.asarray(reference.forward(variables, tokens, sizes))
    worst = 0.0
    for b, row in enumerate(sample):
        p, served = row["prompt"].size, np.asarray(row["reply"]["tokens"])
        at = logits[b, p - 1:p - 1 + served.size]
        gap = at.max(axis=-1) - at[np.arange(served.size), served]
        worst = max(worst, float(gap.max()))
    if worst > tie:
        not_correct.append(f"a served token's reference logit is {worst:.4f}"
                           f" under the top one (tie tolerance {tie})")
    return worst


def run(ctx: dict) -> dict:
    import jax

    import distkeras_tpu as dk
    config, mix = ctx["config"], ctx["traffic"]
    marks = {"imported": time.time()}
    seed = ctx["seed"] % SEED_MOD
    model = resolve(config["builder"])(**config["sizes"],
                                       **config.get("builder_args", {}))
    # weights on the device in one jitted call, in the type they are served in
    variables = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(variables)
    marks["weights_made"] = time.time()
    engine = dk.DecodeEngine(model, variables,
                             dk.ServeConfig(**mix["engine"])).warmup()
    marks["warmed_up"] = time.time()
    server = dk.ServeServer(engine).start()
    offered = Offered(mix, seed, config["sizes"]["vocab_size"])
    rows, stop, lock = [], threading.Event(), threading.Lock()
    counter = itertools.count()
    threads = [threading.Thread(
        target=caller, daemon=True,
        args=(server.port, offered, counter, rows, stop, lock))
        for _ in range(mix["callers"])]
    try:
        with jax.profiler.TraceAnnotation("bench:fill"):
            for t in threads:
                t.start()
            time.sleep(mix["fill_seconds"])  # slots and prefix cache fill
        before = engine.registry.snapshot()
        marks["filled"] = time.time()
        setup_s = marks["filled"] - ctx["t0"]
        t0 = time.perf_counter()
        if ctx["trace"]:
            # the profiler takes only the window's last seconds
            time.sleep(max(0.0, ctx["seconds"] - mix["trace_seconds"]))
            jax.profiler.start_trace(ctx["trace_dir"],
                                     profiler_options=trace_options())
            try:
                with jax.profiler.TraceAnnotation("bench:window"):
                    time.sleep(min(ctx["seconds"], mix["trace_seconds"]))
            finally:
                jax.profiler.stop_trace()
        else:
            time.sleep(ctx["seconds"])
        t1 = time.perf_counter()
        after = engine.registry.snapshot()
        stop.set()
        deadline = time.monotonic() + mix["drain_seconds"]
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        draining = engine._draining
    finally:
        stop.set()
        server.stop(drain=False)
    final = engine.registry.snapshot()
    marks["drained"] = time.time()

    with lock:
        rows = list(rows)
    sent = [r for r in rows if t0 <= r["sent"] <= t1]
    failed = [r for r in sent if not (r["reply"] or {}).get("ok")]
    inside = [r for r in rows if (r["reply"] or {}).get("ok")
              and t0 <= r["done"] <= t1]
    not_correct = []
    if not inside:
        not_correct.append("no reply arrived inside the window")
    for name in ("serve.rejected", "jit.retraces"):
        if final[name]["value"]:
            not_correct.append(f"{name} = {final[name]['value']}")
    if draining:
        not_correct.append("the engine ended up draining")
    if failed:
        not_correct.append(f"{len(failed)} of {len(sent)} requests sent in "
                           f"the window failed: "
                           f"{(failed[0]['reply'] or 'unanswered')}")
    for r in inside:
        if np.asarray(r["reply"]["tokens"]).size != r["new"]:
            not_correct.append(f"request {r['i']} asked for {r['new']} "
                               f"tokens, got "
                               f"{np.asarray(r['reply']['tokens']).size}")
            break
    worst_gap = None
    if inside:
        pick = np.random.default_rng([seed, 4]).choice(
            len(inside), size=min(mix["check_sample"], len(inside)),
            replace=False)
        worst_gap = check_against_reference(
            config, variables, [inside[i] for i in pick], not_correct)

    replies = [(r, r["reply"]) for r in inside]
    # both transport legs as the caller's clock saw them, plus the server's
    # own time to the first token
    ttft = [1e3 * ((r["done"] - r["sent"] - y["e2e_s"]) + y["ttft_s"])
            for r, y in replies]
    tpot = [1e3 * (y["e2e_s"] - y["ttft_s"]) / (r["new"] - 1)
            for r, y in replies if r["new"] > 1]
    end_to_end = {"setup_s": setup_s}
    if inside:
        end_to_end.update(
            serve_tokens_per_s=sum(r["new"] for r in inside) / (t1 - t0),
            ttft_p95_ms=float(np.percentile(ttft, 95)),
            tpot_p95_ms=float(np.percentile(tpot, 95)))
    shared = [r for r in inside if r["shared"]]
    marks["checked"] = time.time()
    sys.stderr.write(f"serve.py: {len(inside)} replies in the window, "
                     f"worst reference gap {worst_gap}\n")
    return {
        "marks": marks,
        "not_correct": not_correct,
        "attempted": len(sent), "failed": len(failed),
        "end_to_end": end_to_end,
        "sources": {
            "histograms": {
                name: histogram_delta(before, after, name)
                for name in ("serve.step_seconds", "serve.host_seconds",
                             "serve.join_seconds")},
            "queue_wait_ms": [1e3 * y.get("queue_wait_s", 0.0)
                              for _, y in replies],
            "prefix_hit_share": (sum(bool(r["reply"].get("warm"))
                                     for r in shared) / len(shared))
            if shared else None,
        },
    }
