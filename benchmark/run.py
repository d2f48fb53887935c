"""One run of one benchmark cell: set-up, a measured window, one result line.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  The cell is looked up in
``BENCHMARK.json``; its configuration is the file the entry names, its
traffic (for a training cell, the job) is ``benchmark/traffic/<traffic>
.json``, the code that drives the system is ``benchmark/runners/<runner>
.py`` (named by the traffic file), and each per-layer metric is read by
``benchmark/layer_metrics/<metric>.py``.  This file knows no cell,
configuration, traffic or metric by name, so a later PR adds them as new
files and new entries.

The last line of standard output is the result, one JSON object.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, taken with
the profiler off; with ``--trace 1`` they are its per-layer metrics, and
the device's busy time and a breakdown come from a profiler trace of a
short call of the same program.

Off the chip (``jax.devices()[0].platform != "tpu"``), or with fewer
devices than the cell asks for, it exits non-zero and prints no result.
``--rehearse`` tries the same control flow at the tiny sizes of the
files' ``rehearse`` blocks wherever JAX runs; it exits 3 and its last
line has no ``metrics`` key, so it can never pass for a result.
"""

import time

T0 = time.time()  # process start, as near as Python can see it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """``benchmark/<directory>/<name>.py`` as a module (a metric's name may
    hold a dot, so this goes by path, not by import name)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: BENCHMARK.json has no {what} {name!r}")


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip.  The allocator's
    ``peak_bytes_in_use`` counts buffers (weights, optimizer state, staged
    data, caches) and leaves out what a running program takes for its own
    temporaries (2.1 GB for a GPT-2 small step whose activations alone are
    over 10 GB; my chip run, PR 24).  So the temporaries of the largest
    program this process holds are added, as the compiler sized them: one
    program runs on a chip at a time, over the buffers."""
    buffers = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)
    temporaries = max(
        (e.get_compiled_memory_stats().temp_size_in_bytes
         for e in devices[0].client.live_executables()), default=0)
    sys.stderr.write(f"run.py: memory: buffers peak {buffers} B, largest "
                     f"program's temporaries {temporaries} B\n")
    return int(buffers + temporaries)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's files here (default: a "
                         "temporary directory, removed at exit)")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    entry = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], entry["config"], "config")
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    if args.rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    # keep every program, however quickly it compiled: a run's set-up is
    # then the same work each time after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    chips = int(entry["chips"])
    if devices[0].platform != "tpu" and not args.rehearse:
        sys.stderr.write(f"run.py: JAX found no TPU (devices: {devices}); "
                         f"nothing was run\n")
        return 2
    if len(devices) < chips:
        sys.stderr.write(f"run.py: {args.workload} needs {chips} devices, "
                         f"JAX found {len(devices)}\n")
        return 2

    import peaks
    import reduce_trace
    runner = load_module("runners", traffic["runner"])
    ctx = {
        "t0": T0, "config": config, "traffic": traffic,
        "seed": int(args.seed), "seconds": float(seconds), "chips": chips,
        "trace": bool(args.trace), "rehearse": args.rehearse,
    }
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
        ctx["trace_dir"] = args.trace_dir or tmp
        out = runner.run(ctx)
        trace = None
        if args.trace:
            trace = reduce_trace.reduce(ctx["trace_dir"])
    for reason in out["not_correct"]:
        sys.stderr.write(f"run.py: not correct: {reason}\n")
    sys.stderr.write("run.py: seconds since process start: " + json.dumps(
        {k: round(v - T0, 3) for k, v in out.get("marks", {}).items()})
        + "\n")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak(devices[:chips])}
    line = {"correct": not out["not_correct"], "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        sources = dict(out["sources"], trace=trace, config=config,
                       traffic=traffic, chips=chips,
                       end_to_end=out["end_to_end"],
                       peak=None if args.rehearse
                       else peaks.peak(devices[0].device_kind))
        values = {}
        for m in bench["per_layer"]:
            if in_cell(m, args.workload):
                v = load_module("layer_metrics", m["name"]).read(sources)
                if v is not None:
                    values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                 "idle_gaps": trace["idle_gaps"][:10]}
    else:
        values = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                              "unit": m["unit"]}
                  for m in bench["end_to_end"] if in_cell(m, args.workload)}
    if args.rehearse:
        print(json.dumps({**line, "rehearsal": True,
                          "rehearsal_values": values, "device": device}),
              flush=True)
        return 3
    print(json.dumps({**line, "metrics": values, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
