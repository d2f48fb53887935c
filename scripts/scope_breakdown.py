"""Device time by layer scope, for one traced run of a benchmark cell.

The step program carries ``jax.named_scope`` names (ISSUE 26: ``loss``,
``optimizer``, ``cast_params``, each container's child by class name,
``qkv`` / ``layout`` / ``out_proj`` in attention, the three flash
kernels), but a profiler trace names a device event by its HLO
instruction alone.  This joins the two:

1. run ``benchmark/run.py --workload <cell> --trace 1`` in this process,
   keeping the profiler's files;
2. take the HLO text of the live epoch executable (the one with the
   largest temporaries, as ``run.py:memory_peak`` picks it) and read each
   instruction's ``op_name`` from its metadata.  A fusion takes the
   ``op_name`` of the matmul it holds (``convolution`` / ``dot``: that is
   where its time goes), else its own, which XLA copies from the fusion's
   root;
3. take each "XLA Ops" event's own time inside the window (as
   ``benchmark/reduce_trace.py`` does, but by instruction, instance number
   kept), look its ``op_name`` up, and sum by scope.

A fusion that spans two scopes (a weight-gradient matmul fused with
adam's update) goes to one of them whole: the table says where an
instruction was born, to within such fusions.  Needs the TPU; writes
``chiprun_out/scope_<cell>.json`` and prints the table.

A stopgap for PERF.md section 5's table: ``SCOPES`` below knows
``zoo.gpt_lm``'s layer paths only, and the join belongs in
``benchmark/reduce_trace.py`` (PERF.md section 7, item 2), which this PR
may not edit.  When the reduction takes the executable's ``op_name``s
the shares become readers under ``benchmark/layer_metrics/`` and this
file goes.

    python scripts/scope_breakdown.py --workload gpt2s-train --seed 11
"""

import argparse
import contextlib
import gzip
import io
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
MATMUL = re.compile(r" = \S+ (?:convolution|dot)\(")

#: first match wins; paths look like
#: ``jit(run)/while/body/transpose(jvp(residual))/sequential/dense/dot_general``
SCOPES = [
    ("optimizer", re.compile(r"/optimizer/")),
    ("cast_params", re.compile(r"cast_params\)*/")),
    ("loss", re.compile(r"\(loss\)+/|/loss/")),
    ("attention: kernels", re.compile(r"/flash_(fwd|bwd_dq|bwd_dkv)/")),
    ("attention: layout copies", re.compile(r"multiheadattention/layout/")),
    ("attention: qkv", re.compile(r"multiheadattention/qkv/")),
    ("attention: out_proj", re.compile(r"multiheadattention/out_proj/")),
    ("attention: other", re.compile(r"multiheadattention/")),
    ("LayerNorm", re.compile(r"layernorm\)*/")),
    ("MLP", re.compile(r"residual\)*/sequential/dense/")),
    ("embedding", re.compile(r"embedding\)*/")),
    ("head", re.compile(r"\(dense\)+/|/dense/")),
]


def scope_of(op_name: str) -> str:
    for scope, pattern in SCOPES:
        if pattern.search(op_name):
            return scope
    return "no scope"


def op_names_of(hlo_text: str) -> dict:
    """instruction name -> op_name, over every computation of a module."""
    own, fused_matmul, calls, computation = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        found = OP_NAME.search(line)
        own[name] = found.group(1) if found else ""
        called = CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if found and MATMUL.search(line):
            fused_matmul.setdefault(computation, found.group(1))
    return {name: fused_matmul.get(calls.get(name)) or op
            for name, op in own.items()}


def by_instruction(loaded: dict) -> tuple:
    """(own seconds by instruction name, busy seconds): the reduction of
    ``reduce_trace.reduce_loaded`` itself (its window, its own times),
    with only its naming of an event swapped for one that keeps the
    instance number."""
    import reduce_trace
    from unittest import mock
    with mock.patch.object(
            reduce_trace, "op_name",
            lambda text: text.split(" = ", 1)[0].lstrip("%")):
        reduced = reduce_trace.reduce_loaded(loaded)
    return dict(reduced["device_ops"]), reduced["busy_s"]


def live_epoch_hlo() -> str:
    import jax
    executables = jax.devices()[0].client.live_executables()
    largest = max(executables, key=lambda e:
                  e.get_compiled_memory_stats().temp_size_in_bytes)
    return largest.hlo_modules()[0].to_string()


def table(seconds: dict, op_names: dict) -> dict:
    scopes, unknown = {}, 0.0
    for name, s in seconds.items():
        if name not in op_names:
            unknown += s
        scope = scope_of(op_names.get(name, ""))
        scopes[scope] = scopes.get(scope, 0.0) + s
    return {"by_scope": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
            "not_in_the_hlo_text_s": unknown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="gpt2s-train")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    trace_dir = os.path.join(args.out, f"trace_{args.workload}")
    os.makedirs(trace_dir, exist_ok=True)

    import run as bench_run
    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        rc = bench_run.main(["--workload", args.workload, "--seed",
                             str(args.seed), "--trace", "1",
                             "--trace-dir", trace_dir])
    line = captured.getvalue().strip().splitlines()[-1] if rc == 0 else None
    if line is None:
        sys.stderr.write(captured.getvalue())
        return rc or 1

    import reduce_trace
    hlo_text = live_epoch_hlo()
    seconds, busy = by_instruction(
        reduce_trace.load(reduce_trace.newest_xplane(trace_dir)))
    out = table(seconds, op_names_of(hlo_text))
    out.update(workload=args.workload, seed=args.seed, busy_s=busy,
               sum_s=sum(out["by_scope"].values()),
               result=json.loads(line))
    with open(os.path.join(args.out, f"scope_{args.workload}.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    # enough to redo the join off the chip: the program's text and the
    # own time of each of its instructions
    with gzip.open(os.path.join(args.out, f"hlo_{args.workload}.txt.gz"),
                   "wt") as f:
        f.write(hlo_text)
    with open(os.path.join(args.out, f"instructions_{args.workload}.json"),
              "w") as f:
        json.dump(seconds, f)
    print(line)
    for scope, s in out["by_scope"].items():
        print(f"{scope:28s} {s:9.4f} s {100 * s / busy:6.2f} %")
    print(f"{'sum':28s} {out['sum_s']:9.4f} s of busy {busy:.4f} s; "
          f"{out['not_in_the_hlo_text_s']:.4f} s in no instruction of the "
          f"text")
    return 0


if __name__ == "__main__":
    sys.exit(main())
