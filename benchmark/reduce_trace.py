"""From a ``jax.profiler`` trace to three things, and no more.

1. Busy and idle: busy is the union of the intervals in which an
   operation runs on device 0, inside the *window*: from the first start
   to the last end of the executions of the program that took most device
   time in the trace (a trainer's epoch program).  What a traced call does
   before and after them (initialising, staging, the variables' way back
   to the host) is outside.  Idle share = 1 - busy / window.  Only events
   with no event nested in them count as an operation running: on the
   chip's "XLA Ops" line a ``while`` spans its whole loop (a trainer's
   epoch is one 16-step scan), and counting it would call every gap
   inside the loop busy.
2. Device time by operation, inside the window: each event's own time
   (its duration less the events nested in it), summed by name.  The
   line's event names are whole HLO instructions (``%fusion.12 = (bf16[..``
   ); the name kept is the instruction's, with the instance number cut off
   (``fusion``), and a Mosaic (Pallas) call goes as
   ``tpu_custom_call:<name>``: the trace carries no kernel name, only the
   JAX transform the call came from (``jvp__`` forward, ``transpose_jvp___``
   backward).
3. The longest idle gaps (the ``NAMED_GAPS`` longest; the rest are summed
   as ``other gaps``), each named by the host: the innermost ``bench:``
   annotation of the runner that covers the gap's start (else
   ``unannotated``), then the innermost host event of any kind there.

The file is read with ``jax.profiler.ProfileData`` alone.  ``reduce``
returns None where the trace holds no device plane (a CPU rehearsal).
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ANNOTATION = "bench:"
MOSAIC = 'custom_call_target="tpu_custom_call"'
NAMED_GAPS = 20


def newest_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def events_of(line) -> list:
    """(name, start_ns, end_ns) of a line, ordered by start; a longer
    event first where two start together."""
    out = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
           for e in line.events]
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def load(path: str) -> dict:
    """The planes this reduction reads, as plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: events_of(line) for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(events_of(line))
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """A short, stable name for an "XLA Ops" event, whose own name is the
    whole HLO instruction."""
    name = re.sub(r"[.\d]+$", "", text.split(" = ", 1)[0].lstrip("%"))
    return f"tpu_custom_call:{name}" if MOSAIC in text else (name or text)


def union_seconds(intervals: list) -> tuple:
    """(covered ns, gaps) of intervals sorted by start; a gap is
    (start, end) between two covered stretches."""
    covered, gaps, reach = 0.0, [], None
    for s, e in intervals:
        if reach is None:
            reach, covered = e, e - s
        elif s > reach:
            gaps.append((reach, s))
            covered += e - s
            reach = e
        elif e > reach:
            covered += e - reach
            reach = e
    return covered, gaps


def self_times(events: list) -> tuple:
    """(own time by name, leaf intervals) of (name, start, end) events
    sorted by start, longer first.  An event nested in another is taken
    off its parent; a leaf is an event with nothing nested in it."""
    totals, leaves, stack = {}, [], []  # stack of [name, start, end, own]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, s, e, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own
            if own == e - s:
                leaves.append((s, e))

    for name, s, e in events:
        close(s)
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    close(float("inf"))
    leaves.sort()
    return totals, leaves


def innermost(host: list, t: float, prefix: str = "") -> str:
    best = None
    for name, s, e in host:
        if s <= t < e and name.startswith(prefix) \
                and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else ""


def reduce_loaded(loaded: dict) -> dict:
    if 0 not in loaded["devices"]:
        return None
    lines = loaded["devices"][0]
    ops = lines.get(OPS_LINE, [])
    modules = lines.get(MODULES_LINE, [])
    if not ops:
        return None
    by_module = {}
    for name, s, e in modules:
        by_module.setdefault(re.sub(r"\(\d+\)$", "", name), []).append((s, e))
    main = max(by_module, key=lambda m: sum(e - s for s, e in by_module[m])) \
        if by_module else None
    if main is not None:
        lo = min(s for s, _ in by_module[main])
        hi = max(e for _, e in by_module[main])
    else:
        lo, hi = ops[0][1], max(e for _, _, e in ops)
    inside = [(op_name(n), max(s, lo), min(e, hi))
              for n, s, e in ops if e > lo and s < hi]
    table, leaves = self_times(inside)
    busy, gaps = union_seconds(leaves)
    first, last = leaves[0][0], max(e for _, e in leaves)
    gaps = ([(lo, first)] if first > lo else []) + gaps \
        + ([(last, hi)] if hi > last else [])
    gaps.sort(key=lambda g: g[0] - g[1])
    named = {}
    for s, e in gaps[:NAMED_GAPS]:
        note = innermost(loaded["host"], s, ANNOTATION)
        other = innermost(loaded["host"], s)
        key = (note[len(ANNOTATION):] or "unannotated") \
            + (f" / {other}" if other and other != note else "")
        named[key] = named.get(key, 0.0) + (e - s)
    if gaps[NAMED_GAPS:]:
        named[f"other gaps ({len(gaps) - NAMED_GAPS})"] = sum(
            e - s for s, e in gaps[NAMED_GAPS:])

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]

    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "idle_share": 1.0 - busy / (hi - lo),
            "window_module": main,
            "module_runs": len(by_module.get(main, ())),
            "device_ops": ranked(table), "idle_gaps": ranked(named),
            "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0}


def reduce(trace_dir: str):
    path = newest_xplane(trace_dir)
    return None if path is None else reduce_loaded(load(path))
