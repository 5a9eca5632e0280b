"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the devices that ran anything;
* device seconds per program: each op's time, credited to the XLA module
  (the jitted function) it ran in, and per kernel or op name;
* idle gaps: the device's idle time, attributed to the innermost host
  span (the program's ``jax.profiler.TraceAnnotation`` spans, telemetry
  with ``jax_annotations`` on) that covers each gap.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re

_OPCODE = re.compile(r"\b([a-z][a-z0-9-]*)\(")

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def profile_options():
    """Profiler options of a traced run: no Python function tracer (it
    records every call and slows the host many times over), host events
    of the program's own annotations only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, seconds."""
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total / 1e9


def merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def load(path: str):
    """The profile at ``path``: an ``.xplane.pb``, or one gzipped."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return ProfileData.from_file(path)


def reduce_file(path: str, span_names: bool = True) -> dict:
    pd = load(path)
    devices = []
    host_spans = []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX) and OPS_LINE in lines:
            ops = _events(lines[OPS_LINE])
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines \
                else []
            if ops:
                devices.append((ops, mods))
        elif plane.name.startswith("/host:") and span_names:
            for line in plane.lines:
                host_spans.extend(_events(line))
    if not devices:
        raise ValueError(f"{path}: no device ran an operation")

    # ops nest (a loop's body ops lie inside the loop op), so each
    # program's and each op name's time is the union of its intervals
    per_program: dict[str, list] = {}
    per_op: dict[str, list] = {}
    timeline = []         # first device: (start_ns, end_ns, program)
    busy = []
    for d, (ops, mods) in enumerate(devices):
        busy.append(union_seconds([(a, b) for a, b, _ in ops]))
        mods = sorted(mods)
        mstarts = [a for a, _, _ in mods]
        for a, b, name in ops:
            i = bisect.bisect_right(mstarts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= b else "(none)"
            per_program.setdefault(prog, []).append((a, b))
            per_op.setdefault(f"{_short(prog)}/{_op_name(name)}",
                              []).append((a, b))
            if d == 0:
                timeline.append((a, b, prog))
    n = len(devices)
    by_program = {k: union_seconds(v) / n for k, v in per_program.items()}
    by_op = {k: union_seconds(v) / n for k, v in per_op.items()}

    # idle gaps of the first device, by the innermost host span over each;
    # the host's first and last event bound the slice, so the device's idle
    # head and tail count as gaps too
    busy0 = merged([(a, b) for a, b, _ in devices[0][0]])
    edges = [busy0[0][0], busy0[-1][1]]
    if host_spans:
        edges = [min(edges[0], min(s0 for s0, _, _ in host_spans)),
                 max(edges[1], max(s1 for _, s1, _ in host_spans))]
    gaps = []
    prev = edges[0]
    for a, b in busy0 + [[edges[1], edges[1]]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    idle: dict[str, float] = {}
    host_spans.sort()
    starts = [s0 for s0, _, _ in host_spans]
    for a, b in gaps:
        name = _covering_span(host_spans, starts, a, b)
        idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
    return {
        "busy_s": sum(busy) / len(busy),
        "by_program": by_program,
        "timeline": merged_timeline(timeline),
        "top_ops": sorted(([k, v] for k, v in by_op.items()),
                          key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
    }


def merged_timeline(timeline):
    """``(start, end, program)`` with the ops nested in another op of the
    same program dropped, sorted by start."""
    out = []
    for a, b, p in sorted(timeline, key=lambda t: (t[0], -t[1])):
        if out and out[-1][2] == p and b <= out[-1][1]:
            continue
        out.append((a, b, p))
    return out


def _op_name(hlo: str) -> str:
    """``%fusion.3 = s32[8]{0} fusion(...), kind=...`` -> ``%fusion.3
    fusion``: the op's name and opcode, without its shapes."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else name


def _short(module_name: str) -> str:
    """``jit_sketch_update(1234)`` -> ``jit_sketch_update``."""
    return module_name.split("(")[0]


def _covering_span(spans, starts, a, b, lookback: int = 512) -> str:
    """Name of the shortest host span (of the ``lookback`` that began last
    before the gap's middle) that covers most of [a, b]."""
    best, best_len = "(no host span)", None
    need = (b - a) / 2
    hi = bisect.bisect_right(starts, (a + b) // 2)
    for s0, s1, name in spans[max(0, hi - lookback):hi]:
        if s1 <= a or s0 >= b:
            continue
        if min(s1, b) - max(s0, a) < need:
            continue
        if best_len is None or s1 - s0 < best_len:
            best, best_len = name, s1 - s0
    return best


def reduce_dir(trace_dir: str, span_names: bool = True) -> dict:
    return reduce_file(find_xplane(trace_dir), span_names)
