#!/usr/bin/env python3
"""The device's idle time inside a fit's ``session.fit`` span, by the
innermost program span over it, read from a profile's raw host plane.

    python3 bench/span_idle.py TRACE.xplane.pb[.gz]
    python3 bench/span_idle.py --workload ad_table1.fit --seed 7 --keep T.gz

The first form reads a kept profile; the second sets the cell up as a
``--trace 1`` run of ``bench/run.py`` does, profiles one traced window,
keeps the profile at ``--keep`` (gzipped) and reads it.  Prints one JSON
object: ``session.fit``'s seconds (``outer_s``), the device's busy and idle
seconds inside it, the idle seconds by innermost span (``idle_by_span``),
the share of the idle under a span other than ``session.fit``
(``named_idle_share``, %) and each span name's total seconds
(``span_s``).

Program spans are the host events whose names start with one of
``PREFIXES``, as ``repro.obs`` mirrors its spans with ``jax_annotations``
on.  Unlike ``trace_reduce``, which names a gap by the last 512 host
events before it, nothing here is cut by a lookback window, so a long
outer span is seen however many runtime events the host logged inside it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

PREFIXES = ("session.", "fit.")
OUTER = "session.fit"
NONE = "(none)"


def idle_by_span(ops, spans) -> dict:
    """Idle of a device inside the first span named ``OUTER``.

    ``ops`` are the device's ``(start_ns, end_ns)``, ``spans`` the
    program's ``(start_ns, end_ns, name)``.  Each stretch of idle between
    two span edges goes to the shortest span over it."""
    o0, o1 = next((a, b) for a, b, n in sorted(spans) if n == OUTER)
    busy = trace_reduce.merged([(max(a, o0), min(b, o1)) for a, b in ops
                                if b > o0 and a < o1])
    gaps, prev = [], o0
    for a, b in busy + [[o1, o1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    idle: dict[str, float] = {}
    for a, b in gaps:
        cuts = sorted({a, b} | {x for s0, s1, _ in spans for x in (s0, s1)
                                if a < x < b})
        for x0, x1 in zip(cuts, cuts[1:]):
            over = [(s1 - s0, n) for s0, s1, n in spans
                    if s0 <= x0 and x1 <= s1]
            name = min(over)[1] if over else NONE
            idle[name] = idle.get(name, 0.0) + (x1 - x0) / 1e9
    total = sum(idle.values())
    named = total - idle.get(OUTER, 0.0) - idle.get(NONE, 0.0)
    return {"outer_s": (o1 - o0) / 1e9,
            "busy_s": trace_reduce.union_seconds(busy),
            "idle_s": total,
            "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "named_idle_share": 100.0 * named / total if total else None}


def read(path: str) -> dict:
    """``idle_by_span`` of the first device in the profile at ``path``,
    and the total seconds of each program span name."""
    ops, spans = None, []
    for plane in trace_reduce.load(path).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX) and \
                trace_reduce.OPS_LINE in lines and ops is None:
            ops = [(a, b) for a, b, _ in
                   trace_reduce._events(lines[trace_reduce.OPS_LINE])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in trace_reduce._events(line)
                             if e[2].startswith(PREFIXES))
    if not ops:
        raise ValueError(f"{path}: no device ran an operation")
    out = idle_by_span(ops, spans)
    span_s: dict[str, float] = {}
    for a, b, name in spans:
        span_s[name] = span_s.get(name, 0.0) + (b - a) / 1e9
    out["span_s"] = span_s
    return out


def profile_cell(workload: str, seed: int, keep: str) -> str:
    """One traced window of ``workload``, set up as ``bench/run.py --trace
    1`` does; the profile is kept gzipped at ``keep``."""
    import jax

    import harness
    import run
    bm = harness.benchmark(harness.ROOT)
    _, cfg, traffic = harness.cell_spec(bm, workload, harness.ROOT)
    jax.config.update("jax_compilation_cache_dir", run.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    c = harness.kind(traffic["kind"], harness.ROOT)(cfg, traffic, seed,
                                                   True, 0)
    c.setup()
    d = tempfile.mkdtemp(prefix="span_idle_")
    jax.profiler.start_trace(d,
                             profiler_options=trace_reduce.profile_options())
    c.window(float(traffic.get("trace_seconds", 1)),
             units=traffic.get("trace_units"))
    jax.profiler.stop_trace()
    with open(trace_reduce.find_xplane(d), "rb") as f, \
            gzip.open(keep, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(d, ignore_errors=True)
    return keep


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", help="where --workload keeps its profile")
    args = ap.parse_args(argv)
    if (args.trace is None) == (args.workload is None):
        ap.error("give a profile or --workload, not both")
    if args.workload and not args.keep:
        ap.error("--workload needs --keep")
    path = args.trace or profile_cell(args.workload, args.seed, args.keep)
    print(json.dumps(read(path)))


if __name__ == "__main__":
    main()
