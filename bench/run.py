#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload ad_table1.fit --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit.  The same checks are the last lines of
standard error.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.  ``--control`` runs the program with
the screen's table cut by 2**6 (2**20 to 2**14 buckets, the fused kernel's
regime): more collisions keep rows the configuration's screen drops, a
broken guarantee the check has to catch.  The benchmark's own runs never
pass it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import trace_reduce  # noqa: E402


#: the control's cut of the screen table, in powers of two
CONTROL_CUT_LOG2 = 6


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control: a screen table 2**6 times smaller")
    return ap.parse_args(argv)


def compile_cache_dir() -> str:
    """JAX's persistent cache: the directory the environment names, else a
    fixed ``.jax_cache/`` at the root of the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        harness.ROOT, ".jax_cache")


def device_info(jax, chips: int, require_tpu: bool = True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform}); "
                         "refusing to report a result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return devs[0], len(devs)


def per_layer_names(bm: dict, cell: dict, e2e: str) -> list[dict]:
    out = []
    for m in bm["per_layer"]:
        cells = m.get("workloads")
        if (cells is not None and cell["name"] in cells) or \
                (cells is None and m["moves"] == e2e):
            out.append(m)
    return out


def run(args, require_tpu: bool = True, root: str = harness.ROOT) -> dict:
    """One run of the checkout at ``root``; returns the result object (also
    what ``main`` prints)."""
    import jax
    bm = harness.benchmark(root)
    cell, cfg, traffic = harness.cell_spec(bm, args.workload, root)
    dev, count = device_info(jax, int(cell["chips"]), require_tpu)
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    kind = harness.kind(traffic["kind"], root)
    c = kind(cfg, traffic, args.seed, bool(args.trace),
             CONTROL_CUT_LOG2 if args.control else 0)
    c.setup()
    setup_s = time.perf_counter() - T_START
    e2e_names = {m["name"]: m for m in bm["end_to_end"]}

    trace_dir = breakdown = None
    with harness.CompileCounter() as cc:
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            t0 = time.perf_counter()
            c.window(float(traffic.get("trace_seconds", args.seconds)),
                     units=traffic.get("trace_units"))
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            e2e = {}
        else:
            e2e = c.window(args.seconds)
    print(f"window: {c.units} {traffic['kind']} units; compiles inside the "
          f"window {cc.compiles} ({cc.compile_s:.3f} s), persistent-cache "
          f"loads {cc.cache_hits}", file=sys.stderr, flush=True)

    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": peak}
    metrics = {}
    if args.trace:
        red = trace_reduce.reduce_dir(trace_dir, span_names=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=traced_s)
        top = sorted(red["by_program"].items(), key=lambda kv: -kv[1])
        print("device seconds by program: " + "; ".join(
            f"{k} {v:.6f}" for k, v in top[:12]), file=sys.stderr, flush=True)
        ctx = ReadContext(c, red, traffic["kind"], dev, traced_s)
        for m in per_layer_names(bm, cell, c.end_to_end):
            v = harness.metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red["top_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
    else:
        e2e["setup_s"] = setup_s
        if stats.get("bytes_limit"):
            e2e["peak_hbm_share"] = 100.0 * peak / stats["bytes_limit"]
        for name, v in e2e.items():
            m = e2e_names.get(name)
            if m is not None and (m.get("workloads") is None
                                  or cell["name"] in m["workloads"]):
                metrics[name] = {"value": v, "unit": m["unit"]}
    print(f"setup_s {setup_s:.3f}; work {json.dumps(c.work)}",
          file=sys.stderr, flush=True)

    c.verify()        # after the peak is read, so the reference never sets it
    result = {"correct": c.correct, "attempted": c.units, "failed": c.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = c.checks
    for name, chk in c.checks.items():
        print(f"check {name} = {chk['value']} (limit {chk['limit']})",
              file=sys.stderr, flush=True)
    return result


class ReadContext:
    """What a per-layer metric reader may read: the cell (its work counts,
    session and units), the trace reduction, the device's peaks."""

    def __init__(self, cell, red: dict, kind: str, dev, traced_s: float):
        self.cell, self.trace, self.kind = cell, red, kind
        self.peaks = harness.peaks(dev)
        self.units = cell.units
        self.work = cell.work
        self.traced_s = traced_s

    def device_seconds(self, patterns) -> float | None:
        """Device seconds of the ops whose program names match any of
        ``patterns`` (substrings), or None where none ran."""
        hit = [s for name, s in self.trace["by_program"].items()
               if any(p in name for p in patterns)]
        return sum(hit) if hit else None

    def device_seconds_before(self, pattern: str) -> float | None:
        """Device seconds of the first device's ops that started before its
        first op in a program whose name contains ``pattern``; None where
        no such program ran."""
        tl = self.trace["timeline"]
        first = next((a for a, _, p in tl if pattern in p), None)
        if first is None:
            return None
        return trace_reduce.union_seconds(
            [(a, b) for a, b, _ in tl if a < first])


def main(argv=None) -> int:
    args = parse(argv)
    result = run(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
