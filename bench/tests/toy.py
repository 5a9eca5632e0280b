"""A toy checkout for the CPU tests: a configuration, two traffic mixes (one
of the ``fit`` kind, one of a toy kind) and a per-layer metric, registered
from files alone."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TOY_CONFIG = {
    "name": "toy", "n_patients": 12,
    "events": {"distribution": "poisson", "mean": 14, "min": 2},
    "codes": {"n": 40, "zipf_s": 1.0}, "days": 300, "codec": "bit",
    "screen": {"mode": "fused", "n_buckets_log2": 10,
               "threshold_share": 0.25},
    "deployment": {"chips": 1, "budget_share": 0.125},
    "reduced": [], "assumed": {},
}
TOY_TRAFFIC = {
    "toy_fit": {"kind": "fit", "patients": 12, "trace_units": 1},
    "toy_refit": {"kind": "toy_refit", "patients": 10, "trace_units": 2},
}
#: a kind of its own, registered from its file alone: refits in one session
TOY_KIND = '''
import os
import time

import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Kind(harness.kind("fit", ROOT)):

    end_to_end = "refits_per_s"

    def window(self, seconds, units=None):
        t0 = time.perf_counter()
        while True:
            self.frame = self.session.fit(self.db)
            self.units += 1
            elapsed = time.perf_counter() - t0
            if (units is not None and self.units >= units) or \\
                    (units is None and elapsed >= seconds):
                break
        self.work["survivors"] = len(self.frame)
        return {self.end_to_end: self.units / elapsed}
'''
TOY_METRIC = '''
def read(ctx):
    return float(ctx.units)
'''


def _write(path: str, text: str) -> None:
    with open(path, "w") as f:
        f.write(text)


def make_root(path: str, config: dict | None = None) -> str:
    """Write a toy checkout under ``path``; returns ``path``.  It holds the
    real ``fit`` kind beside the toy files."""
    config = TOY_CONFIG if config is None else config
    for sub in ("configs", "traffic", "metrics", "kinds"):
        os.makedirs(os.path.join(path, "bench", sub), exist_ok=True)
    _write(os.path.join(path, "bench", "configs", "toy.json"),
           json.dumps(config))
    for name, t in TOY_TRAFFIC.items():
        _write(os.path.join(path, "bench", "traffic", name + ".json"),
               json.dumps(t))
    _write(os.path.join(path, "bench", "metrics", "toy.units.py"),
           TOY_METRIC)
    shutil.copy(os.path.join(BENCH, "kinds", "fit.py"),
                os.path.join(path, "bench", "kinds", "fit.py"))
    _write(os.path.join(path, "bench", "kinds", "toy_refit.py"), TOY_KIND)
    bm = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "test",
                     "file": "bench/configs/toy.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": f"toy.{k}", "config": "toy", "traffic": k,
                       "chips": 1, "why": "test"} for k in TOY_TRAFFIC],
        "end_to_end": [
            {"name": "fit_pairs_per_s", "unit": "pairs/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["toy.toy_fit"]},
            {"name": "refits_per_s", "unit": "fits/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["toy.toy_refit"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "toy.units", "unit": "units",
                       "better": "higher", "source": "program_counter",
                       "layer": "toy", "moves": "fit_pairs_per_s"}],
    }
    _write(os.path.join(path, "BENCHMARK.json"), json.dumps(bm))
    return path


class Args:
    def __init__(self, workload, seed=3, seconds=0.5, trace=0,
                 control=False):
        self.workload, self.seed, self.seconds = workload, seed, seconds
        self.trace, self.control = trace, control
