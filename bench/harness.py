"""The benchmark harness: one run of one cell, driven by data files.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  Each
is found by name:

* ``bench/configs/<config>.json`` — the cohort (sizes, distributions) and
  the screen semantics, with ``source``, ``reduced`` and ``assumed``;
* ``bench/traffic/<traffic>.json`` — the mix's parameters: its patients
  (the cell's cut of the configuration's cohort) and its ``kind``;
* ``bench/kinds/<kind>.py`` — the generator a mix's ``kind`` names: a
  ``Kind`` subclass of ``Cell`` with ``setup``, ``window`` and ``verify``;
* ``bench/metrics/<metric>.py`` — one per-layer metric: a ``read(ctx)``
  that returns a number, or ``None`` where the run has nothing to read.

A run generates its cohort from the seed, warms every shape the window
uses (set-up), measures for ``seconds`` through the program's public entry
points, then compares what the window produced with the plain reference
(``reference.py``).  With ``trace`` on, a bounded slice of the window runs
under the JAX profiler instead, and the per-layer metrics are read from it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys

import numpy as np

import cohorts
import reference as ref

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


# --- registry ------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_spec(bm: dict, name: str, root: str = ROOT) -> tuple[dict, dict,
                                                              dict]:
    """(cell, configuration, traffic) of workload ``name``; files are found
    under ``root``, the checkout."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, traffic


def _load(sub: str, name: str, root: str):
    path = os.path.join(root, "bench", sub, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    return _load("metrics", name, root).read


def kind(name: str, root: str = ROOT) -> type:
    """The ``Cell`` subclass of traffic kind ``name``."""
    return _load("kinds", name, root).Kind


def peaks(device) -> dict:
    """The published peaks of ``device``'s kind; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    kind = device.device_kind
    if device.platform != "tpu" or kind not in table["devices"]:
        raise KeyError(f"no published peaks for {device.platform} "
                       f"{kind!r} in peaks.json")
    return table["devices"][kind]


def budget_bytes(share: float) -> int | None:
    """``share`` of the device's memory, or None (the program's default)
    where the backend reports no limit."""
    import jax
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit * share) if limit else None


def mining_config(**kw):
    """A ``MiningConfig`` from the fields it still has: an option a later
    version drops is left out rather than breaking the harness."""
    from repro.api import MiningConfig
    fields = {f.name for f in dataclasses.fields(MiningConfig)}
    return MiningConfig(**{k: v for k, v in kw.items() if k in fields})


# --- window clock --------------------------------------------------------------
class CompileCounter:
    """Backend compiles and persistent-cache loads while entered."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# --- cells -------------------------------------------------------------------------
class Cell:
    """Shared set-up of one run: the cohort, the screen, the checks.

    A kind (``bench/kinds/<kind>.py``) subclasses it as ``Kind`` and adds
    ``setup()`` (everything before the window, warm-up included),
    ``window(seconds, units=None)`` (the timed work; returns the kind's
    end-to-end metric, or with ``units`` runs that many units untimed for
    the trace) and ``verify()`` (fills ``checks`` against the reference).
    """

    #: the end-to-end metric the kind's window returns
    end_to_end = ""

    def __init__(self, cfg: dict, traffic: dict, seed: int, trace: bool,
                 control_cut_log2: int = 0):
        self.cfg, self.traffic, self.seed, self.trace = cfg, traffic, seed, trace
        screen = cfg["screen"]
        self.n_patients = int(traffic["patients"])
        self.H = int(screen["n_buckets_log2"])
        # the program's table; smaller than the reference's in the control
        self.program_H = self.H - control_cut_log2
        self.threshold = int(math.ceil(screen["threshold_share"]
                                       * self.n_patients))
        self.codec = cfg["codec"]
        self.phenx, self.date, self.nevents = cohorts.generate(
            cfg, self.n_patients, seed)
        self.radix = ref.Radix(cfg["codes"]["n"], self.n_patients,
                               cfg["days"] - 1)
        self.checks: dict[str, dict] = {}
        self.work: dict = {}
        self.units = 0            # units of work (fits) in the window
        self.failed = 0

    def config(self, **kw):
        return mining_config(
            codec=self.codec, threshold=self.threshold,
            n_buckets_log2=self.program_H, backend="auto",
            telemetry=self.trace, jax_annotations=self.trace, **kw)

    def dbmart(self):
        from repro.data.dbmart import DBMart
        return DBMart(self.phenx, self.date, self.nevents)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values())

    def program_keys(self, frame):
        """The frame's rows as reference keys, in the frame's row order,
        with the permutation that sorts them and the rows out of range."""
        seq, dur, pat, _ = frame.arrays()
        keys, bad = self.radix.pack(seq, dur, pat, self.codec)
        if bad:
            return keys, None, bad
        srt = keys[1:] >= keys[:-1]
        order = None if bool(np.all(srt)) else np.argsort(keys,
                                                         kind="stable")
        return keys, order, 0

    def compare(self, frame, want, table, threshold: int, rows: str,
                mask: str) -> None:
        """Check ``frame``'s rows against the reference's sorted keys, and
        its screen at ``threshold`` against the reference table."""
        got, order, bad = self.program_keys(frame)
        self.check(rows, ref.row_diff(got if order is None else got[order],
                                      bad, want), 0)
        keep = np.asarray(frame.screen(threshold).keep_mask())
        if order is not None:
            keep = keep[order]
        want_keep = ref.screen_mask(want, self.radix, table, threshold,
                                    self.H)
        self.check(mask, ref.mask_diff(keep, want_keep) if bad == 0 else bad,
                   0)


def total_pairs(nevents) -> int:
    n = np.asarray(nevents, np.int64)
    return int(np.sum(n * (n - 1) // 2))
