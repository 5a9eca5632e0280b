"""Corpus-free fused screen vs the materializing mine+screen path.

Three claims, all asserted (not just reported):

  * **exactness** — ``screen="fused"`` collect bytes == the materializing
    batch mine + hash-screen oracle on the same cohort;
  * **peak bytes** — under the shared BYTES_PER_PAIR cost model the fused
    screen pass never allocates the [P, n, n] corpus: its working set is
    one patient block + the [2^H] table, stays flat as P doubles, and
    undercuts the materializing working set;
  * **wall** — the corpus-free fit stays within a small multiple of the
    materializing fit on CPU (it re-mines chunk-by-chunk for survivors,
    so it trades one extra mining pass for never holding the corpus).

Plus the autotune sweep that feeds ``analysis.roofline.mining_tile_plan``:
the fused counting pass is timed at several patient-block sizes and the
measured rows are handed back to the planner, closing the loop between
``benchmarks/mining_roofline.py``'s cost model and the kernel's tile
choice.  Prints ``name,us_per_call,derived`` CSV rows;
``main(json_path=...)`` writes BENCH_mining_fused.json.
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.analysis import roofline
from repro.api import MiningConfig, MiningSession
from repro.api.planner import _fused_working_set, _working_set
from repro.data import dbmart, synthea
from repro.kernels.tspm_fused import ops as fused_ops

# the corpus-free fit runs the counting pass plus a full re-mine for
# survivors: ~2x the mining math of the one-pass materializing fit, traded
# for never holding the corpus.  CPU wall must stay under this multiple.
MAX_WALL_RATIO = 6.0


def _best_times(fns: dict, repeats: int) -> tuple[dict, dict]:
    """Interleaved best-of-N (same harness as api_overhead)."""
    times = {name: [] for name in fns}
    outs = {}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            outs[name] = fn()
            times[name].append(time.perf_counter() - t0)
    return {n: float(np.min(ts)) for n, ts in times.items()}, outs


def mining_fused(n_patients=2048, avg_events=24, threshold=3, repeats=3,
                 backend="jnp", n_buckets_log2=12, seed=13):
    pats, dates, phx, _ = synthea.generate_cohort(
        n_patients=n_patients, avg_events=avg_events, seed=seed)
    db = dbmart.from_rows(pats, dates, phx)
    E = int(np.max(db.nevents))
    hash_cfg = MiningConfig(threshold=threshold, screen="hash",
                            n_buckets_log2=n_buckets_log2, backend=backend)
    fused_cfg = hash_cfg.replace(screen="fused")

    # --- exactness ---------------------------------------------------------
    def fit_hash():
        return MiningSession(hash_cfg).fit(db)

    def fit_fused():
        return MiningSession(fused_cfg).fit(db)

    oracle = fit_hash().screen().collect()
    got = fit_fused().screen().collect()
    for field, a, b in zip(oracle._fields, oracle, got):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), \
            f"fused screen diverged from mine+screen on {field}"

    # --- peak bytes (BYTES_PER_PAIR cost model) ----------------------------
    # the acceptance criterion: no [P, n, n] pair corpus on the screen
    # pass.  The fused working set is one patient block + the table; it
    # must undercut the materializing set and stay flat as P doubles
    # (a corpus-shaped allocation would scale with P).
    ws_dense = _working_set(np.asarray(db.nevents), hash_cfg)
    ws_fused = _fused_working_set(np.asarray(db.nevents), fused_cfg)
    assert ws_fused < ws_dense, (ws_fused, ws_dense)
    nev2 = np.concatenate([db.nevents, db.nevents])
    assert _fused_working_set(nev2, fused_cfg) == ws_fused, \
        "fused screen working set scales with P: a corpus is hiding in it"
    peak_ratio = ws_dense / max(ws_fused, 1)

    # --- wall --------------------------------------------------------------
    ts, _ = _best_times({"hash": lambda: fit_hash().screen().n_kept,
                         "fused": lambda: fit_fused().screen().n_kept},
                        repeats)
    wall_ratio = ts["fused"] / max(ts["hash"], 1e-12)
    assert wall_ratio <= MAX_WALL_RATIO, \
        f"fused fit {wall_ratio:.1f}x slower than materializing (cap " \
        f"{MAX_WALL_RATIO}x)"

    # --- autotune sweep -> tile plan ---------------------------------------
    analytic = roofline.mining_tile_plan(E, n_buckets_log2)
    rows = []
    for pb in (4, 8, 16):
        def count(pb=pb):
            return np.asarray(fused_ops.fused_bucket_counts(
                db.phenx, db.date, db.nevents, n_buckets_log2=n_buckets_log2,
                backend=backend, block_patients=pb * 16))
        t, _ = _best_times({"c": count}, max(repeats - 2, 2))
        rows.append({"pb": pb, "wall_s": t["c"]})
    plan = roofline.mining_tile_plan(E, n_buckets_log2, rows=rows)
    assert plan.source == "measured"

    return {
        "patients": n_patients, "avg_events": avg_events, "max_events": E,
        "threshold": threshold, "backend": backend,
        "n_buckets_log2": n_buckets_log2, "repeats": repeats,
        "n_kept": int(len(got.seq)),
        "working_set_dense_bytes": int(ws_dense),
        "working_set_fused_bytes": int(ws_fused),
        "peak_ratio": float(peak_ratio),
        "exact": True,              # asserted above, recorded for the gate
        "corpus_free": True,        # P-doubling invariance asserted above
        "wall_hash_s": ts["hash"], "wall_fused_s": ts["fused"],
        "wall_ratio": float(wall_ratio), "max_wall_ratio": MAX_WALL_RATIO,
        "autotune_rows": rows,
        "tile_plan": {"pb": plan.pb, "ti": plan.ti, "tj": plan.tj,
                      "block_patients": plan.block_patients,
                      "vmem_bytes": plan.vmem_bytes, "source": plan.source},
        "tile_plan_analytic": {"pb": analytic.pb,
                               "block_patients": analytic.block_patients},
    }


def main(small=True, json_path=None, backend="jnp"):
    kw = dict() if small else dict(n_patients=8192, avg_events=40, repeats=5)
    r = mining_fused(backend=backend, **kw)
    print("name,us_per_call,derived")
    print(f"mining_fused/fit_materializing,{r['wall_hash_s']*1e6:.0f},"
          f"kept={r['n_kept']}")
    print(f"mining_fused/fit_corpus_free,{r['wall_fused_s']*1e6:.0f},"
          f"wall_ratio={r['wall_ratio']:.2f}x (cap {r['max_wall_ratio']}x);"
          f"exact=asserted")
    print(f"mining_fused/peak_bytes,,dense={r['working_set_dense_bytes']};"
          f"fused={r['working_set_fused_bytes']};"
          f"ratio={r['peak_ratio']:.1f}x (P-invariance asserted)")
    p = r["tile_plan"]
    print(f"mining_fused/tile_plan,,pb={p['pb']};"
          f"block={p['block_patients']};source={p['source']}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(r, f, indent=1)
        print(f"mining_fused/artifact,,{json_path}")
    return r


if __name__ == "__main__":
    main()
