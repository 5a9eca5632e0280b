"""Benchmark harness — one section per paper table/figure.

  comparison      -> paper Table 1 (original tSPM vs tSPM+, x-factor)
  performance     -> paper Table 2 (scaling, in-memory vs file-based)
  mining_roofline -> kernel arithmetic intensity + TPU projection
  postcovid       -> vignette-2 quality (the paper's use-case claim)
  roofline        -> LM-cell roofline table (reads experiments/dryrun/*.json
                     if the dry-run sweep has been run)
  streaming       -> incremental delta-mining ingest vs full re-mine
                     (``--suite streaming`` runs it alone in CPU-interpret
                     mode and writes a BENCH_streaming.json trajectory)
  streaming_sharded -> mesh-sharded streaming service: shards-vs-single
                     tick throughput + merged-screen (psum) cost
                     (``--suite streaming_sharded`` writes
                     BENCH_streaming_sharded.json)
  streaming_rebalance -> live shard rebalancing on a skewed workload:
                     sticky routing vs load-triggered patient migration
                     (``--suite streaming_rebalance`` writes
                     BENCH_streaming_rebalance.json)
  streaming_placement -> device-pinned shards vs host-serial ticks on
                     forced host devices (sets XLA_FLAGS before jax
                     loads; ``--suite streaming_placement`` writes
                     BENCH_streaming_placement.json, exactness asserted
                     against the batch oracle)
  api_overhead    -> unified session façade (repro.api) vs hand-wired
                     mine->flatten->screen; batch-path dispatch overhead
                     must stay < 5% (``--suite api_overhead`` writes
                     BENCH_api_overhead.json)
  observability_overhead -> telemetry-instrumented vs bare streaming
                     ingest; enabling the metrics registry + span tracer
                     must cost < 3% and change zero mined bytes
                     (``--suite observability_overhead`` writes
                     BENCH_observability_overhead.json)
  mining_fused    -> corpus-free fused screen (screen="fused") vs the
                     materializing mine+screen path: collect bytes
                     asserted identical, peak working set asserted below
                     the dense corpus under the BYTES_PER_PAIR model
                     (and P-invariant), wall within a bounded multiple,
                     plus the autotune sweep feeding
                     analysis.roofline.mining_tile_plan
                     (``--suite mining_fused`` writes
                     BENCH_mining_fused.json)
  storage_tiering -> compressed disk tier: codec compression ratio
                     (asserted >= 3x on the synthea shape), tiered
                     ingest with disk demotion on the eviction path,
                     and checkpoint save/restore timing with the
                     restored bytes asserted identical
                     (``--suite storage_tiering`` writes
                     BENCH_storage_tiering.json)
  serving_latency -> batched QueryServer vs lock-serialized per-query
                     frame evaluation at >= 32 concurrent clients:
                     masks asserted byte-identical, p99 speedup
                     asserted >= 2x (``--suite serving_latency`` writes
                     BENCH_serving_latency.json)
  journal_overhead -> hash-chained tick journal on/off ingest: mined
                     bytes asserted identical, the journal verified and
                     replayed byte-exactly, overhead gated < 5%
                     (``--suite journal_overhead`` writes
                     BENCH_journal_overhead.json)

An unknown ``--suite`` prints the available suites instead of failing
opaquely.  Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time


def _section(title):
    print(f"\n## {title}", flush=True)


def postcovid_bench():
    import numpy as np

    from repro.core import mining, postcovid
    from repro.data import dbmart, synthea

    pats, dates, phx, truth = synthea.generate_cohort(
        n_patients=300, avg_events=40, seed=17)
    db = dbmart.from_rows(pats, dates, phx)
    t0 = time.perf_counter()
    mined = mining.mine(db.phenx, db.date, db.nevents, backend="jnp")
    seq, dur, pat, msk = mining.flatten(mined)
    cfg = postcovid.PostCovidConfig(
        covid_id=db.vocab.phenx_index[synthea.COVID])
    pcc, _ = postcovid.identify(seq, dur, pat, msk, db.phenx, db.nevents,
                                cfg, db.n_patients, db.vocab.n_phenx)
    dt = time.perf_counter() - t0
    pcc = np.asarray(pcc)
    pred = postcovid.decode_symptoms(pcc, db.vocab)
    tp = fp = fn = 0
    for p in range(db.n_patients):
        t, pr = truth.symptom_sets[p], pred[p]
        tp += len(t & pr)
        fp += len(pr - t)
        fn += len(t - pr)
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    f1 = 2 * prec * rec / max(prec + rec, 1e-9)
    acc = (pcc.any(1) == truth.long_covid).mean()
    print("name,us_per_call,derived")
    print(f"postcovid/pipeline,{dt*1e6:.0f},f1={f1:.3f};patient_acc={acc:.3f}")


def roofline_bench():
    print("name,us_per_call,derived")
    files = sorted(glob.glob("experiments/dryrun/*pod16x16.json"))
    if not files:
        print("roofline/missing,,run `python -m repro.launch.dryrun --all`")
        return
    for f in files:
        rec = json.load(open(f))
        if rec.get("status") != "ok":
            tag = rec.get("status", "?")
            print(f"roofline/{rec['arch']}__{rec['shape']},,{tag}")
            continue
        r = rec["roofline"]
        bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        print(f"roofline/{rec['arch']}__{rec['shape']},{bound*1e6:.0f},"
              f"dominant={r['dominant']};frac={r['roofline_fraction']:.3f}")


def streaming_bench(small=True, out_path=None):
    from benchmarks import streaming

    out_path = out_path or "BENCH_streaming.json"
    # kernel backend in interpret mode: exercises the Pallas delta kernel
    # end-to-end on CPU, same as the tier-1 kernel tests
    streaming.main(small=small, json_path=out_path, backend="kernel")


def streaming_sharded_bench(small=True, out_path=None):
    from benchmarks import streaming

    out_path = out_path or "BENCH_streaming_sharded.json"
    streaming.main_sharded(small=small, json_path=out_path, backend="jnp")


def streaming_rebalance_bench(small=True, out_path=None):
    from benchmarks import streaming

    out_path = out_path or "BENCH_streaming_rebalance.json"
    streaming.main_rebalance(small=small, json_path=out_path, backend="jnp")


def _force_host_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices — must happen before jax loads
    (XLA reads the flag at backend init).  A no-op when the process
    already sees >= 2 devices; fails fast when jax is already up with a
    single device (the flag would silently not apply)."""
    if "jax" in sys.modules:
        import jax

        if len(jax.devices()) >= 2:
            return
        raise SystemExit(
            "jax is already initialized with a single device; run "
            "--suite streaming_placement in a fresh process")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + f" --xla_force_host_platform_device_count={n}").strip()


def streaming_placement_bench(small=True, out_path=None):
    _force_host_devices(2 if small else 4)
    from benchmarks import streaming

    out_path = out_path or "BENCH_streaming_placement.json"
    streaming.main_placement(small=small, json_path=out_path, backend="jnp")


def api_overhead_bench(small=True, out_path=None):
    from benchmarks import api_overhead

    out_path = out_path or "BENCH_api_overhead.json"
    api_overhead.main(small=small, json_path=out_path, backend="jnp")


def observability_overhead_bench(small=True, out_path=None):
    from benchmarks import observability

    out_path = out_path or "BENCH_observability_overhead.json"
    observability.main(small=small, json_path=out_path, backend="jnp")


def mining_fused_bench(small=True, out_path=None):
    from benchmarks import mining_fused

    out_path = out_path or "BENCH_mining_fused.json"
    mining_fused.main(small=small, json_path=out_path, backend="jnp")


def serving_latency_bench(small=True, out_path=None):
    from benchmarks import serving_latency

    out_path = out_path or "BENCH_serving_latency.json"
    serving_latency.main(small=small, json_path=out_path, backend="jnp")


def journal_overhead_bench(small=True, out_path=None):
    from benchmarks import journal_overhead

    out_path = out_path or "BENCH_journal_overhead.json"
    journal_overhead.main(small=small, json_path=out_path, backend="kernel")


def storage_tiering_bench(small=True, out_path=None):
    from benchmarks import storage_tiering

    out_path = out_path or "BENCH_storage_tiering.json"
    storage_tiering.main(small=small, json_path=out_path, backend="jnp")


SUITES = {
    "streaming": ("streaming ingest (delta vs re-mine)", streaming_bench),
    "streaming_sharded": ("mesh-sharded streaming (shards vs single)",
                          streaming_sharded_bench),
    "streaming_rebalance": ("live shard rebalancing (sticky vs migrated)",
                            streaming_rebalance_bench),
    "streaming_placement": ("device-pinned shards vs host-serial ticks",
                            streaming_placement_bench),
    "api_overhead": ("session façade vs hand-wired batch path",
                     api_overhead_bench),
    "observability_overhead": ("telemetry on/off ingest (< 3% ceiling)",
                               observability_overhead_bench),
    "mining_fused": ("corpus-free fused screen vs materializing path",
                     mining_fused_bench),
    "storage_tiering": ("compressed disk tier + checkpoint/resume "
                        "(>= 3x ratio asserted)", storage_tiering_bench),
    "serving_latency": ("batched query serving vs per-query eval "
                        "(>= 2x p99 at 32 clients asserted)",
                        serving_latency_bench),
    "journal_overhead": ("hash-chained tick journal on/off ingest "
                         "(< 5% ceiling, replay asserted exact)",
                         journal_overhead_bench),
}


def main() -> None:
    small = "--full" not in sys.argv
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if "--suite" in sys.argv:
        i = sys.argv.index("--suite") + 1
        suite = sys.argv[i] if i < len(sys.argv) else None
        if suite not in SUITES:
            listing = "\n".join(f"  {name:22s} {title}"
                                for name, (title, _) in SUITES.items())
            raise SystemExit(
                f"unknown --suite {suite!r}; available suites:\n{listing}")
        title, bench = SUITES[suite]
        _section(title)
        bench(small=small)
        return

    _section("comparison (paper Table 1)")
    from benchmarks import comparison

    comparison.main(small=small)

    _section("performance (paper Table 2)")
    from benchmarks import performance

    performance.main(full=not small)

    _section("mining roofline")
    from benchmarks import mining_roofline

    mining_roofline.main()

    _section("postcovid (vignette 2)")
    postcovid_bench()

    _section("LM-cell roofline (from dry-run)")
    roofline_bench()


if __name__ == "__main__":
    main()
