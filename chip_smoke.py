#!/usr/bin/env python3
"""Smoke run of tSPM+ mining on a TPU, through ``MiningSession``.

    python3 chip_smoke.py             # one chip: batch fit, stream, serve
    python3 chip_smoke.py --chips 4   # four chips: sharded streaming only

One process runs every phase with ``backend="auto"`` and telemetry on:

  * batch fit — the paper's Table 1 cohort shape (4,985 patients x ~471
    events, ``synthea.generate_benchmark_rows``) through the corpus-free
    fused screen: the fused kernel counts pass 1, the pairgen kernel
    re-mines pass 2.  Exactness: pass-1 table and survivors of a
    full-width 256-patient slice against ``backend="jnp"``, at the run's
    threshold and at one where the screen drops rows;
  * streaming ingest — patients of that cohort (256 by default, of the
    configuration's 1,024: ``--stream-patients``) replayed in 4 waves
    (``launch.stream.replay_waves``) through the delta kernel; the final
    corpus, support table and hash screen must byte-equal batch mining of
    the same patients;
  * query serving — ``session.serve()`` answers 64 post-COVID-style plans
    (``screen().starts_with(code).min_duration(days)``); every mask must
    byte-equal frame evaluation on the served view;
  * sharded streaming (``--chips 4`` only) — the streaming cohort over 4
    device-pinned shards with rebalancing, compared with a one-shard run
    by a digest of the canonically ordered corpus, the support table and
    the pid table.

Each phase reads the ``kernel.dispatch`` counters (``repro.obs``) and fails
when a dispatch that should have run a compiled kernel ran the jnp
reference or the Pallas interpreter.  Times printed here are smoke
timings, not benchmark numbers.  The script exits non-zero when JAX finds
no TPU and when any phase fails or is inexact; its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

TABLE1_PATIENTS = 4985        # paper Table 1 (AD study cohort)
TABLE1_AVG_EVENTS = 471
THRESHOLD = 50                # assumed: ~1% of the Table 1 cohort
BATCH_BUCKETS_LOG2 = 14       # the fused kernel's table regime
CHECK_PATIENTS = 256
STREAM_PATIENTS = 1024        # the streaming cohort of the configuration
# what a default run streams: on a v5e a 16-patient tick of this cohort
# takes seconds of device time and each new (set, slab) shape compiles
# for ~10 s, so 1,024 patients (256 ticks) would bring a cold run near its
# 1200 s limit
STREAM_RUN_PATIENTS = 256
STREAM_WAVES = 4
TICK_PATIENTS = 16
N_QUERIES = 64


def _line(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Wall seconds in which JAX was tracing, lowering or compiling while
    entered (compiles running at once in several threads count once)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans: list[tuple[float, float]] = []

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for t0, t1 in sorted(self.spans):
            if t1 > reach:
                total += t1 - max(t0, reach)
                reach = t1
        return total

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@contextlib.contextmanager
def timed(out: dict):
    """Wall seconds of the block, split into compile and run seconds."""
    t0 = time.perf_counter()
    with CompileClock() as clock:
        yield
    wall = time.perf_counter() - t0
    out["compile_s"] = clock.seconds
    out["run_s"] = wall - clock.seconds


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


def bytes_limit() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats["bytes_limit"])


def dispatches(session) -> dict:
    """``{(op, impl, interpret): count}`` from the session's counters,
    summed over any other label (a stream shard's ``shard=``)."""
    prefix = "kernel.dispatch{"
    out = {}
    for key, n in session.telemetry.metrics.snapshot().items():
        if not key.startswith(prefix):
            continue
        labels = dict(kv.split("=") for kv in key[len(prefix):-1].split(","))
        k = (labels["op"], labels["impl"], labels["interpret"])
        out[k] = out.get(k, 0) + n
    return out


def check_impl(phase: str, session, ops) -> dict:
    """Every dispatch of ``ops`` ran the implementation ``auto`` promises
    on this platform: the compiled kernel on a TPU, the jnp reference
    elsewhere.  Returns ``{op: "impl interpret=..."}`` for the report."""
    import jax
    want = ("kernel", "False") if jax.default_backend() == "tpu" \
        else ("jnp", "False")
    got = dispatches(session)
    report = {}
    for op in ops:
        ran = {(impl, interp): n for (o, impl, interp), n in got.items()
               if o == op}
        if set(ran) != {want}:
            raise SystemExit(f"[{phase}] {op} ran {ran}, expected only "
                             f"impl={want[0]} interpret={want[1]}")
        report[op] = f"{want[0]}(interpret={want[1]},calls={ran[want]})"
    return report


def make_cohort(n_patients: int, avg_events: int, seed: int):
    from repro.data import dbmart, synthea
    pid, date, xid, _ = synthea.generate_benchmark_rows(
        n_patients, avg_events, seed)
    return dbmart.from_rows(pid, date, xid)


def canonical(seq, dur, patient):
    order = np.lexsort((dur, patient, seq))
    return seq[order], dur[order], patient[order]


def canonical_digest(svc) -> str:
    """Hex digest of a stream service's corpus in canonical row order, its
    support table and its pid table.  ``launch.stream.state_digest`` keeps
    the corpus in snapshot order (the replay drill's byte-exact key); a
    sharded snapshot concatenates shards, so it is compared in this order."""
    import hashlib
    snap = svc.snapshot()
    h = hashlib.sha256()
    for arr in (*canonical(*(np.asarray(getattr(snap, k))
                             for k in ("seq", "dur", "patient"))),
                np.asarray(snap.counts)):
        h.update(np.ascontiguousarray(arr).tobytes())
    pids = svc.pids if hasattr(svc, "shards") else svc.store.pids
    h.update(repr(sorted((str(k), int(v))
                         for k, v in dict(pids).items())).encode())
    return h.hexdigest()


def _reference_device():
    """The host CPU: references run apart from the chip under test."""
    import jax
    return jax.devices("cpu")[0]


# --- phases ------------------------------------------------------------------
def batch_fit_phase(db, *, threshold: int = THRESHOLD,
                    n_buckets_log2: int = BATCH_BUCKETS_LOG2,
                    check_patients: int = CHECK_PATIENTS,
                    budget_bytes: int | None = None) -> dict:
    """Corpus-free fit of ``db`` (fused pass 1, pairgen pass 2), then the
    exactness check on a full-width slice of ``check_patients``."""
    import jax
    from repro.api import MiningConfig, MiningSession

    limit = bytes_limit()
    if budget_bytes is None:
        # the chunk planner prices the triangular layout; the kernel path
        # is dense with packing scratch, so leave it most of the device
        budget_bytes = limit // 8
    cfg = MiningConfig(threshold=threshold, screen="fused",
                       n_buckets_log2=n_buckets_log2, backend="auto",
                       budget_bytes=budget_bytes, telemetry=True)
    out = {"patients": db.n_patients, "max_events": int(db.nevents.max()),
           "mean_events": float(np.mean(db.nevents)),
           "pairs": int(np.sum(db.nevents.astype(np.int64)
                               * (db.nevents - 1) // 2)),
           "budget_bytes": budget_bytes}
    session = MiningSession(cfg)
    with timed(out):
        frame = session.fit(db)
        out["kept_rows"] = len(frame)
    out["impl"] = check_impl("batch_fit", session, ("fused", "pairgen"))
    out["plan_engine"] = session.last_plan.engine
    out["peak_bytes"] = peak_bytes()
    if limit and out["peak_bytes"] is not None:
        out["peak_share"] = out["peak_bytes"] / limit

    # At H = 14 every bucket of a Table 1 cohort (and of the slice) holds
    # far more than ``threshold`` patients, so the fit above screens
    # nothing.  The slice is also fitted at one above its median bucket
    # support, where the screen drops rows and pass 2 compacts survivors.
    sub = db.slice_patients(0, min(check_patients, db.n_patients),
                            db.phenx.shape[1])
    out["check_patients"] = sub.n_patients
    out["check_pairs"] = int(np.sum(sub.nevents.astype(np.int64)
                                    * (sub.nevents - 1) // 2))
    t0 = time.perf_counter()
    exact, table, _ = _slice_exact(cfg, sub)
    nz = table[table > 0]
    drop_threshold = int(np.median(nz)) + 1 if len(nz) else 1
    exact_drop, _, out["check_kept_rows"] = _slice_exact(
        cfg.replace(threshold=drop_threshold), sub)
    out["check_s"] = time.perf_counter() - t0
    out["check_screen_threshold"] = drop_threshold
    if out["check_kept_rows"] >= out["check_pairs"]:
        raise SystemExit(f"[batch_fit] threshold {drop_threshold} dropped "
                         "no row of the check slice")
    out["exact"] = exact and exact_drop
    return out


def _slice_exact(cfg, sub):
    """Fit ``sub`` under ``cfg`` and under the jnp reference on the host;
    returns (pass-1 tables and survivors byte-equal, the pass-1 table,
    surviving rows)."""
    import jax
    from repro.api import MiningSession

    got = MiningSession(cfg).fit(sub)
    with jax.default_device(_reference_device()):
        want = MiningSession(cfg.replace(backend="jnp",
                                         telemetry=False)).fit(sub)
        want_arrays = want.arrays()
    g_seq, g_dur, g_pat, _ = got.arrays()
    w_seq, w_dur, w_pat, _ = want_arrays
    table = got._corpus.counts()
    exact = bool(
        np.array_equal(table, want._corpus.counts())
        and np.array_equal(g_seq, w_seq) and np.array_equal(g_dur, w_dur)
        and np.array_equal(g_pat, w_pat))
    return exact, table, len(g_seq)


def stream_phase(db, *, n_patients: int = STREAM_PATIENTS,
                 waves: int = STREAM_WAVES,
                 tick_patients: int = TICK_PATIENTS,
                 threshold: int = THRESHOLD, seed: int = 0):
    """Replay the first ``n_patients`` of ``db`` in ``waves`` through the
    streaming engine; returns ``(session, slice, report)``."""
    import jax
    from repro.api import MiningConfig, MiningSession
    from repro.core import mining, sparsity
    from repro.launch.stream import replay_waves

    sub = db.slice_patients(0, min(n_patients, db.n_patients))
    cfg = MiningConfig(threshold=threshold, screen="hash", backend="auto",
                       tick_patients=tick_patients, telemetry=True)
    session = MiningSession(cfg, vocab=db.vocab)
    out = {"patients": sub.n_patients, "waves": waves,
           "tick_patients": tick_patients,
           "n_buckets_log2": cfg.n_buckets_log2,
           "events": int(np.sum(sub.nevents))}
    with timed(out):
        for _ in replay_waves(sub, session, waves, seed):
            session.run()
        frame = session.frame()
    stats = session.service.stats
    out["ticks"] = session.service.n_ticks
    for part in ("dispatch", "device", "collect"):
        out[f"tick_{part}_s"] = float(sum(getattr(st, f"{part}_s")
                                          for st in stats))
    out["impl"] = check_impl("stream", session, ("delta",))
    out["peak_bytes"] = peak_bytes()

    H = cfg.n_buckets_log2
    with jax.default_device(_reference_device()):
        mined = mining.mine_triangular(sub.phenx, sub.date, sub.nevents)
        seq, dur, pat, msk = (np.asarray(x) for x in mining.flatten(mined))
        counts = np.asarray(sparsity.local_bucket_counts(
            mined.seq, mined.mask, H))
        seq, dur, pat = canonical(seq[msk], dur[msk], pat[msk])
        keep = np.asarray(sparsity.screen_hash_from_counts(
            seq, np.ones(len(seq), bool), counts, threshold, H))
    f_seq, f_dur, f_pat, _ = frame.arrays()
    out["rows"] = len(f_seq)
    out["exact"] = bool(
        np.array_equal(np.asarray(session.service.snapshot().counts),
                       counts)
        and np.array_equal(f_seq, seq) and np.array_equal(f_dur, dur)
        and np.array_equal(f_pat, pat)
        and np.array_equal(frame.screen(threshold).keep_mask(), keep))
    return session, sub, out


def serve_phase(session, db, *, n_queries: int = N_QUERIES,
                seed: int = 0) -> dict:
    """Answer ``n_queries`` plans through ``session.serve()``'s batched
    waves and check each mask against frame evaluation."""
    from repro.serving.tspm import plan

    rng = np.random.default_rng(seed)
    codes, freq = np.unique(db.phenx[db.valid_mask()], return_counts=True)
    # common start codes, as the post-COVID vignette's index diagnoses are
    starts = codes[np.argsort(-freq)[:max(1, n_queries // 4)]]
    plans = [plan().screen().starts_with(int(rng.choice(starts)))
             .min_duration(int(rng.choice((0, 30, 90, 180))))
             for _ in range(n_queries)]
    out = {"queries": n_queries, "impl": "jnp(jitted predicate waves)"}
    with session.serve() as server:
        view = server.view()
        with timed(out):
            results = [t.result(timeout=600)
                       for t in [server.submit(p) for p in plans]]
        thr = session.config.threshold
        exact = all(
            np.array_equal(r.keep,
                           p.resolve(thr).apply(view.frame).keep_mask())
            for p, r in zip(plans, results))
        st = server.stats()
    out.update(rows=view.n_rows, waves=st["waves"],
               kept=int(sum(r.keep.sum() for r in results)),
               peak_bytes=peak_bytes(), exact=bool(exact))
    return out


def sharded_phase(db, *, n_shards: int = 4, waves: int = STREAM_WAVES,
                  tick_patients: int = TICK_PATIENTS,
                  threshold: int = THRESHOLD, rebalance_every: int = 8,
                  seed: int = 0) -> dict:
    """``db`` over ``n_shards`` device-pinned shards with rebalancing,
    compared by canonical digest with a one-shard run of the same replay."""
    from repro.api import MiningConfig, MiningSession
    from repro.launch.mesh import make_data_mesh
    from repro.launch.stream import replay_waves

    base = MiningConfig(threshold=threshold, screen="hash", backend="auto",
                        tick_patients=tick_patients, telemetry=True)
    cfg = base.replace(n_shards=n_shards, placement="devices",
                       rebalance_every=rebalance_every,
                       imbalance_threshold=1.02, min_gain=0.0)
    session = MiningSession(cfg, mesh=make_data_mesh(n_shards),
                            vocab=db.vocab)
    out = {"patients": db.n_patients, "shards": n_shards,
           "rebalance_every": rebalance_every}
    with timed(out):
        for _ in replay_waves(db, session, waves, seed):
            session.run()
        digest = canonical_digest(session.service)
    out["impl"] = check_impl("sharded", session, ("delta",))
    snap = session.telemetry.metrics.snapshot()
    out["migrations"] = len(session.service.migrations)
    out["merge_path"] = ",".join(
        f"{k[len('shard.merge{path='):-1]}x{v}"
        for k, v in sorted(snap.items()) if k.startswith("shard.merge{"))
    out["peak_bytes"] = peak_bytes()

    t0 = time.perf_counter()
    single = MiningSession(base, vocab=db.vocab)
    for _ in replay_waves(db, single, waves, seed):
        single.run()
    out["one_shard_wall_s"] = time.perf_counter() - t0
    out["digest"] = digest[:16]
    out["exact"] = digest == canonical_digest(single.service)
    return out


# --- entry point -------------------------------------------------------------
def _report(phase: str, out: dict) -> None:
    _line(phase, **{k: (f"{v:.3f}" if isinstance(v, float) else v)
                    for k, v in out.items() if k not in ("compile_s",
                                                         "run_s")})
    _line(phase, smoke_compile_s=f"{out['compile_s']:.3f}",
          smoke_run_s=f"{out['run_s']:.3f}",
          note="smoke timings, not benchmark numbers")
    if not out["exact"]:
        raise SystemExit(f"[{phase}] result differs from the reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded streaming phase")
    ap.add_argument("--patients", type=int, default=TABLE1_PATIENTS,
                    help="cohort patients (a cut keeps events per patient)")
    ap.add_argument("--stream-patients", type=int,
                    default=STREAM_RUN_PATIENTS,
                    help="patients streamed (and sharded with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "report a result", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    _line("device", platform=dev.platform, kind=repr(dev.device_kind),
          count=len(jax.devices()), compile_cache=cache)
    if args.patients != TABLE1_PATIENTS:
        _line("cut", patients=f"{args.patients} of {TABLE1_PATIENTS}",
              events_per_patient="uncut")
    if args.stream_patients != STREAM_PATIENTS:
        _line("cut", stream_patients=f"{args.stream_patients} of "
              f"{STREAM_PATIENTS}", events_per_patient="uncut")

    t0 = time.perf_counter()
    db = make_cohort(args.patients, TABLE1_AVG_EVENTS, args.seed)
    _line("cohort", patients=db.n_patients, max_events=db.phenx.shape[1],
          setup_s=f"{time.perf_counter() - t0:.3f}")
    if args.chips == 4:
        sub = db.slice_patients(0, min(args.stream_patients, db.n_patients))
        _report("sharded", sharded_phase(sub, seed=args.seed))
    else:
        _report("batch_fit", batch_fit_phase(db))
        session, sub, out = stream_phase(
            db, n_patients=args.stream_patients, seed=args.seed)
        _report("stream", out)
        _report("serve", serve_phase(session, sub, seed=args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
