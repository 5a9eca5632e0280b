"""Telemetry subsystem: registry semantics, span trees, exactness, retraces.

The observability layer's contract has three legs, all tested here:

  * **recording** — counters/gauges/histograms resolve once and mutate in
    place, labels key distinct series, spans nest per track with legal
    out-of-order finishes, and both exports (nested JSON, Chrome trace)
    round-trip;
  * **absence** — disabled telemetry is the shared no-op singletons:
    identical object every call, zero allocations on the hot path;
  * **exactness** — telemetry never changes a mined byte, across all five
    planner engines, and the jitted ingest still recompiles O(log) times
    over a 200-tick growing stream (the retrace counter measures the
    invariant the geometric-growth policy promises).

A subprocess case forces 2 host devices and requires the per-shard
``tick.device`` spans to *overlap* in time under device placement while
``shard_load()`` reports consumable busy fractions — the async dispatch
win, measured rather than asserted from code structure.
"""
import json
import os
import subprocess
import sys
import textwrap
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.api import MiningConfig, MiningSession
from repro.stream.shard import ShardedStreamService, ShardRouter
from tests.conftest import random_dbmart
from tests.test_stream import H


# --- metrics registry -------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = obs.MetricsRegistry()
    c = reg.counter("ticks")
    c.inc()
    c.inc(4)
    assert c.value == 5 and reg.value("ticks") == 5

    g = reg.gauge("depth")
    g.set(7)
    g.set(3)
    assert g.value == 3

    h = reg.histogram("lat")
    for v in (2e-6, 3e-6, 1e-3, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["min"] == 2e-6 and s["max"] == 5.0
    assert abs(s["sum"] - (2e-6 + 3e-6 + 1e-3 + 5.0)) < 1e-12
    assert sum(s["buckets"].values()) == 4
    # 2us and 3us land in different exponential buckets (bounds are 2^i us)
    assert len(s["buckets"]) >= 3


def test_registry_labels_and_same_object():
    reg = obs.MetricsRegistry()
    a0 = reg.counter("evts", shard=0)
    a1 = reg.counter("evts", shard=1)
    assert a0 is not a1
    a0.inc(3)
    assert reg.value("evts", shard=0) == 3
    assert reg.value("evts", shard=1) == 0
    # same key resolves to the same object, from any layer
    assert reg.counter("evts", shard=0) is a0
    with pytest.raises(TypeError):
        reg.gauge("evts", shard=0)      # kind change is an error
    snap = reg.snapshot()
    assert snap["evts{shard=0}"] == 3 and snap["evts{shard=1}"] == 0


def test_registry_reset_keeps_cached_references():
    reg = obs.MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("t")
    c.inc(9)
    h.observe(1.0)
    reg.reset()
    assert c.value == 0 and h.count == 0 and h.summary()["buckets"] == {}
    c.inc()                             # cached reference still records
    assert reg.value("n") == 1


def test_histogram_rejects_bad_config():
    with pytest.raises(ValueError):
        obs.Histogram(base=1.0)
    with pytest.raises(ValueError):
        obs.Histogram(scale=0.0)


# --- span tracer ------------------------------------------------------------

def test_span_nesting_and_json_forest():
    tr = obs.SpanTracer()
    with tr.span("outer", track="main"):
        with tr.span("inner", track="main", n=3):
            pass
        with tr.span("inner2", track="main"):
            pass
    other = tr.begin("solo", track="side")
    tr.finish(other)
    forest = tr.to_json()
    roots = {n["name"] for n in forest}
    assert roots == {"outer", "solo"}
    outer = next(n for n in forest if n["name"] == "outer")
    assert [c["name"] for c in outer["children"]] == ["inner", "inner2"]
    assert outer["children"][0]["args"] == {"n": 3}
    assert all(n["t1"] >= n["t0"] for n in forest)


def test_out_of_order_finish_is_legal():
    """Async regions close in any order: the device span opened at
    dispatch outlives the collect span opened after it."""
    tr = obs.SpanTracer()
    d0 = tr.begin("device", track="shard0")
    d1 = tr.begin("device", track="shard1")
    tr.finish(d1)                       # shard1 collected first
    c0 = tr.begin("collect", track="shard0")
    tr.finish(c0)
    tr.finish(d0)
    # collect began while device was open on the same track -> nested
    forest = tr.to_json()
    by_track = {n["track"]: n for n in forest}
    assert by_track["shard0"]["name"] == "device"
    assert [c["name"] for c in by_track["shard0"]["children"]] == ["collect"]
    assert tr.find("device", track="shard1")[0]["t1"] is not None \
        if isinstance(tr.find("device", track="shard1")[0], dict) \
        else tr.find("device", track="shard1")[0].t1 is not None


def test_chrome_trace_roundtrip(tmp_path):
    tr = obs.SpanTracer()
    with tr.span("tick", track="shard0", cat="host", pairs=12):
        pass
    with tr.span("tick", track="shard1", cat="device"):
        pass
    path = tmp_path / "trace.json"
    tr.dump_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {m["args"]["name"] for m in meta} == {"shard0", "shard1"}
    assert all(m["name"] == "thread_name" for m in meta)
    assert len(spans) == 2
    assert {s["tid"] for s in spans} == {m["tid"] for m in meta}
    tick0 = next(s for s in spans if s["cat"] == "host")
    assert tick0["args"] == {"pairs": 12}
    assert all(s["dur"] >= 0 and s["ts"] >= 0 for s in spans)


# --- disabled telemetry: no-ops, no allocations -----------------------------

def test_noop_singletons_are_shared():
    assert obs.NOOP.metrics is obs.NOOP_REGISTRY
    assert obs.NOOP.tracer is obs.NOOP_TRACER
    assert not obs.NOOP.enabled
    r = obs.NOOP_REGISTRY
    assert r.counter("a") is r.gauge("b") is r.histogram("c", shard=1)
    assert r.counter("a") is obs.NOOP_METRIC
    assert obs.NOOP_TRACER.begin("x") is obs.NOOP_TRACER.begin("y")
    assert obs.NOOP.snapshot() == {}
    assert obs.NOOP_TRACER.to_chrome_trace()["traceEvents"] == []


def test_noop_hot_path_allocates_nothing():
    m = obs.NOOP_METRIC
    sp_tracer = obs.NOOP_TRACER
    # warm any lazy interning
    m.inc()
    m.set(1.0)
    m.observe(0.5)
    sp = sp_tracer.begin("t")
    sp_tracer.finish(sp)
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(1000):
        m.inc()
        m.inc(2)
        m.set(3.5)
        m.observe(1e-3)
        s = sp_tracer.begin("tick", track="shard0", pairs=1)
        sp_tracer.finish(s)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(base, "lineno")
                if d.size_diff > 0)
    # a few hundred bytes of slack for tracemalloc's own bookkeeping;
    # a real per-call allocation over 5000 calls would be tens of KiB
    assert grown < 4096, f"no-op hot path grew {grown} bytes"


# --- exactness: telemetry never changes mined bytes -------------------------

@pytest.mark.parametrize("engine", ["batch", "chunked", "files", "stream",
                                    "sharded"])
def test_byte_identical_on_off(engine):
    rng = np.random.default_rng(hash(engine) % (1 << 30))
    db = random_dbmart(rng, n_patients=10, max_events=12)
    frames = {}
    for tel in (False, True):
        cfg = MiningConfig(engine=engine, screen="hash", n_buckets_log2=H,
                           threshold=2, tick_patients=3,
                           n_shards=2 if engine == "sharded" else 1,
                           telemetry=tel)
        frames[tel] = MiningSession(cfg).fit(db)
    for a, b in zip(frames[False].arrays(), frames[True].arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b)), engine
    assert (frames[False]._corpus.counts()
            == frames[True]._corpus.counts()).all()
    assert frames[False].screen().n_kept == frames[True].screen().n_kept


def test_session_accessors_require_telemetry():
    s = MiningSession(MiningConfig())
    with pytest.raises(RuntimeError):
        s.metrics()
    with pytest.raises(RuntimeError):
        s.trace()
    s_on = MiningSession(MiningConfig(telemetry=True))
    assert s_on.metrics() == {}          # empty but live
    assert s_on.trace() is s_on.telemetry.tracer


def test_session_metrics_record_mining():
    rng = np.random.default_rng(5)
    db = random_dbmart(rng, n_patients=8, max_events=10)
    s = MiningSession(MiningConfig(engine="stream", telemetry=True,
                                   tick_patients=3, screen="hash",
                                   n_buckets_log2=H))
    s.fit(db)
    snap = s.metrics()
    assert snap["stream.ticks"] > 0
    assert snap["stream.events"] == int(db.nevents.sum())
    assert snap["stream.tick.dispatch_s"]["count"] == snap["stream.ticks"]
    # only patients with events are ever submitted/admitted
    assert snap["store.admits"] == int((np.asarray(db.nevents) > 0).sum())
    assert "sketch.bucket_load_factor" in snap
    fit_spans = s.trace().find("session.fit")
    assert len(fit_spans) == 1 and fit_spans[0].args["engine"] == "stream"
    # tick spans: dispatch/device/collect per tick, on the stream track
    n_ticks = snap["stream.ticks"]
    assert len(s.trace().find("tick.dispatch")) == n_ticks
    assert len(s.trace().find("tick.device")) == n_ticks
    assert len(s.trace().find("tick.collect")) == n_ticks


def test_tick_fetch_span_and_stream_counters(monkeypatch):
    """One ``tick.fetch`` span per tick, inside its ``tick.collect``;
    ``stream.tick.fetch_bytes`` is the bytes of the slabs the ticks
    mined (mask 1 B, id 8 B, duration 4 B a slot), the sum of the spans'
    ``bytes``; ``stream.history_events`` is Σ n_old + n_new over every
    tick's slots."""
    from repro.stream import service as service_lib

    slabs = []
    real = service_lib.delta_lib.delta_mine

    def recording(*a, **k):
        mined = real(*a, **k)
        slabs.append(int(np.prod(mined.seq.shape)))
        return mined
    monkeypatch.setattr(service_lib.delta_lib, "delta_mine", recording)

    rng = np.random.default_rng(8)
    s = MiningSession(MiningConfig(telemetry=True, tick_patients=6,
                                   n_buckets_log2=H))
    held = np.zeros(5, np.int64)
    history, day = 0, 0
    for _ in range(7):
        for p in range(5):                 # one delta a patient a tick
            n = int(rng.integers(1, 9))
            s.submit(p, np.arange(day, day + n, dtype=np.int32),
                     rng.integers(0, 12, n).astype(np.int32))
            history += int(held[p]) + n
            held[p] += n
            day += n
        s.service.tick()
    snap = s.metrics()
    tr = s.trace()
    fetch, collect = tr.find("tick.fetch"), tr.find("tick.collect")
    assert len(fetch) == len(collect) == snap["stream.ticks"] == 7
    for f, c in zip(fetch, collect):
        assert f.parent is c
    assert snap["stream.tick.fetch_bytes"] == 13 * sum(slabs) \
        == sum(f.args["bytes"] for f in fetch)
    assert snap["stream.history_events"] == history


# --- TickStats split (the overlapping-wall fix) -----------------------------

def test_tick_stats_split_populated_without_telemetry():
    """dispatch/collect/device splits are plain perf_counter reads, so
    they are populated even with telemetry off (benchmarks rely on it)."""
    from repro.stream.service import StreamService

    svc = StreamService(tick_patients=4, n_buckets_log2=H)
    rng = np.random.default_rng(2)
    db = random_dbmart(rng, n_patients=6, max_events=8)
    for p in range(db.n_patients):
        n = int(db.nevents[p])
        if n:
            svc.submit(p, db.date[p, :n], db.phenx[p, :n])
    stats = svc.run()
    assert stats
    for st in stats:
        assert st.dispatch_s > 0 and st.collect_s > 0 and st.device_s >= 0
        # the split partitions the begin->finish wall: components can
        # never exceed it (small float slack for the two clock reads)
        assert st.dispatch_s + st.device_s + st.collect_s \
            <= st.wall_s + 1e-6


# --- retrace budget: O(log) recompiles over a growing stream ----------------

def test_retrace_budget_over_growing_stream():
    """200 ticks of ever-growing histories: the geometric capacity policy
    must keep jitted-ingest recompiles O(log total work), measured by the
    jit.retraces counter (satellite of the tick-latency histogram — a
    per-tick retrace would show up as ~200 here)."""
    from repro.stream.service import StreamService

    tel = obs.Telemetry()
    svc = StreamService(tick_patients=4, n_buckets_log2=H, telemetry=tel)
    rng = np.random.default_rng(9)
    n_ticks = 200
    total_events = 0
    for t in range(n_ticks):
        for p in range(int(rng.integers(1, 4))):
            k = int(rng.integers(6))
            n = int(rng.integers(1, 4))
            dates = np.arange(total_events, total_events + n, dtype=np.int32)
            svc.submit(k, dates, rng.integers(0, 5, n).astype(np.int32))
            total_events += n
        svc.run()
    snap = tel.metrics.snapshot()
    assert snap["stream.ticks"] >= n_ticks
    retraces = snap["jit.retraces"]
    budget = 6 * int(np.ceil(np.log2(total_events + 2))) + 12
    assert retraces <= budget, \
        f"{retraces} recompiles over {total_events} events " \
        f"(budget {budget}): ingest is retracing per tick, not O(log)"


# --- device-timed busy signal + busy-weighted rebalance ---------------------

def test_shard_load_fractions():
    svc = ShardedStreamService(n_shards=2, tick_patients=3,
                               n_buckets_log2=H)
    rng = np.random.default_rng(4)
    db = random_dbmart(rng, n_patients=8, max_events=10)
    for p in range(db.n_patients):
        n = int(db.nevents[p])
        if n:
            svc.submit(p, db.date[p, :n], db.phenx[p, :n])
    svc.run()
    fracs = svc.shard_load()
    assert len(fracs) == 2
    assert all(0.0 <= f <= 1.0 for f in fracs)
    assert any(f > 0.0 for f in fracs)   # something ran on some shard
    # the window reset: an immediate re-poll has accumulated ~nothing
    again = svc.shard_load()
    assert all(f < 0.5 for f in again)


def test_busy_weighted_rebalance_exact_and_converges():
    """Weights skew the LPT toward idle shards without changing mined
    results; degenerate weights (all-zero, mismatched length) are
    handled; the safety cap stops any weighted ping-pong."""
    rng = np.random.default_rng(6)
    db = random_dbmart(rng, n_patients=12, max_events=12)
    from tests.test_stream import batch_reference
    from tests.test_stream_sharded import sharded_triples

    seq, dur, pat, msk, cnt = batch_reference(db)
    svc = ShardedStreamService(
        n_shards=3, tick_patients=3, n_buckets_log2=H,
        router=ShardRouter(3, pinned={p: 0 for p in range(db.n_patients)}))
    for p in range(db.n_patients):
        n = int(db.nevents[p])
        if n:
            svc.submit(p, db.date[p, :n], db.phenx[p, :n])
    svc.run()
    # shard 0 holds everything; pretend it is also the busiest device
    moves = svc.rebalance(imbalance_threshold=1.1,
                          busy_weights=[0.9, 0.1, 0.1])
    assert moves                         # the hot shard drained
    assert all(src == 0 for _, src, _ in moves)
    # all-zero weights (nothing polled) fall back to unweighted
    svc.rebalance(busy_weights=[0.0, 0.0, 0.0])
    with pytest.raises(ValueError):
        svc.rebalance(busy_weights=[1.0, 1.0])
    snap, keys = sharded_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (np.asarray(snap.counts) == cnt).all()


def test_busy_weighted_auto_rebalance_exactness():
    """config-driven: busy_weighted_rebalance + rebalance_every feeds
    shard_load() into the periodic LPT pass; results stay batch-exact."""
    rng = np.random.default_rng(13)
    db = random_dbmart(rng, n_patients=10, max_events=12)
    from tests.test_stream import batch_reference

    seq, dur, pat, msk, cnt = batch_reference(db)
    session = MiningSession(MiningConfig(
        engine="sharded", n_shards=3, tick_patients=2, screen="hash",
        n_buckets_log2=H, rebalance_every=2, imbalance_threshold=1.1,
        busy_weighted_rebalance=True, telemetry=True))
    frame = session.fit(db)
    got = sorted(zip(*(np.asarray(a) for a in
                       (frame.arrays()[2], frame.arrays()[0],
                        frame.arrays()[1]))))
    assert got == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (frame._corpus.counts() == cnt).all()


def test_overlapping_device_spans_on_forced_devices():
    """2 forced host devices, device placement, telemetry on: per-shard
    ``tick.device`` spans must overlap in wall time (the dispatched waves
    really run concurrently) and shard_load() must return busy fractions
    the rebalancer can consume."""
    script = textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 2, jax.devices()
        from repro import obs
        from repro.stream.shard import ShardedStreamService
        from tests.conftest import random_dbmart
        from tests.test_stream import H

        tel = obs.Telemetry()
        svc = ShardedStreamService(n_shards=2, placement="devices",
                                   tick_patients=4, n_buckets_log2=H,
                                   telemetry=tel)
        rng = np.random.default_rng(21)
        db = random_dbmart(rng, n_patients=12, max_events=14)
        for p in range(db.n_patients):
            n = int(db.nevents[p])
            if n:
                svc.submit(p, db.date[p, :n], db.phenx[p, :n])
        svc.run()

        d0 = tel.tracer.find("tick.device", track="shard0")
        d1 = tel.tracer.find("tick.device", track="shard1")
        assert d0 and d1, (len(d0), len(d1))
        overlaps = [
            (a, b) for a in d0 for b in d1
            if max(a.t0, b.t0) < min(a.t1, b.t1)]
        if not overlaps:
            raise SystemExit("no overlapping device spans across shards")
        fracs = svc.shard_load()
        assert len(fracs) == 2 and all(0.0 <= f <= 1.0 for f in fracs)
        assert any(f > 0.0 for f in fracs), fracs
        # the busy signal is consumable by the weighted rebalancer
        svc.rebalance(busy_weights=fracs)
        doc = tel.tracer.to_chrome_trace()
        tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert len(tids) >= 2, tids
        print("obs-overlap-ok", len(overlaps))
    """)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(repo, "src"), repo,
                    env.get("PYTHONPATH", "")] if p)
    proc = subprocess.run([sys.executable, "-c", script], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "obs-overlap-ok" in proc.stdout


# --- serving metrics: recorded when on, the shared no-ops when off ----------

def _served_session(telemetry: bool):
    rng = np.random.default_rng(59)
    db = random_dbmart(rng, n_patients=6, max_events=10)
    session = MiningSession(MiningConfig(threshold=2, screen="hash",
                                         n_buckets_log2=H,
                                         telemetry=telemetry))
    session.fit(db)
    return session, int(np.unique(db.phenx[db.phenx >= 0])[0])


def test_serve_metrics_disabled_are_noop_singletons():
    """With telemetry off the server resolves every serve.* instrument to
    the shared no-op objects — the query hot path records nothing,
    allocates no metric state, and ``stats()`` still reports plain
    numbers from its own counters."""
    from repro.serving.tspm import plan

    session, code = _served_session(telemetry=False)
    server = session.serve()
    for m in (server._m_queries, server._m_waves, server._m_occupancy,
              server._m_hits, server._m_misses, server._m_evictions,
              server._m_hit_ratio, server._m_wait):
        assert m is obs.NOOP_METRIC
    assert server._tracer is obs.NOOP_TRACER
    server.query(plan().screen(2).starts_with(code))
    server.query(plan().screen(2).starts_with(code))
    st = server.stats()
    assert st["queries"] == 2 and st["cache_hits"] == 1
    assert session.telemetry.metrics.snapshot() == {}


def test_serve_metrics_and_spans_recorded():
    from repro.serving.tspm import plan

    session, code = _served_session(telemetry=True)
    with session.serve() as server:
        p = plan().screen(2).starts_with(code)
        server.submit(p).result(timeout=60)
        server.query(p)
    snap = session.telemetry.metrics.snapshot()
    assert snap["serve.queries"] == 2
    assert snap["serve.waves"] == 1            # the second query was a hit
    assert snap["serve.cache.hits"] == 1
    assert snap["serve.cache.misses"] == 1
    assert snap["serve.cache.hit_ratio"] == 0.5
    assert snap["serve.batch_occupancy"]["count"] == 1
    assert len(session.telemetry.tracer.find("serve.eval")) == 2
    assert snap["serve.wait_s"]["count"] == 1  # only the submitted query
    evs = session.telemetry.tracer.to_chrome_trace()["traceEvents"]
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert {"serve.eval", "serve.wait"} <= names
    serve_meta = [e for e in evs if e.get("ph") == "M"
                  and e["args"].get("name") == "serve"]
    assert serve_meta, "serve spans are not on their own track"
