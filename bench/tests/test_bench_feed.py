"""The ``feed`` kind at toy size on the CPU: a toy live configuration and
feed traffic run through the harness's entry functions and come out
correct; faults planted in the program and the control come out not
correct; the feed's per-layer readers read a toy session's telemetry."""
from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

import toy

import harness
import run

H = 10
FEED_CONFIG = dict(toy.TOY_CONFIG, name="toy_live",
                   events={"distribution": "poisson", "mean": 40, "min": 2},
                   deployment={"chips": 1, "tick_patients": 4,
                               "max_slot_events": 512})
FEED_TRAFFIC = {"kind": "feed", "patients": 12, "deltas_per_patient": 4,
                "queue_deltas": 8, "warm_ticks": 2, "trace_units": 3}
READERS = ("idle_share.feed", "sketch.fold_s", "tick.collect_s",
           "tick.fetch_bytes_per_pair", "delta_roofline")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A toy checkout with the real ``feed`` kind, its readers and a
    ``toy_live.feed`` cell beside the toy fit cells."""
    path = toy.make_root(str(tmp_path_factory.mktemp("toy_feed")))
    bench = os.path.join(path, "bench")
    shutil.copy(os.path.join(toy.BENCH, "kinds", "feed.py"),
                os.path.join(bench, "kinds", "feed.py"))
    with open(os.path.join(bench, "configs", "toy_live.json"), "w") as f:
        json.dump(FEED_CONFIG, f)
    with open(os.path.join(bench, "traffic", "feed_toy.json"), "w") as f:
        json.dump(FEED_TRAFFIC, f)
    bm = harness.benchmark(path)
    bm["configs"].append({"name": "toy_live", "source": "test",
                          "file": "bench/configs/toy_live.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "toy_live.feed", "config": "toy_live",
                            "traffic": "feed_toy", "chips": 1,
                            "why": "test"})
    bm["end_to_end"][0]["workloads"].append("toy_live.feed")
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return path


def _run(root, **kw):
    """One run of the toy feed cell; its window outlasts the feed, so every
    run ticks the whole feed."""
    return run.run(toy.Args("toy_live.feed", seconds=600, **kw),
                   require_tpu=False, root=root)


def test_toy_feed_runs_and_agrees_with_reference(root):
    res = _run(root)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"survivor_rows_diff", "rescreen_mask_diff",
                                  "sketch_table_diff"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"fit_pairs_per_s", "setup_s"}
    assert res["metrics"]["fit_pairs_per_s"]["value"] > 0
    json.dumps(res)


def test_feed_order_is_by_first_date_then_patient_then_index():
    feed = harness._load("kinds", "feed", harness.ROOT)
    date = np.array([[0, 5, 5, 9, 9, 9], [0, 1, 5, 6, 7, 8]], np.int32)
    pat, start, stop = feed.feed_order(date, np.array([2, 2]),
                                       np.array([6, 6]), 2)
    assert pat.tolist() == [0, 1, 1, 0]       # dates 5, 5 (tie), 7, 9
    assert start.tolist() == [2, 2, 4, 4]
    assert stop.tolist() == [4, 4, 6, 6]


def _drop_one_delta(monkeypatch):
    from repro.stream.service import StreamService
    real = StreamService.submit
    calls = [0]

    def submit(self, key, dates, phenx):
        calls[0] += 1
        if calls[0] == FEED_TRAFFIC["patients"] + 20:   # a feed delta
            return
        real(self, key, dates, phenx)
    monkeypatch.setattr(StreamService, "submit", submit)


def _ingest_one_delta_twice(monkeypatch):
    from repro.stream.service import StreamService
    real = StreamService.submit
    calls = [0]

    def submit(self, key, dates, phenx):
        calls[0] += 1
        real(self, key, dates, phenx)
        if calls[0] == FEED_TRAFFIC["patients"] + 20:
            real(self, key, dates, phenx)
    monkeypatch.setattr(StreamService, "submit", submit)


def _bump_one_bucket(monkeypatch):
    from repro.stream.counts import OnlineSupportSketch
    real = OnlineSupportSketch.update_finish
    done = []

    def update_finish(self, pending):
        out = real(self, pending)
        if not done:
            self.counts = self.counts.at[7].add(1)
            done.append(1)
        return out
    monkeypatch.setattr(OnlineSupportSketch, "update_finish", update_finish)


def _publish_before_last_tick(monkeypatch):
    from repro.stream.service import StreamService
    real_tick, real_snapshot = StreamService.tick, StreamService.snapshot

    def tick(self):
        if self.queue:                 # the frame as it stood before a tick
            self._stale = real_snapshot(self)
        return real_tick(self)

    def snapshot(self):
        return getattr(self, "_stale", None) or real_snapshot(self)
    monkeypatch.setattr(StreamService, "tick", tick)
    monkeypatch.setattr(StreamService, "snapshot", snapshot)


FAULTS = {
    "delta-dropped": _drop_one_delta,
    "delta-ingested-twice": _ingest_one_delta_twice,
    "sketch-bucket-incremented": _bump_one_bucket,
    "frame-before-last-tick": _publish_before_last_tick,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_feed_fault_is_not_correct(root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res = _run(root)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_feed_control_is_not_correct(root):
    res = _run(root, control=True)
    assert res["correct"] is False, res["checks"]


# --- the feed's per-layer readers ------------------------------------------
class Ctx:
    kind = "feed"
    peaks = {"hbm_bytes_per_s": 819e9}
    traced_s = 2.0

    def __init__(self, session, units, by_program):
        self.cell = type("Cell", (), {"session": session})()
        self.units = units
        self.trace = {"busy_s": 0.5, "by_program": by_program}

    def device_seconds(self, patterns):
        hit = [s for name, s in self.trace["by_program"].items()
               if any(p in name for p in patterns)]
        return sum(hit) if hit else None


PROGRAMS = {"jit_sketch_update": 0.25, "jit_sketch_rows": 0.01,
            "jit_sketch_land": 0.02, "jit_delta_planes": 0.004,
            "jit_pairgen_planes": 9.0}


def _session(telemetry=True, ticks=3):
    """A toy live session whose telemetry covers its last ``ticks``."""
    from repro.api import MiningSession
    rng = np.random.default_rng(2)
    s = MiningSession(harness.mining_config(
        screen="fused", threshold=2, n_buckets_log2=H, backend="jnp",
        tick_patients=4, telemetry=telemetry))
    day = 0
    for t in range(ticks + 1):
        if t == 1:
            s.telemetry.reset()
        for p in range(4):
            n = int(rng.integers(3, 9))
            s.submit(p, np.arange(day, day + n, dtype=np.int32),
                     rng.integers(0, 15, n).astype(np.int32))
            day += n
        s.service.tick()
    return s


def _read(name, session, units=3, by_program=PROGRAMS):
    return harness.metric_reader(name)(Ctx(session, units, by_program))


def test_feed_readers_read_a_toy_session():
    s = _session()
    snap = s.metrics()
    collect = [sp.duration_s for sp in s.trace().find("tick.collect")]
    assert len(collect) == 3
    assert _read("idle_share.feed", s) == pytest.approx(75.0)
    assert _read("sketch.fold_s", s) == pytest.approx(0.28 / 3)
    assert _read("tick.collect_s", s) == pytest.approx(sum(collect) / 3)
    assert _read("tick.fetch_bytes_per_pair", s) == pytest.approx(
        snap["stream.tick.fetch_bytes"] / snap["stream.pairs"])
    least = 8 * snap["stream.history_events"] + 8 * snap["stream.events"] \
        + 12 * snap["stream.pairs"]
    assert _read("delta_roofline", s) == pytest.approx(
        100 * least / 819e9 / 0.004)


@pytest.mark.parametrize("name", ["tick.collect_s",
                                  "tick.fetch_bytes_per_pair",
                                  "delta_roofline"])
def test_feed_telemetry_readers_are_none_with_telemetry_off(name):
    assert _read(name, _session(telemetry=False)) is None


@pytest.mark.parametrize("name", ["sketch.fold_s", "delta_roofline"])
def test_feed_trace_readers_are_none_without_their_programs(name):
    assert _read(name, _session(), by_program={"jit_pairgen_planes": 1.0}) \
        is None


@pytest.mark.parametrize("name", READERS)
def test_feed_readers_are_none_outside_feed_cells(name):
    ctx = Ctx(_session(), 3, PROGRAMS)
    ctx.kind = "fit"
    assert harness.metric_reader(name)(ctx) is None


def test_feed_readers_are_declared_for_the_feed_cell():
    bm = harness.benchmark()
    declared = {m["name"]: m for m in bm["per_layer"]}
    for name in READERS:
        assert declared[name]["workloads"] == ["ad_table1_live.feed"]
        assert declared[name]["moves"] == "fit_pairs_per_s"
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "ad_table1_live.feed" in e2e["fit_pairs_per_s"]["workloads"]
