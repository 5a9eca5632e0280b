"""The per-layer metrics read from the fit's own spans and counters, fed a
toy session on the CPU: the right number with telemetry on, None with it
off or where the program records no such span or counter."""
from __future__ import annotations

import numpy as np
import pytest

import toy  # noqa: F401  (puts bench/ on the path)

import harness

READERS = ("pass2.fetch_s", "pass2.compact_s", "pass2.fetch_bytes_per_pair",
           "pass1.real_share")
H = 10
BUDGET = 12_000          # several pass-2 chunks at the toy size


class Ctx:
    kind = "fit"

    def __init__(self, session):
        self.cell = type("Cell", (), {"session": session})()


def _db():
    from repro.data.dbmart import DBMart
    rng = np.random.default_rng(5)
    nevents = rng.integers(2, 15, 12).astype(np.int32)
    E = 16
    phenx = rng.integers(0, 20, (12, E)).astype(np.int32)
    date = np.sort(rng.integers(0, 300, (12, E)), axis=1).astype(np.int32)
    return DBMart(phenx, date, nevents)


def _session(telemetry=True, fits=1, engine=None):
    from repro.api import MiningSession
    s = MiningSession(harness.mining_config(
        screen="fused", threshold=2, n_buckets_log2=H, backend="jnp",
        budget_bytes=BUDGET, engine=engine, telemetry=telemetry))
    db = _db()
    for _ in range(fits):
        s.fit(db)
    return s, db


def _read(name, session):
    return harness.metric_reader(name)(Ctx(session))


@pytest.mark.parametrize("fits", [1, 2])
def test_span_readers_divide_by_the_fits(fits):
    s, _ = _session(fits=fits)
    tr = s.trace()
    fetch = sum(sp.duration_s for sp in tr.find("fit.pass2.fetch"))
    compact = sum(sp.duration_s for sp in tr.find("fit.pass2.compact")) \
        + sum(sp.duration_s for sp in tr.find("fit.assemble"))
    assert len(tr.find("fit.assemble")) == fits
    assert _read("pass2.fetch_s", s) == pytest.approx(fetch / fits)
    assert _read("pass2.compact_s", s) == pytest.approx(compact / fits)
    assert _read("pass2.fetch_s", s) > 0 and _read("pass2.compact_s", s) > 0


def test_fetch_bytes_per_pair_reader():
    from repro.core import chunking, mining
    s, db = _session()
    n = db.nevents.astype(np.int64)
    pairs = int(np.sum(n * (n - 1) // 2))
    # the jnp backend fetches keep (1 B), id (8 B) and duration (4 B) for
    # each slot of each chunk's packed triangle
    slots = sum(ch.n_patients * mining.n_pairs(ch.max_events)
                for ch in chunking.plan_chunks(db.nevents, BUDGET))
    assert _read("pass2.fetch_bytes_per_pair", s) == pytest.approx(
        13 * slots / pairs)


def test_real_share_reader():
    s, db = _session()
    n = db.nevents.astype(np.int64)
    P, E = db.phenx.shape
    assert _read("pass1.real_share", s) == pytest.approx(
        100 * np.sum(n * (n - 1) // 2) / (P * E * E))


@pytest.mark.parametrize("name", READERS)
def test_readers_are_none_with_telemetry_off(name):
    s, _ = _session(telemetry=False)
    assert _read(name, s) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_are_none_without_the_fit_spans(name):
    """A session whose fit records none of the names (the streaming
    engine, as a program from before them would) reads None."""
    s, _ = _session(engine="stream")
    assert s.trace().find("session.fit")
    assert _read(name, s) is None


def test_readers_are_declared_for_the_fit_cells():
    bm = harness.benchmark()
    fit_cells = [w["name"] for w in bm["workloads"]
                 if harness.cell_spec(bm, w["name"])[2]["kind"] == "fit"]
    declared = {m["name"]: m for m in bm["per_layer"]}
    for name in READERS:
        assert declared[name]["workloads"] == fit_cells
        assert declared[name]["moves"] == "fit_pairs_per_s"
