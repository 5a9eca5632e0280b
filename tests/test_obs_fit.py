"""Telemetry of the fused batch fit (``chunking.mine_fused``): the span
tree of both passes, counters in closed form, exactness and absence with
telemetry off, and the spans' mirror on the profiler's host timeline."""
import glob
import os
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.api import MiningConfig, MiningSession
from repro.core import chunking, mining, sparsity
from repro.kernels.tspm_fused import ops as fused_ops
from tests.conftest import random_dbmart

H = 10      # a small table, so buckets collide and the screen drops pairs

PASS2_PHASES = ["fit.pass2.dispatch", "fit.pass2.wait", "fit.pass2.compact",
                "fit.pass2.fetch"]


def _db(seed=11, n_patients=14, max_events=14):
    return random_dbmart(np.random.default_rng(seed), n_patients=n_patients,
                         max_events=max_events)


def _fit(db, budget=None, telemetry=True, backend="jnp", jax_annotations=False,
         engine=None):
    s = MiningSession(MiningConfig(
        screen="fused", threshold=2, n_buckets_log2=H, backend=backend,
        budget_bytes=budget, engine=engine, telemetry=telemetry,
        jax_annotations=jax_annotations))
    return s, s.fit(db)


def _chunks(db, budget):
    return chunking.plan_chunks(np.asarray(db.nevents), budget or (1 << 28))


# --- the span tree ----------------------------------------------------------

@pytest.mark.parametrize("budget", [None, 12_000])
def test_fit_span_tree(budget, monkeypatch):
    # every saving cuts a width group, so the toy cohort takes two
    monkeypatch.setattr(fused_ops, "GROUP_SLOTS", 0)
    db = _db()
    s, _ = _fit(db, budget)
    chunks = _chunks(db, budget)
    assert (len(chunks) > 1) == (budget is not None)
    root, = s.trace().to_json()
    assert root["name"] == "session.fit"
    kids = root["children"]
    assert [k["name"] for k in kids] == (
        ["fit.pass1"] + ["fit.pass2"] * len(chunks) + ["fit.assemble"])
    cp = fused_ops.counting_plan(db.nevents, H, "jnp")
    assert kids[0]["args"] == {"impl": "jnp", "blocks": len(cp.blocks),
                               "widths": list(cp.widths), "H": H}
    assert len(cp.widths) > 1       # lengths 0..14 pad to 8 and to 16
    for node, ch in zip(kids[1:-1], chunks):
        assert node["args"] == {"patients": ch.n_patients,
                                "E": ch.max_events}
        assert [c["name"] for c in node["children"]] == PASS2_PHASES
        assert all(c["children"] == [] for c in node["children"])
        t = [(c["t0"], c["t1"]) for c in node["children"]]
        assert all(a1 <= b0 for (_, a1), (b0, _) in zip(t, t[1:]))
        assert node["t0"] <= t[0][0] and t[-1][1] <= node["t1"]
    # every span arg is a host scalar or a list of host integers: no
    # device array is kept alive
    for sp in s.trace().spans:
        assert all(type(v) in (int, str) or
                   (type(v) is list and all(type(x) is int for x in v))
                   for v in sp.args.values()), sp


def test_span_durations_add_up_to_the_fit():
    s, _ = _fit(_db(), 12_000)
    tr = s.trace()
    fit, = tr.find("session.fit")
    parts = sum(sp.duration_s for name in ("fit.pass1", "fit.pass2",
                                           "fit.assemble")
                for sp in tr.find(name))
    assert parts <= fit.duration_s
    assert parts >= 0.95 * fit.duration_s


# --- counters in closed form ------------------------------------------------

@pytest.mark.parametrize("budget", [None, 12_000])
def test_fit_counters_closed_form(budget, monkeypatch):
    monkeypatch.setattr(fused_ops, "GROUP_SLOTS", 0)
    db = _db()
    s, frame = _fit(db, budget)
    snap = s.metrics()
    P, E = db.phenx.shape
    assert snap["fit.pairs"] == int(mining.count_sequences(db.nevents))
    # jnp blocks: each patient's group width squared, padding included,
    # and never more than one width for the whole cohort
    cp = fused_ops.counting_plan(db.nevents, H, "jnp")
    assert snap["fit.pass1.slots"] == cp.slots == sum(
        len(b.patients) * b.width ** 2 for b in cp.blocks)
    assert cp.slots < P * E * E
    # pass 2 copies only each chunk's compacted buffers: capacity slots of
    # SURVIVOR_BYTES, the capacity the least step that holds the survivors
    tr = s.trace()
    compact = tr.find("fit.pass2.compact")
    assert len(compact) == len(_chunks(db, budget))
    for sp in compact:
        n, cap = sp.args["survivors"], sp.args["capacity"]
        assert cap == sparsity.survivor_capacity(n)
        assert n <= cap <= 1.2 * n + sparsity.CAPACITY_GRANULE
    assert sum(sp.args["survivors"] for sp in compact) == len(frame)
    assert snap["fit.pass2.capacity"] == sum(sp.args["capacity"]
                                             for sp in compact)
    assert snap["fit.pass2.fetch_bytes"] == \
        sparsity.SURVIVOR_BYTES * snap["fit.pass2.capacity"]
    assert sum(sp.args["bytes"] for sp in tr.find("fit.pass2.fetch")) \
        == snap["fit.pass2.fetch_bytes"]


def test_kernel_backend_counts_dense_planes():
    """Pairgen mines dense [P, E, E] planes, which pass 2 compacts on the
    device, so it copies only the survivors' capacity whatever the padding;
    the fused counting kernel's slots are its padded planes."""
    db = _db(seed=3, n_patients=10, max_events=9)
    s, frame = _fit(db, backend="kernel")
    snap = s.metrics()
    P = db.phenx.shape[0]
    assert snap["fit.pass2.capacity"] == sparsity.survivor_capacity(len(frame))
    assert snap["fit.pass2.fetch_bytes"] == \
        sparsity.SURVIVOR_BYTES * snap["fit.pass2.capacity"]
    cp = fused_ops.counting_plan(db.nevents, H, "kernel")
    assert cp.use_kernel and len(cp.blocks) == 1
    assert cp.widths == (128,)  # every history pads to one lane tile
    rows = -(-P // 8) * 8      # 10 patients padded to the kernel's pb = 8
    assert snap["fit.pass1.slots"] == cp.slots == rows * 128 * 128
    pass1, = s.trace().find("fit.pass1")
    assert pass1.args["impl"] == "kernel"
    assert pass1.args["widths"] == [128]
    _, ref = _fit(db, backend="kernel", telemetry=False)
    for a, b in zip(frame.arrays(), ref.arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("P,E,blk,want", [(10, 16, 4, 10 * 16 * 16),
                                          (0, 16, 4, 0)])
def test_counting_plan_jnp_slots(P, E, blk, want):
    """Equal lengths: one width, and P x E x E slots (blocks of 2 leave no
    row empty, and blocks of 4 or 3 would)."""
    cp = fused_ops.counting_plan(np.full(P, E), 20, "jnp", block_patients=blk)
    assert not cp.use_kernel
    assert cp.slots == want
    assert [len(b.patients) for b in cp.blocks] == [2] * (P // 2)
    assert cp.widths == ((E,) if P else ())


# --- exactness and absence --------------------------------------------------

@pytest.mark.parametrize("engine", ["batch", "chunked", "files"])
def test_fused_fit_byte_identical_on_off(engine):
    db = _db(seed={"batch": 1, "chunked": 2, "files": 3}[engine])
    frames = {tel: _fit(db, 12_000, telemetry=tel, engine=engine)[1]
              for tel in (False, True)}
    for a, b in zip(frames[False].arrays(), frames[True].arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b)), engine
    assert np.array_equal(frames[False]._corpus.counts(),
                          frames[True]._corpus.counts())


def test_failed_chunk_closes_its_spans(monkeypatch):
    """An exception inside a pass-2 phase ends every span it was under, so
    the tracer's stack and the profiler's annotations are left closed."""
    def failing(*a, **k):
        raise RuntimeError("compaction failed")
    monkeypatch.setattr(sparsity, "_compact", failing)
    s = MiningSession(MiningConfig(screen="fused", threshold=2,
                                   n_buckets_log2=H, backend="jnp",
                                   telemetry=True))
    with pytest.raises(RuntimeError, match="compaction failed"):
        s.fit(_db())
    tr = s.trace()
    assert [sp.name for sp in sorted(tr.spans, key=lambda sp: sp.t0)] == [
        "session.fit", "fit.pass1", "fit.pass2"] + PASS2_PHASES[:3]
    assert all(sp.t1 is not None for sp in tr.spans)
    assert tr.begin("next").parent is None      # nothing left open


def test_fit_off_records_and_allocates_nothing():
    db = _db()
    _fit(db, 12_000, telemetry=False)        # warm every compile
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    s, _ = _fit(db, 12_000, telemetry=False)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    assert s.telemetry is obs.NOOP
    assert obs.NOOP_TRACER.spans == [] and obs.NOOP_SPAN.args == {}
    assert obs.NOOP_REGISTRY.snapshot() == {}
    obs_dir = os.path.dirname(obs.__file__)
    keep = [tracemalloc.Filter(True, os.path.join(obs_dir, "*"))]
    grown = sum(d.size_diff for d in after.filter_traces(keep).compare_to(
        base.filter_traces(keep), "lineno") if d.size_diff > 0)
    assert grown == 0, f"telemetry-off fit allocated {grown} B in repro.obs"


# --- the profiler's host timeline -------------------------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


def test_spans_mirror_onto_the_profiler_host_plane(tmp_path):
    import jax
    db = _db()
    _fit(db, 12_000, telemetry=False)        # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        s, _ = _fit(db, 12_000, jax_annotations=True)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    tr = s.trace()
    mirrored = {}
    for name in ["session.fit", "fit.pass1", "fit.pass2", "fit.assemble"] \
            + PASS2_PHASES:
        spans = tr.find(name)
        evs = sorted((e for e in events if e[0] == name),
                     key=lambda e: e[1])
        assert len(evs) == len(spans) > 0, name
        for sp, ev in zip(spans, evs):
            dur = (ev[2] - ev[1]) / 1e9
            assert abs(dur - sp.duration_s) < 1e-3, (name, dur, sp)
            mirrored[id(sp)] = ev
    # the host plane nests the events as the tracer nests the spans
    for sp in tr.spans:
        if sp.parent is not None:
            _, a0, a1 = mirrored[id(sp.parent)]
            _, b0, b1 = mirrored[id(sp)]
            assert a0 <= b0 and b1 <= a1, (sp.parent, sp)
