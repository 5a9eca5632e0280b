"""Streaming mining == batch mining: the subsystem's headline invariant.

Replays random dbmarts as per-patient deltas (random chunk sizes, patients
interleaved) through stream.StreamService and checks the final screened
corpus, support counts, and query masks against core.mining + core.sparsity
on the same dbmart.  Seeded-loop property tests so they run in offline
environments without hypothesis.
"""
import numpy as np
import pytest

from repro.core import mining, queries, sparsity
from repro.stream.service import StreamService
from tests.conftest import random_dbmart

H = 10  # small table so collisions actually happen in the one-sided test


def replay(db, svc, rng):
    """Submit each patient's history as random chronological chunks, with
    patients interleaved round-robin (arbitrary arrival order)."""
    cursors = np.zeros(db.n_patients, np.int64)
    alive = [p for p in range(db.n_patients) if db.nevents[p] > 0]
    while alive:
        p = alive[int(rng.integers(len(alive)))]
        lo = int(cursors[p])
        hi = min(lo + int(rng.integers(1, 4)), int(db.nevents[p]))
        svc.submit(p, db.date[p, lo:hi], db.phenx[p, lo:hi])
        cursors[p] = hi
        if hi == int(db.nevents[p]):
            alive.remove(p)
        if rng.random() < 0.3:
            svc.run()
    svc.run()


def batch_reference(db, n_buckets_log2=H):
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    seq, dur, pat, msk = (np.asarray(x) for x in mining.flatten(mined))
    cnt = np.asarray(sparsity.local_bucket_counts(
        np.asarray(mined.seq), np.asarray(mined.mask), n_buckets_log2))
    return seq, dur, pat, msk, cnt


def stream_triples(svc):
    """Corpus as (original patient key, seq, dur) triples."""
    snap = svc.snapshot()
    pid_to_key = {pid: k for k, pid in svc.store.pids.items()}
    keys = np.asarray([pid_to_key[int(p)] for p in snap.patient]
                      if len(snap.patient) else [], np.int64)
    return snap, keys


@pytest.mark.parametrize("case", range(6))
def test_streaming_equals_batch(case):
    rng = np.random.default_rng(1000 + case)
    db = random_dbmart(rng)
    svc = StreamService(tick_patients=int(rng.integers(1, 5)),
                        n_buckets_log2=H)
    replay(db, svc, rng)
    seq, dur, pat, msk, cnt = batch_reference(db)
    snap, keys = stream_triples(svc)

    # 1. corpus multiset
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    # 2. support sketch counts are *exactly* the batch bucket counts
    assert (snap.counts == cnt).all()
    # 3. screened corpus
    thr = int(rng.integers(1, 4))
    bkeep = np.asarray(sparsity.screen_hash_from_counts(seq, msk, cnt, thr, H))
    skeep = svc.screened_keep(thr)
    assert sorted(zip(keys[skeep], snap.seq[skeep], snap.dur[skeep])) \
        == sorted(zip(pat[bkeep], seq[bkeep], dur[bkeep]))
    # 4. query masks over the live corpus
    x = int(rng.integers(0, 30))
    for smask, bmask in [
        (svc.query_starts_with(x),
         np.asarray(queries.starts_with(seq, x)) & msk),
        (svc.query_ends_with(x, threshold=thr),
         np.asarray(queries.ends_with(seq, x)) & bkeep),
        (svc.query_min_duration(30),
         np.asarray(queries.min_duration(dur, 30)) & msk),
    ]:
        assert sorted(zip(keys[smask], snap.seq[smask], snap.dur[smask])) \
            == sorted(zip(pat[bmask], seq[bmask], dur[bmask]))


@pytest.mark.parametrize("case", range(3))
def test_streaming_fused_duration_equals_batch(case):
    """fuse_duration=True: streaming and batch agree on the fused codec
    (duration bucket packed into the id's low bits), for corpus, support
    counts, screen, and the duration query (dur stays carried separately)."""
    rng = np.random.default_rng(2000 + case)
    db = random_dbmart(rng)
    svc = StreamService(tick_patients=int(rng.integers(1, 5)),
                        n_buckets_log2=H, fuse_duration=True)
    replay(db, svc, rng)

    mined = mining.mine_triangular(db.phenx, db.date, db.nevents,
                                   fuse_duration=True)
    seq, dur, pat, msk = (np.asarray(x) for x in mining.flatten(mined))
    cnt = np.asarray(sparsity.local_bucket_counts(
        np.asarray(mined.seq), np.asarray(mined.mask), H))
    snap, keys = stream_triples(svc)

    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()
    thr = int(rng.integers(1, 4))
    bkeep = np.asarray(sparsity.screen_hash_from_counts(seq, msk, cnt, thr, H))
    skeep = svc.screened_keep(thr)
    assert sorted(zip(keys[skeep], snap.seq[skeep], snap.dur[skeep])) \
        == sorted(zip(pat[bkeep], seq[bkeep], dur[bkeep]))
    smask = svc.query_min_duration(30)
    bmask = np.asarray(queries.min_duration(dur, 30)) & msk
    assert sorted(zip(keys[smask], snap.seq[smask])) \
        == sorted(zip(pat[bmask], seq[bmask]))


def test_streaming_fused_duration_kernel_backend():
    """The Pallas delta kernel path agrees on the fused codec too."""
    rng = np.random.default_rng(11)
    db = random_dbmart(rng, n_patients=5, max_events=10)
    svc = StreamService(tick_patients=2, n_buckets_log2=H, fuse_duration=True,
                        backend="kernel", interpret=True)
    replay(db, svc, rng)
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents,
                                   fuse_duration=True)
    seq, dur, pat, msk = (np.asarray(x) for x in mining.flatten(mined))
    cnt = np.asarray(sparsity.local_bucket_counts(
        np.asarray(mined.seq), np.asarray(mined.mask), H))
    snap, keys = stream_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()


def test_streaming_equals_batch_under_eviction():
    """A tiny byte budget forces spill/restore churn; results are exact."""
    rng = np.random.default_rng(42)
    db = random_dbmart(rng, n_patients=10, max_events=16)
    svc = StreamService(tick_patients=3, n_buckets_log2=H,
                        budget_bytes=40_000)
    replay(db, svc, rng)
    assert svc.store.spilled_count or len(svc.store.rows) < 10  # budget did bite
    seq, dur, pat, msk, cnt = batch_reference(db)
    snap, keys = stream_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()


def test_streaming_kernel_backend_equals_batch():
    rng = np.random.default_rng(7)
    db = random_dbmart(rng, n_patients=6, max_events=12)
    svc = StreamService(tick_patients=2, n_buckets_log2=H,
                        backend="kernel", interpret=True)
    replay(db, svc, rng)
    seq, dur, pat, msk, cnt = batch_reference(db)
    snap, keys = stream_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()


def test_sketch_merges_with_batch_screen_counts():
    """Half the cohort batch-mined, half streamed: merged tables equal the
    all-batch table (cold + hot cohorts screen together)."""
    rng = np.random.default_rng(3)
    db = random_dbmart(rng, n_patients=8, max_events=14)
    half = db.n_patients // 2
    cold = db.slice_patients(0, half)
    mined = mining.mine_triangular(cold.phenx, cold.date, cold.nevents)
    cold_cnt = np.asarray(sparsity.local_bucket_counts(
        np.asarray(mined.seq), np.asarray(mined.mask), H))

    svc = StreamService(tick_patients=2, n_buckets_log2=H)
    hot = db.slice_patients(half, db.n_patients)
    replay(hot, svc, rng)
    merged = svc.merged_counts(cold_cnt)

    _, _, _, _, full_cnt = batch_reference(db)
    assert (merged == full_cnt).all()


@pytest.mark.parametrize("seed", range(4))
def test_sketch_fold_merges_like_a_sort(seed):
    """The fold's merge returns exactly sort(stored + novel ids),
    sentinel-padded, over empty, full and overlapping sets and duplicate
    slab ids (its sorts are unstable: plain keys, no payload)."""
    import jax.numpy as jnp

    from repro.core.encoding import SENTINEL
    from repro.stream import counts as counts_lib

    rng = np.random.default_rng(seed)
    B, C, T = 4, 16, 24
    stored = np.full((B, C), SENTINEL, np.int64)
    for b, k in enumerate((0, C, 5, 11)):
        stored[b, :k] = np.sort(rng.choice(40, size=k, replace=False))
    seq = rng.integers(0, 40, size=(B, T)).astype(np.int64)
    mask = rng.random((B, T)) < 0.8
    table = jnp.zeros(1 << H, jnp.int32)
    counts, merged, n_novel = counts_lib.sketch_update(
        table, jnp.asarray(stored), jnp.asarray(seq), jnp.asarray(mask), H)

    want = np.full((B, C + T), SENTINEL, np.int64)
    want_table = np.zeros(1 << H, np.int32)
    for b in range(B):
        old = set(stored[b][stored[b] != SENTINEL].tolist())
        novel = sorted(set(seq[b][mask[b]].tolist()) - old)
        want[b, :len(old) + len(novel)] = sorted(old | set(novel))
        assert int(n_novel[b]) == len(novel)
        np.add.at(want_table, np.asarray(sparsity.hash_bucket(
            np.asarray(novel, np.int64), H)), 1)
    np.testing.assert_array_equal(np.asarray(merged), want)
    np.testing.assert_array_equal(np.asarray(counts), want_table)


def test_sketch_error_is_one_sided():
    """Collisions may false-keep, but a non-sparse sequence NEVER drops."""
    rng = np.random.default_rng(5)
    db = random_dbmart(rng, n_patients=12, max_events=10, n_codes=4)
    svc = StreamService(tick_patients=4, n_buckets_log2=4)  # heavy collisions
    replay(db, svc, rng)
    snap, keys = stream_triples(svc)
    thr = 3
    keep = svc.screened_keep(thr)
    support = {}
    for k, s in set(zip(keys, snap.seq)):
        support[s] = support.get(s, 0) + 1
    for i, s in enumerate(snap.seq):
        if support[s] >= thr:
            assert keep[i]


def test_service_coalesces_second_delta_into_patient_slot():
    """Slot-level admission: a repeat delta joins its patient's slot in the
    same tick (chronological concat) instead of deferring a wave."""
    svc = StreamService(tick_patients=4)
    svc.submit(0, [1, 2], [3, 4])
    svc.submit(0, [5], [6])
    svc.submit(1, [1], [2])
    st = svc.tick()
    assert st.n_patients == 2 and len(svc.queue) == 0
    ph, dt = svc.store.history(0)
    assert ph.tolist() == [3, 4, 6] and dt.tolist() == [1, 2, 5]


def test_flooding_patient_drains_in_one_tick_and_stays_exact():
    """Regression for wave deferral: one patient flooding the queue used to
    admit one delta per tick (O(queue) ticks + O(queue^2) re-scans); slot
    admission drains the flood in a single tick, other patients still get
    their slots, and the mined corpus equals batch."""
    rng = np.random.default_rng(21)
    db = random_dbmart(rng, n_patients=3, max_events=24)
    svc = StreamService(tick_patients=2, n_buckets_log2=H)
    # patient 0 floods event-by-event; 1 and 2 queue behind it
    for i in range(int(db.nevents[0])):
        svc.submit(0, db.date[0, i : i + 1], db.phenx[0, i : i + 1])
    for p in (1, 2):
        n = int(db.nevents[p])
        svc.submit(p, db.date[p, :n], db.phenx[p, :n])
    st = svc.tick()
    assert st.n_patients == 2                  # flood slot + patient 1
    assert st.n_events == int(db.nevents[0]) + int(db.nevents[1])
    assert len(svc.queue) == 1                 # only patient 2 deferred
    svc.run()
    seq, dur, pat, msk, cnt = batch_reference(db)
    snap, keys = stream_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()


def test_slot_coalescing_caps_wave_width():
    """max_slot_events bounds a slot (the wave's slab pads to its widest
    slot, so one flood must not inflate every other patient's row); the
    overflow defers in per-patient order and the result stays exact."""
    rng = np.random.default_rng(6)
    db = random_dbmart(rng, n_patients=2, max_events=24)
    n0 = int(db.nevents[0])
    assert n0 > 8
    svc = StreamService(tick_patients=4, n_buckets_log2=H,
                        max_slot_events=8)
    for i in range(n0):    # flood patient 0 event-by-event
        svc.submit(0, db.date[0, i : i + 1], db.phenx[0, i : i + 1])
    st = svc.tick()
    assert st.n_events == 8            # slot closed at the cap
    assert len(svc.queue) == n0 - 8    # overflow deferred, order kept
    svc.run()
    seq, dur, pat, msk, cnt = batch_reference(db.slice_patients(0, 1))
    snap, keys = stream_triples(svc)
    assert sorted(zip(keys, snap.seq, snap.dur)) \
        == sorted(zip(pat[msk], seq[msk], dur[msk]))
    assert (snap.counts == cnt).all()


def test_store_regrowth_keeps_history():
    from repro.stream.store import PatientStore

    st = PatientStore(init_patients=2, init_events=8)
    rng = np.random.default_rng(0)
    want = {k: ([], []) for k in range(7)}
    for step in range(30):
        k = int(rng.integers(7))
        d = int(rng.integers(1, 6))
        ph = rng.integers(0, 50, d).astype(np.int32)
        dt = np.full(d, step, np.int32)
        rows, _ = st.admit([k])
        st.append(rows, ph[None], dt[None], np.asarray([d], np.int32))
        want[k][0].extend(ph.tolist())
        want[k][1].extend(dt.tolist())
    for k, (ph, dt) in want.items():
        if not ph:
            continue
        gp, gd = st.history(k)
        assert gp.tolist() == ph and gd.tolist() == dt


# --- a date-ordered encounter feed, checked against batch mining -----------

def _feed(db, per_patient):
    """Each patient's history cut into ``per_patient`` chronological
    deltas, ordered by each delta's first date (ties by patient, index)."""
    out = []
    for p in range(db.n_patients):
        n = int(db.nevents[p])
        cuts = np.linspace(0, n, per_patient + 1).round().astype(int)
        for i in range(per_patient):
            if cuts[i + 1] > cuts[i]:
                out.append((int(db.date[p, cuts[i]]), p, i, cuts[i],
                            cuts[i + 1]))
    return sorted(out)


def _visible_reference(db, visible, threshold):
    """Fused-screen survivors as sorted (patient, seq, dur) triples and the
    bucket table, batch-mined over each patient's first ``visible[p]``
    events."""
    mined = mining.mine_triangular(db.phenx, db.date,
                                   np.asarray(visible, np.int32))
    seq, dur, pat, msk = (np.asarray(x) for x in mining.flatten(mined))
    cnt = np.asarray(sparsity.local_bucket_counts(
        np.asarray(mined.seq), np.asarray(mined.mask), H))
    seq, dur, pat = seq[msk], dur[msk], pat[msk]
    keep = cnt[np.asarray(sparsity.hash_bucket(seq, H))] >= threshold
    return sorted(zip(pat[keep].tolist(), seq[keep].tolist(),
                      dur[keep].tolist())), cnt


def test_date_ordered_feed_equals_batch_while_it_runs():
    """Forty patients, sixteen date-ordered deltas each, fed four slots a
    tick through the session: at several points of the replay the
    published frame and the sketch table equal batch mining of exactly the
    visible events, while the set planes grow through several widths of
    their family."""
    from repro.api import MiningSession
    from repro.stream.counts import set_width

    rng = np.random.default_rng(15)
    db = random_dbmart(rng, n_patients=40, max_events=64, n_codes=48)
    thr = 3
    s = MiningSession(screen="fused", threshold=thr, n_buckets_log2=H,
                      tick_patients=4)
    feed = _feed(db, 16)
    submitted = np.zeros(db.n_patients, np.int64)
    widths, checked = set(), 0
    for i, (_, p, _, a, b) in enumerate(feed):
        s.submit(p, db.date[p, a:b], db.phenx[p, a:b])
        submitted[p] = b
        if len(s.service.queue) >= 8:
            s.service.tick()
            widths.add(s.service.sketch.set_columns)
        if i % 150 == 149 or i == len(feed) - 1:
            frame = s.frame()
            queued = np.zeros(db.n_patients, np.int64)
            for d in s.service.queue:
                queued[d.key] += len(d.dates)
            want, cnt = _visible_reference(db, submitted - queued, thr)
            seq, dur, pat, _ = frame.arrays()
            assert sorted(zip(pat.tolist(), seq.tolist(),
                              dur.tolist())) == want
            np.testing.assert_array_equal(
                np.asarray(s.service.sketch.counts), cnt)
            checked += 1
    s.run()
    want, cnt = _visible_reference(db, db.nevents, thr)
    seq, dur, pat, _ = s.frame().arrays()
    assert sorted(zip(pat.tolist(), seq.tolist(), dur.tolist())) == want
    assert checked >= 4
    assert len(widths) >= 3, widths          # two growth steps at least
    assert all(w == set_width(w, 64) for w in widths)


def test_set_planes_grow_on_one_family_and_landing_keeps_other_rows():
    """Every width the growth policy reaches is pad_multiple * 2**k, and
    landing a tick's rows leaves every other patient's set as it was."""
    import jax.numpy as jnp

    from repro.core.encoding import SENTINEL
    from repro.stream.counts import OnlineSupportSketch, set_width

    rng = np.random.default_rng(3)
    sk = OnlineSupportSketch(n_buckets_log2=H, pad_multiple=8)
    model = [set() for _ in range(12)]
    seen = set()
    for step in range(40):
        pids = rng.choice(12, size=int(rng.integers(1, 5)), replace=False)
        T = int(rng.integers(1, 9)) * 8
        seq = rng.integers(0, 40 + 40 * step, (len(pids), T)).astype(np.int64)
        mask = rng.random((len(pids), T)) < 0.7
        if step == 0:     # a first delta past twice the width: one jump
            T = 100
            seq = np.tile(np.arange(T, dtype=np.int64), (len(pids), 1))
            mask = np.ones((len(pids), T), bool)
        sk.update(pids, jnp.asarray(seq), jnp.asarray(mask))
        for b, p in enumerate(pids):
            model[p] |= set(seq[b][mask[b]].tolist())
        C = sk.set_columns
        seen.add(C)
        assert C == set_width(C, 8) and C >= max(len(m) for m in model)
        rows = np.asarray(sk.rows(np.arange(sk.n_patients)))
        for p, ids in enumerate(model):
            want = np.full(C, SENTINEL, np.int64)
            want[:len(ids)] = sorted(ids)
            np.testing.assert_array_equal(rows[p], want)
            assert sk.n_distinct[p] == len(ids)
    assert len(seen) >= 3, seen
    assert [set_width(n, 64) for n in (0, 1, 64, 65, 29952)] == \
        [64, 64, 64, 128, 32768]
