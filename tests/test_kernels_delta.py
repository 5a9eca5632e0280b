"""Delta Pallas kernel vs jnp oracle: shape sweeps + slab-union property."""
import numpy as np
import pytest

from repro.core import mining
from repro.kernels.tspm_delta import delta as delta_kernel
from repro.kernels.tspm_delta import ops, ref
from repro.stream import delta as stream_delta
from tests.conftest import random_dbmart


def split_delta(db, frac=0.5):
    """(n_old, n_new, new_phenx, new_date) splitting each history at frac."""
    n_old = (db.nevents * frac).astype(np.int32)
    n_new = (db.nevents - n_old).astype(np.int32)
    D = max(int(n_new.max(initial=1)), 1)
    new_ph = np.zeros((db.n_patients, D), np.int32)
    new_dt = np.zeros((db.n_patients, D), np.int32)
    for p in range(db.n_patients):
        o, n = int(n_old[p]), int(db.nevents[p])
        new_ph[p, : n - o] = db.phenx[p, o:n]
        new_dt[p, : n - o] = db.date[p, o:n]
    return n_old, n_new, new_ph, new_dt


@pytest.mark.parametrize("P,E", [(1, 8), (3, 16), (8, 48), (7, 130)])
def test_delta_kernel_matches_jnp(P, E):
    db = random_dbmart(np.random.default_rng(P * 100 + E),
                       n_patients=P, max_events=E)
    n_old, n_new, new_ph, new_dt = split_delta(db)
    got = ops.delta_pairgen(db.phenx, db.date, n_old, n_new, new_ph, new_dt,
                            interpret=True)
    want = stream_delta.delta_mine_jnp(db.phenx, db.date, n_old, n_new,
                                       new_ph, new_dt)
    m = np.asarray(want.mask)
    assert (np.asarray(got.mask) == m).all()
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()
    assert (np.asarray(got.dur)[m] == np.asarray(want.dur)[m]).all()


def test_delta_planes_kernel_matches_planes_ref():
    db = random_dbmart(np.random.default_rng(2), n_patients=8, max_events=32)
    n_old, n_new, new_ph, new_dt = split_delta(db)
    ph = np.zeros((8, 128), np.int32)
    dt = np.zeros((8, 128), np.int32)
    ph[:, :32] = db.phenx[:, :32]
    dt[:, :32] = db.date[:, :32]
    nph = np.zeros((8, 128), np.int32)
    ndt = np.zeros((8, 128), np.int32)
    nph[:, : new_ph.shape[1]] = new_ph
    ndt[:, : new_dt.shape[1]] = new_dt
    outs = delta_kernel.delta_planes(ph, dt, n_old, n_new, nph, ndt,
                                     pb=8, ti=128, tj=128, interpret=True)
    refs = ref.delta_planes_ref(ph, dt, n_old, n_new, nph, ndt)
    for got, want in zip(outs, refs):
        assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("codec,fuse", [("bit", False), ("paper", True)])
def test_delta_codecs_and_fusion(codec, fuse):
    db = random_dbmart(np.random.default_rng(5), n_patients=6, max_events=20)
    n_old, n_new, new_ph, new_dt = split_delta(db)
    got = ops.delta_pairgen(db.phenx, db.date, n_old, n_new, new_ph, new_dt,
                            codec=codec, fuse_duration=fuse, interpret=True)
    want = stream_delta.delta_mine_jnp(db.phenx, db.date, n_old, n_new,
                                       new_ph, new_dt, codec=codec,
                                       fuse_duration=fuse)
    m = np.asarray(want.mask)
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()


def test_old_pairs_plus_delta_slab_is_full_mine():
    """The streaming invariant at one split point: mine(n_old) + delta slab
    == mine(n) as multisets of (patient, seq, dur)."""
    for s in range(4):
        db = random_dbmart(np.random.default_rng(s), n_patients=5)
        n_old, n_new, new_ph, new_dt = split_delta(db, frac=0.4)
        slab = stream_delta.delta_mine_jnp(db.phenx, db.date, n_old, n_new,
                                           new_ph, new_dt)
        old = mining.mine_triangular(db.phenx, db.date, n_old)
        os_, od, op, om = (np.asarray(x) for x in mining.flatten(old))
        sm = np.asarray(slab.mask)
        got = sorted(
            list(zip(op[om], os_[om], od[om]))
            + [(p, s_, d_) for p in range(db.n_patients)
               for s_, d_ in zip(np.asarray(slab.seq)[p][sm[p]],
                                 np.asarray(slab.dur)[p][sm[p]])])
        full = mining.mine_triangular(db.phenx, db.date, db.nevents)
        fs, fd, fp, fm = (np.asarray(x) for x in mining.flatten(full))
        assert got == sorted(zip(fp[fm], fs[fm], fd[fm]))


def _assert_kernel_matches_jnp(phenx, date, n_old, n_new, new_ph, new_dt):
    got = ops.delta_pairgen(phenx, date, n_old, n_new, new_ph, new_dt,
                            interpret=True)
    want = stream_delta.delta_mine_jnp(phenx, date, n_old, n_new,
                                       new_ph, new_dt)
    assert got.mask.shape == want.mask.shape
    m = np.asarray(want.mask)
    assert (np.asarray(got.mask) == m).all()
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()
    assert (np.asarray(got.dur)[m] == np.asarray(want.dur)[m]).all()
    return m


def test_delta_kernel_empty_delta_window():
    """d == 0 for every patient: the j-grid is all padding, no pair is
    valid, and the D == 0 slab shape round-trips."""
    db = random_dbmart(np.random.default_rng(0), n_patients=4, max_events=16)
    zeros = np.zeros(db.n_patients, np.int32)
    # D > 0 planes but no new events anywhere
    m = _assert_kernel_matches_jnp(
        db.phenx, db.date, np.asarray(db.nevents, np.int32), zeros,
        np.zeros((db.n_patients, 4), np.int32),
        np.zeros((db.n_patients, 4), np.int32))
    assert not m.any()
    # literally zero-width delta planes (D == 0)
    m = _assert_kernel_matches_jnp(
        db.phenx, db.date, np.asarray(db.nevents, np.int32), zeros,
        np.zeros((db.n_patients, 0), np.int32),
        np.zeros((db.n_patients, 0), np.int32))
    assert m.size == 0


def test_delta_kernel_mixed_empty_rows():
    """Some patients contribute no delta this wave (d == 0 rows inside a
    nonempty batch) — their slab rows must be fully masked."""
    db = random_dbmart(np.random.default_rng(1), n_patients=6, max_events=12)
    n_old, n_new, new_ph, new_dt = split_delta(db)
    n_new[::2] = 0
    m = _assert_kernel_matches_jnp(db.phenx, db.date, n_old, n_new,
                                   new_ph, new_dt)
    assert not m[::2].any()


def test_delta_kernel_single_event_history():
    """n_old == 1 everywhere: the smallest non-degenerate i-extent, plus
    the first-ever delta case n_old == 0 for one patient."""
    rng = np.random.default_rng(2)
    P, E, D = 3, 8, 5
    phenx = rng.integers(0, 30, (P, E)).astype(np.int32)
    date = np.sort(rng.integers(0, 100, (P, E)).astype(np.int32), axis=1)
    n_old = np.asarray([1, 1, 0], np.int32)
    n_new = np.asarray([D, 1, 2], np.int32)
    new_ph = rng.integers(0, 30, (P, D)).astype(np.int32)
    new_dt = np.sort(rng.integers(100, 200, (P, D)).astype(np.int32), axis=1)
    m = _assert_kernel_matches_jnp(phenx, date, n_old, n_new, new_ph, new_dt)
    # patient 0: each new event pairs with the 1 old + earlier new events
    assert m[0].sum() == D + D * (D - 1) // 2
    # patient 2 (empty history): only new-x-new pairs
    assert m[2].sum() == 1


def test_delta_kernel_at_pad_and_tile_boundary():
    """E and D exactly at the 128 tile edge: no padding inserted, masks
    must still cut at n_old + j / n_new, not the tile."""
    rng = np.random.default_rng(3)
    P, E, D = 2, 128, 128
    phenx = rng.integers(0, 50, (P, E)).astype(np.int32)
    date = np.sort(rng.integers(0, 500, (P, E)).astype(np.int32), axis=1)
    n_old = np.asarray([E - D // 2, 96], np.int32)
    n_new = np.asarray([D // 2, D], np.int32)
    new_ph = rng.integers(0, 50, (P, D)).astype(np.int32)
    new_dt = np.sort(rng.integers(500, 900, (P, D)).astype(np.int32), axis=1)
    _assert_kernel_matches_jnp(phenx, date, n_old, n_new, new_ph, new_dt)


def test_delta_kernel_history_at_full_plane_capacity():
    """n_old + d == E: the updated history fills every plane slot (the
    store's regrowth edge just before a geometric doubling)."""
    rng = np.random.default_rng(4)
    P, E, D = 3, 16, 4
    phenx = rng.integers(0, 30, (P, E)).astype(np.int32)
    date = np.sort(rng.integers(0, 300, (P, E)).astype(np.int32), axis=1)
    n_new = np.asarray([D, D, D], np.int32)
    n_old = np.asarray([E - D] * P, np.int32)     # planes exactly full
    new_ph = phenx[:, E - D:]                      # delta lives at the tail
    new_dt = date[:, E - D:]
    m = _assert_kernel_matches_jnp(phenx, date, n_old, n_new, new_ph, new_dt)
    # every (i, j) with i < n_old + j is real: sum the closed form
    want = int(stream_delta.count_delta_pairs(n_old, n_new))
    assert m.sum() == want


def test_count_delta_pairs_closed_form():
    db = random_dbmart(np.random.default_rng(9), n_patients=7)
    n_old, n_new, new_ph, new_dt = split_delta(db, frac=0.3)
    slab = stream_delta.delta_mine_jnp(db.phenx, db.date, n_old, n_new,
                                       new_ph, new_dt)
    assert int(stream_delta.count_delta_pairs(n_old, n_new)) \
        == int(np.asarray(slab.mask).sum())
