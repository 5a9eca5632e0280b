"""Sparsity screening: sort-based exactness + hash-based one-sided error."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core import baseline_tspm, encoding, mining, sparsity
from tests.conftest import random_dbmart


def _oracle_support(db):
    """distinct-patient support per (start, end) string pair."""
    from collections import defaultdict

    pats = defaultdict(set)
    for p in range(db.n_patients):
        n = int(db.nevents[p])
        for i in range(n):
            for j in range(i + 1, n):
                pats[(int(db.phenx[p, i]), int(db.phenx[p, j]))].add(p)
    return {k: len(v) for k, v in pats.items()}


@given(st.integers(0, 10_000), st.integers(1, 6))
def test_screen_sorted_exact(s, threshold):
    db = random_dbmart(np.random.default_rng(s))
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    seq, dur, pat, msk = mining.flatten(mined)
    scr = sparsity.screen_sorted(seq, dur, pat, msk, threshold)
    support = _oracle_support(db)
    expect = sum(1 for p in range(db.n_patients)
                 for i in range(int(db.nevents[p]))
                 for j in range(i + 1, int(db.nevents[p]))
                 if support[(int(db.phenx[p, i]), int(db.phenx[p, j]))] >= threshold)
    assert int(scr.n_kept) == expect
    # kept prefix is sorted and sentinel-free
    kept = np.asarray(scr.seq)[: int(scr.n_kept)]
    assert (kept != encoding.SENTINEL).all()
    assert (np.diff(kept) >= 0).all()


@given(st.integers(0, 10_000), st.integers(1, 5))
def test_screen_hash_one_sided(s, threshold):
    """hash screen NEVER drops a non-sparse sequence; with a large table it
    is exact on small universes."""
    db = random_dbmart(np.random.default_rng(s))
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    keep = np.asarray(sparsity.screen_hash(mined.seq, mined.mask, threshold,
                                           n_buckets_log2=22))
    support = _oracle_support(db)
    seqs = np.asarray(mined.seq)
    msk = np.asarray(mined.mask)
    s_arr, e_arr = (np.asarray(x) for x in encoding.unpack(seqs, "bit"))
    for p in range(seqs.shape[0]):
        for t in range(seqs.shape[1]):
            if not msk[p, t]:
                assert not keep[p, t]
                continue
            sup = support[(int(s_arr[p, t]), int(e_arr[p, t]))]
            if sup >= threshold:
                assert keep[p, t], "non-sparse sequence dropped (one-sided!)"


def test_screen_hash_matches_exact_on_cohort(small_cohort):
    db, _ = small_cohort
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    seq, dur, pat, msk = mining.flatten(mined)
    for threshold in (2, 4, 8):
        scr = sparsity.screen_sorted(seq, dur, pat, msk, threshold)
        keep = np.asarray(sparsity.screen_hash(mined.seq, mined.mask, threshold,
                                               n_buckets_log2=22))
        assert int(scr.n_kept) == int(keep.sum())
        rows = baseline_tspm.mine_and_screen(db, threshold)
        assert len(rows) == int(scr.n_kept)


def test_support_counts_unique_table():
    db = random_dbmart(np.random.default_rng(42))
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    seq, dur, pat, msk = mining.flatten(mined)
    _, _, _, u_key, u_sup, n_unique = sparsity.support_counts(seq, pat, msk)
    support = _oracle_support(db)
    assert int(n_unique) == len(support)
    u_key, u_sup = np.asarray(u_key), np.asarray(u_sup)
    got = {}
    for k in range(int(n_unique)):
        s, e = encoding.unpack(np.int64(u_key[k]), "bit")
        got[(int(s), int(e))] = int(u_sup[k])
    assert got == support


def test_hash_bucket_deterministic_and_in_range():
    ids = np.random.default_rng(0).integers(0, 2**48, 1000).astype(np.int64)
    h1 = np.asarray(sparsity.hash_bucket(ids, 16))
    h2 = np.asarray(sparsity.hash_bucket(ids, 16))
    assert (h1 == h2).all() and (h1 >= 0).all() and (h1 < 2**16).all()


# --- survivor compaction: device path == host boolean indexing -------------

H = 10      # a small table, so buckets collide and the screen drops pairs


def _host_survivors(seq, dur, patient, counts, threshold, mask):
    """The plain reference: flatten, screen, boolean-index on the host."""
    seq, mask = np.asarray(seq), np.asarray(mask)
    keep = np.asarray(sparsity.screen_hash_from_counts(
        seq, mask, counts, threshold, H)).reshape(-1)
    P = seq.shape[0]
    patient = np.broadcast_to(np.asarray(patient, np.int32).reshape(
        (P,) + (1,) * (seq.ndim - 1)), seq.shape)
    return (seq.reshape(-1)[keep], np.asarray(dur, np.int32).reshape(-1)[keep],
            patient.reshape(-1)[keep])


def _mined_case(backend, threshold, patient_shape):
    db = random_dbmart(np.random.default_rng(21), n_patients=9,
                       max_events=15, n_codes=12)
    mined = mining.mine(db.phenx, db.date, db.nevents, backend=backend)
    counts = np.asarray(sparsity.local_bucket_counts(
        mined.seq, mined.mask, H))
    P = mined.seq.shape[0]
    pat = np.arange(5, 5 + P, dtype=np.int32)
    if patient_shape == "column":
        pat = pat.reshape((P,) + (1,) * (mined.seq.ndim - 1))
    return (mined.seq, mined.dur, pat, counts,
            int(counts.max()) + 1 if threshold == "above" else threshold,
            mined.mask)


def _boundary_case():
    """Exactly ``survivor_capacity``'s first step of survivors: every
    buffer slot is filled."""
    n = sparsity.CAPACITY_GRANULE
    assert sparsity.survivor_capacity(n) == n
    rng = np.random.default_rng(4)
    P, E = 6, 32
    mask = np.zeros(P * E * E, bool)
    mask[rng.choice(P * E * E, n, replace=False)] = True
    mask = mask.reshape(P, E, E)
    seq = np.where(mask, rng.integers(0, 1 << 40, (P, E, E)), encoding.SENTINEL)
    dur = np.where(mask, rng.integers(-5, 900, (P, E, E)), 0).astype(np.int32)
    pat = np.arange(P, dtype=np.int32).reshape(P, 1, 1)
    counts = np.asarray(sparsity.local_bucket_counts(seq, mask, H))
    return seq, dur, pat, counts, 0, mask


CASES = {
    "no-survivors": lambda: _mined_case("jnp", "above", "vector"),
    "screen-skipped": lambda: _mined_case("jnp", 0, "vector"),
    "packed-triangle": lambda: _mined_case("jnp", 2, "column"),
    "dense-kernel": lambda: _mined_case("kernel", 2, "vector"),
    "broadcast-patient": lambda: _mined_case("kernel", 2, "column"),
    "capacity-boundary": _boundary_case,
}


@pytest.mark.parametrize("case", list(CASES))
def test_device_survivors_match_host_indexing(case):
    import jax
    seq, dur, pat, counts, threshold, mask = CASES[case]()
    seq, dur, mask = (jax.numpy.asarray(a) for a in (seq, dur, mask))
    phases = []
    got = sparsity.screen_survivors(
        seq, dur, pat, counts, threshold, H, mask=mask,
        phase=lambda name, **args: phases.append((name, args)))
    want = _host_survivors(seq, dur, pat, counts, threshold, mask)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes(), case
    n = len(want[0])
    cap = sparsity.survivor_capacity(n)
    assert phases == [("wait", {}),
                      ("compact", {"survivors": n, "capacity": cap}),
                      ("fetch", {"bytes": cap * sparsity.SURVIVOR_BYTES})]
    assert (n == 0) == (case == "no-survivors")
    if case == "screen-skipped":
        assert n == int(np.asarray(mask).sum())
    # host inputs take the host path, to the same bytes
    host = sparsity.screen_survivors(
        np.asarray(seq), np.asarray(dur), pat, counts, threshold, H,
        mask=np.asarray(mask), phase=lambda *a, **k: phases.append(a))
    assert len(phases) == 3
    for g, w in zip(host, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes(), case


def test_chunked_fused_fit_matches_host_indexing():
    """More than one chunk through ``chunking.mine_fused`` under a small
    budget: the concatenated device-compacted chunks equal host boolean
    indexing of the whole cohort's mined corpus, order included."""
    from repro.core import chunking
    db = random_dbmart(np.random.default_rng(8), n_patients=14,
                       max_events=14, n_codes=10)
    budget = 12_000
    assert len(chunking.plan_chunks(np.asarray(db.nevents), budget)) > 1
    out = chunking.mine_fused(db, threshold=2, budget_bytes=budget,
                              n_buckets_log2=H)
    mined = mining.mine_triangular(db.phenx, db.date, db.nevents)
    pat = np.arange(db.n_patients, dtype=np.int32)[:, None]
    want = _host_survivors(mined.seq, mined.dur, pat, out["counts"], 2,
                           mined.mask)
    assert len(want[0]) > 0
    for k, w in zip(("seq", "dur", "patient"), want):
        assert out[k].dtype == w.dtype and out[k].tobytes() == w.tobytes(), k


@pytest.mark.parametrize("n", [1, 2, 1023, 1024, 1025, 5000, 65_536,
                               1_000_003, 41_818_954])
def test_survivor_capacity_ladder(n):
    cap = sparsity.survivor_capacity(n)
    g = sparsity.CAPACITY_GRANULE
    assert cap >= n and cap % g == 0
    assert cap <= 2 ** 0.25 * n + g
    assert sparsity.survivor_capacity(cap) == cap     # a step is its own
    assert sparsity.survivor_capacity(n - 1) <= cap   # monotone
    assert sparsity.survivor_capacity(0) == 0


def test_flat_device_survivors_index_on_host():
    """A flat device array has no rows to compact by: it is indexed on the
    host, announcing no phase, to the same bytes."""
    import jax
    seq, dur, pat, counts, threshold, mask = _mined_case("jnp", 2, "column")
    pat = np.broadcast_to(pat, seq.shape)
    seq, dur, pat, mask = (np.asarray(a).reshape(-1)
                           for a in (seq, dur, pat, mask))
    phases = []
    got = sparsity.screen_survivors(
        jax.numpy.asarray(seq), jax.numpy.asarray(dur), pat, counts,
        threshold, H, mask=jax.numpy.asarray(mask),
        phase=lambda *a, **k: phases.append(a))
    want = _host_survivors(seq, dur, pat, counts, threshold, mask)
    assert phases == [] and len(want[0]) > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


@pytest.mark.parametrize("n_buckets_log2", [1, 10, 14, 20, 24])
def test_host_hash_bucket_is_hash_bucket_bit_for_bit(n_buckets_log2):
    """The host screen's numpy hash equals the device ``hash_bucket`` on
    ids of every sign, the sentinel included."""
    rng = np.random.default_rng(n_buckets_log2)
    ids = np.concatenate([
        rng.integers(-2**63, 2**63 - 1, 4000, dtype=np.int64),
        rng.integers(0, 1 << 48, 4000).astype(np.int64),
        np.asarray([0, 1, -1, encoding.SENTINEL], np.int64)])
    got = sparsity._hash_bucket_host(ids, n_buckets_log2)
    want = np.asarray(sparsity.hash_bucket(ids, n_buckets_log2))
    np.testing.assert_array_equal(got, want)
