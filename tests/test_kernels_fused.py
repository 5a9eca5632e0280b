"""Conformance suite for the fused mine+screen kernel (kernels/tspm_fused).

The contract: ``fused_bucket_counts`` is byte-identical to materializing
the corpus and screening it — ``sparsity.local_bucket_counts`` over
``mining.mine(...)`` — for every codec, fused/unfused duration ids, both
backends, and every edge the tiling can hit (tile-boundary E, duplicate
values/timestamps, empty cohorts, adversarial hash collisions).  Plus the
limb-hash unit contract (hash_parts == hash_bucket(pack) without ever
forming the int64 id) and the roofline tile-selection pins.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import random_dbmart
from repro.analysis import roofline
from repro.core import encoding, mining, sparsity
from repro.kernels.tspm_fused import fused, ops, ref

BACKENDS = ("kernel", "jnp")


def oracle_counts(db, codec="bit", fuse_duration=False, bucket_days=30,
                  n_buckets_log2=12):
    """The materializing path's table: mine the whole corpus, then count."""
    m = mining.mine_triangular(db.phenx, db.date, db.nevents, codec,
                               fuse_duration, bucket_days)
    return np.asarray(sparsity.local_bucket_counts(
        m.seq, m.mask, n_buckets_log2))


def fused_counts(db, backend, codec="bit", fuse_duration=False,
                 bucket_days=30, n_buckets_log2=12, **kw):
    return np.asarray(ops.fused_bucket_counts(
        db.phenx, db.date, db.nevents, codec=codec,
        fuse_duration=fuse_duration, bucket_days=bucket_days,
        n_buckets_log2=n_buckets_log2, backend=backend, **kw))


# --- limb hash unit contract -------------------------------------------------
@pytest.mark.parametrize("codec", ("bit", "paper"))
@pytest.mark.parametrize("H", (1, 8, 12, 14, 20, 24))
def test_hash_parts_equals_hash_bucket(codec, H):
    """The int32 13-bit-limb hash == hash_bucket(pack) for unfused ids and
    hash_bucket(fuse_duration(pack)) for fused ones, across the whole H
    range the kernel admits."""
    rng = np.random.default_rng(7 * H)
    s = rng.integers(0, encoding.max_vocab(codec), 512).astype(np.int32)
    e = rng.integers(0, encoding.max_vocab(codec), 512).astype(np.int32)
    b = rng.integers(0, 1 << encoding.DUR_BITS, 512).astype(np.int32)
    want = np.asarray(sparsity.hash_bucket(encoding.pack(s, e, codec), H))
    got = np.asarray(fused.hash_parts(s, e, codec=codec, n_buckets_log2=H))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    fid = encoding.fuse_duration(encoding.pack(s, e, codec), b)
    wantf = np.asarray(sparsity.hash_bucket(fid, H))
    gotf = np.asarray(fused.hash_parts(s, e, b, codec=codec,
                                       n_buckets_log2=H, fused_ids=True))
    np.testing.assert_array_equal(gotf, wantf)


def test_hash_parts_rejects_out_of_range_tables():
    with pytest.raises(AssertionError):
        fused.hash_parts(np.int32(1), np.int32(2), n_buckets_log2=25)
    with pytest.raises(AssertionError):
        fused.hash_parts(np.int32(1), np.int32(2), n_buckets_log2=0)


def test_hash_constants_linear_in_fields():
    """hash(pack(s, e)) == top bits of (s*C1 + e*C2) mod 2^64 — the
    linearity the kernel's corpus-free hashing rests on."""
    for codec in ("bit", "paper"):
        c_start, c_end, c_bucket = fused.hash_constants(codec)
        mult = ((1 << encoding.BIT_SHIFT) if codec == "bit"
                else encoding.PAPER_SHIFT)
        assert c_start == (sparsity.HASH_MULT * mult) % (1 << 64)
        assert c_end == sparsity.HASH_MULT
        assert c_bucket == sparsity.HASH_MULT
        cf_start, cf_end, _ = fused.hash_constants(codec, fused_ids=True)
        assert cf_start == (c_start << encoding.DUR_BITS) % (1 << 64)
        assert cf_end == (c_end << encoding.DUR_BITS) % (1 << 64)


# --- kernel vs materializing oracle -----------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("codec", ("bit", "paper"))
@pytest.mark.parametrize("P,E", [(1, 8), (3, 16), (8, 48), (16, 30), (7, 19)])
def test_conformance_random_cohorts(backend, codec, P, E):
    rng = np.random.default_rng(P * 100 + E)
    db = random_dbmart(rng, n_patients=P, max_events=E)
    want = oracle_counts(db, codec=codec)
    got = fused_counts(db, backend, codec=codec)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_fused_duration_ids(backend):
    """Fused-duration ids take the blocked jnp fallback on both backends
    (cross-row dedup does not decompose over tiles) and still match."""
    rng = np.random.default_rng(11)
    db = random_dbmart(rng, n_patients=9, max_events=24, date_range=900)
    want = oracle_counts(db, fuse_duration=True, bucket_days=30)
    got = fused_counts(db, backend, fuse_duration=True, bucket_days=30,
                       block_patients=4)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_block_partition_invariance(backend):
    """Counts are additive over patient blocks: any block size gives the
    same table."""
    rng = np.random.default_rng(23)
    db = random_dbmart(rng, n_patients=13, max_events=20)
    tables = [fused_counts(db, backend, block_patients=blk)
              for blk in (1, 3, 13, 64)]
    for t in tables[1:]:
        np.testing.assert_array_equal(t, tables[0])


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=20)
def test_conformance_hypothesis_sweep(seed):
    """Random cohorts x random codec/backend: fused table == oracle."""
    rng = np.random.default_rng(seed)
    db = random_dbmart(rng)
    codec = ("bit", "paper")[int(rng.integers(2))]
    backend = BACKENDS[int(rng.integers(2))]
    H = int(rng.integers(4, 13))
    want = oracle_counts(db, codec=codec, n_buckets_log2=H)
    got = fused_counts(db, backend, codec=codec, n_buckets_log2=H)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_support_equals_threshold_edge(backend):
    """The screen keep decision at support == threshold is identical
    whether counts come from the fused path or the materialized corpus —
    at the exact threshold and one past it."""
    rng = np.random.default_rng(5)
    db = random_dbmart(rng, n_patients=10, max_events=16, n_codes=4)
    H = 10
    want = oracle_counts(db, n_buckets_log2=H)
    got = fused_counts(db, backend, n_buckets_log2=H)
    np.testing.assert_array_equal(got, want)
    m = mining.mine_triangular(db.phenx, db.date, db.nevents)
    supports = want[want > 0]
    assert supports.size, "degenerate cohort: no support mass"
    thr = int(supports.max())          # some bucket sits exactly at thr
    for t in (thr, thr + 1):
        keep_oracle = np.asarray(sparsity.screen_hash_from_counts(
            m.seq, m.mask, want, t, H))
        keep_fused = np.asarray(sparsity.screen_hash_from_counts(
            m.seq, m.mask, got, t, H))
        np.testing.assert_array_equal(keep_fused, keep_oracle)
    assert keep_oracle.sum() == 0      # thr+1 kills the max bucket's ids


# --- edge cases --------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("P,E", [(0, 8), (4, 0)])
def test_zero_width_slab_guard(backend, P, E):
    """Mirrors tspm_delta/ops.py: an empty patient or event axis yields an
    all-zero table of the right shape instead of a degenerate grid."""
    db_phenx = np.zeros((P, E), np.int32)
    got = np.asarray(ops.fused_bucket_counts(
        db_phenx, np.zeros((P, E), np.int32), np.zeros(P, np.int32),
        n_buckets_log2=8, backend=backend))
    assert got.shape == (256,) and got.sum() == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_no_pairable_patients(backend):
    """P > 0 but every patient has 0 or 1 events: no pairs, empty table."""
    phenx = np.tile(np.arange(6, dtype=np.int32), (4, 1))
    date = np.zeros((4, 6), np.int32)
    nev = np.array([0, 1, 0, 1], np.int32)
    got = np.asarray(ops.fused_bucket_counts(
        phenx, date, nev, n_buckets_log2=8, backend=backend))
    assert got.sum() == 0


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("E", (127, 128, 129))
def test_tile_boundary_event_counts(backend, E):
    """E exactly on and one past the 128 tile boundary."""
    rng = np.random.default_rng(E)
    db = random_dbmart(rng, n_patients=2, max_events=E, n_codes=6)
    assert int(db.nevents.max()) > 0
    want = oracle_counts(db, n_buckets_log2=10)
    got = fused_counts(db, backend, n_buckets_log2=10)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_duplicate_timestamps_and_codes(backend):
    """Same-day events and repeated codes: dedup must keep exactly one
    contribution per distinct (patient, id), including the a == b runs."""
    phenx = np.array([[2, 2, 2, 5, 5, 2, 7, 7],
                      [1, 1, 1, 1, 1, 1, 1, 1]], np.int32)
    date = np.array([[3, 3, 3, 3, 9, 9, 9, 9],
                     [0, 0, 0, 0, 0, 0, 0, 0]], np.int32)
    nev = np.array([8, 8], np.int32)
    from repro.data.dbmart import DBMart
    db = DBMart(phenx, date, nev, None)
    want = oracle_counts(db, n_buckets_log2=10)
    got = fused_counts(db, backend, n_buckets_log2=10)
    np.testing.assert_array_equal(got, want)
    # patient 1 mines only (1 -> 1): exactly one distinct contribution
    h = int(np.asarray(sparsity.hash_bucket(encoding.pack(1, 1), 10)))
    assert got[h] >= 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_hash_adversary_single_bucket(backend):
    """H=1 + identical codes: every id collides into few buckets; counts
    must still match the oracle exactly (collisions merge identically)."""
    rng = np.random.default_rng(31)
    db = random_dbmart(rng, n_patients=6, max_events=12, n_codes=1)
    for H in (1, 2):
        want = oracle_counts(db, n_buckets_log2=H)
        got = fused_counts(db, backend, n_buckets_log2=H)
        np.testing.assert_array_equal(got, want)
        assert got.sum() == want.sum()


def test_kernel_dispatch_regime():
    """backend='kernel' falls back to the jnp block path past
    KERNEL_MAX_LOG2 and for fused ids — stays exact there, and says so on
    the kernel.dispatch counter."""
    from repro import obs
    rng = np.random.default_rng(41)
    db = random_dbmart(rng, n_patients=5, max_events=10)
    H = ops.KERNEL_MAX_LOG2 + 1
    reg = obs.MetricsRegistry()
    want = oracle_counts(db, n_buckets_log2=H)
    got = fused_counts(db, "kernel", n_buckets_log2=H, metrics=reg)
    np.testing.assert_array_equal(got, want)
    assert reg.snapshot() == {
        "kernel.dispatch{impl=jnp,interpret=False,op=fused}": 1}
    fused_counts(db, "kernel", n_buckets_log2=H - 1, metrics=reg,
                 interpret=True)
    assert reg.value("kernel.dispatch", op="fused", impl="kernel",
                     interpret=True) == 1


# --- roofline tile selection -------------------------------------------------
def test_tile_plan_analytic_defaults():
    plan = roofline.mining_tile_plan(96, 12)
    assert plan.source == "analytic"
    assert plan.ti == plan.tj == 128
    assert plan.block_patients % plan.pb == 0
    assert plan.vmem_bytes <= roofline.VMEM_BYTES // 2
    # the modeled working set holds the whole [2^H] table
    small = roofline.mining_tile_plan(96, 8)
    assert small.pb == plan.pb
    assert plan.vmem_bytes == roofline.fused_kernel_vmem(plan.pb, 128, 128,
                                                         12, 96)
    assert small.vmem_bytes < plan.vmem_bytes


def test_tile_plan_pins_measured_rows():
    """Known autotune rows: the fastest VMEM-fitting row wins; a faster
    row that blows VMEM is rejected."""
    rows = [{"pb": 4, "wall_s": 5e-3},
            {"pb": 8, "wall_s": 3e-3},
            {"pb": 512, "wall_s": 1e-3}]   # fastest, but never fits VMEM
    plan = roofline.mining_tile_plan(96, 12, rows=rows)
    assert plan.source == "measured"
    assert plan.pb == 8
    assert roofline.fused_kernel_vmem(512, 128, 128, 9, 96) \
        > roofline.VMEM_BYTES // 2
    # no fitting row at all -> analytic fallback
    plan2 = roofline.mining_tile_plan(96, 12, rows=[rows[2]])
    assert plan2.source == "analytic"


def test_tile_plan_feeds_the_kernel():
    """ops.fused_bucket_counts actually consumes the plan: overriding the
    block size against the plan's choice changes nothing in the result
    (partition invariance) but the default block comes from the plan."""
    plan = roofline.mining_tile_plan(24, 10)
    assert plan.block_patients >= plan.pb
    rng = np.random.default_rng(53)
    db = random_dbmart(rng, n_patients=4, max_events=12)
    a = fused_counts(db, "kernel", n_buckets_log2=10)
    b = fused_counts(db, "kernel", n_buckets_log2=10,
                     block_patients=plan.block_patients)
    np.testing.assert_array_equal(a, b)


def test_ref_block_counts_is_the_contract():
    """ref.block_bucket_counts == local_bucket_counts(mine_dense) — the
    documented semantic contract of the kernel."""
    rng = np.random.default_rng(61)
    db = random_dbmart(rng, n_patients=3, max_events=10)
    m = mining.mine_dense(db.phenx, db.date, db.nevents)
    P = m.seq.shape[0]
    want = np.asarray(sparsity.local_bucket_counts(
        m.seq.reshape(P, -1), m.mask.reshape(P, -1), 10))
    got = np.asarray(ref.block_bucket_counts(
        db.phenx, db.date, db.nevents, n_buckets_log2=10))
    np.testing.assert_array_equal(got, want)
