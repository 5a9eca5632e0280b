"""Conformance suite for the fused mine+screen kernel (kernels/tspm_fused).

The contract: ``fused_bucket_counts`` is byte-identical to materializing
the corpus and screening it — ``sparsity.local_bucket_counts`` over
``mining.mine(...)`` — for every codec, fused/unfused duration ids, both
backends, and every edge the tiling can hit (tile-boundary E, duplicate
values/timestamps, empty cohorts, adversarial hash collisions).  Plus the
limb-hash unit contract (hash_parts == hash_bucket(pack) without ever
forming the int64 id) and the roofline tile-selection pins.
"""
import itertools
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import SYNTHEA_32, random_dbmart
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


# --- length-grouped counting plan -------------------------------------------
def heavy_tailed_dbmart(rng, n_patients=14, n_codes=12):
    """Lognormal history lengths (most short) plus 0- and 1-event patients
    and one of 200 events, in shuffled order, padded to the longest."""
    n = np.minimum(rng.lognormal(3.0, 1.0, n_patients - 3).astype(np.int32),
                   300)
    nev = rng.permutation(np.concatenate([[0, 1, 200], n]).astype(np.int32))
    e_pad = -(-max(int(nev.max()), 1) // 8) * 8
    phenx = rng.integers(0, n_codes, (len(nev), e_pad)).astype(np.int32)
    date = np.sort(rng.integers(0, 900, (len(nev), e_pad)), axis=1)
    from repro.data.dbmart import DBMart
    return DBMart(phenx, date.astype(np.int32), nev, None)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("codec", ("bit", "paper"))
@pytest.mark.parametrize("fuse_duration", (False, True))
def test_grouped_counts_equal_single_width_oracle(backend, codec,
                                                  fuse_duration, monkeypatch):
    """Blocks of length-sorted patients, each padded to its own group's
    width, count exactly what one width for the whole cohort counts (every
    saving cuts a group here, so the small cohort takes several)."""
    monkeypatch.setattr(ops, "GROUP_SLOTS", 0)
    rng = np.random.default_rng([BACKENDS.index(backend), len(codec),
                                 int(fuse_duration)])
    db = heavy_tailed_dbmart(rng)
    H = 12
    cp = ops.counting_plan(db.nevents, H, backend, fuse_duration)
    assert len(cp.widths) > 1
    want = np.asarray(ref.fused_bucket_counts_ref(
        db.phenx, db.date, db.nevents, codec, fuse_duration, 30, H))
    got = fused_counts(db, backend, codec=codec, fuse_duration=fuse_duration,
                       n_buckets_log2=H)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_rows_count_nothing(backend):
    """7 patients in blocks of at most 4 take two blocks of 4: the row that
    fills the second (row P, no events) adds nothing to the table."""
    db = random_dbmart(np.random.default_rng(31), n_patients=7,
                       max_events=20)
    cp = ops.counting_plan(db.nevents, 12, backend, block_patients=4)
    assert [int(np.sum(b.patients == 7)) for b in cp.blocks] == [0, 1]
    np.testing.assert_array_equal(fused_counts(db, backend, block_patients=4),
                                  oracle_counts(db))


def _check_partition(cp, nevents):
    """Every patient in exactly one block, no block narrower than its
    longest history, at most MAX_WIDTHS widths and one block shape a
    width, and fewer empty rows (row P) in a width's blocks than one
    block holds."""
    P = len(nevents)
    rows = np.concatenate([b.patients for b in cp.blocks]) if cp.blocks \
        else np.zeros(0, np.int64)
    assert sorted(rows[rows != P].tolist()) == list(range(P))
    for b in cp.blocks:
        assert b.width in cp.widths
        real = b.patients[b.patients != P]
        assert b.width >= int(np.max(nevents[real], initial=0))
    assert list(cp.widths) == sorted(set(cp.widths))
    assert len(cp.widths) <= ops.MAX_WIDTHS
    shapes = {(len(b.patients), b.width) for b in cp.blocks}
    assert len(shapes) == len(cp.widths)
    for n, w in shapes:
        assert sum(int(np.sum(b.patients == P)) for b in cp.blocks
                   if b.width == w) < n


def test_counting_plan_synthea_lengths():
    nev = np.array(SYNTHEA_32)
    cp = ops.counting_plan(nev, 20, "jnp")
    _check_partition(cp, nev)
    assert cp.slots == sum(len(b.patients) * b.width ** 2 for b in cp.blocks)
    assert cp.slots <= 20_000_000          # one width: 332,613,632
    assert len(cp.widths) == ops.MAX_WIDTHS and cp.widths[-1] == 3224


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", range(4))
def test_counting_plan_partitions_the_cohort(backend, seed):
    rng = np.random.default_rng(seed)
    nev = np.concatenate([[0, 1, 0], rng.lognormal(4.0, 1.2, 200)
                          .astype(np.int64)])
    nev = rng.permutation(nev)
    cp = ops.counting_plan(nev, 12, backend)
    _check_partition(cp, nev)
    step = 128 if backend == "kernel" else ops.JNP_WIDTH_MULTIPLE
    assert all(w % step == 0 for w in cp.widths)


@pytest.mark.parametrize("backend", BACKENDS)
def test_counting_plan_is_fast_at_table2_scale(backend):
    """The paper's full Table 2 cohort, 35,000 lognormal histories (the
    Synthea configuration's mu and sigma), plans in under 0.5 s of host
    time (best of three)."""
    rng = np.random.default_rng(35_000)
    nev = np.maximum(rng.lognormal(5.0566, 1.1878, 35_000), 2).astype(
        np.int64)
    times = []
    for _ in range(3):
        t0 = time.process_time()
        cp = ops.counting_plan(nev, 20, backend)
        times.append(time.process_time() - t0)
    assert min(times) < 0.5, times
    _check_partition(cp, nev)


@pytest.mark.parametrize("group_slots", (0, ops.GROUP_SLOTS))
@pytest.mark.parametrize("seed", range(6))
def test_counting_plan_is_the_least_padding(seed, group_slots, monkeypatch):
    """The dynamic program's groups reach the least sum of patients x
    width^2 plus GROUP_SLOTS a group that any cut of the sorted widths into
    MAX_WIDTHS runs does (the fewest runs among equals)."""
    monkeypatch.setattr(ops, "GROUP_SLOTS", group_slots)
    rng = np.random.default_rng(seed)
    nev = rng.integers(0, 120, int(rng.integers(1, 40)))
    cp = ops.counting_plan(nev, 20, "jnp", block_patients=1 << 20)
    w = np.sort(ops._padded_widths(nev, False, 128))
    d = np.unique(w)
    best = None
    for k in range(min(ops.MAX_WIDTHS, len(d))):
        for cuts in itertools.combinations(range(len(d) - 1), k):
            tops = [d[c] for c in cuts] + [d[-1]]
            slots = sum(int(t) ** 2 * int(np.sum(
                (w <= t) & (w > (tops[i - 1] if i else -1))))
                for i, t in enumerate(tops))
            best = min(best or (slots + k * group_slots, k, slots),
                       (slots + k * group_slots, k, slots))
    assert (cp.slots, len(cp.widths)) == (best[2], best[1] + 1)


def test_counting_plan_small_saving_keeps_one_width(monkeypatch):
    """A group is cut off only where it saves more than GROUP_SLOTS pair
    slots: 12 histories of 2..14 events save 2,304 of 3,072 slots by a
    width-8 group, too few for one more program."""
    nev = np.random.default_rng(5).integers(2, 15, 12)
    cp = ops.counting_plan(nev, 10, "jnp")
    assert cp.widths == (16,) and cp.slots == 12 * 16 * 16
    monkeypatch.setattr(ops, "GROUP_SLOTS", 12 * 16 * 16 - 1728 - 1)
    cp = ops.counting_plan(nev, 10, "jnp")
    assert cp.widths == (8, 16) and cp.slots == 1728


@pytest.mark.parametrize("n", (1, 8, 9, 129))
def test_counting_plan_equal_lengths_one_width(n):
    """A uniform cohort: one width, and the slots of one block width for
    all, as before grouping (the kernel pads to its 128-lane tile)."""
    nev = np.full(11, n)
    jnp_plan = ops.counting_plan(nev, 12, "jnp")
    w = -(-n // 8) * 8
    assert jnp_plan.widths == (w,) and jnp_plan.slots == 11 * w * w
    kern = ops.counting_plan(nev, 12, "kernel")
    w = -(-n // 128) * 128
    assert kern.widths == (w,) and kern.slots == 16 * w * w   # pb = 8


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
