"""Sparsity screening — sort-based (paper-faithful) and hash-based (scalable).

The paper screens sequences by *patient support*: a sequence is sparse when
it occurs for fewer than ``threshold`` distinct patients.  Its C++ recipe:

  1. parallel-sort all sequences by id (ips4o);
  2. linear pass: run boundaries -> per-sequence patient counts;
  3. mark sparse entries by writing UINT_MAX into the key;
  4. one more sort; truncate at the first sentinel.

``screen_sorted`` is the exact TPU port of that recipe (lax.sort +
shifted-compare + segment_sum + sentinel re-sort; static shapes, so
"truncate" returns a valid-prefix length instead of shrinking).

``screen_hash`` is the *beyond-paper distributed* variant: per-patient
dedupe, multiply-shift hash into 2^H buckets, scatter-add, one psum over the
patient-sharded mesh axes.  Collisions merge counts, so the error is
one-sided — a sparse sequence may survive, a non-sparse one is NEVER
dropped (property-tested).  This turns a global sort into one all-reduce.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import SENTINEL

# multiply-shift hash constant (odd; splitmix64's golden-gamma)
_HASH_K = jnp.int64(-7046029254386353131)  # == 0x9E3779B97F4A7C15 mod 2^64
# the same constant as an unsigned Python int, for host-side modular
# arithmetic (kernels/tspm_fused derives its limb-decomposed per-field
# hash constants from this; the two spellings must stay equal mod 2^64)
HASH_MULT = 0x9E3779B97F4A7C15


class Screened(NamedTuple):
    """Sort-compacted screening result (paper's post-truncate layout).

    Arrays are full length; the first ``n_kept`` entries are the surviving
    sequences in sorted-id order, the rest carry the SENTINEL key."""

    seq: jax.Array      # [N] int64, sorted, kept-prefix
    dur: jax.Array      # [N] int32
    patient: jax.Array  # [N] int32
    support: jax.Array  # [N] int32 distinct-patient support (0 on sentinel)
    n_kept: jax.Array   # scalar int64


def _run_flags(keys, patients):
    """(new-sequence, new-(sequence,patient)) flags on sorted arrays."""
    seq_change = jnp.concatenate(
        [jnp.ones(1, bool), keys[1:] != keys[:-1]])
    pat_change = jnp.concatenate(
        [jnp.ones(1, bool), (patients[1:] != patients[:-1])]) | seq_change
    return seq_change, pat_change


@functools.partial(jax.jit, static_argnames=())
def support_counts(seq, patient, mask):
    """Distinct-patient support per element + unique table.

    Returns (sorted keys, sorted patients, per-element support, unique ids
    (sentinel-padded, sorted, compacted to front), unique supports,
    n_unique).
    """
    seq = jnp.asarray(seq, jnp.int64).reshape(-1)
    patient = jnp.asarray(patient, jnp.int32).reshape(-1)
    mask = jnp.asarray(mask, bool).reshape(-1)
    n = seq.shape[0]
    keys = jnp.where(mask, seq, SENTINEL)
    keys, patient = jax.lax.sort((keys, patient), num_keys=2)
    seq_change, pat_change = _run_flags(keys, patient)
    seg = jnp.cumsum(seq_change) - 1
    seg_support = jax.ops.segment_sum(
        pat_change.astype(jnp.int32), seg, num_segments=n)
    support = jnp.where(keys != SENTINEL, seg_support[seg], 0)
    first = seq_change & (keys != SENTINEL)
    u_key = jnp.where(first, keys, SENTINEL)
    u_key, u_support = jax.lax.sort(
        (u_key, jnp.where(first, support, 0)), num_keys=1)
    return keys, patient, support, u_key, u_support, jnp.sum(first)


@functools.partial(jax.jit, static_argnames=())
def screen_sorted(seq, dur, patient, mask, threshold) -> Screened:
    """Paper-faithful sort/mark/re-sort/truncate sparsity screen (exact)."""
    seq = jnp.asarray(seq, jnp.int64).reshape(-1)
    dur = jnp.asarray(dur, jnp.int32).reshape(-1)
    patient = jnp.asarray(patient, jnp.int32).reshape(-1)
    mask = jnp.asarray(mask, bool).reshape(-1)
    n = seq.shape[0]

    keys = jnp.where(mask, seq, SENTINEL)
    keys, patient, dur = jax.lax.sort((keys, patient, dur), num_keys=2)
    seq_change, pat_change = _run_flags(keys, patient)
    seg = jnp.cumsum(seq_change) - 1
    seg_support = jax.ops.segment_sum(
        pat_change.astype(jnp.int32), seg, num_segments=n)
    support = seg_support[seg]
    keep = (support >= threshold) & (keys != SENTINEL)

    # the paper's marking trick: sparse entries get the sentinel key, one
    # more sort pushes them to the tail, n_kept is the truncation point.
    marked = jnp.where(keep, keys, SENTINEL)
    marked, patient, dur, support = jax.lax.sort(
        (marked, patient, dur, jnp.where(keep, support, 0)), num_keys=2)
    return Screened(marked, dur, patient, support, jnp.sum(keep))


# --- hash-based distributed screen (beyond paper) ---------------------------
def hash_bucket(seq, n_buckets_log2: int):
    """Multiply-shift hash of int64 sequence ids into [0, 2^H)."""
    seq = jnp.asarray(seq, jnp.int64)
    h = (seq * _HASH_K) >> (64 - n_buckets_log2)
    return (h & ((1 << n_buckets_log2) - 1)).astype(jnp.int32)


def _hash_bucket_host(seq, n_buckets_log2: int) -> np.ndarray:
    """``hash_bucket`` in numpy, bit for bit (the top H bits of the 64-bit
    product): a host array of a new length compiles nothing."""
    h = np.asarray(seq, np.int64).view(np.uint64) * np.uint64(HASH_MULT)
    h >>= np.uint64(64 - n_buckets_log2)
    return h


def row_first_flags(sorted_rows):
    """First-occurrence flags on row-wise sorted sentinel-padded id rows —
    the per-patient dedup step shared by the batch screen and the streaming
    sketch (stream/counts), so their distinct-(patient, sequence) semantics
    cannot drift apart."""
    first = jnp.concatenate(
        [jnp.ones((sorted_rows.shape[0], 1), bool),
         sorted_rows[:, 1:] != sorted_rows[:, :-1]], axis=1)
    return first & (sorted_rows != SENTINEL)


def local_bucket_counts(seq, mask, n_buckets_log2: int):
    """Per-shard distinct-patient bucket counts for row-major [P, T] input.

    Rows are patients; dedupes (patient, sequence) by a row-wise sort before
    counting, matching the paper's distinct-patient support semantics.
    """
    seq = jnp.asarray(seq, jnp.int64)
    mask = jnp.asarray(mask, bool)
    P = seq.shape[0]
    flat = jnp.where(mask, seq, SENTINEL).reshape(P, -1)
    srt = jnp.sort(flat, axis=1, stable=False)   # keys only: same result
    first = row_first_flags(srt)
    h = hash_bucket(srt, n_buckets_log2)
    counts = jnp.zeros(1 << n_buckets_log2, jnp.int32)
    return counts.at[h.reshape(-1)].add(first.reshape(-1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_buckets_log2", "axis_names"))
def screen_hash(seq, mask, threshold, n_buckets_log2: int = 20,
                axis_names: tuple[str, ...] | None = None):
    """Keep-mask for [P, T] mined rows; one psum when patient-sharded.

    Inside shard_map pass ``axis_names`` (e.g. ('pod', 'data')) to reduce
    bucket counts over the patient-sharded axes.  One-sided error under
    collisions (false-keep only).
    """
    counts = local_bucket_counts(seq, mask, n_buckets_log2)
    if axis_names:
        counts = jax.lax.psum(counts, axis_names)
    keep = counts[hash_bucket(seq, n_buckets_log2)] >= threshold
    return keep & jnp.asarray(mask, bool)


def merge_bucket_counts(*counts):
    """Host-side merge of per-chunk bucket count arrays (chunked pipeline)."""
    out = counts[0]
    for c in counts[1:]:
        out = out + c
    return out


def screen_hash_from_counts(seq, mask, counts, threshold, n_buckets_log2: int):
    """Apply a pre-merged global bucket-count table to a chunk."""
    keep = counts[hash_bucket(seq, n_buckets_log2)] >= threshold
    return keep & jnp.asarray(mask, bool)


def _no_phase(name, **args):
    pass


#: bytes one survivor occupies in the compacted device buffers, and so
#: moves device -> host: id 8, duration 4 (the host rebuilds the patient
#: column from each leading row's survivor count)
SURVIVOR_BYTES = 8 + 4
#: compacted buffers hold a multiple of this many slots
CAPACITY_GRANULE = 1024


def survivor_capacity(n: int) -> int:
    """Slots of the compacted buffers for ``n`` survivors: the least step
    ``ceil(2**(k/4))``, rounded up to a multiple of ``CAPACITY_GRANULE``,
    that holds ``n`` (0 for none).  Capacity is a static shape, so compiles
    grow with log n, and the slack stays under
    ``2**0.25 * n + CAPACITY_GRANULE`` slots."""
    if n <= 0:
        return 0
    g = CAPACITY_GRANULE
    floor = -(-n // g) * g - g      # a step must exceed this to round to >= n
    k = (floor ** 4).bit_length()   # least k with 2**k > floor**4
    m = math.isqrt(math.isqrt(1 << k))
    while m ** 4 < 1 << k:          # exact ceil(2**(k/4))
        m += 1
    return -(-m // g) * g


def _row_starts(rows):
    """Exclusive row-major prefix sums of per-row counts ``rows`` (any
    rank), built axis by axis so no plane is reshaped on the device."""
    starts = jnp.cumsum(rows, axis=-1, dtype=jnp.int32) - rows
    if rows.ndim == 1:
        return starts
    return _row_starts(jnp.sum(rows, axis=-1, dtype=jnp.int32))[..., None] \
        + starts


@functools.partial(jax.jit, static_argnames=("n_buckets_log2",))
def _screen_rows(seq, mask, counts, threshold, n_buckets_log2: int):
    """The hash screen of a mined chunk, the first output slot of each row
    (its last axis) in row-major order, and the survivors of each index of
    the leading axis."""
    if mask is None:
        mask = seq != SENTINEL
    keep = screen_hash_from_counts(seq, mask, counts, threshold,
                                   n_buckets_log2)
    rows = jnp.sum(keep, axis=-1, dtype=jnp.int32)
    lead = rows if rows.ndim == 1 else jnp.sum(
        rows, axis=tuple(range(1, rows.ndim)), dtype=jnp.int32)
    return keep, _row_starts(rows), lead


@functools.partial(jax.jit, static_argnames=("capacity",))
def _compact(seq, dur, keep, starts, capacity: int):
    """Stream-compact the kept slots into ``capacity``-long int32 buffers,
    in row-major order: the ids' low and high halves, and the durations.

    A slot's place is the number of kept slots before it: its row's start
    plus its exclusive rank in the row.  The places never decrease, so the
    scatters are declared sorted and add: a slot not kept adds 0 at the
    place of the next kept one, or past the end.  A TPU runs a sorted
    32-bit scatter as one streaming pass (~1.3 s over a [512, 544, 544]
    plane, v5e); an unsorted one it sorts first, and an int64 or a
    windowed one runs 10-15x slower.  Int32 buffers also leave the device
    at the copy engine's rate, where an int64 one goes through a slow
    host-side relayout."""
    pos = starts[..., None] + jnp.cumsum(keep, axis=-1, dtype=jnp.int32) \
        - keep
    seq = seq.astype(jnp.int64)
    low = jax.lax.bitcast_convert_type(
        (seq & 0xFFFFFFFF).astype(jnp.uint32), jnp.int32)
    high = (seq >> 32).astype(jnp.int32)

    def put(vals):
        return jnp.zeros(capacity, jnp.int32).at[pos].add(
            jnp.where(keep, vals, 0), mode="drop", indices_are_sorted=True)
    return put(low), put(high), put(dur.astype(jnp.int32))


def screen_survivors(seq, dur, patient, counts, threshold,
                     n_buckets_log2: int, mask=None, phase=_no_phase):
    """Compacted survivors of the hash screen (corpus-free path).

    The materialization half of ``screen="fused"``: given the global
    bucket-count table from the corpus-free counting pass, keep only the
    rows whose bucket clears ``threshold`` and return them as flat numpy
    ``(seq, dur, patient)`` in row-major order.  Keeping is per-*id*
    (every row of a surviving id survives), so supports, re-screens and
    the canonical lexsort order of the compacted arrays are byte-identical
    to screening the materialized corpus with the same table.
    ``patient`` holds one value per index of the leading axis (any shape
    of ``seq.shape[0]`` values, e.g. a [P, 1, 1] column).

    Where the data lives decides where it is compacted.  Host (numpy)
    inputs, and flat ones, which have no rows, are screened and indexed
    on the host, in numpy: a live corpus has a new length at every
    snapshot, and a device screen would compile for each.
    Device arrays are screened and compacted on the device, and only the
    survivors are copied back: ``phase(name, **args)`` is called as each
    host phase starts, so a caller can time them (``chunking.mine_fused``
    makes them spans): ``"wait"`` until the screen and the survivors of
    each leading index are ready and on the host, ``"compact"``
    (``survivors=``, ``capacity=``, the slots of the buffers,
    ``survivor_capacity``) the device compaction, enqueue to ready, and
    ``"fetch"`` (``bytes=``, ``SURVIVOR_BYTES`` a slot) the device-to-host
    copy of the buffers, the ids joined from their halves and the patient
    column rebuilt from the survivor counts.
    """
    P = seq.shape[0]
    patient = np.asarray(patient, np.int32).reshape(P)
    if not isinstance(seq, jax.Array) or seq.ndim < 2:
        seq = np.asarray(seq, np.int64)
        keep = np.asarray(counts)[_hash_bucket_host(
            seq, n_buckets_log2)] >= threshold
        keep &= seq != SENTINEL if mask is None else np.asarray(mask, bool)
        keep = keep.reshape(-1)
        patient = np.broadcast_to(
            patient.reshape((P,) + (1,) * (seq.ndim - 1)), seq.shape)
        return (seq.reshape(-1)[keep],
                np.asarray(dur, np.int32).reshape(-1)[keep],
                patient.reshape(-1)[keep])
    # the screen and the compaction run in the caller's layout: an eager
    # TPU reshape of a [P, E, E] plane can take minutes to compile (v5e,
    # P=256, E=536)
    keep, starts, lead = _screen_rows(seq, mask, jnp.asarray(counts),
                                      threshold, n_buckets_log2)
    phase("wait")
    lead = np.asarray(lead)
    n = int(lead.sum())
    cap = survivor_capacity(n)
    phase("compact", survivors=n, capacity=cap)
    bufs = _compact(seq, dur, keep, starts, capacity=cap) if cap else ()
    del keep, starts
    jax.block_until_ready(bufs)
    phase("fetch", bytes=cap * SURVIVOR_BYTES)
    if not cap:
        return (np.zeros(0, np.int64), np.zeros(0, np.int32),
                np.zeros(0, np.int32))
    low, high, dur = (a[:n] for a in jax.device_get(bufs))
    seq = high.astype(np.int64)
    seq <<= 32
    seq |= low.view(np.uint32)
    return seq, dur, np.repeat(patient, lead)
