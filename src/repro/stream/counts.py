"""Online support sketch: incremental distinct-(patient, sequence) counts.

Batch screening (core/sparsity.local_bucket_counts) dedupes sequences per
patient row, multiply-shift hashes them into 2^H buckets and scatter-adds.
The streaming sketch maintains the *same* bucket table incrementally: per
patient it keeps the sorted set of sequence ids already contributed, and a
tick's delta slab increments a bucket only for ids the patient has never
produced (dedup within the delta by sort-run flags, against history by
binary search).  Consequences, both property-tested:

  * the table equals ``local_bucket_counts`` of the full batch-mined
    corpus after any replay order — not an approximation of it;
  * it stays mergeable with batch-screen counts
    (``sparsity.merge_bucket_counts``) and keeps the one-sided error of
    the hash screen: collisions only ever over-count, so a non-sparse
    sequence is never dropped.

The per-patient sets live on the device as two int32 planes, the high and
the low halves of the ids (``set_hi``/``set_lo`` [P, C]): a v5e splits an
int64 array into such halves for every gather or scatter, and for a whole
[P, C] plane that split is a copy of the plane.  The planes grow on one
family of widths, ``pad_multiple * 2**k`` (``set_width``), before a
tick's fold, to a bound the host knows; a tick reads its patients' rows
with ``sketch_rows`` and writes the merged rows back in place with
``sketch_land`` (row slices, the planes donated), so no tick copies a
plane.

Shard migration hands a patient's row between sketches with
``extract_row`` / ``admit_row``: the sorted distinct-id set moves, and the
bucket table transfers by subtract-at-source / add-at-dest — each side's
table stays exactly ``local_bucket_counts`` of *its* patient set, so the
merged (psum'd) table is unchanged by any migration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib
from repro.core import sparsity
from repro.core.encoding import SENTINEL

#: the two int32 halves of SENTINEL: the fill of empty set slots
_HI_FILL = int(SENTINEL) >> 32
_LO_FILL = -1


def set_width(n: int, pad_multiple: int) -> int:
    """Width of set planes that hold ``n`` ids: the least
    ``pad_multiple * 2**k`` >= n, the one family every width lies in."""
    w = pad_multiple
    while w < n:
        w *= 2
    return w


def _split(x):
    """int64 ids -> (high, low) int32 halves."""
    return (x >> 32).astype(jnp.int32), x.astype(jnp.int32)


def _join(hi, lo):
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & 0xFFFFFFFF)


@jax.jit
def sketch_rows(set_hi, set_lo, pids):
    """The sets of patients ``pids`` as int64 rows [B, C].  One row slice
    per patient: a gather of whole rows copies the plane on a v5e once a
    row passes 256 KB."""
    C = set_hi.shape[1]
    zero = jnp.zeros((), pids.dtype)

    def take(plane):
        return jnp.concatenate([jax.lax.dynamic_slice(plane, (pids[i], zero),
                                                      (1, C))
                                for i in range(pids.shape[0])])
    return _join(take(set_hi), take(set_lo))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def sketch_land(set_hi, set_lo, pids, rows):
    """Write int64 ``rows`` [B, W] over the sets of ``pids`` in place: each
    row cut or sentinel-padded to the planes' width C, split into halves
    and written with one row update.  The planes are donated; an int64
    plane would be split and joined whole, a copy per call."""
    C = set_hi.shape[1]
    W = rows.shape[1]
    if W < C:
        rows = jnp.pad(rows, ((0, 0), (0, C - W)), constant_values=SENTINEL)
    hi, lo = _split(rows[:, :C])
    zero = jnp.zeros((), pids.dtype)
    for i in range(pids.shape[0]):
        set_hi = jax.lax.dynamic_update_slice(set_hi, hi[i:i + 1],
                                              (pids[i], zero))
        set_lo = jax.lax.dynamic_update_slice(set_lo, lo[i:i + 1],
                                              (pids[i], zero))
    return set_hi, set_lo


@functools.partial(jax.jit, static_argnames=("n_buckets_log2",))
def sketch_update(counts, stored, seq, mask, n_buckets_log2: int):
    """One tick: (counts', merged per-patient sets, per-row novel counts).

    ``stored`` [B, C] are the patients' sorted sentinel-padded sequence
    sets; ``seq``/``mask`` [B, T] the tick's delta slab rows.
    """
    B, C = stored.shape
    flat = jnp.where(mask, jnp.asarray(seq, jnp.int64), SENTINEL).reshape(B, -1)
    # plain keys with no payload: an unstable sort returns the same array,
    # and both int64 sorts compile ~2.4x faster for a TPU v5e
    srt = jnp.sort(flat, axis=1, stable=False)
    first = sparsity.row_first_flags(srt)   # same dedup as the batch screen
    idx = jax.vmap(jnp.searchsorted)(stored, srt)
    present = jnp.take_along_axis(stored, jnp.clip(idx, 0, C - 1), axis=1) == srt
    novel = first & ~present
    h = sparsity.hash_bucket(srt, n_buckets_log2)
    counts = counts.at[h.reshape(-1)].add(novel.reshape(-1).astype(jnp.int32))
    merged = jnp.sort(
        jnp.concatenate([stored, jnp.where(novel, srt, SENTINEL)], axis=1),
        axis=1, stable=False)
    return counts, merged, jnp.sum(novel, axis=1).astype(jnp.int32)


class _PendingSketchUpdate:
    """Device phase of one tick's sketch fold, awaiting host bookkeeping.

    ``counts`` and the set planes were already swapped in by
    ``update_begin`` (device arrays are futures; nothing blocked).
    ``update_finish`` materializes ``n_novel``."""

    __slots__ = ("pids", "n_novel")

    def __init__(self, pids, n_novel):
        self.pids = pids
        self.n_novel = n_novel


class OnlineSupportSketch:
    """Incrementally maintained hash-bucket support table + per-patient sets.

    ``device`` pins the table and set planes (same commitment contract as
    :class:`~repro.stream.store.PatientStore`): tick folds and handoff
    scatters stay on that device."""

    def __init__(self, n_buckets_log2: int = 20, pad_multiple: int = 64,
                 device=None, telemetry=None, labels: dict | None = None):
        self.n_buckets_log2 = n_buckets_log2
        self.pad_multiple = pad_multiple
        self.device = device
        self.counts = jnp.zeros(1 << n_buckets_log2, jnp.int32)
        self.set_hi = jnp.full((0, pad_multiple), _HI_FILL, jnp.int32)
        self.set_lo = jnp.full((0, pad_multiple), _LO_FILL, jnp.int32)
        self.n_distinct = np.zeros(0, np.int32)
        if device is not None:
            self.counts = jax.device_put(self.counts, device)
            self.set_hi = jax.device_put(self.set_hi, device)
            self.set_lo = jax.device_put(self.set_lo, device)
        self.obs = telemetry if telemetry is not None else obs_lib.NOOP
        lbl = labels or {}
        m = self.obs.metrics
        self._m_novel = m.counter("sketch.novel_ids", **lbl)
        self._m_growths = m.counter("sketch.plane_growths", **lbl)
        self._m_load = m.gauge("sketch.bucket_load_factor", **lbl)
        self._m_cols = m.gauge("sketch.set_columns", **lbl)

    @property
    def n_patients(self) -> int:
        return self.set_hi.shape[0]

    @property
    def set_columns(self) -> int:
        return self.set_hi.shape[1]

    def _pad_planes(self, rows: int, cols: int) -> None:
        pad = ((0, rows), (0, cols))
        self.set_hi = jnp.pad(self.set_hi, pad, constant_values=_HI_FILL)
        self.set_lo = jnp.pad(self.set_lo, pad, constant_values=_LO_FILL)

    def ensure_patients(self, n: int) -> None:
        if n <= self.n_patients:
            return
        # geometric, like the set columns and the store planes: every
        # plane height is a new shape for the per-tick row programs
        grow = max(-(-n // 8) * 8, 2 * self.n_patients) - self.n_patients
        self._pad_planes(grow, 0)
        self.n_distinct = np.pad(self.n_distinct, (0, grow))

    def _ensure_columns(self, n: int) -> None:
        """Widen the per-patient set planes to hold ``n`` ids, to the next
        width of the family (``set_width``) — one growth policy for tick
        updates and migration admits.  A plane restored from a checkpoint
        of another width rejoins the family at its next growth."""
        need = set_width(max(n, 1), self.pad_multiple)
        if need <= self.set_columns:
            return
        self._pad_planes(0, need - self.set_columns)
        self._m_growths.inc()

    def rows(self, pids):
        """The sets of ``pids`` as sentinel-padded int64 rows [B, C]."""
        return sketch_rows(self.set_hi, self.set_lo,
                           jnp.asarray(np.asarray(pids, np.int32)))

    def _land(self, pids, rows) -> None:
        self.set_hi, self.set_lo = sketch_land(
            self.set_hi, self.set_lo, jnp.asarray(np.asarray(pids, np.int32)),
            rows)

    def update(self, pids, seq, mask, max_new=None) -> int:
        """Fold a tick's delta slab rows into the table; returns #novel ids.

        Pids must be distinct: rows gather/scatter the per-patient sets,
        so a repeated pid would double-count its buckets and lose part of
        its merged set."""
        return self.update_finish(self.update_begin(pids, seq, mask,
                                                    max_new))

    def update_begin(self, pids, seq, mask,
                     max_new=None) -> _PendingSketchUpdate:
        """Device phase only: grow the set planes, dispatch the jitted fold
        and land its merged rows, without waiting on the device, so a
        sharded tick can enqueue every shard's fold before blocking on the
        first (``update_finish`` completes the host bookkeeping).

        The planes grow before the fold, to hold each row's stored ids
        plus ``max_new`` more, an upper bound of its new ids (the host
        knows it: the row's new pairs; read from ``mask`` when not
        given).  So growth never waits on the fold's result, and happens
        where nothing of the tick but its slab is on the device."""
        pids = np.asarray(pids, np.int32)
        if len(np.unique(pids)) != len(pids):
            raise ValueError("duplicate pids in one sketch update")
        self.ensure_patients(int(pids.max(initial=-1)) + 1)
        B = len(pids)
        seq = jnp.asarray(seq).reshape(B, -1)
        mask = jnp.asarray(mask).reshape(B, -1)
        if max_new is None:
            max_new = np.asarray(jnp.sum(mask, axis=1))
        self._ensure_columns(int(np.max(
            self.n_distinct[pids] + np.asarray(max_new), initial=1)))
        self.counts, merged, n_novel = sketch_update(
            self.counts, self.rows(pids), seq, mask, self.n_buckets_log2)
        self._land(pids, merged)
        return _PendingSketchUpdate(pids, n_novel)

    def update_finish(self, pending: _PendingSketchUpdate) -> int:
        """Host phase: materialize the novel counts."""
        pids = pending.pids
        self.n_distinct[pids] += np.asarray(pending.n_novel)
        n_novel = int(np.asarray(pending.n_novel).sum())
        self._m_novel.inc(n_novel)
        return n_novel

    def sample_metrics(self) -> None:
        """Snapshot-time gauges: bucket load factor (occupied / 2^H — one
        device->host table copy, so never sampled per tick) and the
        per-patient set plane width."""
        if not self.obs.enabled:
            return
        table = np.asarray(self.counts)
        self._m_load.set(float(np.count_nonzero(table)) / max(len(table), 1))
        self._m_cols.set(self.set_columns)

    # --- migration handoff --------------------------------------------------
    def _bucket_transfer(self, ids: np.ndarray, sign: int) -> None:
        """Scatter ``sign`` into the ids' buckets, padded to the column
        multiple with zero weights — handoff sizes vary per patient, so an
        exact-length hash would compile one XLA program per distinct set
        size; quantizing keeps the variant count O(log)."""
        cap = -(-max(len(ids), 1) // self.pad_multiple) * self.pad_multiple
        padded = np.zeros(cap, np.int64)
        padded[: len(ids)] = ids
        w = np.zeros(cap, np.int32)
        w[: len(ids)] = sign
        h = sparsity.hash_bucket(jnp.asarray(padded), self.n_buckets_log2)
        self.counts = self.counts.at[h].add(jnp.asarray(w))

    def extract_row(self, pid: int) -> np.ndarray:
        """Withdraw a patient's set: returns its sorted distinct sequence
        ids and *subtracts* one from each id's bucket, so this table is
        again exactly ``local_bucket_counts`` of the remaining patients.
        The row stays allocated (pids are never reused) but zeroed."""
        if pid >= self.n_patients:
            return np.zeros(0, np.int64)
        n = int(self.n_distinct[pid])
        ids = np.asarray(self.rows([pid]))[0, :n]   # host slice: stable shapes
        if n:
            self._bucket_transfer(ids, -1)
            self._land([pid], jnp.full((1, 1), SENTINEL, jnp.int64))
            self.n_distinct[pid] = 0
        return ids

    def admit_row(self, pid: int, ids) -> None:
        """Install a migrated patient's sorted distinct-id set at ``pid``
        and *add* one to each id's bucket (the other half of the
        subtract/add transfer; extract then admit is a global no-op)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        self.ensure_patients(pid + 1)
        self._ensure_columns(len(ids))
        row = np.full((1, self.set_columns), SENTINEL, np.int64)
        row[0, : len(ids)] = ids
        self._land([pid], jnp.asarray(row))
        self.n_distinct[pid] = len(ids)
        if len(ids):
            self._bucket_transfer(ids, 1)

    # --- checkpoint ---------------------------------------------------------
    def state_dict(self) -> dict:
        """Bucket table + per-patient sets as one int64 [P, C] array
        (shapes included: the restored planes keep their exact width, so
        the first post-restore tick retraces nothing the uninterrupted run
        wouldn't)."""
        hi = np.asarray(self.set_hi).astype(np.int64)
        lo = np.asarray(self.set_lo).astype(np.int64)
        return {"counts": np.asarray(self.counts),
                "seqset": (hi << 32) | (lo & 0xFFFFFFFF),
                "n_distinct": self.n_distinct.copy()}

    def load_state_dict(self, state: dict) -> None:
        seqset = np.asarray(state["seqset"], np.int64)
        self.counts = jnp.asarray(np.asarray(state["counts"], np.int32))
        self.set_hi = jnp.asarray((seqset >> 32).astype(np.int32))
        self.set_lo = jnp.asarray(seqset.astype(np.int32))
        if self.device is not None:
            self.counts = jax.device_put(self.counts, self.device)
            self.set_hi = jax.device_put(self.set_hi, self.device)
            self.set_lo = jax.device_put(self.set_lo, self.device)
        self.n_distinct = np.asarray(state["n_distinct"], np.int32).copy()

    # --- interop with the batch screen -------------------------------------
    def merged_with(self, batch_counts):
        """Sketch counts + batch-screen bucket counts (same table format)."""
        return sparsity.merge_bucket_counts(self.counts, batch_counts)

    def keep_mask(self, seq, mask, threshold: int):
        """Hash-screen keep mask over any corpus using the live table."""
        return sparsity.screen_hash_from_counts(
            seq, mask, self.counts, threshold, self.n_buckets_log2)

    def survivors(self, seq, dur, patient, threshold: int, mask=None):
        """Compact a corpus to its hash-screen survivors using the live
        table — the streaming half of ``screen='fused'``: because this
        table exactly equals the batch ``local_bucket_counts``, the
        compacted arrays are byte-identical to the corpus-free batch
        path's survivors on the same corpus."""
        return sparsity.screen_survivors(
            seq, dur, patient, np.asarray(self.counts), threshold,
            self.n_buckets_log2, mask=mask)
