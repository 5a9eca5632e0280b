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

    ``counts`` was already swapped in by ``update_begin`` (device arrays
    are futures; nothing blocked).  ``update_finish`` materializes
    ``n_novel`` and lands ``merged`` in the set planes."""

    __slots__ = ("pids", "merged", "n_novel")

    def __init__(self, pids, merged, n_novel):
        self.pids = pids
        self.merged = merged
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
        self.seqset = jnp.full((0, pad_multiple), SENTINEL, jnp.int64)
        self.n_distinct = np.zeros(0, np.int32)
        if device is not None:
            self.counts = jax.device_put(self.counts, device)
            self.seqset = jax.device_put(self.seqset, device)
        self.obs = telemetry if telemetry is not None else obs_lib.NOOP
        lbl = labels or {}
        m = self.obs.metrics
        self._m_novel = m.counter("sketch.novel_ids", **lbl)
        self._m_growths = m.counter("sketch.plane_growths", **lbl)
        self._m_load = m.gauge("sketch.bucket_load_factor", **lbl)
        self._m_cols = m.gauge("sketch.set_columns", **lbl)

    @property
    def n_patients(self) -> int:
        return self.seqset.shape[0]

    def ensure_patients(self, n: int) -> None:
        if n <= self.n_patients:
            return
        # geometric, like the set columns and the store planes: every
        # plane height is a new shape for the per-tick gather/scatter
        grow = max(-(-n // 8) * 8, 2 * self.n_patients) - self.n_patients
        self.seqset = jnp.pad(self.seqset, ((0, grow), (0, 0)),
                              constant_values=SENTINEL)
        self.n_distinct = np.pad(self.n_distinct, (0, grow))

    def _ensure_columns(self, n: int) -> None:
        """Widen the per-patient set planes to hold ``n`` ids (round up to
        the pad multiple, double geometrically — one growth policy for
        tick updates and migration admits)."""
        need = -(-max(n, 1) // self.pad_multiple) * self.pad_multiple
        if need <= self.seqset.shape[1]:
            return
        need = max(need, 2 * self.seqset.shape[1])
        self.seqset = jnp.pad(
            self.seqset, ((0, 0), (0, need - self.seqset.shape[1])),
            constant_values=SENTINEL)
        self._m_growths.inc()

    def update(self, pids, seq, mask) -> int:
        """Fold a tick's delta slab rows into the table; returns #novel ids.

        Pids must be distinct: rows gather/scatter the per-patient sets,
        so a repeated pid would double-count its buckets and lose part of
        its merged set."""
        return self.update_finish(self.update_begin(pids, seq, mask))

    def update_begin(self, pids, seq, mask) -> _PendingSketchUpdate:
        """Device phase only: dispatch the jitted fold and swap the new
        table in without forcing any host transfer, so a sharded tick can
        enqueue every shard's fold before blocking on the first
        (``update_finish`` completes the host bookkeeping)."""
        pids = np.asarray(pids, np.int32)
        if len(np.unique(pids)) != len(pids):
            raise ValueError("duplicate pids in one sketch update")
        self.ensure_patients(int(pids.max(initial=-1)) + 1)
        stored = self.seqset[pids]
        B = stored.shape[0]
        self.counts, merged, n_novel = sketch_update(
            self.counts, stored, jnp.asarray(seq).reshape(B, -1),
            jnp.asarray(mask).reshape(B, -1), self.n_buckets_log2)
        return _PendingSketchUpdate(pids, merged, n_novel)

    def update_finish(self, pending: _PendingSketchUpdate) -> int:
        """Host phase: materialize the novel counts, grow the set planes if
        a patient's distinct set outgrew them, and land the merged rows."""
        pids, merged = pending.pids, pending.merged
        self.n_distinct[pids] += np.asarray(pending.n_novel)
        self._ensure_columns(int(self.n_distinct.max(initial=1)))
        C = self.seqset.shape[1]
        if merged.shape[1] < C:
            merged = jnp.pad(merged, ((0, 0), (0, C - merged.shape[1])),
                             constant_values=SENTINEL)
        self.seqset = self.seqset.at[pids].set(merged[:, :C])
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
        self._m_cols.set(int(self.seqset.shape[1]))

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
        ids = np.asarray(self.seqset[pid])[:n]   # host slice: stable shapes
        if n:
            self._bucket_transfer(ids, -1)
            self.seqset = self.seqset.at[pid].set(SENTINEL)
            self.n_distinct[pid] = 0
        return ids

    def admit_row(self, pid: int, ids) -> None:
        """Install a migrated patient's sorted distinct-id set at ``pid``
        and *add* one to each id's bucket (the other half of the
        subtract/add transfer; extract then admit is a global no-op)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        self.ensure_patients(pid + 1)
        self._ensure_columns(len(ids))
        row = np.full(self.seqset.shape[1], SENTINEL, np.int64)
        row[: len(ids)] = ids
        self.seqset = self.seqset.at[pid].set(jnp.asarray(row))
        self.n_distinct[pid] = len(ids)
        if len(ids):
            self._bucket_transfer(ids, 1)

    # --- checkpoint ---------------------------------------------------------
    def state_dict(self) -> dict:
        """Bucket table + per-patient set planes (shapes included: the
        restored planes keep their exact width, so the first post-restore
        tick retraces nothing the uninterrupted run wouldn't)."""
        return {"counts": np.asarray(self.counts),
                "seqset": np.asarray(self.seqset),
                "n_distinct": self.n_distinct.copy()}

    def load_state_dict(self, state: dict) -> None:
        self.counts = jnp.asarray(np.asarray(state["counts"], np.int32))
        self.seqset = jnp.asarray(np.asarray(state["seqset"], np.int64))
        if self.device is not None:
            self.counts = jax.device_put(self.counts, self.device)
            self.seqset = jax.device_put(self.seqset, self.device)
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
