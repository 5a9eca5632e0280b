"""Plain numpy reference of the mining semantics the configurations state.

Independent of the program under test: it imports nothing of ``repro`` and
takes only the cohort arrays the benchmark generated.  Semantics:

* every ordered pair of a patient's events ``i < j`` (date-sorted position
  order) is one row ``(start, end, duration, patient)`` with
  ``duration = date[j] - date[i]``;
* the sequence id is ``start * 2**24 + end`` (codec ``bit``);
* a sequence's support is its number of distinct patients, counted per
  hash bucket: bucket = top ``H`` bits of ``id * 0x9E3779B97F4A7C15 mod
  2**64`` (multiply-shift), table[b] = distinct (patient, id) pairs in b;
* the screen keeps every row whose bucket count is at least the threshold
  (one-sided: a collision can only keep more).

Rows are compared as canonical uint64 keys: mixed-radix (start, end,
patient, duration), whose sort order is the lexicographic order by
(sequence id, patient, duration).
"""
from __future__ import annotations

import numpy as np

CODEC_SHIFT = {"bit": 24}
HASH_MULT = np.uint64(0x9E3779B97F4A7C15)


def seq_ids(start, end, codec: str = "bit"):
    return (np.asarray(start, np.int64) << CODEC_SHIFT[codec]) \
        | np.asarray(end, np.int64)


def split_ids(seq, codec: str = "bit"):
    seq = np.asarray(seq, np.int64)
    s = CODEC_SHIFT[codec]
    return seq >> s, seq & ((1 << s) - 1)


def buckets(seq, n_buckets_log2: int) -> np.ndarray:
    h = np.asarray(seq, np.int64).astype(np.uint64) * HASH_MULT
    return (h >> np.uint64(64 - n_buckets_log2)).astype(np.int64)


def _groups(nevents):
    """Patients grouped by event count: ``{n: patient indices}``."""
    n = np.asarray(nevents, np.int64)
    order = np.argsort(n, kind="stable")
    ns, starts = np.unique(n[order], return_index=True)
    return {int(k): order[a:b] for k, a, b in
            zip(ns, starts, list(starts[1:]) + [len(order)]) if k > 1}


def mine(phenx, date, nevents, codec: str = "bit"):
    """Yield ``(patients[k], seq[k, T], dur[k, T])`` blocks, one per event
    count, covering every pair of every patient."""
    for n, pats in _groups(nevents).items():
        i, j = np.triu_indices(n, k=1)
        x = np.asarray(phenx)[pats, :n]
        d = np.asarray(date)[pats, :n]
        yield pats, seq_ids(x[:, i], x[:, j], codec), \
            (d[:, j] - d[:, i]).astype(np.int64)


def bucket_table(blocks, n_buckets_log2: int) -> np.ndarray:
    """Distinct-patient support table over ``mine`` blocks."""
    table = np.zeros(1 << n_buckets_log2, np.int64)
    for _, seq, _ in blocks:
        srt = np.sort(seq, axis=1)
        first = np.ones(srt.shape, bool)
        first[:, 1:] = srt[:, 1:] != srt[:, :-1]
        table += np.bincount(buckets(srt[first], n_buckets_log2),
                             minlength=len(table))
    return table


class Radix:
    """Mixed-radix packing of (start, end, patient, duration) into uint64."""

    def __init__(self, n_codes: int, n_patients: int, max_days: int):
        self.n_codes, self.n_patients = int(n_codes), int(n_patients)
        self.n_days = int(max_days) + 1
        if (self.n_codes ** 2) * self.n_patients * self.n_days >= 2 ** 64:
            raise ValueError("cohort too large for a 64-bit row key")

    def pack(self, seq, dur, patient, codec: str = "bit"):
        """Keys of the rows, and the number of rows out of range (a row out
        of range has no key and counts as a difference)."""
        s, e = split_ids(seq, codec)
        dur = np.asarray(dur, np.int64)
        patient = np.asarray(patient, np.int64)
        ok = ((s >= 0) & (s < self.n_codes) & (e >= 0) & (e < self.n_codes)
              & (patient >= 0) & (patient < self.n_patients)
              & (dur >= 0) & (dur < self.n_days))
        u = lambda a: a[ok].astype(np.uint64)
        key = ((u(s) * np.uint64(self.n_codes) + u(e))
               * np.uint64(self.n_patients) + u(patient)) \
            * np.uint64(self.n_days) + u(dur)
        return key, int(len(ok) - ok.sum())

    def unpack(self, key):
        key = np.asarray(key, np.uint64)
        dur = key % np.uint64(self.n_days)
        key = key // np.uint64(self.n_days)
        pat = key % np.uint64(self.n_patients)
        key = key // np.uint64(self.n_patients)
        e = key % np.uint64(self.n_codes)
        s = key // np.uint64(self.n_codes)
        return (seq_ids(s.astype(np.int64), e.astype(np.int64)),
                dur.astype(np.int64), pat.astype(np.int64))


def corpus(phenx, date, nevents, radix: Radix, n_buckets_log2: int,
           threshold: int | None, codec: str = "bit"):
    """Sorted row keys of the cohort's pairs (only the screen's survivors
    when ``threshold`` is set) and the support table."""
    table = bucket_table(mine(phenx, date, nevents, codec), n_buckets_log2)
    keys = []
    for pats, seq, dur in mine(phenx, date, nevents, codec):
        pat = np.broadcast_to(pats[:, None], seq.shape)
        if threshold is not None:
            keep = table[buckets(seq, n_buckets_log2)] >= threshold
            seq, dur, pat = seq[keep], dur[keep], pat[keep]
        keys.append(radix.pack(seq.reshape(-1), dur.reshape(-1),
                               pat.reshape(-1), codec)[0])
    keys = np.concatenate(keys) if keys else np.zeros(0, np.uint64)
    keys.sort()
    return keys, table


def row_diff(got_keys, n_bad: int, want_keys) -> int:
    """Size of the multiset difference between two sorted key arrays, plus
    the rows that had no key."""
    if n_bad == 0 and np.array_equal(got_keys, want_keys):
        return 0
    u1, c1 = np.unique(got_keys, return_counts=True)
    u2, c2 = np.unique(want_keys, return_counts=True)
    _, i1, i2 = np.intersect1d(u1, u2, assume_unique=True,
                               return_indices=True)
    common = int(np.minimum(c1[i1], c2[i2]).sum())
    return int(len(got_keys) + len(want_keys) - 2 * common + n_bad)


def screen_mask(keys, radix: Radix, table, threshold: int,
                n_buckets_log2: int) -> np.ndarray:
    """Keep mask of the screen over rows in key order."""
    seq, _, _ = radix.unpack(keys)
    return table[buckets(seq, n_buckets_log2)] >= threshold


def mask_diff(got, want) -> int:
    got = np.asarray(got, bool)
    want = np.asarray(want, bool)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
