"""Adaptive dbmart partitioning + file-based mining (paper's two modes).

The R package "split[s] the dbmart in chunks with an adaptive size to fit
the available memory limitations", and the C++ library has a *file-based*
mode that spills per-patient sequence files.  Here the same two ideas govern
HBM instead of RAM:

  * ``plan_chunks`` — greedy patient ranges such that the mining working set
    ``P_chunk * E_chunk^2 * BYTES_PER_PAIR`` fits the byte budget;
    per-chunk ``E`` adapts to the longest patient in the chunk (padded to a
    tile multiple), so short-history chunks pack many more patients.
  * ``mine_chunked`` — in-memory mode: mine chunk-by-chunk, merge on host.
  * ``mine_to_files`` / ``screen_files`` — file-based mode: spill each
    chunk's packed sequences to ``.npz`` and stream them back for a global
    hash-count screen (counts merge across chunks exactly like the psum in
    the distributed screen).

Chunked == unchunked is property-tested.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Iterable

import numpy as np

from repro import obs as obs_lib
from repro.core import mining, sparsity
from repro.data.dbmart import DBMart

# dense pair tile: 8B seq + 4B dur + 1B mask, x2 for sort scratch
BYTES_PER_PAIR = 26


@dataclasses.dataclass(frozen=True)
class Chunk:
    start: int
    stop: int
    max_events: int

    @property
    def n_patients(self) -> int:
        return self.stop - self.start


def plan_chunks(nevents: np.ndarray, budget_bytes: int,
                pad_multiple: int = 8, layout: str = "triangular") -> list[Chunk]:
    """Greedy adaptive partitioning under a working-set byte budget."""
    chunks: list[Chunk] = []
    P = len(nevents)
    factor = 0.5 if layout == "triangular" else 1.0
    i = 0
    while i < P:
        e = max(int(nevents[i]), 1)
        e = -(-e // pad_multiple) * pad_multiple
        j = i + 1
        while j < P:
            e2 = max(e, -(-max(int(nevents[j]), 1) // pad_multiple) * pad_multiple)
            cost = (j + 1 - i) * e2 * e2 * BYTES_PER_PAIR * factor
            if cost > budget_bytes and j > i:
                break
            e = e2
            j += 1
        if (j - i) * e * e * BYTES_PER_PAIR * factor > budget_bytes and j - i > 1:
            j -= 1
            e = max(1, -(-int(max(nevents[i:j], default=1)) // pad_multiple) * pad_multiple)
        chunks.append(Chunk(i, j, e))
        i = j
    return chunks


def mine_chunked(db: DBMart, budget_bytes: int = 1 << 28, threshold: int | None = None,
                 codec: str = "bit", backend: str = "jnp",
                 n_buckets_log2: int = 22, fuse_duration: bool = False,
                 bucket_days: int = 30, with_counts: bool = False,
                 metrics=obs_lib.NOOP_REGISTRY) -> dict:
    """In-memory chunked mining (+ optional global hash screen).

    Returns flat numpy arrays {seq, dur, patient, mask} over all chunks
    (concatenated; masks mark real pairs), plus 'keep' when screening and
    'counts' (the merged bucket table) when screening or ``with_counts``.
    """
    chunks = plan_chunks(np.asarray(db.nevents), budget_bytes)
    parts = []
    counts = None
    for ch in chunks:
        sub = db.slice_patients(ch.start, ch.stop, ch.max_events)
        mined = mining.mine(sub.phenx, sub.date, sub.nevents, codec=codec,
                            fuse_duration=fuse_duration,
                            bucket_days=bucket_days, backend=backend,
                            metrics=metrics)
        if threshold is not None or with_counts:
            c = sparsity.local_bucket_counts(mined.seq, mined.mask, n_buckets_log2)
            counts = c if counts is None else sparsity.merge_bucket_counts(counts, c)
        seq, dur, pat, msk = mining.flatten(mined, patient_offset=ch.start)
        parts.append((np.asarray(seq), np.asarray(dur), np.asarray(pat),
                      np.asarray(msk)))
    out = {
        "seq": np.concatenate([p[0] for p in parts]),
        "dur": np.concatenate([p[1] for p in parts]),
        "patient": np.concatenate([p[2] for p in parts]),
        "mask": np.concatenate([p[3] for p in parts]),
    }
    if counts is not None:
        out["counts"] = np.asarray(counts)
    if threshold is not None:
        keep = sparsity.screen_hash_from_counts(
            out["seq"], out["mask"], np.asarray(counts), threshold, n_buckets_log2)
        out["keep"] = np.asarray(keep)
    return out


def mine_fused(db: DBMart, threshold: int, budget_bytes: int = 1 << 28,
               codec: str = "bit", backend: str = "jnp",
               n_buckets_log2: int = 20, fuse_duration: bool = False,
               bucket_days: int = 30, telemetry=obs_lib.NOOP) -> dict:
    """Screen-then-materialize: corpus-free counting, survivors-only pairs.

    Pass 1 builds the global [2^H] bucket table with the fused mine+screen
    kernel (``kernels/tspm_fused``) — no [P, n, n] corpus exists during the
    screen.  Pass 2 re-mines chunk-by-chunk under ``budget_bytes`` and
    compacts each chunk straight to its hash-screen survivors, so the only
    pair allocations are one chunk slab at a time plus the survivors
    themselves.  Byte-identical to mine + hash screen (keeping is per-id,
    so supports and canonical order are preserved).

    ``telemetry`` gets the spans ``fit.pass1``, one ``fit.pass2`` per
    chunk with the phases ``.dispatch`` (enqueue of pairgen and the
    screen), ``.wait`` (until the screen's survivor total is on the host),
    ``.compact`` (``survivors``, ``capacity``: the device compaction) and
    ``.fetch`` (``bytes``: the copy of the compacted buffers) inside, the
    last three announced by ``sparsity.screen_survivors``, and
    ``fit.assemble`` (joining the chunks; a single chunk is not copied);
    and the counters ``fit.pairs`` (real pairs), ``fit.pass1.slots`` (pair
    slots pass 1 computes, padding included), ``fit.pass2.capacity``
    (slots of the compacted buffers) and ``fit.pass2.fetch_bytes`` (their
    bytes) — host integers, from shapes and the survivor totals.

    Returns compacted numpy {seq, dur, patient} (every row real) plus the
    global 'counts' table.
    """
    from repro.kernels.tspm_fused import ops as fused_ops

    tracer, m = telemetry.tracer, telemetry.metrics
    fetch_bytes = m.counter("fit.pass2.fetch_bytes")
    capacity = m.counter("fit.pass2.capacity")
    nevents = np.asarray(db.nevents)
    if telemetry.enabled:
        n = nevents.astype(np.int64)
        m.counter("fit.pairs").inc(int(np.sum(n * (n - 1) // 2)))
    cp = fused_ops.counting_plan(*db.phenx.shape, n_buckets_log2, backend,
                                 fuse_duration)
    m.counter("fit.pass1.slots").inc(cp.slots)
    with tracer.span("fit.pass1", impl="kernel" if cp.use_kernel else "jnp",
                     blocks=cp.n_blocks, H=n_buckets_log2):
        counts = np.asarray(fused_ops.fused_bucket_counts(
            db.phenx, db.date, db.nevents, codec=codec,
            fuse_duration=fuse_duration, bucket_days=bucket_days,
            n_buckets_log2=n_buckets_log2, backend=backend, metrics=m))
    chunks = plan_chunks(nevents, budget_bytes)
    parts = []
    for ch in chunks:
        with tracer.span("fit.pass2", patients=ch.n_patients,
                         E=ch.max_events), \
                _Phases(tracer, fetch_bytes, capacity) as phase:
            phase("dispatch")
            sub = db.slice_patients(ch.start, ch.stop, ch.max_events)
            mined = mining.mine(sub.phenx, sub.date, sub.nevents, codec=codec,
                                fuse_duration=fuse_duration,
                                bucket_days=bucket_days, backend=backend,
                                metrics=m)
            pat = np.arange(ch.start, ch.stop, dtype=np.int32)
            parts.append(sparsity.screen_survivors(
                mined.seq, mined.dur, pat, counts, threshold, n_buckets_log2,
                mask=mined.mask, phase=phase))
            del sub, mined      # the next chunk's planes need the room
    with tracer.span("fit.assemble"):
        cat = lambda k, dt: (parts[0][k] if len(parts) == 1 else
                             np.concatenate([p[k] for p in parts]) if parts
                             else np.zeros(0, dt))
        out = {"seq": cat(0, np.int64), "dur": cat(1, np.int32),
               "patient": cat(2, np.int32), "counts": counts}
    return out


class _Phases:
    """The consecutive phase spans of one pass-2 chunk: ``phase(name,
    **args)`` ends the open phase and begins ``fit.pass2.<name>``, counting
    a ``bytes`` arg on ``fetch_bytes`` and a ``capacity`` arg on
    ``capacity``; leaving the ``with`` block ends the last, also on an
    exception."""

    def __init__(self, tracer, fetch_bytes, capacity):
        self.tracer, self.open = tracer, None
        self.counters = {"bytes": fetch_bytes, "capacity": capacity}

    def __call__(self, name, **args):
        self.close()
        for arg, counter in self.counters.items():
            if arg in args:
                counter.inc(args[arg])
        self.open = self.tracer.begin("fit.pass2." + name, **args)

    def close(self):
        if self.open is not None:
            self.tracer.finish(self.open)
            self.open = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def mine_to_files(db: DBMart, out_dir: str, budget_bytes: int = 1 << 28,
                  codec: str = "bit", backend: str = "jnp",
                  n_buckets_log2: int = 22, fuse_duration: bool = False,
                  bucket_days: int = 30,
                  metrics=obs_lib.NOOP_REGISTRY) -> list[str]:
    """File-based mode: one .npz per chunk + a merged bucket-count table."""
    os.makedirs(out_dir, exist_ok=True)
    for name in os.listdir(out_dir):   # stale spill from a previous cohort
        if name.startswith("chunk_") or name == "bucket_counts.npy":
            os.remove(os.path.join(out_dir, name))
    chunks = plan_chunks(np.asarray(db.nevents), budget_bytes)
    paths = []
    counts = None
    for k, ch in enumerate(chunks):
        sub = db.slice_patients(ch.start, ch.stop, ch.max_events)
        mined = mining.mine(sub.phenx, sub.date, sub.nevents, codec=codec,
                            fuse_duration=fuse_duration,
                            bucket_days=bucket_days, backend=backend,
                            metrics=metrics)
        c = sparsity.local_bucket_counts(mined.seq, mined.mask, n_buckets_log2)
        counts = c if counts is None else sparsity.merge_bucket_counts(counts, c)
        seq, dur, pat, msk = mining.flatten(mined, patient_offset=ch.start)
        path = os.path.join(out_dir, f"chunk_{k:05d}.npz")
        # compact before spilling: only real pairs hit the disk
        msk = np.asarray(msk)
        np.savez(path, seq=np.asarray(seq)[msk], dur=np.asarray(dur)[msk],
                 patient=np.asarray(pat)[msk])
        paths.append(path)
    np.save(os.path.join(out_dir, "bucket_counts.npy"), np.asarray(counts))
    return paths


def load_files(out_dir: str) -> dict:
    """Read a spill directory back unscreened: flat compacted {seq, dur,
    patient} arrays (every row real — spills drop padding) + the merged
    'counts' table.  The screening twin of this loader is
    :func:`screen_files`; the API façade's file engine uses this one so a
    threshold can still be applied (and re-applied) lazily."""
    counts = np.load(os.path.join(out_dir, "bucket_counts.npy"))
    seq, dur, pat = [], [], []
    for name in sorted(os.listdir(out_dir)):
        if not name.startswith("chunk_"):
            continue
        z = np.load(os.path.join(out_dir, name))
        seq.append(z["seq"])
        dur.append(z["dur"])
        pat.append(z["patient"])
    cat = lambda parts, dt: (np.concatenate(parts) if parts
                             else np.zeros(0, dt))
    return {"seq": cat(seq, np.int64), "dur": cat(dur, np.int32),
            "patient": cat(pat, np.int32), "counts": counts}


def screen_files(out_dir: str, threshold: int,
                 n_buckets_log2: int = 22) -> Iterable[dict]:
    """Stream chunks back, applying the merged global count table."""
    counts = np.load(os.path.join(out_dir, "bucket_counts.npy"))
    for name in sorted(os.listdir(out_dir)):
        if not name.startswith("chunk_"):
            continue
        z = np.load(os.path.join(out_dir, name))
        seq = z["seq"]
        keep = np.asarray(sparsity.screen_hash_from_counts(
            seq, np.ones(seq.shape, bool), counts, threshold, n_buckets_log2))
        yield {"seq": seq[keep], "dur": z["dur"][keep],
               "patient": z["patient"][keep]}
