"""Transitive sequence mining (the tSPM/tSPM+ core loop) in JAX.

For every patient, every ordered pair of events ``(i, j)`` with ``i < j`` in
(date-sorted) position order becomes one sequence:

    seq_id   = pack(phenx[i], phenx[j])       (64-bit, see encoding.py)
    duration = date[j] - date[i]              (days; >= 0 by the sort)

yielding exactly ``n(n-1)/2`` sequences per patient with ``n`` events —
the paper's count.  The C++ version grows thread-local vectors; on TPU the
output is a *statically shaped, masked* tensor instead (DESIGN.md §2):

  * ``mine_triangular`` — packed upper-triangular ``[P, T]``, T = E(E-1)/2
    (pure-jnp; memory-lean; what the chunker uses on host);
  * ``mine_dense`` — dense ``[P, E, E]`` tiles (what the Pallas kernel
    produces; MXU/VPU-friendly layout, masked below the diagonal).

``mine(...)`` dispatches to the Pallas kernel (kernels/tspm_pairgen) or the
jnp reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib
from repro.core import encoding


class Mined(NamedTuple):
    """Masked mined sequences.  ``seq`` is int64 (optionally duration-fused),
    ``dur`` int32 days, ``mask`` marks real (non-padding) pairs.
    Patient identity is the leading row index (+ chunk offset)."""

    seq: jax.Array   # [P, T] or [P, E, E] int64
    dur: jax.Array   # int32
    mask: jax.Array  # bool

    @property
    def n_mined(self):
        return jnp.sum(self.mask)


@functools.lru_cache(maxsize=64)
def pair_indices(E: int) -> tuple[np.ndarray, np.ndarray]:
    """Static upper-triangular (i, j) index pair table for E events."""
    i, j = np.triu_indices(E, k=1)
    return i.astype(np.int32), j.astype(np.int32)


def n_pairs(E: int) -> int:
    return E * (E - 1) // 2


def _fuse(seq, dur, fuse_duration: bool, bucket_days: int):
    if not fuse_duration:
        return seq
    return encoding.fuse_duration(seq, encoding.bucket_duration(dur, bucket_days))


@functools.partial(jax.jit, static_argnames=("codec", "fuse_duration", "bucket_days"))
def mine_triangular(
    phenx, date, nevents, codec: str = "bit",
    fuse_duration: bool = False, bucket_days: int = 30,
) -> Mined:
    """Pure-jnp reference mining to packed-triangular [P, T] layout."""
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32)
    E = phenx.shape[-1]
    i_idx, j_idx = pair_indices(E)
    seq = encoding.pack(phenx[..., i_idx], phenx[..., j_idx], codec)
    dur = date[..., j_idx] - date[..., i_idx]
    mask = j_idx[None, :] < nevents[:, None]
    seq = _fuse(seq, dur, fuse_duration, bucket_days)
    return Mined(jnp.where(mask, seq, encoding.SENTINEL), dur * mask, mask)


@functools.partial(jax.jit, static_argnames=("codec", "fuse_duration", "bucket_days"))
def mine_dense(
    phenx, date, nevents, codec: str = "bit",
    fuse_duration: bool = False, bucket_days: int = 30,
) -> Mined:
    """Pure-jnp reference mining to dense [P, E, E] layout (kernel oracle)."""
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32)
    E = phenx.shape[-1]
    seq = encoding.pack(phenx[:, :, None], phenx[:, None, :], codec)
    dur = date[:, None, :] - date[:, :, None]
    ar = jnp.arange(E, dtype=jnp.int32)
    upper = ar[:, None] < ar[None, :]
    mask = upper[None] & (ar[None, None, :] < nevents[:, None, None])
    seq = _fuse(seq, dur, fuse_duration, bucket_days)
    return Mined(jnp.where(mask, seq, encoding.SENTINEL), dur * mask, mask)


def mine(
    phenx, date, nevents, codec: str = "bit", fuse_duration: bool = False,
    bucket_days: int = 30, backend: str = "auto", interpret: bool | None = None,
    metrics=obs_lib.NOOP_REGISTRY,
) -> Mined:
    """Mine transitive sequences.  backend: 'kernel' | 'jnp' | 'auto'.

    'kernel' uses the Pallas pair-generation kernel (dense layout);
    'jnp' the packed-triangular reference.  'auto' uses the kernel on TPU
    and the reference elsewhere.  The implementation that ran is counted
    on ``metrics`` (``kernel.dispatch{op=pairgen}``).
    """
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    obs_lib.count_dispatch(metrics, "pairgen", backend == "kernel", interpret)
    if backend == "kernel":
        from repro.kernels.tspm_pairgen import ops as pairgen_ops

        return pairgen_ops.pairgen(
            phenx, date, nevents, codec=codec, fuse_duration=fuse_duration,
            bucket_days=bucket_days, interpret=interpret,
        )
    return mine_triangular(phenx, date, nevents, codec, fuse_duration, bucket_days)


def flatten(mined: Mined, patient_offset: int = 0):
    """[P, ...] masked layout -> flat (seq, dur, patient, mask) arrays."""
    P = mined.seq.shape[0]
    T = int(np.prod(mined.seq.shape[1:]))
    pat = jnp.broadcast_to(
        (jnp.arange(P, dtype=jnp.int32) + patient_offset)[:, None], (P, T)
    ).reshape(-1)
    return (
        mined.seq.reshape(-1),
        mined.dur.reshape(-1),
        pat,
        mined.mask.reshape(-1),
    )


def count_sequences(nevents) -> jax.Array:
    """Closed-form total: sum_p n_p (n_p - 1) / 2 (the paper's count)."""
    n = jnp.asarray(nevents, jnp.int64)
    return jnp.sum(n * (n - 1) // 2)
