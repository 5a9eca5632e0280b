"""Dispatching wrapper: pad -> fused Pallas counting -> [2^H] bucket table.

Mirrors tspm_pairgen/ops.py (padding recipe, interpret default).  Tile
sizes come from
``analysis.roofline.mining_tile_plan`` — an 8-patient block by default,
measured autotune rows when ``benchmarks/mining_fused.py`` hands them in.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib
from repro.analysis import roofline
from repro.kernels.tspm_fused import fused as _k
from repro.kernels.tspm_fused import ref as _ref
from repro.kernels.util import pad_to as _pad_to

# largest bucket table the kernel keeps in VMEM: its one-hot matmul
# histogram costs O(pairs * 2^H) MXU work, and past 2^14 buckets the jnp
# block reference wins
KERNEL_MAX_LOG2 = 14


def _kernel_dims(P, E, plan, pb, tile) -> tuple[int, int]:
    """(patient rows, lane tile) the kernel pads a [P, E] block to."""
    tile = int(tile or plan.ti)
    pb = min(int(pb or plan.pb), P)
    return pb, min(tile, max(128, 1 << int(np.ceil(np.log2(max(E, 1))))))


def _kernel_block(phenx, nevents, codec, n_buckets_log2, plan, pb, tile,
                  interpret):
    P, E = phenx.shape
    pb, t = _kernel_dims(P, E, plan, pb, tile)
    x = _pad_to(phenx, t, 1)
    x = _pad_to(x, pb, 0)
    nev = _pad_to(nevents, pb, 0)      # padded patients: nevents == 0
    return _k.fused_table(
        x, nev, n_buckets_log2=n_buckets_log2, codec=codec, pb=pb, ti=t,
        tj=t, interpret=interpret)


class CountingPlan(NamedTuple):
    """How :func:`fused_bucket_counts` covers a [P, E] cohort."""

    use_kernel: bool
    tiles: roofline.MiningTilePlan
    block_patients: int
    n_blocks: int
    slots: int          # pair slots the blocks compute, padding included


def counting_plan(P: int, E: int, n_buckets_log2: int = 20,
                  backend: str = "auto", fuse_duration: bool = False,
                  block_patients: int | None = None, pb: int | None = None,
                  tile: int | None = None) -> CountingPlan:
    """The implementation, patient blocks and computed pair slots of one
    counting pass (host arithmetic on shapes only).  A block of p patients
    computes p x E x E slots on the jnp fallback and its kernel-padded
    planes on the kernel."""
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    plan = roofline.mining_tile_plan(E, n_buckets_log2)
    blk = int(block_patients or plan.block_patients)
    use_kernel = (backend == "kernel" and not fuse_duration
                  and n_buckets_log2 <= KERNEL_MAX_LOG2)
    slots = 0
    for s in range(0, P, blk):
        p = min(blk, P - s)
        if use_kernel:
            rows, t = _kernel_dims(p, E, plan, pb, tile)
            p, e = -(-p // rows) * rows, -(-E // t) * t
            slots += p * e * e
        else:
            slots += p * E * E
    return CountingPlan(use_kernel, plan, blk, -(-P // blk), slots)


def fused_bucket_counts(phenx, date, nevents, codec: str = "bit",
                        fuse_duration: bool = False, bucket_days: int = 30,
                        n_buckets_log2: int = 20, backend: str = "auto",
                        block_patients: int | None = None,
                        pb: int | None = None, tile: int | None = None,
                        interpret: bool | None = None,
                        metrics=obs_lib.NOOP_REGISTRY):
    """Corpus-free [2^H] bucket counts == local_bucket_counts(mine(...)).

    backend: 'kernel' | 'jnp' | 'auto' ('auto' = kernel on TPU, jnp ref
    elsewhere, as mining.mine).  The Pallas kernel covers unfused ids with
    H <= KERNEL_MAX_LOG2; fused-duration ids (whose cross-row dedup does
    not decompose over tiles) and larger tables take the blocked jnp
    reference — still corpus-free at cohort level (peak is one
    [block, E, E] slab, never [P, E, E]).  Which of the two ran is counted
    on ``metrics`` (``kernel.dispatch{op=fused}``, see ``obs.dispatch``).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32).reshape(-1)
    H = n_buckets_log2
    P, E = phenx.shape if phenx.ndim == 2 else (0, 0)
    if P == 0 or E == 0:
        # zero-width-slab guard (mirrors tspm_delta/ops.py): no events,
        # empty table
        return jnp.zeros(1 << H, jnp.int32)
    cp = counting_plan(P, E, H, backend, fuse_duration, block_patients, pb,
                       tile)
    use_kernel, plan, blk = cp.use_kernel, cp.tiles, cp.block_patients
    obs_lib.count_dispatch(metrics, "fused", use_kernel, interpret)
    counts = jnp.zeros(1 << H, jnp.int32)
    for s in range(0, P, blk):
        e = s + blk
        if use_kernel:
            part = _kernel_block(phenx[s:e], nevents[s:e], codec, H, plan,
                                 pb, tile, interpret)
        else:
            part = _ref.block_bucket_counts(
                phenx[s:e], date[s:e], nevents[s:e], codec, fuse_duration,
                bucket_days, H)
        counts = counts + part
    return counts
