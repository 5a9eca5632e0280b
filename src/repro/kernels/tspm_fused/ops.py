"""Dispatching wrapper: pad -> fused Pallas counting -> [2^H] bucket table.

Mirrors tspm_pairgen/ops.py (padding recipe, interpret default).  Tile
sizes come from
``analysis.roofline.mining_tile_plan`` — an 8-patient block by default,
measured autotune rows when ``benchmarks/mining_fused.py`` hands them in.
"""
from __future__ import annotations

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


def _kernel_block(phenx, nevents, codec, n_buckets_log2, plan, pb, tile,
                  interpret):
    P, E = phenx.shape
    tile = int(tile or plan.ti)
    pb = min(int(pb or plan.pb), P)
    t = min(tile, max(128, 1 << int(np.ceil(np.log2(max(E, 1))))))
    x = _pad_to(phenx, t, 1)
    x = _pad_to(x, pb, 0)
    nev = _pad_to(nevents, pb, 0)      # padded patients: nevents == 0
    return _k.fused_table(
        x, nev, n_buckets_log2=n_buckets_log2, codec=codec, pb=pb, ti=t,
        tj=t, interpret=interpret)


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
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32).reshape(-1)
    H = n_buckets_log2
    P, E = phenx.shape if phenx.ndim == 2 else (0, 0)
    if P == 0 or E == 0:
        # zero-width-slab guard (mirrors tspm_delta/ops.py): no events,
        # empty table
        return jnp.zeros(1 << H, jnp.int32)
    plan = roofline.mining_tile_plan(E, H)
    blk = int(block_patients or plan.block_patients)
    use_kernel = (backend == "kernel" and not fuse_duration
                  and H <= KERNEL_MAX_LOG2)
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
