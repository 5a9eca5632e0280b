"""Delta mining: pair only *new* events against stored history.

The batch miner (core/mining) fills the dense ``[P, E, E]`` pair matrix;
after appending ``d`` events to an ``n``-event history only the last ``d``
columns are new, so the streaming hot loop computes the ``[P, E, D]`` slab

    seq[p, i, j] = pack(phenx[p, i], new_phenx[p, j])
    valid iff     i < n_old[p] + j   and   j < n_new[p]

where the i-axis spans the *updated* history planes (delta already written
at the cursors) — new-x-new pairs are the ``i >= n_old`` rows of the same
slab.  ``delta_mine`` dispatches between the pure-jnp reference below and
the Pallas kernel (kernels/tspm_delta), mirroring ``mining.mine``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs as obs_lib
from repro.core import encoding
from repro.core.mining import Mined
from repro.kernels.tspm_delta.ref import delta_planes_ref


@functools.partial(jax.jit, static_argnames=("codec", "fuse_duration", "bucket_days"))
def delta_mine_jnp(
    phenx, date, n_old, n_new, new_phenx, new_date, codec: str = "bit",
    fuse_duration: bool = False, bucket_days: int = 30,
) -> Mined:
    """Pure-jnp reference delta mining to the dense [P, E, D] slab."""
    s, e, dur, mask = delta_planes_ref(
        phenx, date, n_old, n_new, new_phenx, new_date)
    seq = encoding.pack(jnp.maximum(s, 0), jnp.maximum(e, 0), codec)
    if fuse_duration:
        seq = encoding.fuse_duration(
            seq, encoding.bucket_duration(dur, bucket_days))
    return Mined(jnp.where(mask, seq, encoding.SENTINEL), dur, mask)


def delta_mine(
    phenx, date, n_old, n_new, new_phenx, new_date, codec: str = "bit",
    fuse_duration: bool = False, bucket_days: int = 30,
    backend: str = "auto", interpret: bool | None = None,
    metrics=obs_lib.NOOP_REGISTRY, labels: dict | None = None,
) -> Mined:
    """Mine the new-pair slab.  backend: 'kernel' | 'jnp' | 'auto'.  The
    implementation that ran is counted on ``metrics``
    (``kernel.dispatch{op=delta}`` plus ``labels``)."""
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    obs_lib.count_dispatch(metrics, "delta", backend == "kernel", interpret,
                           labels)
    if backend == "kernel":
        from repro.kernels.tspm_delta import ops as delta_ops

        return delta_ops.delta_pairgen(
            phenx, date, n_old, n_new, new_phenx, new_date, codec=codec,
            fuse_duration=fuse_duration, bucket_days=bucket_days,
            interpret=interpret,
        )
    return delta_mine_jnp(phenx, date, n_old, n_new, new_phenx, new_date,
                          codec, fuse_duration, bucket_days)


def count_delta_pairs(n_old, n_new) -> jax.Array:
    """Closed-form new-pair count: sum_p [ d*n_old + d(d-1)/2 ] — the
    O(delta * n) streaming cost (vs the batch n(n-1)/2 re-mine)."""
    n_old = jnp.asarray(n_old, jnp.int64)
    d = jnp.asarray(n_new, jnp.int64)
    return jnp.sum(d * n_old + d * (d - 1) // 2)
