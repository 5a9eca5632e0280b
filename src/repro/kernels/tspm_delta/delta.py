"""Pallas TPU kernel for *delta* pair enumeration (streaming tSPM+).

Batch mining (kernels/tspm_pairgen) fills the full dense E x E pair matrix
per patient; when a patient's history grows by d new events, only the last
d columns of that matrix are new.  This kernel computes exactly that slab:

    output planes [P, E, D]   (i = any stored event, j = delta event)

with column ``j`` standing for global event position ``n_old[p] + j`` —
the i-axis spans the *updated* history planes (which already contain the
appended delta at positions ``n_old .. n_old + n_new``), so new-x-new pairs
fall out of the same mask ``i < n_old + j`` with no special casing.  The
union of these slabs over all ticks is the batch pair set (property-tested
in tests/test_stream.py).

Tiling mirrors tspm_pairgen (Pb x Ti x Tj tiles, lane dim 128), but the
j-grid covers only the delta window: a tick touching d events of an
n-event history costs O(n * d) pairs instead of the O(n^2) re-mine.

64-bit note (same as pairgen): the kernel emits int32 start/end planes;
the 64-bit packed key is formed by the XLA consumer in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import int32_trace


def _delta_kernel(nold_ref, nnew_ref, xi_ref, di_ref, xj_ref, dj_ref,
                  s_ref, e_ref, dur_ref, msk_ref, *, ti: int, tj: int):
    pi = pl.program_id(1)
    pj = pl.program_id(2)
    gi = pi * ti + jax.lax.broadcasted_iota(jnp.int32, (1, ti, 1), 1)
    gj = pj * tj + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tj), 2)
    n_old = nold_ref[:][:, :, None]          # [Pb, 1, 1]
    n_new = nnew_ref[:][:, :, None]
    # i precedes the delta event's global position; j inside the delta window
    mask = (gi < n_old + gj) & (gj < n_new)
    xi = xi_ref[:][:, :, None]               # [Pb, Ti, 1] stored history
    xj = xj_ref[:][:, None, :]               # [Pb, 1, Tj] delta events
    di = di_ref[:][:, :, None]
    dj = dj_ref[:][:, None, :]
    s_ref[:] = jnp.where(mask, xi, -1)
    e_ref[:] = jnp.where(mask, xj, -1)
    dur_ref[:] = jnp.where(mask, dj - di, 0)
    msk_ref[:] = mask


@int32_trace
@functools.partial(jax.jit, static_argnames=("pb", "ti", "tj", "interpret"))
def delta_planes(phenx, date, n_old, n_new, new_phenx, new_date,
                 pb: int = 8, ti: int = 128, tj: int = 128,
                 interpret: bool = False):
    """Delta pair planes: (start, end, duration, mask), each [P, E, D].

    ``phenx``/``date`` are the updated [P, E] history planes (delta already
    appended at the per-patient cursors); ``new_phenx``/``new_date`` are the
    [P, D] delta events aligned at column 0.  P must divide by pb, E by ti,
    D by tj (ops.py pads).
    """
    P, E = phenx.shape
    D = new_phenx.shape[1]
    assert P % pb == 0 and E % ti == 0 and D % tj == 0, (P, E, D, pb, ti, tj)
    grid = (P // pb, E // ti, D // tj)
    nold2 = n_old.reshape(P, 1).astype(jnp.int32)
    nnew2 = n_new.reshape(P, 1).astype(jnp.int32)
    kernel = functools.partial(_delta_kernel, ti=ti, tj=tj)
    out_shape = [
        jax.ShapeDtypeStruct((P, E, D), jnp.int32),   # start plane
        jax.ShapeDtypeStruct((P, E, D), jnp.int32),   # end plane
        jax.ShapeDtypeStruct((P, E, D), jnp.int32),   # duration (days)
        jax.ShapeDtypeStruct((P, E, D), jnp.bool_),   # validity
    ]
    scalar = pl.BlockSpec((pb, 1), lambda p, i, j: (p, 0))
    row_i = pl.BlockSpec((pb, ti), lambda p, i, j: (p, i))
    row_j = pl.BlockSpec((pb, tj), lambda p, i, j: (p, j))
    tile = pl.BlockSpec((pb, ti, tj), lambda p, i, j: (p, i, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scalar, scalar, row_i, row_i, row_j, row_j],
        out_specs=[tile, tile, tile, tile],
        out_shape=out_shape,
        interpret=interpret,
    )(nold2, nnew2, phenx.astype(jnp.int32), date.astype(jnp.int32),
      new_phenx.astype(jnp.int32), new_date.astype(jnp.int32))
