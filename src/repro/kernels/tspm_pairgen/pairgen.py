"""Pallas TPU kernel for transitive pair enumeration (the tSPM+ hot loop).

The C++ algorithm is a thread-per-patient double loop appending to a
thread-local vector.  The TPU-native shape (DESIGN.md §2): a grid over
(patient-block, i-tile, j-tile) computing VMEM tiles of the dense E x E
pair matrix — start/end phenX planes, duration and validity mask — in one
fused pass, so no [P, E, E] intermediates ever round-trip through HBM.

64-bit note: Mosaic's vector int64 support is limited, so the kernel emits
two int32 planes (start, end); the 64-bit key `(start << 24) | end` is
formed by one fused elementwise op in the XLA consumer (ops.py).  The
paper's "numeric representation + cheap bitshifts" insight is preserved;
only the word size of the kernel's store changes.

Tiling: Pb x Ti x Tj output tiles (defaults 8 x 128 x 128) keep the working
set ~1.5 MB in VMEM and the lane dimension at the TPU-native 128.  Tiles
entirely below the diagonal still write (masked) — grid-level skipping of
the lower triangle is a layout change tracked in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import int32_trace


def _pairgen_kernel(nev_ref, xi_ref, di_ref, xj_ref, dj_ref,
                    s_ref, e_ref, dur_ref, msk_ref, *, ti: int, tj: int):
    pi = pl.program_id(1)
    pj = pl.program_id(2)
    gi = pi * ti + jax.lax.broadcasted_iota(jnp.int32, (1, ti, 1), 1)
    gj = pj * tj + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tj), 2)
    nev = nev_ref[:]                     # [Pb, 1]
    mask = (gi < gj) & (gj < nev[:, :, None])   # i < j and j in-bounds
    xi = xi_ref[:][:, :, None]           # [Pb, Ti, 1]
    xj = xj_ref[:][:, None, :]           # [Pb, 1, Tj]
    di = di_ref[:][:, :, None]
    dj = dj_ref[:][:, None, :]
    s_ref[:] = jnp.where(mask, xi, -1)
    e_ref[:] = jnp.where(mask, xj, -1)
    dur_ref[:] = jnp.where(mask, dj - di, 0)
    msk_ref[:] = mask


@int32_trace
@functools.partial(jax.jit, static_argnames=("pb", "ti", "tj", "interpret"))
def pairgen_planes(phenx, date, nevents, pb: int = 8, ti: int = 128,
                   tj: int = 128, interpret: bool = False):
    """Dense pair planes: (start, end, duration, mask), each [P, E, E].

    P must divide by pb and E by ti == tj (ops.py pads).
    """
    P, E = phenx.shape
    assert P % pb == 0 and E % ti == 0 and E % tj == 0, (P, E, pb, ti, tj)
    grid = (P // pb, E // ti, E // tj)
    nev2 = nevents.reshape(P, 1).astype(jnp.int32)
    kernel = functools.partial(_pairgen_kernel, ti=ti, tj=tj)
    out_shape = [
        jax.ShapeDtypeStruct((P, E, E), jnp.int32),   # start plane
        jax.ShapeDtypeStruct((P, E, E), jnp.int32),   # end plane
        jax.ShapeDtypeStruct((P, E, E), jnp.int32),   # duration (days)
        jax.ShapeDtypeStruct((P, E, E), jnp.bool_),   # validity
    ]
    row_i = pl.BlockSpec((pb, ti), lambda p, i, j: (p, i))
    row_j = pl.BlockSpec((pb, tj), lambda p, i, j: (p, j))
    tile = pl.BlockSpec((pb, ti, tj), lambda p, i, j: (p, i, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pb, 1), lambda p, i, j: (p, 0)),  # nevents
            row_i,  # phenx_i
            row_i,  # date_i
            row_j,  # phenx_j
            row_j,  # date_j
        ],
        out_specs=[tile, tile, tile, tile],
        out_shape=out_shape,
        interpret=interpret,
    )(nev2, phenx.astype(jnp.int32), date.astype(jnp.int32),
      phenx.astype(jnp.int32), date.astype(jnp.int32))
