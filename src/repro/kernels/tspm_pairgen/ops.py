"""jit'd wrapper: pad -> Pallas pairgen -> 64-bit packed Mined (dense)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.core.mining import Mined
from repro.kernels.tspm_pairgen import pairgen as _k
from repro.kernels.util import pad_to as _pad_to


def pairgen(phenx, date, nevents, codec: str = "bit",
            fuse_duration: bool = False, bucket_days: int = 30,
            pb: int = 8, tile: int = 128, interpret: bool | None = None) -> Mined:
    """Kernel-backed mining to the dense [P, E, E] layout (== mine_dense)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32)
    P, E = phenx.shape
    t = min(tile, max(128, 1 << int(np.ceil(np.log2(max(E, 1))))))
    phenx_p = _pad_to(phenx, t, 1)
    date_p = _pad_to(date, t, 1)
    # padded patients have nevents == 0, so none of their pairs is valid
    pbb = min(pb, P)
    phenx_p = _pad_to(phenx_p, pbb, 0)
    date_p = _pad_to(date_p, pbb, 0)
    nev_p = _pad_to(nevents, pbb, 0)

    s, e, dur, mask = _k.pairgen_planes(
        phenx_p, date_p, nev_p, pb=pbb, ti=t, tj=t, interpret=interpret)
    s = s[:P, :E, :E]
    e = e[:P, :E, :E]
    dur = dur[:P, :E, :E]
    mask = mask[:P, :E, :E]

    seq = encoding.pack(jnp.maximum(s, 0), jnp.maximum(e, 0), codec)
    if fuse_duration:
        seq = encoding.fuse_duration(
            seq, encoding.bucket_duration(dur, bucket_days))
    seq = jnp.where(mask, seq, encoding.SENTINEL)
    return Mined(seq, dur, mask)
