"""Pallas TPU kernel: fused mine+screen — corpus-free support counting.

The materializing path writes the dense [P, E, E] pair corpus, then sorts
each patient row to dedup and scatter-adds hashed ids into the [2^H]
screen table (``sparsity.local_bucket_counts``).  This kernel produces the
*same table* without ever writing a pair: each Pb x Ti x Tj tile (the
tiling shared with tspm_pairgen / tspm_delta) decides in-register which of
its pairs is the patient's first contribution of that (start, end) value
pair, hashes those, and counts them into a VMEM-resident bucket table with
one-hot matmuls on the MXU (TPU has no vector scatter).

Dedup without the row sort: pair (i, j) is its patient's first occurrence
of the value pair (x_i, x_j) iff

    i < j < nevents
    and no k < i has x_k == x_i          (i is the value's first start)
    and max{k < j : x_k == x_j} <= i     (no closer end occurrence)

which keeps exactly one (i, j) per distinct present (a, b) — including
a == b, where it keeps (first, second) occurrence — so the counts match
the sort-based dedup bucket for bucket.  The lookbacks need the patient's
*full* event row (not just the tile), which rides in as one extra
[Pb, E] operand; dates are not needed at all (unfused ids are
duration-free, and validity is positional).

64-bit note: ids are int64 but Mosaic's vector int64 support is limited
(see tspm_pairgen).  The kernel never forms the id: the multiply-shift
hash is *linear* in the packed fields mod 2^64 —

    hash(pack(s, e)) = top_H((s * K * codec_mult + e * K) mod 2^64)

— so it evaluates the hash directly from the int32 phenX planes with a
13-bit-limb modular multiply: fields split into two 13-bit limbs,
constants into five, partial products < 2^26 and column sums < 2^29 stay
int32-exact, one carry propagation, then the top H bits are stitched from
the limbs (H <= 24 keeps every stitch shift in-range).

Histogram: the [2^H] table lives in VMEM as [rows, 128] (bucket h at
row h >> 7, lane h & 127).  For each row of the pair tile, a bf16 one-hot
of h >> 7 ([rows, Tj]) times the transposed one-hot of h & 127
([128, Tj]) adds every pair to its cell; dead pairs carry h = -1 and
match no row.  Pairs sit on lanes in both operands, so Mosaic needs no
lane-to-sublane relayout, and the MXU does the O(pairs x 2^H) work the
compare-and-reduce form would put on the VPU.  Eight rows are stacked
per matmul (K = 8 Tj).

Grid: (patient-blocks, i-tiles, j-tiles), every step accumulating into
the one table block.  The kernel body is traced with x64 off
(``kernels.util.int32_trace``): every literal and reduction is int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import encoding, sparsity
from repro.kernels.util import int32_trace

LIMB_BITS = 13
LIMB_MASK = (1 << LIMB_BITS) - 1
N_LIMBS = 5                       # 4 * 13 + 12 = 64 bits
_M64 = (1 << 64) - 1
MAX_BUCKETS_LOG2 = 24             # stitch shifts stay < 13 bits for H <= 24
LANE_BITS = 7
TABLE_LANES = 1 << LANE_BITS      # bucket table laid out [rows, 128]


def _limbs(c: int) -> tuple[int, ...]:
    return tuple((c >> (LIMB_BITS * t)) & LIMB_MASK for t in range(N_LIMBS))


def hash_constants(codec: str = "bit", fused_ids: bool = False):
    """Per-field multiply-shift constants mod 2^64 (host-side ints).

    hash(id) depends linearly on (start, end[, bucket]) because pack /
    fuse_duration are sums of disjoint shifted fields:

        id = start * mult * 2^shift + end * 2^shift + bucket
    """
    mult = (1 << encoding.BIT_SHIFT) if codec == "bit" else encoding.PAPER_SHIFT
    shift = encoding.DUR_BITS if fused_ids else 0
    k = sparsity.HASH_MULT
    c_start = (k * mult << shift) & _M64
    c_end = (k << shift) & _M64
    c_bucket = k & _M64
    return c_start, c_end, c_bucket


def hash_parts(start, end, bucket=None, *, codec: str = "bit",
               n_buckets_log2: int = 20, fused_ids: bool = False):
    """``sparsity.hash_bucket(pack(start, end))`` without forming the id.

    int32-only 13-bit-limb evaluation of (start*C1 + end*C2 [+ bucket*K])
    mod 2^64, returning the top ``n_buckets_log2`` bits as int32.  Inputs
    broadcast (the kernel passes [Pb, Ti, 1] x [Pb, 1, Tj]); fields must
    be < 2^26 (vocab < 2^24, buckets < 2^15 — both hold by construction).
    """
    H = n_buckets_log2
    assert 1 <= H <= MAX_BUCKETS_LOG2, H
    c_start, c_end, c_bucket = hash_constants(codec, fused_ids)
    terms = [(start, _limbs(c_start)), (end, _limbs(c_end))]
    if fused_ids:
        assert bucket is not None
        terms.append((bucket, _limbs(c_bucket)))

    cols = [0] * N_LIMBS
    for x, cl in terms:
        x = jnp.asarray(x, jnp.int32)
        x0 = x & LIMB_MASK
        x1 = x >> LIMB_BITS
        for t in range(N_LIMBS):
            if not cl[t]:
                continue
            cols[t] = cols[t] + x0 * cl[t]
            if t + 1 < N_LIMBS:          # column 5 is bit >= 65: 0 mod 2^64
                cols[t + 1] = cols[t + 1] + x1 * cl[t]

    limbs = []
    carry = 0
    for t in range(N_LIMBS):
        tot = cols[t] + carry
        limbs.append(tot & LIMB_MASK)
        carry = tot >> LIMB_BITS
    limbs[-1] = limbs[-1] & 0xFFF        # top limb is 12 bits; drop bit 64+

    sh = 64 - H
    h = 0
    for t in range(N_LIMBS):
        lo = LIMB_BITS * t
        width = 12 if t == N_LIMBS - 1 else LIMB_BITS
        if lo + width <= sh:
            continue
        h = h | (limbs[t] << (lo - sh)) if lo >= sh \
            else h | (limbs[t] >> (sh - lo))
    return jnp.asarray(h & ((1 << H) - 1), jnp.int32)


def table_rows(n_buckets_log2: int) -> int:
    """Rows of the kernel's [rows, 128] bucket table: bucket ``h`` sits at
    (h >> 7, h & 127); at least 8 rows keep the block sublane-aligned."""
    return max(8, (1 << n_buckets_log2) // TABLE_LANES)


def _fused_kernel(nev_ref, xi_ref, xj_ref, xr_ref, out_ref, h_scr, *,
                  ti: int, tj: int, codec: str, n_buckets_log2: int):
    pi = pl.program_id(1)
    pj = pl.program_id(2)
    gi = pi * ti + jax.lax.broadcasted_iota(jnp.int32, (1, ti, 1), 1)
    gj = pj * tj + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tj), 2)
    nev = nev_ref[:]                                    # [Pb, 1]
    valid = (gi < gj) & (gj < nev[:, :, None])

    xi = xi_ref[:]                                      # [Pb, Ti]
    xj = xj_ref[:]                                      # [Pb, Tj]
    xr = xr_ref[:]                                      # [Pb, E] full row
    E = xr.shape[1]
    k = jax.lax.broadcasted_iota(jnp.int32, (1, 1, E), 2)

    # lookbacks stay on real events: k < gi < nevents for any valid pair,
    # so padded positions are never consulted
    eq_i = (xr[:, None, :] == xi[:, :, None]) & (k < gi)       # [Pb, Ti, E]
    # int32 reductions: Mosaic reduces no bool vectors
    first_start = jnp.max(eq_i.astype(jnp.int32), axis=2) == 0  # [Pb, Ti]
    gj_col = pj * tj + jax.lax.broadcasted_iota(jnp.int32, (1, tj, 1), 1)
    eq_j = (xr[:, None, :] == xj[:, :, None]) & (k < gj_col)   # [Pb, Tj, E]
    prev_end = jnp.max(jnp.where(eq_j, k, -1), axis=2)         # [Pb, Tj]

    first = valid & first_start[:, :, None] & (prev_end[:, None, :] <= gi)
    h = hash_parts(xi[:, :, None], xj[:, None, :], codec=codec,
                   n_buckets_log2=n_buckets_log2)
    h = jnp.where(first, h, -1)          # dead pairs match no table row
    pb = h.shape[0]
    for p in range(pb):
        h_scr[p * ti:(p + 1) * ti, :] = h[p]

    # histogram on the MXU: for a row of pairs, one-hot(h >> 7) [rows, Tj]
    # times one-hot(h & 127)^T [Tj, 128] counts every pair into its
    # (row, lane) cell of the table.  The one-hots are exact in bf16 and a
    # grid step adds at most Pb*Ti*Tj < 2^24 to a cell, so the f32
    # accumulator is exact too.
    rows = out_ref.shape[0]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, tj), 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (TABLE_LANES, tj), 0)

    def body(g, acc):
        blk = h_scr[pl.ds(pl.multiple_of(g * 8, 8), 8), :]    # [8, Tj]
        hot_r, hot_l = [], []
        for r in range(8):
            hr = blk[r:r + 1, :]
            hot_r.append((hr >> LANE_BITS == row_id).astype(jnp.bfloat16))
            hot_l.append(((hr & (TABLE_LANES - 1)) == lane_id)
                         .astype(jnp.bfloat16))
        a = jnp.concatenate(hot_r, axis=1)                     # [rows, 8Tj]
        b = jnp.concatenate(hot_l, axis=1)                     # [128, 8Tj]
        return acc + jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    partial = jax.lax.fori_loop(
        0, pb * ti // 8, body, jnp.zeros((rows, TABLE_LANES), jnp.float32))

    @pl.when((pl.program_id(0) == 0) & (pi == 0) & (pj == 0))
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += partial.astype(jnp.int32)


@int32_trace
@functools.partial(jax.jit, static_argnames=(
    "n_buckets_log2", "codec", "pb", "ti", "tj", "interpret"))
def fused_table(phenx, nevents, n_buckets_log2: int, codec: str = "bit",
                pb: int = 8, ti: int = 128, tj: int = 128,
                interpret: bool = False):
    """[2^H] int32 bucket counts of a padded [P, E] cohort (== the table
    ``sparsity.local_bucket_counts`` builds from the materialized corpus).

    P must divide by pb and E by ti == tj (ops.py pads).
    """
    P, E = phenx.shape
    B = 1 << n_buckets_log2
    assert P % pb == 0 and E % ti == 0 and E % tj == 0, (P, E, pb, ti, tj)
    assert ti % 8 == 0, ti
    rows = table_rows(n_buckets_log2)
    grid = (P // pb, E // ti, E // tj)
    nev2 = nevents.reshape(P, 1).astype(jnp.int32)
    x = phenx.astype(jnp.int32)
    kernel = functools.partial(
        _fused_kernel, ti=ti, tj=tj, codec=codec,
        n_buckets_log2=n_buckets_log2)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pb, 1), lambda p, i, j: (p, 0)),   # nevents
            pl.BlockSpec((pb, ti), lambda p, i, j: (p, i)),  # phenx_i
            pl.BlockSpec((pb, tj), lambda p, i, j: (p, j)),  # phenx_j
            pl.BlockSpec((pb, E), lambda p, i, j: (p, 0)),   # full row
        ],
        out_specs=pl.BlockSpec((rows, TABLE_LANES), lambda p, i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, TABLE_LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((pb * ti, tj), jnp.int32)],
        interpret=interpret,
    )(nev2, x, x, x)
    return out.reshape(-1)[:B]
