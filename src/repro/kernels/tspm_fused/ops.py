"""Dispatching wrapper: pad -> fused Pallas counting -> [2^H] bucket table.

Mirrors tspm_pairgen/ops.py (padding recipe, interpret default).  Tile
sizes come from
``analysis.roofline.mining_tile_plan`` — an 8-patient block by default,
measured autotune rows when ``benchmarks/mining_fused.py`` hands them in.
"""
from __future__ import annotations

import functools
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


#: most width groups a counting plan cuts a cohort into; a group's blocks
#: share one shape, so one fit runs at most this many pass-1 programs
MAX_WIDTHS = 4
#: the jnp fallback pads a group's width to a multiple of one sublane
JNP_WIDTH_MULTIPLE = 8
#: what one more width group costs, in pair slots: its program to compile
#: and its blocks to dispatch each fit.  2^16 slots are ~1 ms of pass-1
#: device time on a v5e (10-23 ns a slot), so a group is cut off only
#: where it saves more than that, and a small cohort keeps one width
GROUP_SLOTS = 1 << 16


class CountingBlock(NamedTuple):
    """One patient block of a counting pass."""

    patients: np.ndarray    # cohort rows, in length order; P is empty
    width: int              # events each row is cut or padded to
    tiles: roofline.MiningTilePlan     # the tile plan at ``width``


class CountingPlan(NamedTuple):
    """How :func:`fused_bucket_counts` covers a cohort."""

    use_kernel: bool
    blocks: tuple[CountingBlock, ...]
    widths: tuple[int, ...]     # the width groups' widths, ascending
    slots: int          # pair slots the blocks compute, padding included


def _padded_widths(n: np.ndarray, use_kernel: bool, ti: int) -> np.ndarray:
    """The width each history of ``n`` events pads to: a multiple of
    JNP_WIDTH_MULTIPLE on the jnp fallback, the lane tile that
    :func:`_kernel_dims` pads it to on the kernel; never below one step."""
    if use_kernel:
        pow2 = 1 << np.ceil(np.log2(np.maximum(n, 1))).astype(np.int64)
        step = np.minimum(ti, np.maximum(128, pow2))
    else:
        step = np.int64(JNP_WIDTH_MULTIPLE)
    return np.maximum(-(-n // step), 1) * step


def _width_groups(widths: np.ndarray) -> list[tuple[int, int]]:
    """Cut ascending ``widths`` into at most MAX_WIDTHS runs, each padded to
    its widest, with the least sum of patients x width^2 plus GROUP_SLOTS a
    run: an exact dynamic program over the distinct widths.  Returns
    (patients, width) per run, ascending; the fewest runs that reach the
    least sum."""
    d, c = np.unique(widths, return_counts=True)
    if not len(d):
        return []
    cum = np.concatenate([[0], np.cumsum(c)])      # patients below d[j]
    sq = d.astype(np.int64) ** 2
    m = len(d)
    # cost[k][j]: least sum covering d[:j] with k + 1 runs; start[k][j]:
    # where the last of those runs begins
    cost = [cum[1:] * sq]
    start = [np.zeros(m, np.int64)]
    for k in range(1, min(MAX_WIDTHS, m)):
        prev = cost[-1]
        best = np.full(m, np.iinfo(np.int64).max)
        arg = np.zeros(m, np.int64)
        for j in range(k, m):
            # the last run covers d[i + 1 : j + 1]
            i = np.arange(k - 1, j)
            tot = prev[i] + (cum[j + 1] - cum[i + 1]) * sq[j]
            a = int(np.argmin(tot))
            best[j], arg[j] = tot[a], i[a] + 1
        cost.append(best)
        start.append(arg)
    k = min(range(len(cost)),
            key=lambda k: (cost[k][-1] + k * GROUP_SLOTS, k))
    runs, j = [], m
    while j > 0:
        i = int(start[k][j - 1]) if k else 0
        runs.append((int(cum[j] - cum[i]), int(d[j - 1])))
        j, k = i, k - 1
    return runs[::-1]


def _block_size(count: int, blk: int) -> int:
    """Patients a block of a ``count``-patient group takes: at most ``blk``
    and at least half of it, leaving the fewest empty rows in the group's
    last block (the largest such size)."""
    hi = min(count, blk)
    return min(range(hi, (hi - 1) // 2, -1), key=lambda b: -count % b)


def counting_plan(nevents, n_buckets_log2: int = 20, backend: str = "auto",
                  fuse_duration: bool = False,
                  block_patients: int | None = None, pb: int | None = None,
                  tile: int | None = None) -> CountingPlan:
    """The implementation, patient blocks and computed pair slots of one
    counting pass (host arithmetic on the events per patient only).

    The patients are sorted by length (stably) and cut into at most
    MAX_WIDTHS width groups, each padded to its own longest history
    (:func:`_width_groups`), so a few long histories no longer pad every
    block.  A group is cut into blocks of one shape, of at most its width's
    tile-plan ``block_patients`` (:func:`_block_size`); its last block is
    filled up with row P, which stands for an empty patient.  A block of p
    rows at width w computes p x w x w slots on the jnp fallback and its
    kernel-padded planes on the kernel.  The table is a sum over patients,
    so any such partition counts the same."""
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    use_kernel = (backend == "kernel" and not fuse_duration
                  and n_buckets_log2 <= KERNEL_MAX_LOG2)
    n = np.asarray(nevents, np.int64).reshape(-1)
    order = np.argsort(n, kind="stable")
    ti = int(tile or roofline.mining_tile_plan(
        int(n.max(initial=1)), n_buckets_log2).ti)
    blocks, widths, slots, s = [], [], 0, 0
    for count, width in _width_groups(
            _padded_widths(n[order], use_kernel, ti)):
        tiles = roofline.mining_tile_plan(width, n_buckets_log2)
        p = _block_size(count, int(block_patients or tiles.block_patients))
        group = np.full(-(-count // p) * p, len(n))
        group[:count] = order[s:s + count]
        blocks += [CountingBlock(rows, width, tiles)
                   for rows in group.reshape(-1, p)]
        rows = p
        if use_kernel:      # the kernel pads a block's rows to its pb
            pad = _kernel_dims(p, width, tiles, pb, tile)[0]
            rows = -(-p // pad) * pad
        slots += len(group) // p * rows * width * width
        widths.append(width)
        s += count
    return CountingPlan(use_kernel, tuple(blocks), tuple(widths), slots)


def _take(x, patients):
    """Rows ``patients`` of ``x``; row P, the empty patient, reads as
    zeros."""
    return x.at[patients].get(mode="fill", fill_value=0)


def _rows(x, patients, width):
    """Rows ``patients`` of [P, E] ``x``, cut or zero-padded to ``width``."""
    x = _take(x, patients)
    if width <= x.shape[1]:
        return x[:, :width]
    return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))


@functools.partial(jax.jit, static_argnames=(
    "width", "codec", "fuse_duration", "bucket_days", "n_buckets_log2"))
def _jnp_block_counts(phenx, date, nevents, patients, width, codec,
                      fuse_duration, bucket_days, n_buckets_log2):
    """:func:`ref.block_bucket_counts` of one block as one program per
    (patients, width) shape; eager, it dispatches about ten."""
    return _ref.block_bucket_counts(
        _rows(phenx, patients, width), _rows(date, patients, width),
        _take(nevents, patients), codec, fuse_duration, bucket_days,
        n_buckets_log2)


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
    [block, w, w] slab at its group's width w, never [P, E, E]).  The
    blocks follow :func:`counting_plan`.  Which of the two ran is counted
    on ``metrics`` (``kernel.dispatch{op=fused}``, see ``obs.dispatch``).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lengths = np.asarray(nevents).reshape(-1)
    phenx = jnp.asarray(phenx, jnp.int32)
    date = jnp.asarray(date, jnp.int32)
    nevents = jnp.asarray(nevents, jnp.int32).reshape(-1)
    H = n_buckets_log2
    P, E = phenx.shape if phenx.ndim == 2 else (0, 0)
    if P == 0 or E == 0:
        # zero-width-slab guard (mirrors tspm_delta/ops.py): no events,
        # empty table
        return jnp.zeros(1 << H, jnp.int32)
    cp = counting_plan(lengths, H, backend, fuse_duration, block_patients,
                       pb, tile)
    obs_lib.count_dispatch(metrics, "fused", cp.use_kernel, interpret)
    counts = jnp.zeros(1 << H, jnp.int32)
    for b in cp.blocks:
        if cp.use_kernel:
            part = _kernel_block(_rows(phenx, b.patients, b.width),
                                 _take(nevents, b.patients), codec, H,
                                 b.tiles, pb, tile, interpret)
        else:
            part = _jnp_block_counts(
                phenx, date, nevents, b.patients, width=b.width, codec=codec,
                fuse_duration=fuse_duration, bucket_days=bucket_days,
                n_buckets_log2=H)
        counts = counts + part
    return counts
