"""Logical-axis sharding: rules context + activation constraints.

Model code annotates activations with *logical* axis names
(``constrain(x, ("batch", "seq", None))``).  The launcher installs a rule
set mapping logical names to mesh axes; outside any rule context the
annotations are no-ops, so CPU unit tests never see a mesh.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs as obs_lib

_RULES: contextvars.ContextVar = contextvars.ContextVar("axis_rules",
                                                        default=None)

# default logical -> mesh-axis mapping (single- and multi-pod meshes)
def default_rules(mesh) -> dict:
    axes = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in axes)
    return {
        "batch": batch if len(batch) > 1 else (batch[0] if batch else None),
        "model": "model" if "model" in axes else None,
        "fsdp": "data" if "data" in axes else None,
        "seq": None,            # flipped to ('data',) for long-context SP
        "seq_res": None,        # Megatron-SP residual (cfg.sp_residual)
        "expert": "model" if "model" in axes else None,
    }


@contextlib.contextmanager
def axis_rules(mesh, rules: dict | None = None):
    token = _RULES.set((mesh, rules or default_rules(mesh)))
    try:
        yield
    finally:
        _RULES.reset(token)


def current_rules():
    return _RULES.get()


def logical_to_pspec(names, rules) -> P:
    return P(*[rules.get(n) if isinstance(n, str) else n for n in names])


def constrain(x, names):
    ctx = _RULES.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_pspec(names, rules)))


def sanitize_pspec(spec: P, shape, mesh) -> P:
    """Drop mesh axes a dim is not divisible by (small weights replicate).
    Mirrors the fallback rule every production sharder needs: a [768, 8]
    gate projection cannot shard 8 ways over a 16-wide 'model' axis."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if shape[i] % size == 0 else None)
    return P(*out)


def sanitize_tree(spec_tree, struct_tree, mesh):
    return jax.tree.map(
        lambda s, x: sanitize_pspec(s, x.shape, mesh), spec_tree, struct_tree,
        is_leaf=lambda v: isinstance(v, P))


def param_shardings(mesh, spec_tree, struct_tree=None):
    """PartitionSpec tree (from model init) -> NamedSharding tree,
    sanitized against the struct shapes when provided."""
    if struct_tree is not None:
        spec_tree = sanitize_tree(spec_tree, struct_tree, mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda v: isinstance(v, P))


def _device_resident_stack(tables, mesh, axis: str):
    """[S, B] global array assembled from per-shard tables *in place* when
    each table already lives on its mesh-position device (the
    device-pinned streaming layout): no host round-trip, no cross-device
    copy — the psum reads each device's table where it sits.  Returns
    None when the layout doesn't match (then the caller host-gathers)."""
    mesh_devs = list(mesh.devices.flat)
    if len(tables) != len(mesh_devs) or mesh.shape[axis] != len(mesh_devs):
        return None
    parts = []
    for t, d in zip(tables, mesh_devs):
        if not isinstance(t, jax.Array) or t.devices() != {d}:
            return None
        parts.append(t[None])
    return jax.make_array_from_single_device_arrays(
        (len(tables),) + tables[0].shape,
        NamedSharding(mesh, P(axis)), parts)


def merge_sharded_counts(tables, mesh=None, axis: str = "data",
                         metrics=obs_lib.NOOP_REGISTRY):
    """Global screen table from per-shard bucket-count tables: one psum.

    Per-shard sketch tables count distinct (patient, sequence) pairs over
    *disjoint* patient sets, so the global table is their elementwise sum —
    the same merge the batch screen does per chunk
    (``sparsity.merge_bucket_counts``).  With a mesh, the [S, B] stack is
    sharded over ``axis`` and reduced with a single shard_map'd psum (each
    device folds its local shard rows first), the collective pattern of
    ``sparsity.screen_hash``; without one, the sum runs locally.  Tables
    pinned one-per-mesh-device (``ShardedStreamService`` with
    ``placement='devices'``) are stacked in place; any other committed
    layout gathers through the host first — ``jnp.stack`` cannot mix
    device commitments.  The path taken is counted on ``metrics``
    (``shard.merge{path=device_stack|host_gather|stacked|local}``).
    """
    tables = [jnp.asarray(t) for t in tables]
    if mesh is not None:
        resident = _device_resident_stack(tables, mesh, axis)
        if resident is not None:
            metrics.counter("shard.merge", path="device_stack").inc()
            return _jitted_merge(mesh, axis)(resident)
    gather = len({d for t in tables for d in t.devices()}) > 1
    if gather:
        tables = [np.asarray(t) for t in tables]
    stacked = jnp.stack(tables)
    if mesh is None:
        metrics.counter("shard.merge", path="local").inc()
        return stacked.sum(axis=0, dtype=stacked.dtype)
    metrics.counter("shard.merge",
                    path="host_gather" if gather else "stacked").inc()
    n = mesh.shape[axis]
    if stacked.shape[0] % n:   # pad with zero tables to a shardable count
        pad = n - stacked.shape[0] % n
        stacked = jnp.concatenate(
            [stacked, jnp.zeros((pad,) + stacked.shape[1:], stacked.dtype)])
    merge = _jitted_merge(mesh, axis)
    return merge(jax.device_put(stacked, NamedSharding(mesh, P(axis))))


@functools.lru_cache(maxsize=8)
def _jitted_merge(mesh, axis: str):
    # jit'd once per (mesh, axis): the merge runs on every snapshot rebuild
    return jax.jit(jax.shard_map(
        lambda c: jax.lax.psum(c.sum(axis=0, dtype=c.dtype), axis),
        mesh=mesh,
        in_specs=P(axis), out_specs=P()))


def fsdp_axis_for(cfg):
    if not cfg.fsdp:
        return None
    # with TP disabled the 'model' axis would idle — fold it into FSDP so
    # block weights shard 256-way (grad sync shrinks accordingly)
    return "data" if cfg.tp_internals else ("data", "model")
