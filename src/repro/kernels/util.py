"""Shared helpers for the kernel op wrappers."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def int32_trace(fn):
    """Trace and call ``fn`` with JAX's 64-bit mode off.

    The package enables x64 at import (``repro/__init__.py``), which makes
    every untyped literal, index-map value and reduction in a kernel body
    int64 — a width Mosaic cannot lower.  The kernels compute in int32
    only, so their entry points run under this scope and every caller
    keeps its 64-bit ids outside.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return wrapped


def pad_to(x, m, axis, value=0):
    """Pad ``axis`` of ``x`` up to the next multiple of ``m``."""
    n = x.shape[axis]
    pad = (-n) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
