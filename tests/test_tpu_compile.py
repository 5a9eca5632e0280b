"""Compile-only checks of the Pallas kernels for a TPU v5e.

Interpret mode on the CPU runs a kernel's semantics, not Mosaic's lowering
rules (tile alignment, supported reductions and shape casts, 32-bit
vectors).  These tests compile every mining kernel for one chip of a
described ``v5e:2x2`` topology, with the package imported as users import
it (x64 on), and check that the program holds a Mosaic custom call.
Nothing runs; a compile that passes here is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.  All tests stay in this one file so that one worker loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tspm_delta import delta, ops as delta_ops
from repro.kernels.tspm_fused import fused
from repro.kernels.tspm_pairgen import ops as pairgen_ops, pairgen


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2.  Skips only where no TPU compiler (libtpu) is
    installed, as under a ``jax[cpu]`` install; any other failure to
    describe the topology fails the tests."""
    from jax._src import xla_bridge
    if xla_bridge.get_tpu_library_path() is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off
    (a described-device compile is written but can never be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compile_for_chip(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; the program must
    contain the Pallas kernel (a ``tpu_custom_call``), not a fallback."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


I32 = jnp.int32


def test_pairgen_compiles_for_v5e(one_chip):
    compile_for_chip(pairgen.pairgen_planes, one_chip,
                     ((16, 512), I32), ((16, 512), I32), ((16,), I32))


def test_delta_compiles_for_v5e(one_chip):
    compile_for_chip(delta.delta_planes, one_chip,
                     ((16, 512), I32), ((16, 512), I32), ((16,), I32),
                     ((16,), I32), ((16, 128), I32), ((16, 128), I32))


def test_fused_compiles_for_v5e(one_chip):
    """The one-hot matmul histogram over the whole 2^14 table."""
    compile_for_chip(
        lambda x, n: fused.fused_table(x, n, n_buckets_log2=14),
        one_chip, ((16, 512), I32), ((16,), I32))


def test_pairgen_pads_ragged_patient_count(one_chip):
    """P=13 is not a multiple of the 8-patient block: ops pads P up to
    the block instead of falling back to (1, 128) blocks."""
    compile_for_chip(
        lambda p, d, n: pairgen_ops.pairgen(p, d, n, interpret=False),
        one_chip, ((13, 100), I32), ((13, 100), I32), ((13,), I32))


def test_pairgen_is_lowerable_for_tpu_style_blocks(one_chip):
    """The kernel wrapper compiles with MXU-aligned blocks (no interpret)."""
    compile_for_chip(
        lambda p, d, n: pairgen_ops.pairgen(p, d, n, interpret=False),
        one_chip, ((8, 104), I32), ((8, 104), I32), ((8,), I32))


def test_delta_kernel_is_lowerable_for_tpu_style_blocks(one_chip):
    compile_for_chip(
        lambda *a: delta_ops.delta_pairgen(*a, interpret=False),
        one_chip, ((8, 104), I32), ((8, 104), I32), ((8,), I32),
        ((8,), I32), ((8, 40), I32), ((8, 40), I32))


#: the sketch's set-plane widths, 64 * 2**k up to 2**18 columns
SET_WIDTHS = [64 << k for k in range(13)]


@pytest.mark.parametrize("B", [16, 32])
@pytest.mark.parametrize("C", SET_WIDTHS)
def test_sketch_rows_and_land_compile_in_place_for_v5e(one_chip, B, C):
    """Reading a wave's sets and landing its merged rows compile at every
    width the sketch's growth policy reaches, for a 1,024-patient plane.
    The land updates the donated planes in place and neither program
    holds a copy of a plane (an int64 plane's scatter was refused at
    width 29,952, and even donated it was split and joined whole)."""
    from repro.stream import counts

    P = 1024
    plane = P * C * 4
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
            (((P, C), I32), ((P, C), I32), ((B,), I32))]
    rows = counts.sketch_rows.lower(*args).compile().memory_analysis()
    merged = jax.ShapeDtypeStruct((B, C + 4096), jnp.int64,
                                  sharding=one_chip)
    land = counts.sketch_land.lower(*args, merged).compile().memory_analysis()
    assert rows.temp_size_in_bytes < max(plane // 8, 1 << 21)
    assert land.temp_size_in_bytes < max(plane // 8, 1 << 21)
    assert land.alias_size_in_bytes == 2 * plane
