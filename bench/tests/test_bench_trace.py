"""Trace reduction: interval arithmetic, and a small trace recorded on a
TPU v5e (a 16-patient fused fit and 8 streaming deltas, telemetry with
``jax_annotations`` on)."""
from __future__ import annotations

import glob
import os

import pytest

import toy  # noqa: F401  (puts bench/ on the path)

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_counts_overlaps_once():
    assert trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40)]) \
        == pytest.approx(30e-9)
    assert trace_reduce.union_seconds([]) == 0


def test_merged_intervals():
    assert trace_reduce.merged([(5, 9), (0, 3), (2, 4), (9, 12)]) \
        == [[0, 4], [5, 12]]


def test_covering_span_is_the_innermost():
    spans = sorted([(0, 100, "outer"), (10, 60, "inner"), (70, 80, "x")])
    starts = [s for s, _, _ in spans]
    assert trace_reduce._covering_span(spans, starts, 20, 50) == "inner"
    assert trace_reduce._covering_span(spans, starts, 62, 69) == "outer"
    assert trace_reduce._covering_span(spans, starts, 200, 300) \
        == "(no host span)"


@pytest.fixture(scope="module")
def recorded():
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    return trace_reduce.reduce_file(path)


def test_recorded_trace_busy_time(recorded):
    assert 0 < recorded["busy_s"] < 1
    # programs do not overlap on one device: their unions add up to busy
    assert sum(recorded["by_program"].values()) == pytest.approx(
        recorded["busy_s"], rel=1e-6)


@pytest.mark.parametrize("program", ["jit_mine_dense", "jit_pairgen_planes",
                                     "jit_delta_planes", "jit_sketch_update"])
def test_recorded_trace_names_the_programs(recorded, program):
    assert any(k.startswith(program + "(") and v > 0
               for k, v in recorded["by_program"].items())


def test_recorded_trace_top_ops_and_gaps(recorded):
    ops = recorded["top_ops"]
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert ops[0][0].startswith("jit_sketch_update/%while")
    gaps = dict(recorded["idle_gaps"])
    assert "session.fit" in gaps          # a program span, by annotation
    tl = recorded["timeline"]
    first = next(a for a, _, p in tl if "pairgen" in p)
    before = trace_reduce.union_seconds([(a, b) for a, b, _ in tl
                                         if a < first])
    assert 0 < before < recorded["busy_s"]
