"""The device's idle time by innermost program span, read with no lookback
window: interval arithmetic on a hand-made timeline, and the small trace
recorded on a TPU v5e that ``test_bench_trace`` reads."""
from __future__ import annotations

import glob
import os

import pytest

import toy  # noqa: F401  (puts bench/ on the path)

import span_idle

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: a fit from 0 to 100 ns: pass 1 busy, pass 2 idle in fetch and compact
SPANS = [(0, 100, "session.fit"), (0, 30, "fit.pass1"),
         (30, 95, "fit.pass2"), (40, 70, "fit.pass2.fetch"),
         (70, 90, "fit.pass2.compact")]


def test_idle_goes_to_the_innermost_span():
    ops = [(0, 25), (20, 30), (30, 40), (120, 130)]
    got = span_idle.idle_by_span(ops, SPANS)
    want = {"fit.pass2.fetch": 30e-9, "fit.pass2.compact": 20e-9,
            "fit.pass2": 5e-9, "session.fit": 5e-9}
    assert got["idle_by_span"] == pytest.approx(want)
    assert got["outer_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["idle_s"] == pytest.approx(60e-9)
    assert got["named_idle_share"] == pytest.approx(100 * 55 / 60)


def test_a_gap_with_no_edge_inside_is_whole():
    got = span_idle.idle_by_span([(0, 10), (90, 100)], SPANS[:1])
    assert got["idle_by_span"] == pytest.approx({"session.fit": 80e-9})
    assert got["named_idle_share"] == 0


def test_no_lookback_limit():
    """A gap deep inside an outer span that thousands of shorter spans
    began in before it still goes to that outer span."""
    many = [(10 * i, 10 * i + 1, f"fit.x{i}") for i in range(2000)]
    spans = [(0, 100_000, "session.fit"), (50_000, 90_000, "fit.pass2")] \
        + many
    got = span_idle.idle_by_span([(0, 50_000)], spans)
    assert got["idle_by_span"]["fit.pass2"] == pytest.approx(40_000e-9)


def test_recorded_trace():
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    got = span_idle.read(path)
    assert got["busy_s"] > 0 and got["idle_s"] > 0
    assert got["busy_s"] + got["idle_s"] == pytest.approx(got["outer_s"])
    assert sum(got["idle_by_span"].values()) == pytest.approx(got["idle_s"])
    assert got["span_s"]["session.fit"] == pytest.approx(got["outer_s"])
    assert 0 <= got["named_idle_share"] <= 100


def test_cli_reads_a_kept_profile(capsys):
    import json
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    span_idle.main([path])
    assert json.loads(capsys.readouterr().out) == span_idle.read(path)
