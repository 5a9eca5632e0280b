"""Faults planted under the timed path, and the control, at toy size on
the CPU: each run has to come out not correct."""
from __future__ import annotations

import pytest

import toy

import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("toy")))


def _run(root, traffic, **kw):
    return run.run(toy.Args(f"toy.{traffic}", **kw), require_tpu=False,
                   root=root)


def _alter_survivor(monkeypatch):
    from repro.core import sparsity
    real = sparsity.screen_survivors

    def altered(*a, **k):
        seq, dur, pat = real(*a, **k)
        dur = dur.copy()
        if len(dur):
            dur[len(dur) // 2] += 1
        return seq, dur, pat
    monkeypatch.setattr(sparsity, "screen_survivors", altered)


def _half_batch_fit(monkeypatch):
    from repro.api import session
    real = session.MiningSession._fit_fused

    def half(self, db):
        return real(self, db.slice_patients(0, db.n_patients // 2,
                                            db.phenx.shape[1]))
    monkeypatch.setattr(session.MiningSession, "_fit_fused", half)


def _screen_threshold(scale):
    def plant(monkeypatch):
        from repro.core import sparsity
        real = sparsity.screen_survivors

        def screened(seq, dur, patient, counts, threshold, *a, **k):
            return real(seq, dur, patient, counts, int(threshold * scale),
                        *a, **k)
        monkeypatch.setattr(sparsity, "screen_survivors", screened)
    return plant


FAULTS = {
    "fit-answer-altered": _alter_survivor,
    "fit-half-batch": _half_batch_fit,
    "fit-screen-skipped": _screen_threshold(0),
    "fit-screen-doubled": _screen_threshold(2),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_the_timed_path_is_not_correct(root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res = _run(root, "toy_fit")
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("traffic", ["toy_fit", "toy_refit"])
def test_control_is_not_correct(root, traffic):
    res = _run(root, traffic, control=True)
    assert res["correct"] is False, res["checks"]
