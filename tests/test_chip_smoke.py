"""chip_smoke.py's phases at toy size on the CPU, and the compile cache.

The script's contract on the chip is checked by running it there; here
its phase functions run the same code paths at a few dozen patients (the
jnp reference dispatch, since ``backend="auto"`` picks it off-TPU), each
asserting its own exactness check, and ``main`` must refuse to report a
result on a non-TPU platform.
"""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@pytest.fixture(scope="module")
def cohort():
    return chip_smoke.make_cohort(40, 24, seed=0)


@pytest.fixture(scope="module")
def streamed(cohort):
    return chip_smoke.stream_phase(cohort, n_patients=24, tick_patients=4,
                                   threshold=3)


def test_batch_fit_phase_is_exact(cohort):
    out = chip_smoke.batch_fit_phase(cohort, threshold=3, check_patients=16,
                                     budget_bytes=1 << 22)
    assert out["exact"]
    assert out["kept_rows"] > 0
    # the second slice threshold really screens
    assert 0 < out["check_kept_rows"] < out["check_pairs"]
    assert out["impl"] == {"fused": "jnp(interpret=False,calls=1)",
                           "pairgen": "jnp(interpret=False,calls=1)"}
    assert out["compile_s"] >= 0 and out["run_s"] > 0


def test_stream_phase_is_exact(streamed):
    _, sub, out = streamed
    assert out["exact"]
    assert out["patients"] == sub.n_patients == 24
    assert out["ticks"] >= 4 and out["rows"] > 0
    assert out["impl"]["delta"].startswith("jnp(interpret=False")


def test_serve_phase_is_exact(streamed):
    session, sub, _ = streamed
    out = chip_smoke.serve_phase(session, sub, n_queries=8)
    assert out["exact"]
    assert out["queries"] == 8 and out["kept"] > 0


def test_sharded_phase_matches_one_shard(cohort):
    """Two shards on whatever devices exist (forced 'devices' placement
    round-robins), with rebalancing: same digest as one shard."""
    sub = cohort.slice_patients(0, 24)
    out = chip_smoke.sharded_phase(sub, n_shards=2, tick_patients=4,
                                   threshold=3, rebalance_every=2)
    assert out["exact"]
    assert out["migrations"] > 0
    assert out["merge_path"]


def test_state_digest_stays_byte_exact_while_the_check_canonicalises():
    """The journal replay drill's state_digest hashes the corpus in
    snapshot order, so a replay that reorders rows fails it; the sharded
    check's canonical digest accepts the reordering."""
    import types

    import numpy as np

    from repro.launch.stream import state_digest
    from repro.stream.service import Snapshot

    rng = np.random.default_rng(0)
    seq = rng.integers(0, 1 << 40, 32)
    dur = rng.integers(0, 90, 32).astype(np.int32)
    pat = rng.integers(0, 4, 32).astype(np.int32)
    counts = rng.integers(0, 9, 1 << 6).astype(np.int32)
    perm = rng.permutation(32)

    def svc(order):
        snap = Snapshot(seq[order], dur[order], pat[order], counts, 6)
        return types.SimpleNamespace(
            snapshot=lambda: snap,
            store=types.SimpleNamespace(pids={"a": 0, "b": 1}))

    a, b = svc(np.arange(32)), svc(perm)
    assert state_digest(a) != state_digest(b)
    assert chip_smoke.canonical_digest(a) == chip_smoke.canonical_digest(b)


def test_check_impl_rejects_the_wrong_implementation(cohort):
    """A phase meant to run the jnp reference off-TPU fails when the
    dispatch counters show the (interpreted) kernel instead."""
    from repro.api import MiningConfig, MiningSession
    s = MiningSession(MiningConfig(threshold=3, screen="fused",
                                   backend="kernel", n_buckets_log2=10,
                                   telemetry=True))
    s.fit(cohort.slice_patients(0, 4))
    with pytest.raises(SystemExit, match="fused ran"):
        chip_smoke.check_impl("batch_fit", s, ("fused",))


def test_main_refuses_a_non_tpu_platform(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_compile_cache_honours_the_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
