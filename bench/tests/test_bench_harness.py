"""The harness on the CPU at toy size: cells registered from files alone,
the plain reference agreeing with the program, generators and peaks."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import toy

import cohorts
import harness
import reference as ref
import run

KINDS = ("toy_fit", "toy_refit")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("toy")))


@pytest.mark.parametrize("traffic", KINDS)
def test_toy_cell_runs_and_agrees_with_reference(root, traffic):
    res = run.run(toy.Args(f"toy.{traffic}"), require_tpu=False, root=root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e = {"toy_fit": "fit_pairs_per_s", "toy_refit": "refits_per_s"}[traffic]
    assert set(res["metrics"]) == {e2e, "setup_s"}
    assert res["metrics"][e2e]["value"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    json.dumps(res)


def test_toy_metric_is_found_by_name(root):
    read = harness.metric_reader("toy.units", root)

    class Ctx:
        units = 3
    assert read(Ctx()) == 3.0


def test_unknown_workload_is_refused(root):
    with pytest.raises(SystemExit):
        run.run(toy.Args("toy.nothing"), require_tpu=False, root=root)


def test_cpu_run_is_refused_without_a_result(root):
    with pytest.raises(SystemExit, match="no TPU"):
        run.run(toy.Args("toy.toy_fit"), require_tpu=True, root=root)


def test_real_benchmark_names_resolve():
    bm = harness.benchmark()
    for cell in bm["workloads"]:
        c, cfg, traffic = harness.cell_spec(bm, cell["name"])
        assert issubclass(harness.kind(traffic["kind"]), harness.Cell)
        assert isinstance(traffic["patients"], int)
        assert cfg["name"] == cell["config"]
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("kind,exc", [("TPU v99", KeyError),
                                      ("cpu", KeyError)])
def test_peaks_refuse_unknown_devices(kind, exc):
    class Dev:
        platform = "tpu" if kind.startswith("TPU") else "cpu"
        device_kind = kind
    with pytest.raises(exc):
        harness.peaks(Dev())


def test_peaks_of_v5e_and_source():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    p = harness.peaks(Dev())
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    table = harness.load_json(os.path.join(harness.BENCH_DIR, "peaks.json"))
    assert "TPU v5e" in table["source"]


def test_synthea_lengths_reproduce_table2():
    cfg = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                         "synthea_table2.json"))
    n = cohorts.lengths(cfg, 35000)
    pairs = n * (n - 1) // 2
    assert abs(n.mean() - 318) <= 1
    assert abs(pairs.mean() / 205714 - 1) < 0.01
    assert abs(pairs.sum() / 7.2e9 - 1) < 0.01


@pytest.mark.parametrize("n_patients", [32, 64, 1000, 35000])
def test_lognormal_cut_keeps_both_moments(n_patients):
    mu, sigma = 5.0566, 1.1878
    n = cohorts.lognormal_strata(mu, sigma, n_patients)
    m1 = np.exp(mu + sigma ** 2 / 2)
    m2 = np.exp(2 * mu + 2 * sigma ** 2)
    assert n.mean() == pytest.approx(m1, rel=1e-9)
    assert np.mean(n ** 2) == pytest.approx(m2, rel=1e-9)
    assert np.all(n > 0) and np.all(np.diff(n) >= 0)


@pytest.mark.parametrize("n_patients", [1, 2, 3])
def test_lognormal_cut_refuses_too_few_strata(n_patients):
    with pytest.raises(ValueError, match="cannot keep both moments"):
        cohorts.lognormal_strata(5.0566, 1.1878, n_patients)


def test_synthea_cell_cut_keeps_the_tail():
    bm = harness.benchmark()
    cell = next(w for w in bm["workloads"]
                if w["config"] == "synthea_table2")
    _, cfg, traffic = harness.cell_spec(bm, cell["name"])
    n = cohorts.lengths(cfg, traffic["patients"]).astype(np.float64)
    assert abs(n.mean() / 318 - 1) < 0.005
    assert abs(np.mean(n ** 2) / n.mean() ** 2 / 4.0995 - 1) < 0.01


@pytest.mark.parametrize("name", ["ad_table1", "synthea_table2"])
def test_every_seed_has_the_same_lengths(name):
    cfg = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                         name + ".json"))
    a = cohorts.generate(cfg, 16, 1)
    b = cohorts.generate(cfg, 16, 2 ** 33 + 5)
    assert np.array_equal(a[2], b[2]) and a[0].shape == b[0].shape
    assert not np.array_equal(a[0], b[0])
    c = cohorts.generate(cfg, 16, 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, c))


def test_reference_mines_every_pair():
    phenx = np.array([[3, 1, 2, 0], [5, 4, 0, 0]], np.int32)
    date = np.array([[1, 2, 4, 4], [0, 7, 7, 7]], np.int32)
    nev = np.array([3, 2], np.int32)
    radix = ref.Radix(8, 2, 10)
    keys, table = ref.corpus(phenx, date, nev, radix, 4, None)
    seq, dur, pat = radix.unpack(keys)
    got = sorted(zip(*(ref.split_ids(seq)), dur.tolist(), pat.tolist()))
    got = [tuple(int(v) for v in row) for row in got]
    assert got == sorted([(3, 1, 1, 0), (3, 2, 3, 0), (1, 2, 2, 0),
                          (5, 4, 7, 1)])
    assert table.sum() == 4


def test_row_diff_counts_a_multiset_difference():
    a = np.array([1, 2, 2, 5], np.uint64)
    assert ref.row_diff(a, 0, a) == 0
    assert ref.row_diff(np.array([1, 2, 5], np.uint64), 0, a) == 1
    assert ref.row_diff(np.array([1, 2, 2, 6], np.uint64), 0, a) == 2
    assert ref.row_diff(a, 3, a) == 3
