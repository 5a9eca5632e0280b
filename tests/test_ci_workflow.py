"""CI pipeline invariants, enforced from inside tier-1.

The workflow is data; these tests are the lint that keeps its guarantees
from rotting: the bench-smoke matrix must stay generated from the suite
registry (so a new ``benchmarks/run.py`` suite can never be silently
missing from the smoke list), every suite must write the artifact the
smoke job uploads, the scheduled slow job must exist and actually select
the ``slow`` marker, and every job must carry a timeout under the shared
cancel-in-progress concurrency group.
"""
import os
import re

import pytest

yaml = pytest.importorskip("yaml")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(REPO, ".github", "workflows", "ci.yml")


@pytest.fixture(scope="module")
def workflow():
    with open(WORKFLOW) as f:
        return yaml.safe_load(f)


@pytest.fixture(scope="module")
def suites():
    from benchmarks.run import SUITES

    return SUITES


def _triggers(workflow):
    # YAML 1.1 parses a bare `on:` key as boolean True
    return workflow.get("on", workflow.get(True))


def test_workflow_parses_and_has_all_jobs(workflow):
    assert {"tier1", "bench-registry", "bench-smoke",
            "slow-nightly"} <= set(workflow["jobs"])


def test_scheduled_slow_job(workflow):
    crons = _triggers(workflow)["schedule"]
    assert crons and all(len(c["cron"].split()) == 5 for c in crons)
    slow = workflow["jobs"]["slow-nightly"]
    assert "schedule" in slow["if"]
    run_steps = " ".join(s.get("run", "") for s in slow["steps"])
    assert "-m slow" in run_steps
    assert "hypothesis" in " ".join(s.get("run", "") for s in slow["steps"])


def test_concurrency_and_timeouts(workflow):
    conc = workflow["concurrency"]
    # cancel-in-progress is scoped to PR updates: superseded pushes to
    # main must still get a completed verdict
    assert "pull_request" in str(conc["cancel-in-progress"])
    assert "github.ref" in conc["group"]
    for name, job in workflow["jobs"].items():
        assert "timeout-minutes" in job, f"job {name} has no timeout"


def test_pip_cache_keyed_on_requirements(workflow):
    req = os.path.join(REPO, ".github", "requirements-ci.txt")
    assert os.path.exists(req)
    for name in ("tier1", "bench-smoke", "slow-nightly"):
        setup = [s for s in workflow["jobs"][name]["steps"]
                 if "setup-python" in s.get("uses", "")]
        assert setup, f"job {name} has no setup-python step"
        with_ = setup[0]["with"]
        assert with_.get("cache") == "pip"
        assert with_.get("cache-dependency-path") == \
            ".github/requirements-ci.txt"


def test_jax_version_matrix_covers_both_sides(workflow):
    """CI installs exactly the jax this checkout runs against: the
    requirements pin names the installed version, and no matrix leg or
    install step swaps in another one."""
    import jax

    req = os.path.join(REPO, ".github", "requirements-ci.txt")
    with open(req) as f:
        pins = re.findall(r"^jax\[cpu\]==(\S+)$", f.read(), re.M)
    assert pins == [jax.__version__]
    legs = workflow["jobs"]["tier1"]["strategy"]["matrix"]["include"]
    assert all("jax" not in leg for leg in legs)
    installs = " ".join(s.get("run", "") for job in workflow["jobs"].values()
                        for s in job["steps"])
    assert not re.search(r"pip install [^\n]*jax", installs)


def test_bench_smoke_matrix_is_the_registry(workflow, suites):
    """The smoke matrix is *generated from* benchmarks.run.SUITES via the
    bench-registry job, so no registered suite can be missing from the
    smoke list; this pins the wiring on both ends."""
    smoke = workflow["jobs"]["bench-smoke"]
    assert smoke["needs"] == "bench-registry" \
        or smoke["needs"] == ["bench-registry"]
    matrix = smoke["strategy"]["matrix"]["suite"]
    assert "fromJSON(needs.bench-registry.outputs.suites)" in matrix
    listing = " ".join(s.get("run", "")
                       for s in workflow["jobs"]["bench-registry"]["steps"])
    assert "from benchmarks.run import SUITES" in listing
    # and the registry itself is intact / importable with entries
    assert len(suites) >= 5
    assert "streaming_placement" in suites


def test_every_suite_writes_its_smoke_artifact(workflow, suites):
    """The smoke job uploads BENCH_<suite>.json with if-no-files-found:
    error — every registered suite's runner must default to exactly that
    path or the upload (and so the job) fails."""
    upload = [s for s in workflow["jobs"]["bench-smoke"]["steps"]
              if "upload-artifact" in s.get("uses", "")]
    assert upload and upload[0]["with"]["if-no-files-found"] == "error"
    assert upload[0]["with"]["path"] == "BENCH_${{ matrix.suite }}.json"
    with open(os.path.join(REPO, "benchmarks", "run.py")) as f:
        src = f.read()
    for name in suites:
        assert f'"BENCH_{name}.json"' in src, \
            f"suite {name} does not write BENCH_{name}.json"


def test_overhead_regression_gate_present(workflow):
    """The checked-in BENCH_api_overhead.json is a regression baseline:
    the gate must compare against it (2x) besides the 5% ceiling."""
    runs = " ".join(s.get("run", "")
                    for s in workflow["jobs"]["tier1"]["steps"])
    assert "BENCH_api_overhead.json" in runs
    assert "2 * stored" in runs
    assert "0.05" in runs


def test_observability_gate_present(workflow, suites):
    """Telemetry must stay < 3% on the ingest hot path: tier-1 carries a
    gate running the observability suite against the checked-in
    BENCH_observability_overhead.json, and the suite is registered (so
    bench-smoke regenerates the artifact on every PR)."""
    assert "observability_overhead" in suites
    runs = " ".join(s.get("run", "")
                    for s in workflow["jobs"]["tier1"]["steps"])
    assert "BENCH_observability_overhead.json" in runs
    assert "observability_overhead" in runs
    assert "0.03" in runs


def test_fused_screen_gate_present(workflow, suites):
    """The corpus-free screen must stay byte-invisible: tier-1 carries a
    gate fitting a live screen="fused" session against the materializing
    path and re-validating the checked-in BENCH_mining_fused.json
    (exactness + peak-bytes ratio under the BYTES_PER_PAIR cost model),
    and the mining_fused suite is registered so bench-smoke regenerates
    the artifact on every PR."""
    assert "mining_fused" in suites
    runs = " ".join(s.get("run", "")
                    for s in workflow["jobs"]["tier1"]["steps"])
    assert "BENCH_mining_fused.json" in runs
    assert "mining_fused" in runs
    assert 'screen="fused"' in runs


def test_nightly_checkpoint_resume_drill(workflow, suites):
    """The nightly must kill a checkpointing replay mid-run and resume it
    across a real process boundary, diffing query results against an
    uninterrupted run — and the storage_tiering suite must be registered
    (so bench-smoke regenerates BENCH_storage_tiering.json per PR)."""
    assert "storage_tiering" in suites
    slow = workflow["jobs"]["slow-nightly"]
    runs = " ".join(s.get("run", "") for s in slow["steps"])
    assert "--checkpoint-dir" in runs and "--resume" in runs
    assert "--stop-after-wave" in runs
    assert "--disk-bytes" in runs, \
        "the resume drill must exercise the compressed disk tier"
    assert "diff " in runs, "resumed output is never compared"


def test_nightly_uploads_trace_artifact(workflow):
    """The nightly chaos leg must produce an inspectable Chrome trace: a
    sharded telemetry-on replay with --trace-out on forced host devices,
    uploaded with if-no-files-found: error so a silently-empty trace
    fails the job."""
    slow = workflow["jobs"]["slow-nightly"]
    runs = " ".join(s.get("run", "") for s in slow["steps"])
    assert "--trace-out" in runs and "--metrics-json" in runs
    assert "--shards" in runs and "repro.launch.stream" in runs
    envs = [s.get("env", {}) for s in slow["steps"] if s.get("run")]
    assert any("xla_force_host_platform_device_count"
               in str(e.get("XLA_FLAGS", "")) for e in envs)
    upload = [s for s in slow["steps"]
              if "upload-artifact" in s.get("uses", "")]
    assert upload, "slow-nightly has no artifact upload step"
    assert upload[0]["with"]["if-no-files-found"] == "error"
    assert "chaos_trace.json" in upload[0]["with"]["path"]


def test_journal_conformance_gate_present(workflow, suites):
    """The audit log must acquit honest runs and convict forgeries:
    tier-1 carries a gate that verifies + replays a live journaled
    session, forges a re-chained delta edit (which must yield a typed
    fraud proof), and re-validates the checked-in
    BENCH_journal_overhead.json (< 5% ceiling with replay asserted
    exact); the journal_overhead suite is registered so bench-smoke
    regenerates the artifact on every PR."""
    assert "journal_overhead" in suites
    runs = " ".join(s.get("run", "")
                    for s in workflow["jobs"]["tier1"]["steps"])
    assert "BENCH_journal_overhead.json" in runs
    assert "journal_dir" in runs
    assert "write_journal" in runs, \
        "the gate never forges a re-chained journal"
    assert "MiningSession.replay" in runs
    assert "overhead_ceiling" in runs and "replay_exact" in runs


def test_nightly_journal_replay_drill(workflow):
    """The nightly must journal a sharded chaos run (eviction + live
    rebalancing, commitments exercised) and replay it in a separate
    process, diffing the printed state digests — the byte-exact audit
    contract across real process boundaries."""
    slow = workflow["jobs"]["slow-nightly"]
    runs = " ".join(s.get("run", "") for s in slow["steps"])
    assert "--journal-dir" in runs and "--replay-journal" in runs
    assert "--journal-commit-every" in runs, \
        "the drill must exercise merkle commitments, not just the chain"
    assert "--rebalance-every" in runs.split("--journal-dir")[0] \
        or "--rebalance-every" in runs
    assert "state_digest" in runs
    assert "diff " in runs, "the replayed digest is never compared"


def test_serving_conformance_gate_present(workflow, suites):
    """The batched read path must stay byte-invisible: tier-1 carries a
    gate driving a live session.serve() against frame-chain evaluation
    and re-validating the checked-in BENCH_serving_latency.json (exact
    masks + the >= 2x p99 speedup floor at >= 32 clients), and the
    serving_latency suite is registered so bench-smoke regenerates the
    artifact on every PR."""
    assert "serving_latency" in suites
    runs = " ".join(s.get("run", "")
                    for s in workflow["jobs"]["tier1"]["steps"])
    assert "BENCH_serving_latency.json" in runs
    assert "session.serve" in runs
    assert "p99_speedup" in runs
    assert "min_p99_speedup" in runs
