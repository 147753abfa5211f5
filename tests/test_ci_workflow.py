"""CI contract tests: the committed workflow must keep gating the repo.

The acceptance criterion for the CI satellite work is mechanical:
``.github/workflows/ci.yml`` parses, the fast-tier job runs the ROADMAP
tier-1 command *verbatim*, the kernel leg pins interpret mode explicitly,
the nightly/dispatch leg runs ``--runslow``, and the smoke-benchmark job
schema-gates + uploads its artifact.  These tests pin that contract so a
workflow edit that silently weakens the gate fails the gate itself.

Also covers the benchmark artifact schema checker
(``benchmarks/check_artifact_schema.py``): the committed artifact matches
the committed schema, and injected drift (dropped or renamed keys) is
detected.
"""
import importlib.util
import json
import pathlib

import pytest

yaml = pytest.importorskip("yaml")

ROOT = pathlib.Path(__file__).resolve().parents[1]
WORKFLOW = ROOT / ".github" / "workflows" / "ci.yml"
TIER1 = "PYTHONPATH=src python -m pytest -x -q"


@pytest.fixture(scope="module")
def wf():
    return yaml.safe_load(WORKFLOW.read_text())


def _triggers(wf):
    # YAML 1.1 parses the bare key `on` as boolean True
    return wf.get("on", wf.get(True))


def _steps(job):
    return job["steps"]


def _run_lines(job):
    return [s["run"] for s in _steps(job) if "run" in s]


def test_workflow_parses_with_all_triggers(wf):
    trig = _triggers(wf)
    assert set(trig) >= {"push", "pull_request", "workflow_dispatch",
                         "schedule"}
    assert trig["schedule"], "nightly leg needs a cron schedule"
    assert set(wf["jobs"]) >= {"tests", "bench-smoke", "serve-smoke",
                               "serve-chaos", "lint", "nightly-slow",
                               "recovery-drill", "recovery-drill-tpu"}


def test_fast_tier_runs_tier1_command_verbatim(wf):
    legs = wf["jobs"]["tests"]["strategy"]["matrix"]["include"]
    by_tier = {leg["tier"]: leg for leg in legs}
    assert by_tier["fast"]["run"] == TIER1
    # the matrix command is what the job actually executes
    assert "${{ matrix.run }}" in _run_lines(wf["jobs"]["tests"])[-1]


def test_kernel_leg_sets_interpret_mode_explicitly(wf):
    legs = wf["jobs"]["tests"]["strategy"]["matrix"]["include"]
    by_tier = {leg["tier"]: leg for leg in legs}
    kernel_run = by_tier["kernels-interpret"]["run"]
    assert kernel_run.startswith("REPRO_PALLAS_INTERPRET=1 ")
    assert "tests/test_kernels.py" in kernel_run
    # the compressed-traversal-wire acceptance grid rides the same leg:
    # it drives the quantizer kernels end to end through the transport
    assert "tests/test_wire_compression.py" in kernel_run


def test_test_jobs_pin_cpu_backend_and_jax_wheel(wf):
    for name in ("tests", "bench-smoke", "serve-smoke", "serve-chaos",
                 "nightly-slow"):
        assert wf["jobs"][name]["env"]["JAX_PLATFORMS"] == "cpu", name
    # pip caching keyed on the pinned requirements file
    for name in ("tests", "bench-smoke", "serve-smoke", "serve-chaos",
                 "nightly-slow"):
        setup = [s for s in _steps(wf["jobs"][name])
                 if "setup-python" in s.get("uses", "")][0]
        assert setup["with"]["cache"] == "pip", name
        assert setup["with"]["cache-dependency-path"] == "requirements-ci.txt"
    reqs = (ROOT / "requirements-ci.txt").read_text()
    assert "jax==" in reqs and "jaxlib==" in reqs
    # one installation: CI pins exactly the jax/jaxlib this suite runs on
    import jax
    import jaxlib
    assert f"jax=={jax.__version__}\n" in reqs
    assert f"jaxlib=={jaxlib.__version__}\n" in reqs


def test_nightly_leg_is_gated_and_runs_slow_tests(wf):
    job = wf["jobs"]["nightly-slow"]
    assert "schedule" in job["if"] and "workflow_dispatch" in job["if"]
    assert any("--runslow" in r for r in _run_lines(job))
    # the fast gate must NOT creep into running slow depth tests
    assert all("--runslow" not in str(leg)
               for leg in wf["jobs"]["tests"]["strategy"]["matrix"]["include"])


def test_bench_smoke_job_gates_schema_and_uploads_artifact(wf):
    job = wf["jobs"]["bench-smoke"]
    runs = " ".join(_run_lines(job))
    assert "benchmarks/run.py --only tl_step_smoke" in runs
    assert "check_artifact_schema.py" in runs
    assert "benchmarks/schemas/tl_step_smoke.schema.json" in runs
    uploads = [s for s in _steps(job)
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and uploads[0]["with"]["path"] == "BENCH_tl_step_smoke.json"


def test_bench_smoke_job_gates_hierarchy_schema_and_uploads_artifact(wf):
    """The two-tier hierarchy smoke (64 simulated nodes) rides the
    bench-smoke job: run, schema-gated, uploaded — like tl_step_smoke."""
    job = wf["jobs"]["bench-smoke"]
    runs = " ".join(_run_lines(job))
    assert "hierarchy_smoke" in runs
    assert "BENCH_hierarchy_smoke.json" in runs
    assert "benchmarks/schemas/hierarchy_smoke.schema.json" in runs
    uploads = [s for s in _steps(job)
               if "upload-artifact" in s.get("uses", "")]
    hier = [u for u in uploads
            if u["with"]["path"] == "BENCH_hierarchy_smoke.json"]
    assert hier and hier[0]["if"] == "always()"


def test_serve_smoke_job_gates_schema_and_uploads_artifact(wf):
    job = wf["jobs"]["serve-smoke"]
    runs = " ".join(_run_lines(job))
    assert "benchmarks/run.py --only serve_smoke" in runs
    assert "check_artifact_schema.py" in runs
    assert "benchmarks/schemas/serve_smoke.schema.json" in runs
    uploads = [s for s in _steps(job)
               if "upload-artifact" in s.get("uses", "")]
    assert uploads and uploads[0]["with"]["path"] == "BENCH_serve_smoke.json"
    assert uploads[0]["if"] == "always()"


def test_recovery_drill_job_verifies_the_elastic_guarantee(wf):
    """The recovery-drill job must (a) run the elastic drill on the
    forced-8 host mesh and pin the bit-equal verdict, and (b) prove the
    non-elastic flavor fails *loudly* — a specific exit code, so a
    timeout-killed silent hang can never pass."""
    job = wf["jobs"]["recovery-drill"]
    assert job["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in job["env"]["XLA_FLAGS"]
    runs = " ".join(_run_lines(job))
    assert "--mesh host --elastic" in runs
    assert "--drill kill-device:2" in runs
    assert "RECOVERY_DRILL bit_equal=true" in runs
    # the loud-failure leg: watchdog-classified hang, pinned exit code
    assert "hang-device:1" in runs and "--elastic" not in runs.split(
        "hang-device:1")[1]
    assert 'test "$code" -eq 2' in runs


def test_serve_chaos_job_verifies_token_identity_and_loud_failure(wf):
    """The serve-chaos job must (a) run the supervised hang+crash drill
    and pin the token-identity verdict, and (b) prove the unsupervised
    flavor fails *loudly* with the CLI's pinned exit code — a timeout kill
    (124) of a silently wedged engine can never pass."""
    job = wf["jobs"]["serve-chaos"]
    assert job["env"]["JAX_PLATFORMS"] == "cpu"
    runs = " ".join(_run_lines(job))
    assert "repro.launch.serve" in runs
    assert "--engine continuous" in runs
    assert "--chaos hang:3,crash:6" in runs
    assert "SERVE_DRILL token_identical=true" in runs
    # the loud-failure leg: watchdog-classified hang, pinned exit code
    tail = runs.split("--no-supervise")
    assert len(tail) == 2 and "--chaos hang:1" in tail[0]
    assert 'test "$code" -eq 2' in runs


def test_recovery_drill_tpu_stub_is_dispatch_only(wf):
    job = wf["jobs"]["recovery-drill-tpu"]
    assert job["if"] == "github.event_name == 'workflow_dispatch'"
    assert any("repro.launch.train" in r for r in _run_lines(job))


def test_lint_job_runs_ruff_with_committed_config(wf):
    runs = " ".join(_run_lines(wf["jobs"]["lint"]))
    assert "ruff check" in runs
    cfg = (ROOT / "pyproject.toml").read_text()
    assert "[tool.ruff" in cfg and "F401" in cfg


def test_readme_documents_tiers_and_badge():
    readme = (ROOT / "README.md").read_text()
    assert "actions/workflows/ci.yml/badge.svg" in readme
    assert TIER1 in readme                       # local repro command
    assert "--runslow" in readme
    assert "REPRO_PALLAS_INTERPRET=1" in readme


# ------------------------------------------------ artifact schema checker
def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_artifact_schema",
        ROOT / "benchmarks" / "check_artifact_schema.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_committed_artifact_matches_committed_schema():
    mod = _checker()
    rc = mod.main([str(ROOT / "BENCH_tl_step_smoke.json"),
                   "--schema",
                   str(ROOT / "benchmarks" / "schemas"
                       / "tl_step_smoke.schema.json")])
    assert rc == 0


def test_committed_serve_artifact_matches_committed_schema():
    """The serve-smoke CI gate, run locally: the committed artifact and
    schema agree, and numeric offered-load keys are wildcarded so changing
    the load grid is not drift."""
    mod = _checker()
    schema = str(ROOT / "benchmarks" / "schemas" / "serve_smoke.schema.json")
    assert mod.main([str(ROOT / "BENCH_serve_smoke.json"),
                     "--schema", schema]) == 0
    art = json.loads((ROOT / "BENCH_serve_smoke.json").read_text())
    loads = art["result"]["archs"]["deepseek-7b"]["loads"]
    loads["64.0"] = next(iter(loads.values()))    # extra load point: fine
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "sweep.json"
        p.write_text(json.dumps(art))
        assert mod.main([str(p), "--schema", schema]) == 0
        broken = json.loads((ROOT / "BENCH_serve_smoke.json").read_text())
        for point in broken["result"]["archs"]["deepseek-7b"][
                "loads"].values():
            point.pop("p99_token_latency_ms")     # dropped metric: drift
        p.write_text(json.dumps(broken))
        assert mod.main([str(p), "--schema", schema]) == 1


def test_schema_drift_is_detected(tmp_path):
    mod = _checker()
    artifact = json.loads((ROOT / "BENCH_tl_step_smoke.json").read_text())
    schema = str(ROOT / "benchmarks" / "schemas"
                 / "tl_step_smoke.schema.json")

    dropped = dict(artifact)
    dropped["result"] = {k: v for k, v in artifact["result"].items()
                         if k != "backend"}
    p1 = tmp_path / "dropped.json"
    p1.write_text(json.dumps(dropped))
    assert mod.main([str(p1), "--schema", schema]) == 1

    renamed = json.loads(json.dumps(artifact))
    renamed["result"]["nodes"]["2"]["speedup_x"] = \
        renamed["result"]["nodes"]["2"].pop("speedup")
    p2 = tmp_path / "renamed.json"
    p2.write_text(json.dumps(renamed))
    assert mod.main([str(p2), "--schema", schema]) == 1


def test_schema_wildcards_node_counts(tmp_path):
    """A full-sweep artifact with extra node counts has the SAME schema —
    numeric table keys are wildcarded, so sweeping 2/4/8 nodes instead of 2
    is not drift."""
    mod = _checker()
    artifact = json.loads((ROOT / "BENCH_tl_step_smoke.json").read_text())
    artifact["result"]["nodes"]["4"] = artifact["result"]["nodes"]["2"]
    artifact["result"]["nodes"]["8"] = artifact["result"]["nodes"]["2"]
    p = tmp_path / "sweep.json"
    p.write_text(json.dumps(artifact))
    schema = str(ROOT / "benchmarks" / "schemas"
                 / "tl_step_smoke.schema.json")
    assert mod.main([str(p), "--schema", schema]) == 0


def test_schema_write_roundtrip(tmp_path):
    mod = _checker()
    out = tmp_path / "schema.json"
    art = str(ROOT / "BENCH_tl_step_smoke.json")
    assert mod.main([art, "--schema", str(out), "--write"]) == 0
    assert mod.main([art, "--schema", str(out)]) == 0
    committed = json.loads(
        (ROOT / "benchmarks" / "schemas"
         / "tl_step_smoke.schema.json").read_text())
    assert json.loads(out.read_text())["paths"] == committed["paths"]
