"""bench.py is the round's driver-facing artifact: its LAST stdout line
must be one parseable JSON metric under every failure mode (the round-3
lesson — a timed-out device phase must not lose the host number)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/root/reference/test/data"),
    reason="sample data missing")


def run_bench(env_extra, timeout=400):
    env = dict(os.environ, **env_extra)
    # CPU-only child
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    return proc


def test_bench_host_only_emits_json_line():
    proc = run_bench({"RACON_TPU_POA_BATCHES": "0"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "sample_polish_consensus_throughput_host"
    assert rec["unit"] == "windows/sec"
    assert rec["value"] > 0
    # both fields are independently rounded (value to 2 dp, vs_baseline to
    # 3 dp) — compare with an absolute tolerance covering both roundings
    assert rec["vs_baseline"] == pytest.approx(rec["value"] / 50.0,
                                               abs=1.1e-3)
    # the artifact must carry the per-stage pipeline counters so CI can
    # see a silently-dead pipeline: the compute stage reading ~0 seconds
    # while the phase reported a throughput would be the tell
    stages = rec["stages"]
    for key in ("pack_s", "device_s", "unpack_s", "fallback_s",
                "launches", "chunks", "errors"):
        assert key in stages
    assert stages["device_s"] > 0
    assert stages["launches"] >= 1
    assert stages["errors"] == 0
    # the unified observability snapshot rides the same line: one
    # namespaced schema consolidating the stage/occupancy/degradation
    # telemetry (racon_tpu/obs), consistent with the legacy fields
    metrics = rec["metrics"]
    for ns in ("pipeline", "resilience", "sched"):
        assert ns in metrics
    assert metrics["pipeline"]["chunks"] == stages["chunks"]
    assert all(not v for v in metrics["resilience"].values())


def test_bench_emits_json_even_when_phases_cannot_run():
    # budget too small for any phase: the host phase still gets its floor
    # cap and the line is still emitted
    proc = run_bench({"RACON_TPU_POA_BATCHES": "0",
                      "RACON_TPU_BENCH_BUDGET": "1"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["unit"] == "windows/sec"
