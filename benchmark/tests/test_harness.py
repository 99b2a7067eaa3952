"""The harness finds a cell's configuration, traffic and metrics by
name, and refuses to measure without a TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "ecoli-ont30-w500.paf"


def load_run(bench_dir: str):
    spec = importlib.util.spec_from_file_location(
        f"run_{abs(hash(bench_dir))}", os.path.join(bench_dir, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = json.loads((root / "benchmark/configs/ecoli-ont30-w500.json")
                     .read_text())
    cfg.update(name="tiny", genome_bp=30_000)
    (root / "benchmark/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/burst.json").write_text(json.dumps(
        {"jobs": 2}))
    (root / "benchmark/metrics/jobs_done.py").write_text(
        "def read(run):\n    return float(len(run.done)) or None\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmark/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "jobs_done", "unit": "jobs", "better": "higher",
        "source": "host_clock", "layer": "harness",
        "moves": "windows_per_s", "workloads": ["tiny.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    run = load_run(str(root / "benchmark"))
    cell, got_cfg, traffic, e2e, per_layer = run.cell_spec(
        run.load_json(str(root / "BENCHMARK.json")), "tiny.burst")
    assert got_cfg["genome_bp"] == 30_000 and traffic["jobs"] == 2
    assert "jobs_done" in [m["name"] for m in per_layer]
    assert {m["name"] for m in e2e} == {"windows_per_s", "setup_s"}
    done = run.JobResult("j", True, windows=7)
    assert run.load_reader("jobs_done")(run.Run(1.0, 2.0, [done])) == 1.0
    # the old cell does not get the new cell's metric
    assert "jobs_done" not in [m["name"] for m in run.cell_spec(
        run.load_json(str(root / "BENCHMARK.json")), CELL)[4]]


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**32 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
