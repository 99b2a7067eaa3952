"""Read correction (`-f`) on the device path: the benchmark cell
`lambda-ont-kF.split250k` pinned on the CPU backend.

- The cell's argv (`-f -c 1 --tpualigner-batches 1 --tpu-strict`, from
  `benchmark/run.py`'s `job_argv`) corrects a seeded job of the
  benchmark's fragment maker byte for byte as the host engines (`-f`
  alone) do, with no window inside the device envelope on the host,
  and every pair the aligner left to the host counted under a reason.
- The aligner's pair counters: a pair past the length ladder counts
  once, under `ladder`, and keeps the host's CIGAR; the counters fold
  through `merge_from` and the snapshot.
- The polisher's span arguments for what kF adds: overlap rows kept and
  dropped, targets, windows and layers, targets stitched and dropped.
- The benchmark's `align_host_pct` reader and the cell's files.
"""

import collections
import io
import json
import os
import sys

import pytest

from racon_tpu import cli
from racon_tpu.core import polisher as polisher_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CELL = "lambda-ont-kF.split250k"
#: the cell's configuration at a test's size: the lambda read set's
#: error model and racon's scores over a 5 kb genome at about 8x. A job
#: corrects the first 5 kb of reads: three targets, each also a read and
#: each ending in a ragged window (11 windows), against 38 overlap rows
#: on both strands, so that the device programs on the CPU backend take
#: seconds
SMALL = {"mode": "fragment", "overlaps": "paf", "genome_bp": 5_000,
         "n_reads": 20, "total_read_bp": 40_000, "read_len_sd": 800,
         "layout_seed": 48502, "min_read_bp": 600, "read_err": 0.12,
         "min_overlap_bp": 500, "window_length": 500, "match": 3,
         "mismatch": -5, "gap": -4}
SPLIT = {"split_bytes": 5_000}


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cli(argv):
    """(FASTA bytes, the polisher) of one `racon_tpu.cli.main(argv)`.
    The environment the CLI's posture flags set is restored after."""
    built = []
    real = polisher_mod.create_polisher

    def capture(*a, **kw):
        built.append(real(*a, **kw))
        return built[-1]

    buf = io.BytesIO()

    class _Out:
        buffer = buf

        @staticmethod
        def write(s):
            pass

        @staticmethod
        def flush():
            pass

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_STRICT", "")
        mp.setattr(polisher_mod, "create_polisher", capture)
        mp.setattr(sys, "stdout", _Out)
        assert cli.main(argv) == 0
    return buf.getvalue(), built[0]


@pytest.fixture(params=[2**32 + 11, 2**31 + 5], ids=["seed-a", "seed-b"])
def small_job(request, tmp_path):
    job = gen.make_jobs(request.param, SMALL, SPLIT, 1)[0]
    return job, job.write(str(tmp_path))


def test_cell_argv_corrects_reads_as_the_host_engines(small_job):
    job, paths = small_job
    argv = run.job_argv(paths, SMALL, 2)
    assert argv[-6:] == ["-c", "1", "--tpualigner-batches", "1",
                         "--tpu-strict", "-f"]
    device, p = _cli(argv)
    host, _ = _cli([*paths, "-w", "500", "-m", "3", "-x", "-5", "-g", "-4",
                    "-t", "2", "-f"])
    assert device == host and device.count(b">") == len(job.target_names)
    assert p.window_counts.get("host", 0) == 0
    assert p.window_counts.get("device", 0) > 0
    aligner = p.occupancy_stats["aligner"]
    assert aligner["pairs"] == p.n_aligner_pairs > 0
    assert aligner["host_pairs"] == p.n_aligner_host_fallback
    assert aligner["host_pairs"] == sum(
        aligner["host_pairs_by_reason"].values())
    c = reference.compare(job, device, True, 0)
    assert c["missing"] == 0 and c["extra"] == 0


def _initialize(paths, batches, monkeypatch):
    """Initialize a kF polisher over `paths`; returns it and each
    aligned overlap's CIGAR, keyed by its read pair."""
    cigars = {}
    p = polisher_mod.create_polisher(
        *paths, polisher_mod.PolisherType.kF, 500, 10.0, 0.3,
        num_threads=2, tpu_aligner_batches=batches)
    real = p.find_overlap_breaking_points

    def keep(overlaps):
        real(overlaps)
        for o in overlaps:
            cigars[(o.q_id, o.t_id, o.strand)] = o.cigar
    monkeypatch.setattr(p, "find_overlap_breaking_points", keep)
    p.initialize()
    return p, cigars


def test_pair_past_the_ladder_counts_once_and_keeps_the_host_cigar(
        tmp_path, monkeypatch):
    job = gen.make_jobs(7, SMALL, SPLIT, 1)[0]
    paths = job.write(str(tmp_path))
    rows = [r.split(b"\t") for r in job.overlaps.split(b"\n") if r]
    longest = max(max(int(r[3]) - int(r[2]), int(r[8]) - int(r[7]))
                  for r in rows)
    # a ladder whose top bucket is shorter than the longest pair
    edge = max(e for e in (512, 1024, 2048, 4096, 8192) if e < longest)
    past = [r for r in rows
            if max(int(r[3]) - int(r[2]), int(r[8]) - int(r[7])) > edge]
    monkeypatch.setenv("RACON_TPU_ALIGNER_MAXLEN", str(edge))
    p, device = _initialize(paths, 1, monkeypatch)
    monkeypatch.delenv("RACON_TPU_ALIGNER_MAXLEN")
    _, host = _initialize(paths, 0, monkeypatch)
    aligner = p.occupancy_stats["aligner"]
    assert aligner["pairs"] == len(rows)
    assert aligner["host_pairs_by_reason"].get("ladder") == len(past) >= 1
    assert aligner["host_pairs"] == p.n_aligner_host_fallback
    assert device == host


def test_pair_counters_fold_and_snapshot():
    from racon_tpu.sched import OccupancyStats

    a, b = OccupancyStats(), OccupancyStats()
    a.record_pairs("aligner", 10, {"band": 1})
    b.record_pairs("aligner", 5, collections.Counter(ladder=2, band=1))
    a.merge_from(b)
    snap = a.snapshot()["aligner"]
    assert snap["pairs"] == 15 and snap["host_pairs"] == 4
    assert snap["host_pairs_by_reason"] == {"band": 2, "ladder": 2}
    # the snapshot is a copy: later records leave it as it was
    a.record_pairs("aligner", 1, {"cost": 1})
    assert snap["host_pairs_by_reason"] == {"band": 2, "ladder": 2}
    assert a.summary() is None  # no dispatched batch, no occupancy line


def test_kf_spans_carry_rows_windows_and_stitch_counts(tmp_path):
    job = gen.make_jobs(3, SMALL, SPLIT, 1)[0]
    paths = job.write(str(tmp_path))
    path = str(tmp_path / "t.json")
    fasta, _ = _cli([*paths, "-t", "2", "-f", "--tpu-trace", path])
    with open(path) as f:
        args = {e["name"]: e.get("args", {})
                for e in json.load(f)["traceEvents"] if e["ph"] == "X"}
    n_rows = job.overlaps.count(b"\n")
    load = args["polisher.load_overlaps"]
    assert load["rows"] == n_rows and load["dropped_self"] == 0
    assert load["kept"] + load["dropped_error"] == n_rows
    windows = args["polisher.build_windows"]
    assert windows["targets"] == len(job.target_names)
    assert windows["windows"] == sum(
        -(-len(s) // 500) for _, s in reference.parse_fasta(job.targets))
    assert windows["layers"] >= load["kept"]
    stitch = args["polisher.stitch"]
    assert stitch["targets"] == len(job.target_names)
    assert stitch["targets"] - stitch["dropped"] == fasta.count(b">")


def _job(occupancy):
    return run.JobResult("j", True, windows=1, occupancy=occupancy)


@pytest.mark.parametrize("occupancy,want", [
    ([{}, {"session": {"useful_cells": 1}}], None),
    ([{"aligner": {"buckets": {}}}], None),
    ([{"aligner": {"pairs": 100, "host_pairs": 1}},
      {"aligner": {"pairs": 300, "host_pairs": 3}}], 1.0),
    ([{"aligner": {"pairs": 50, "host_pairs": 0}}], 0.0)],
    ids=["parent-no-counters", "no-pair-keys", "two-jobs", "none-on-host"])
def test_align_host_pct_reader(occupancy, want):
    read = run.load_reader("align_host_pct")
    failed = run.JobResult("x", False, occupancy={
        "aligner": {"pairs": 1, "host_pairs": 1}})
    got = read(run.Run(1.0, 2.0, [_job(o) for o in occupancy] + [failed]))
    assert got == (None if want is None else pytest.approx(want))


def test_cell_resolves_with_its_files():
    cell, cfg, traffic, e2e, per_layer = run.cell_spec(_bench(), CELL)
    assert cell["chips"] == 1 and cfg["mode"] == "fragment"
    assert traffic["split_bytes"] == 250_000 and traffic["jobs"] == 1
    assert {m["name"] for m in e2e} == {"windows_per_s", "setup_s"}
    # every per-layer metric reads the layers the kF job runs too
    assert [m["name"] for m in per_layer] == [
        "initialize_ms_per_win", "polish_ms_per_win", "poa_occupancy_pct",
        "device_idle_pct", "align_kernel_ms_per_win",
        "poa_kernel_ms_per_win", "align_host_pct"]
    assert set(cfg["limits"]) == {"err_ppm", "worst_piece_pct", "missing"}


def test_cell_job_at_full_size():
    _, cfg, traffic, _, _ = run.cell_spec(_bench(), CELL)
    job = gen.make_jobs(2**32 + 1, cfg, traffic, 1)[0]
    rows = job.overlaps.count(b"\n")
    assert 32 <= len(job.target_names) <= 36
    assert 2_100 <= rows <= 2_200
    reads = reference.parse_fasta(job.reads)
    assert len(reads) == cfg["n_reads"]
    assert sum(len(s) for _, s in reads) == pytest.approx(
        cfg["total_read_bp"], rel=0.02)
    assert sum(len(s) for _, s in reference.parse_fasta(job.targets)) \
        <= traffic["split_bytes"]
