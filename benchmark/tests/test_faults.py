"""A run with the timed path broken underneath reads `correct` false.

Each case drives the whole harness past its look for a chip
(`run.measure` on the CPU, the device programs on XLA's CPU backend) at
a size a test run can hold, with one fault of `faults.py` planted where
the program produces its answer: a state left unchanged, half of the
windows left out, one window's consensus garbled. The same run with no
fault reads `correct` true. One chip has no exchange between chips to
leave out."""

import argparse
import json

import jax
import pytest

from racon_tpu.core import polisher as polisher_mod

import faults
import run


CELL = "ecoli-ont30-w500.paf"
#: a kF configuration at a test's size (the lambda read set's shape,
#: racon's scores), for the fragment maker
FRAGMENT_CFG = {"mode": "fragment", "overlaps": "paf", "genome_bp": 12_000,
                "n_reads": 100, "total_read_bp": 300_000,
                "read_len_sd": 1500, "layout_seed": 48502,
                "min_read_bp": 600, "read_err": 0.12, "min_overlap_bp": 500,
                "window_length": 500, "match": 3, "mismatch": -5,
                "gap": -4,
                "limits": {"err_ppm": 8000, "worst_piece_pct": 20.0,
                           "missing": 0}}


def measure(capsys, fragment: bool, size: dict):
    bench = run.load_json(f"{run.ROOT}/BENCHMARK.json")
    _, cfg, traffic, e2e, per_layer = run.cell_spec(bench, CELL)
    cfg = dict(FRAGMENT_CFG if fragment else cfg, **size)
    traffic = dict(traffic, jobs=1, split_bytes=30_000)
    args = argparse.Namespace(seed=2**32 + 77, seconds=1e-3, trace=0)
    capsys.readouterr()
    assert run.measure(args, cfg, traffic, e2e, per_layer,
                       jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CONTIG = (False, {"genome_bp": 24_000})
FRAGMENT = (True, {})


@pytest.mark.parametrize("cell,fault", [
    (CONTIG, None), (CONTIG, "unchanged"), (CONTIG, "half"),
    (CONTIG, "altered"), (FRAGMENT, None), (FRAGMENT, "unchanged"),
    (FRAGMENT, "half"), (FRAGMENT, "altered")],
    ids=["contig-sound", "contig-unchanged", "contig-half",
         "contig-altered", "fragment-sound", "fragment-unchanged",
         "fragment-half", "fragment-altered"])
def test_fault_reads_not_correct(capsys, cell, fault):
    with faults.planted(fault):
        out = measure(capsys, *cell)
    assert out["attempted"] == 1 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["checks"]


def test_job_that_raises_reads_not_correct(capsys, monkeypatch):
    """A job that gives no answer: its targets count as missing."""
    real = polisher_mod.Polisher.polish
    calls = []

    def broken(self, *a, **kw):
        calls.append(1)
        if len(calls) > 1:  # the warm-up polishes the job first
            raise RuntimeError("planted fault")
        return real(self, *a, **kw)
    monkeypatch.setattr(polisher_mod.Polisher, "polish", broken)
    out = measure(capsys, *CONTIG)
    assert out["attempted"] == 1 and out["failed"] == 1
    assert out["correct"] is False
    assert out["checks"]["missing"]["value"] == 1
