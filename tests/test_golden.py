"""Golden end-to-end fixtures.

Ports the reference's ten RaconPolishingTest integration tests
(/root/reference/test/racon_test.cpp:88-290) plus the factory validation
tests (racon_test.cpp:55-86). Each fixture runs the full pipeline on the
packaged lambda-phage sample data and asserts consensus quality.

The reference pins exact per-backend values (CPU vs CUDA differ:
e.g. 1312 vs 1385 for the first fixture, racon_test.cpp:107,312) — numeric
divergence between engines is accepted, each pinned separately. This
implementation is pinned the same way: every fixture asserts THIS
implementation's measured value exactly (both engines produce the same
bytes, so one pin covers both; tools/measure_fixtures.py regenerates the
numbers after an intentional algorithm change). Reference CPU/GPU values
are noted inline for comparison.
"""

import os

import pytest

from racon_tpu.core.polisher import create_polisher, PolisherType
from racon_tpu.errors import RaconError
from racon_tpu.io.parsers import create_sequence_parser
from racon_tpu.native import edit_distance


@pytest.fixture(autouse=True)
def _one_device_mesh(monkeypatch):
    # real-data identity fixtures exercise the production envelope, not
    # sharding (dedicated sharded tests cover that at small shapes) — on
    # the 8-virtual-device CPU test mesh every shard re-runs the
    # sequential DP, so pin this heavyweight module to one device
    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")


DATA = "/root/reference/test/data/"

pytestmark = pytest.mark.skipif(
    not os.path.isdir(DATA), reason="reference sample data not available")


def run_pipeline(reads, overlaps, target, type_=PolisherType.kC,
                 window_length=500, quality_threshold=10.0,
                 error_threshold=0.3, match=5, mismatch=-4, gap=-8,
                 drop_unpolished=True):
    polisher = create_polisher(
        DATA + reads, DATA + overlaps, DATA + target, type_, window_length,
        quality_threshold, error_threshold, True, match, mismatch, gap,
        num_threads=4)
    polisher.initialize()
    return polisher.polish(drop_unpolished)


def reference_distance(polished):
    """Edit distance of the polished contig (reverse-complemented, as in
    racon_test.cpp:104-109) against the curated reference assembly."""
    ref = []
    create_sequence_parser(DATA + "sample_reference.fasta.gz",
                           "test").parse(ref, -1)
    return edit_distance(polished.reverse_complement, ref[0].data)


# -- factory validation (racon_test.cpp:55-86) ----------------------------

def test_polisher_type_error():
    with pytest.raises(RaconError, match="invalid polisher type"):
        create_polisher("", "", "", 3, 0, 0, 0)


def test_window_length_error():
    with pytest.raises(RaconError, match="invalid window length"):
        create_polisher("", "", "", PolisherType.kC, 0, 0, 0)


def test_sequences_path_extension_error():
    with pytest.raises(RaconError, match="unsupported format extension"):
        create_polisher("", "", "", PolisherType.kC, 500, 0, 0)


def test_overlaps_path_extension_error():
    with pytest.raises(RaconError, match="unsupported format extension"):
        create_polisher(DATA + "sample_reads.fastq.gz", "", "",
                        PolisherType.kC, 500, 0, 0)


def test_target_path_extension_error():
    with pytest.raises(RaconError, match="unsupported format extension"):
        create_polisher(DATA + "sample_reads.fastq.gz",
                        DATA + "sample_overlaps.paf.gz", "",
                        PolisherType.kC, 500, 0, 0)


# -- contig polishing goldens (racon_test.cpp:88-218) ---------------------
# pins: THIS implementation's measured value, exact

def test_consensus_with_qualities():
    # reference: CPU 1312 / GPU 1385 (racon_test.cpp:107,312)
    polished = run_pipeline("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
                            "sample_layout.fasta.gz")
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1352


def test_consensus_without_qualities():
    # reference: CPU 1566 / GPU 1607 (racon_test.cpp:129,334)
    polished = run_pipeline("sample_reads.fasta.gz", "sample_overlaps.paf.gz",
                            "sample_layout.fasta.gz")
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1530


def test_consensus_with_qualities_and_alignments():
    # reference: CPU 1317 / GPU 1541 (racon_test.cpp:151,356)
    polished = run_pipeline("sample_reads.fastq.gz", "sample_overlaps.sam.gz",
                            "sample_layout.fasta.gz")
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1358


def test_consensus_without_qualities_and_with_alignments():
    # reference: CPU 1770 / GPU 1661 (racon_test.cpp:173,378); ~5% behind
    # the reference CPU engine on this one fixture
    polished = run_pipeline("sample_reads.fasta.gz", "sample_overlaps.sam.gz",
                            "sample_layout.fasta.gz")
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1859


def test_consensus_with_qualities_larger_window():
    # reference: CPU 1289 / GPU 4168 (racon_test.cpp:195,400)
    polished = run_pipeline("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
                            "sample_layout.fasta.gz", window_length=1000)
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1353


def test_consensus_with_qualities_edit_distance():
    # unit scores m=1 x=-1 g=-1; reference: CPU 1321 / GPU 1361
    # (racon_test.cpp:217,422)
    polished = run_pipeline("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
                            "sample_layout.fasta.gz",
                            match=1, mismatch=-1, gap=-1)
    assert len(polished) == 1
    assert reference_distance(polished[0]) == 1331


# -- fragment correction goldens (racon_test.cpp:220-290) -----------------

def total_length(polished):
    return sum(len(s.data) for s in polished)


def test_fragment_correction_with_qualities():
    # kC on all-vs-all overlaps; reference: 39 seqs, 389394 bp (CPU) /
    # 385543 (GPU) (racon_test.cpp:229-235,434-440)
    polished = run_pipeline("sample_reads.fastq.gz",
                            "sample_ava_overlaps.paf.gz",
                            "sample_reads.fastq.gz",
                            match=1, mismatch=-1, gap=-1)
    assert len(polished) == 39
    assert total_length(polished) == 389340


def test_fragment_correction_with_qualities_full():
    # reference: 236 seqs, 1658216 bp (CPU) / 1655505 (GPU)
    polished = run_pipeline("sample_reads.fastq.gz",
                            "sample_ava_overlaps.paf.gz",
                            "sample_reads.fastq.gz", type_=PolisherType.kF,
                            match=1, mismatch=-1, gap=-1,
                            drop_unpolished=False)
    assert len(polished) == 236
    assert total_length(polished) == 1658859


# -- whole-output golden diff (ci/gpu/cuda_test.sh:30-44 analogue) --------
# both engines must reproduce the committed files byte-for-byte; the
# synthetic one is regenerated by tools/make_golden.py

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "sample_golden.fasta")


def polished_fasta_bytes(device_batches=0):
    polisher = create_polisher(
        DATA + "sample_reads.fastq.gz", DATA + "sample_overlaps.paf.gz",
        DATA + "sample_layout.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
        True, 5, -4, -8, num_threads=4, tpu_poa_batches=device_batches)
    polisher.initialize()
    out = bytearray()
    for seq in polisher.polish():
        out += b">" + seq.name.encode() + b"\n" + seq.data + b"\n"
    return bytes(out)


def test_golden_output_exact_diff_host():
    with open(GOLDEN, "rb") as fh:
        golden = fh.read()
    assert polished_fasta_bytes() == golden


full_goldens = pytest.mark.skipif(
    not os.environ.get("RACON_TPU_FULL_GOLDENS"),
    reason="several-minute fixture; set RACON_TPU_FULL_GOLDENS=1 to run "
           "(verified passing; kept out of the default suite for speed)")


@full_goldens
def test_synth_genome_golden_exact_diff():
    """Whole-genome-scale golden: a deterministic 50 kb synthetic ONT
    workload (tools/synthbench.py, seed 42) must reproduce the committed
    polished FASTA byte-for-byte — the scale analogue of the reference's
    5.2 MB CI golden (ci/gpu/cuda_test.sh:30-44)."""
    import subprocess
    import sys
    import tempfile

    golden_path = os.path.join(os.path.dirname(__file__), "data",
                               "synth_50kb_golden.fasta")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.NamedTemporaryFile(suffix=".fasta") as tmp:
        subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "synthbench.py"),
             "--genome-kb", "50", "--coverage", "20", "--seed", "42",
             "--golden-out", tmp.name],
            check=True, capture_output=True, cwd=repo)
        with open(tmp.name, "rb") as fh:
            got = fh.read()
    with open(golden_path, "rb") as fh:
        assert got == fh.read()


@full_goldens
def test_golden_output_exact_diff_device(monkeypatch, capsys):
    # the device engine must hit the SAME golden (byte-identity design);
    # the default suite covers this via
    # test_determinism.py::test_device_output_matches_host_bytes — this
    # variant additionally diffs the PAF path against the committed file.
    # STRICT catches whole-engine device failures; per-window host
    # fallbacks (status 1) don't raise, so additionally assert the
    # engine's fallback report never appeared — every window really was
    # polished on device
    monkeypatch.setenv("RACON_TPU_STRICT", "1")
    with open(GOLDEN, "rb") as fh:
        golden = fh.read()
    out = polished_fasta_bytes(device_batches=1)
    assert "windows polished on host" not in capsys.readouterr().err
    assert out == golden


@full_goldens
def test_fragment_correction_without_qualities_full():
    # reference: 236 seqs, 1663982 bp (CPU) / 1663732 (GPU)
    polished = run_pipeline("sample_reads.fasta.gz",
                            "sample_ava_overlaps.paf.gz",
                            "sample_reads.fasta.gz", type_=PolisherType.kF,
                            match=1, mismatch=-1, gap=-1,
                            drop_unpolished=False)
    assert len(polished) == 236
    assert total_length(polished) == 1664167


@full_goldens
def test_fragment_correction_with_qualities_full_mhap():
    # reference: 236 seqs, 1658216 bp (CPU) / 1655505 (GPU); must equal the
    # PAF fixture's value exactly, as in the reference
    polished = run_pipeline("sample_reads.fastq.gz",
                            "sample_ava_overlaps.mhap.gz",
                            "sample_reads.fastq.gz", type_=PolisherType.kF,
                            match=1, mismatch=-1, gap=-1,
                            drop_unpolished=False)
    assert len(polished) == 236
    assert total_length(polished) == 1658859
