"""The reference comparison: the banded DP against a plain full DP, and
compare() on outputs whose edits are known."""

import numpy as np
import pytest

import gen
import reference


def full_distance(p: bytes, t: bytes, free_end: bool) -> int:
    prev = list(range(len(t) + 1))
    for i in range(1, len(p) + 1):
        cur = [i] + [0] * len(t)
        for j in range(1, len(t) + 1):
            cur[j] = min(prev[j - 1] + (p[i - 1] != t[j - 1]), prev[j] + 1,
                         cur[j - 1] + 1)
        prev = cur
    return min(prev) if free_end else prev[-1]


def seq(rng, n: int) -> bytes:
    return np.frombuffer(gen.ACGT, np.uint8)[rng.integers(0, 4, n)].tobytes()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_banded_matches_full_dp(seed):
    rng = np.random.default_rng(seed)
    pieces, free = [], []
    for _ in range(30):
        t = seq(rng, int(rng.integers(0, 150)))
        p, _ = gen.mutate_fast(rng, t, 0.15)
        pieces.append((p, t))
        free.append(bool(rng.integers(0, 2)))
    want = [full_distance(p, t, f) for (p, t), f in zip(pieces, free)]
    assert reference.banded_distances(pieces, free) == want


def test_band_overflow_counts_longer_length():
    assert reference.banded_distances([(b"A" * 10, b"A" * 200)]) == [200]


def _contig_job(seed=11, genome=40_000):
    cfg = {"mode": "contig", "overlaps": "paf", "genome_bp": genome,
           "read_len": 8000, "read_len_sd": 4000, "layout_seed": 1,
           "min_read_bp": 1000, "coverage": 2, "read_err": 0.12,
           "draft_err": 0.10}
    return gen.make_jobs(seed, cfg, {}, 1)[0]


def test_perfect_contig_reads_zero():
    job = _contig_job()
    fasta = b">draft LN:i:1\n" + job.truth["draft"] + b"\n"
    c = reference.compare(job, fasta, False, 0)
    assert (c["edits"], c["missing"], c["extra"]) == (0, 0, 0)
    assert c["bases"] == 40_000


def test_unpolished_draft_and_garbled_window_fail():
    job = _contig_job()
    draft = job.targets.split(b"\n")[1]
    c = reference.compare(job, b">draft\n" + draft + b"\n", False, 0)
    assert c["edits"] / c["bases"] > 0.05
    t = bytearray(job.truth["draft"])
    t[20_000:20_500] = t[20_000:20_500].translate(
        bytes.maketrans(b"ACGT", b"CATG"))
    c = reference.compare(job, b">draft\n" + bytes(t) + b"\n", False, 0)
    assert 200 <= c["edits"] <= 500
    # anchors half a window apart: the window fills a piece
    assert c["worst_piece_pct"] > 30


def test_truncated_contig_counts_the_missing_stretch():
    job = _contig_job()
    half = job.truth["draft"][:20_000]
    c = reference.compare(job, b">draft\n" + half + b"\n", False, 0)
    assert c["edits"] >= 20_000 - 2 * reference.ANCHOR_STEP


FRAG = {"mode": "fragment", "overlaps": "paf", "genome_bp": 12_000,
        "n_reads": 12, "total_read_bp": 36_000, "read_len_sd": 1000,
        "layout_seed": 1, "min_read_bp": 1500, "read_err": 0.12,
        "min_overlap_bp": 500}


def test_fragment_trimmed_record_and_missing_target():
    job = gen.make_jobs(5, FRAG, {"split_bytes": 20_000}, 1)[0]
    name = job.target_names[0]
    trimmed = job.truth[name][150:-200]
    fasta = b">" + name.encode() + b"r LN:i:1\n" + trimmed + b"\n"
    c = reference.compare(job, fasta, True, 0)
    assert c["edits"] == 0
    assert c["missing"] == len(job.target_names) - 1


def test_garbled_window_of_a_corrected_read_fills_a_piece():
    """One garbled 500-base window of one corrected read: diluted in
    the job's edits, plain in its worst piece."""
    job = gen.make_jobs(5, FRAG, {"split_bytes": 20_000}, 1)[0]
    recs = []
    for k, name in enumerate(job.target_names):
        t = bytearray(job.truth[name])
        if k == 0:
            t[1000:1500] = t[1000:1500].translate(
                bytes.maketrans(b"ACGT", b"CATG"))
        recs.append(b">" + name.encode() + b"r\n" + bytes(t) + b"\n")
    c = reference.compare(job, b"".join(recs), True, 0)
    assert c["edits"] / c["bases"] < 0.02
    assert c["worst_piece_pct"] > 30
