"""The seeded generators: determinism, PAF rows that lie inside both
sequences, every true overlap of a read set kept, read lengths that
spread, the byte-bounded first chunk, makers found by name, and a
generated fragment job polished by the host engine."""

import contextlib
import io

import numpy as np
import pytest

import gen
import reference

FRAG = {"mode": "fragment", "overlaps": "paf", "genome_bp": 15_000,
        "n_reads": 30, "total_read_bp": 90_000, "read_len_sd": 1500,
        "layout_seed": 1, "min_read_bp": 600, "read_err": 0.12,
        "min_overlap_bp": 500}
SPLIT = {"split_bytes": 40_000}
CONTIG = {"mode": "contig", "overlaps": "paf", "genome_bp": 30_000,
          "read_len": 8000, "read_len_sd": 4000, "layout_seed": 2,
          "min_read_bp": 1000, "coverage": 5, "read_err": 0.12,
          "draft_err": 0.10}


def rows(job):
    return [r.split("\t") for r in job.overlaps.decode().split("\n") if r]


def lengths(fasta: bytes) -> dict:
    return {n: len(s) for n, s in reference.parse_fasta(fasta)}


def test_same_seed_same_jobs_with_a_large_seed():
    a = gen.make_jobs(2**33 + 17, FRAG, SPLIT, 3)
    b = gen.make_jobs(2**33 + 17, FRAG, SPLIT, 3)
    c = gen.make_jobs(2**33 + 18, FRAG, SPLIT, 3)
    assert [j.overlaps for j in a] == [j.overlaps for j in b]
    assert a[0].reads != c[0].reads


def test_fragment_rows_inside_both_reads_and_dual():
    for job in gen.make_jobs(3, FRAG, dict(SPLIT, split_bytes=10**9), 2):
        reads = lengths(job.reads)
        targets = set(job.target_names)
        pairs = set()
        for q, ql, qs, qe, strand, t, tl, ts, te, *_ in rows(job):
            assert int(ql) == reads[q] and int(tl) == reads[t]
            assert 0 <= int(qs) < int(qe) <= int(ql)
            assert 0 <= int(ts) < int(te) <= int(tl)
            assert t in targets and q != t and strand in "+-"
            pairs.add((q, t))
        # dual, and every true overlap kept: with every read a target,
        # each pair appears both ways
        assert pairs and all((t, q) in pairs for q, t in pairs)
        assert len(reads) == FRAG["n_reads"]
        assert sum(reads.values()) == pytest.approx(
            FRAG["total_read_bp"], rel=0.02)


def test_every_seed_gets_the_same_layout_and_lengths_spread():
    a, b = (gen.make_jobs(s, CONTIG, {}, 1)[0] for s in (5, 6))
    la, lb = ([int(r[9]) for r in rows(j)] for j in (a, b))
    assert sorted(la) == sorted(lb) and la != lb
    assert np.std(la) > 1000
    # reads are drawn up to the coverage, less what the contig's ends cut
    assert sum(la) >= 0.85 * 5 * 30_000
    assert a.reads != b.reads
    fa, fb = (gen.make_jobs(s, FRAG, {"split_bytes": 10**9}, 1)[0]
              for s in (5, 6))
    assert [r[9] for r in rows(fa)] == [r[9] for r in rows(fb)]
    assert fa.reads != fb.reads


def test_fragment_first_chunk_is_byte_bounded():
    job = gen.make_jobs(3, FRAG, SPLIT, 1)[0]
    assert 0 < len(job.target_names) < FRAG["n_reads"]
    assert sum(lengths(job.targets).values()) <= SPLIT["split_bytes"]
    assert set(job.truth) == set(job.target_names)


def test_contig_rows_inside_reads_and_draft_and_ends_covered():
    job = gen.make_jobs(9, CONTIG, {}, 1)[0]
    reads = lengths(job.reads)
    draft = lengths(job.targets)["draft"]
    spans = []
    for q, ql, qs, qe, strand, t, tl, ts, te, *_ in rows(job):
        assert int(ql) == reads[q] and (t, int(tl)) == ("draft", draft)
        assert 0 <= int(ts) < int(te) <= draft
        spans.append((int(ts), int(te)))
    # reads are cut at both ends of the contig, so both ends are covered
    assert sum(s == 0 for s, _ in spans) >= 1
    assert sum(e == draft for _, e in spans) >= 1


def test_a_maker_is_found_by_name_and_a_missing_one_refused():
    assert gen.maker(CONTIG) is not gen.maker(FRAG)
    with pytest.raises(ValueError, match="contig-sam"):
        gen.make_jobs(1, dict(CONTIG, overlaps="sam"), {}, 1)


def test_host_engine_polishes_a_fragment_job(tmp_path):
    from racon_tpu import cli

    # 24x, as the lambda read set's ~34x
    job = gen.make_jobs(4, dict(FRAG, n_reads=120, total_read_bp=360_000),
                        SPLIT, 1)[0]
    out = io.TextIOWrapper(io.BytesIO(), encoding="latin-1")
    with contextlib.redirect_stdout(out):
        assert cli.main(job.write(str(tmp_path)) + ["-f", "-t", "2"]) == 0
    out.flush()
    c = reference.compare(job, out.buffer.getvalue(), True, 0)
    assert c["missing"] == 0 and c["extra"] == 0
    assert c["edits"] / c["bases"] < 0.03
