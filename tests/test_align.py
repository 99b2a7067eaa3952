import numpy as np
import pytest

from racon_tpu.ops.align import BatchAligner, band_offsets, edit_distance


def _mutate(rng, seq: bytes, sub=0.05, ins=0.03, dele=0.03) -> bytes:
    bases = b"ACGT"
    out = bytearray()
    for ch in seq:
        r = rng.random()
        if r < dele:
            continue
        if r < dele + sub:
            out.append(bases[rng.integers(4)])
        else:
            out.append(ch)
        if rng.random() < ins:
            out.append(bases[rng.integers(4)])
    return bytes(out)


def _random_seq(rng, n) -> bytes:
    return bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), n))


def _cigar_cost_and_spans(runs, q: bytes, t: bytes):
    """Walk op runs, returning (cost, q_consumed, t_consumed)."""
    qi = ti = cost = 0
    for n, op in runs:
        if op == "M":
            for _ in range(n):
                cost += q[qi] != t[ti]
                qi += 1
                ti += 1
        elif op == "I":
            qi += n
            cost += n
        elif op == "D":
            ti += n
            cost += n
    return cost, qi, ti


def test_band_offsets_monotone_and_cover_corners():
    for m, n in [(100, 100), (37, 154), (500, 400), (1, 99)]:
        band = 32
        off = band_offsets(m, n, band, m + n + 1)
        steps = np.diff(off)
        assert ((steps == 0) | (steps == 1)).all()
        assert off[0] <= 0 < off[0] + band
        assert off[m + n] <= m < off[m + n] + band


def test_edit_distance_host():
    assert edit_distance(b"ACGT", b"ACGT") == 0
    assert edit_distance(b"ACGT", b"AGT") == 1
    assert edit_distance(b"AAAA", b"TTTT") == 4
    assert edit_distance(b"", b"ACG") == 3
    assert edit_distance(b"KITTEN", b"SITTING") == 3


@pytest.mark.parametrize("n,err", [(200, 0.05), (900, 0.10), (1500, 0.15)])
def test_banded_alignment_matches_exact_distance(n, err):
    rng = np.random.default_rng(n)
    pairs = []
    for _ in range(4):
        t = _random_seq(rng, n)
        q = _mutate(rng, t, sub=err, ins=err / 2, dele=err / 2)
        pairs.append((q, t))

    runs = BatchAligner().align(pairs)
    for (q, t), r in zip(pairs, runs):
        assert r is not None
        cost, q_used, t_used = _cigar_cost_and_spans(r, q, t)
        assert q_used == len(q) and t_used == len(t)
        exact = edit_distance(q, t)
        # banded result must be a valid alignment; with a 10% band and these
        # error rates it should be exact
        assert cost == exact


def test_mixed_length_buckets():
    rng = np.random.default_rng(7)
    pairs = []
    for n in (100, 600, 600, 3000):
        t = _random_seq(rng, n)
        q = _mutate(rng, t)
        pairs.append((q, t))
    runs = BatchAligner().align(pairs)
    for (q, t), r in zip(pairs, runs):
        cost, q_used, t_used = _cigar_cost_and_spans(r, q, t)
        assert q_used == len(q) and t_used == len(t)


def test_oversize_rejected():
    al = BatchAligner(max_length=512)
    res = al.align([(b"A" * 600, b"A" * 600)])
    assert res == [None]


def test_determinism():
    rng = np.random.default_rng(3)
    t = _random_seq(rng, 400)
    q = _mutate(rng, t)
    r1 = BatchAligner().align([(q, t)])
    r2 = BatchAligner().align([(q, t)])
    assert r1 == r2


def test_pathological_indel_rejected_not_wrong():
    """A large balanced indel forces the optimal path far off the ideal
    diagonal; the banded kernel must flag it for exact host realignment
    (reference pattern: cudaaligner status -> CPU, cudaaligner.cpp:63-71)
    instead of returning a silently clipped alignment."""
    rng = np.random.default_rng(11)
    t = _random_seq(rng, 2000)
    # rotation: the optimal path runs ~1000 rows off the ideal diagonal,
    # far outside a 128-wide band; the in-band "alignment" is mismatch soup
    q = t[1000:] + t[:1000]
    al = BatchAligner(band_width=128)
    res = al.align([(q, t)])
    assert res == [None]
    assert al.n_band_rejects == 1


def test_device_aligner_through_polisher(reference_data):
    """tpu_aligner_batches=1 routes PAF overlaps through the device kernel
    with host fallback; windows/layers must match the host-only path."""
    from racon_tpu.core.polisher import create_polisher, PolisherType

    def build(dev):
        p = create_polisher(
            str(reference_data / "sample_reads.fastq.gz"),
            str(reference_data / "sample_overlaps.paf.gz"),
            str(reference_data / "sample_layout.fasta.gz"),
            PolisherType.kC, 500, 10.0, 0.3, num_threads=2,
            tpu_aligner_batches=dev)
        p.initialize()
        return p

    host = build(0)
    dev = build(1)
    assert len(host.windows) == len(dev.windows)
    n_equal = sum(hw.num_layers == dw.num_layers
                  for hw, dw in zip(host.windows, dev.windows))
    # banded device CIGARs may shift a few window boundaries (the reference
    # accepts the same CPU-vs-GPU divergence); structure must agree broadly
    assert n_equal >= int(0.9 * len(host.windows))


@pytest.mark.parametrize("edge", [512, 1024])
def test_packed_kernel_matches_int8_kernel(edge):
    """The 2-bit packed program returns the int8 program's bytes: random
    ACGT pairs with lengths off a multiple of 4, at the bucket edge and
    skewed against each other."""
    from racon_tpu.ops.align import _kernel_for
    from racon_tpu.ops.encode import encode_padded, pack_2bit, packable

    rng = np.random.default_rng(edge)
    band, n_waves = 128, 2 * edge + 1
    lengths = [(edge - 3, edge - 2), (edge, edge), (edge, edge - 1),
               (edge // 2 + 1, edge - 5), (edge - 7, edge * 3 // 4 + 2),
               (97, 131), (edge - 1, edge // 3 + 3), (5, 9)]
    pairs = []
    for lq, lt in lengths:
        t = _random_seq(rng, lt)
        q = _mutate(rng, t, sub=0.08, ins=0.04, dele=0.04)[:lq]
        pairs.append((q + _random_seq(rng, lq - len(q)), t))
    q_arr, q_lens = encode_padded([p[0] for p in pairs], edge)
    t_arr, t_lens = encode_padded([p[1] for p in pairs], edge)
    assert [tuple(x) for x in zip(q_lens, t_lens)] == lengths
    assert packable(q_arr, q_lens) and packable(t_arr, t_lens)
    offs = np.stack([band_offsets(int(ql), int(tl), band, n_waves)
                     for ql, tl in zip(q_lens, t_lens)])
    lens = (q_lens.astype(np.int32), t_lens.astype(np.int32), offs)
    ops, meta = _kernel_for(band, n_waves, "int32", False)(q_arr, t_arr,
                                                           *lens)
    ops_p, meta_p = _kernel_for(band, n_waves, "int32", True)(
        pack_2bit(q_arr), pack_2bit(t_arr), *lens)
    np.testing.assert_array_equal(np.asarray(ops_p), np.asarray(ops))
    np.testing.assert_array_equal(np.asarray(meta_p), np.asarray(meta))
