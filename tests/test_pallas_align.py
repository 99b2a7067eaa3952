"""Pallas wavefront-aligner kernel tests (ops/align_pallas.py),
interpret mode — plus the dtype-shrinking and base-packing identity
pins for the aligner plane.

The kernel must reproduce the XLA banded program EXACTLY — same DP,
same INF clamp, same tie order, same traceback walk (touched-edge flags
and final distance included) — because BatchAligner's rejection
decisions (band-clip -> host realign) ride on them. Fuzzed across
random pairs, band-riding pathological pairs, bucket-filling lengths,
and the int16 envelope, in every (dtype, packed) variant.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from racon_tpu.ops import align_pallas
from racon_tpu.ops.align import (BatchAligner, _kernel_for, band_offsets,
                                 decode_paths)
from racon_tpu.ops.dtypes import (aligner_int16_ok, dtype_mode,
                                  poa_int16_ok, resolve_dtype)
from racon_tpu.ops.encode import (encode_padded, pack_2bit, packable,
                                  unpack_2bit_jax)

ACGT = b"ACGT"


def _mutate(rng, s, rate):
    out = bytearray()
    for c in s:
        r = rng.random()
        if r < rate / 3:
            continue
        if r < 2 * rate / 3:
            out.append(rng.choice(ACGT))
            out.append(c)
            continue
        if r < rate:
            out.append(rng.choice(ACGT))
            continue
        out.append(c)
    return bytes(out)


def _xla_decode(pairs, edge, band, dtype="int32"):
    """The XLA reference path: kernel + device traceback -> (runs,
    touched, dist)."""
    n_waves = 2 * edge + 1
    q_arr, q_lens = encode_padded([p[0] for p in pairs], edge)
    t_arr, t_lens = encode_padded([p[1] for p in pairs], edge)
    offs = np.stack([band_offsets(int(ql), int(tl), band, n_waves)
                     for ql, tl in zip(q_lens, t_lens)])
    fn = _kernel_for(band, n_waves, dtype, False)
    ops, meta = fn(q_arr, t_arr, q_lens.astype(np.int32),
                   t_lens.astype(np.int32), offs)
    meta = np.asarray(meta)
    runs = decode_paths(np.asarray(ops).T, meta[:, 0])
    return (runs, meta[:, 2] > 0, meta[:, 1].astype(np.int64),
            (q_arr, t_arr, q_lens, t_lens, offs))


def _pallas_decode(operands, edge, band, dtype, packed):
    q_arr, t_arr, q_lens, t_lens, offs = operands
    fn = align_pallas.wavefront_align(edge, band, dtype, packed,
                                      interpret=True)
    qx, tx = align_pallas.build_ext(q_arr, t_arr, band)
    if packed:
        qx, tx = pack_2bit(qx), pack_2bit(tx)
    ops, meta = fn(qx, tx, q_lens.astype(np.int32),
                   t_lens.astype(np.int32), offs)
    meta = np.asarray(meta)
    runs = decode_paths(np.asarray(ops), meta[:, 0])
    return runs, meta[:, 2] > 0, meta[:, 1].astype(np.int64)


@pytest.mark.parametrize("dtype", ["int32", "int16"])
@pytest.mark.parametrize("packed", [False, True])
def test_pallas_matches_xla_fuzz(dtype, packed):
    """Random pairs across lengths (bucket-filling included), both
    dtypes, both operand packings: identical runs, touched flags and
    distances."""
    rng = random.Random(17)
    edge, band = 512, 64
    pairs = []
    for _ in range(5):
        t = bytes(rng.choice(ACGT) for _ in range(rng.randint(30, edge)))
        pairs.append((_mutate(rng, t, 0.15)[:edge], t))
    pairs.append((b"A" * edge, b"T" * edge))   # maximal cost, full bucket
    pairs.append((b"A", b"A"))                 # minimal pair (pad lanes)

    runs_x, touched_x, dist_x, operands = _xla_decode(pairs, edge, band,
                                                      dtype)
    runs_p, touched_p, dist_p = _pallas_decode(operands, edge, band,
                                               dtype, packed)
    assert runs_p == runs_x
    assert touched_p.tolist() == touched_x.tolist()
    assert dist_p.tolist() == dist_x.tolist()


def test_pallas_band_edge_cases_match():
    """Pairs whose optimal path rides or crosses the band boundary —
    the rejection signals (touched / suspicious-cost) must agree, since
    they decide which pairs get host-realigned."""
    rng = random.Random(23)
    edge, band = 512, 32
    base = bytes(rng.choice(ACGT) for _ in range(400))
    pairs = [
        (base[100:] + base[:100], base),           # rotation: off-band
        (base[:200] + base[300:], base),           # 100 bp deletion
        (base, base[:150]),                        # very skewed lengths
        (_mutate(rng, base, 0.4)[:edge], base),    # mismatch soup
    ]
    runs_x, touched_x, dist_x, operands = _xla_decode(pairs, edge, band)
    runs_p, touched_p, dist_p = _pallas_decode(operands, edge, band,
                                               "int32", False)
    assert runs_p == runs_x
    assert touched_p.tolist() == touched_x.tolist()
    assert dist_p.tolist() == dist_x.tolist()
    # the cases were chosen to exercise the signal: at least one pair
    # must actually trip it, or this test pins nothing
    assert touched_x.any() or (dist_x > 0.4 * 400).any()


def test_int16_envelope_predicates():
    """The overflow proofs' exact boundaries."""
    # aligner: INF16 = 1<<14 must exceed every real score (<= 2*edge)
    assert aligner_int16_ok(4096)
    assert aligner_int16_ok(8191)
    assert not aligner_int16_ok(8192)
    # POA: (N + L + 2) * mp <= 16383
    assert poa_int16_ok(1024, 1021, 5, -4, -8)        # 16376 <= 16383
    assert not poa_int16_ok(1024, 1022, 5, -4, -8)    # 16384 > 16383
    mp3 = (16383 // 3) - 2
    assert poa_int16_ok(mp3 // 2, mp3 - mp3 // 2, 3, -3, -1)  # == bound
    assert not poa_int16_ok(mp3 // 2 + 1, mp3 - mp3 // 2, 3, -3, -1)
    # the envelope session bucket at default scoring stays int32
    assert not poa_int16_ok(2048, 640, 5, -4, -8)
    assert poa_int16_ok(2048, 640, 3, -5, -4)


def test_int16_bitwise_identical_at_max_cost():
    """int16 vs int32 XLA kernels: RAW outputs (paths, path lengths,
    distances, edge flags) must be bit-identical, including the
    worst-cost pair the bucket can hold (cost == edge, the envelope's
    score ceiling)."""
    edge, band = 512, 64
    rng = random.Random(3)
    t = bytes(rng.choice(ACGT) for _ in range(edge))
    pairs = [(b"G" * edge, b"C" * edge), (_mutate(rng, t, 0.1)[:edge], t)]
    n_waves = 2 * edge + 1
    q_arr, q_lens = encode_padded([p[0] for p in pairs], edge)
    t_arr, t_lens = encode_padded([p[1] for p in pairs], edge)
    offs = np.stack([band_offsets(int(ql), int(tl), band, n_waves)
                     for ql, tl in zip(q_lens, t_lens)])
    outs = {}
    for dt in ("int32", "int16"):
        ops, meta = _kernel_for(band, n_waves, dt, False)(
            q_arr, t_arr, q_lens.astype(np.int32),
            t_lens.astype(np.int32), offs)
        outs[dt] = (np.asarray(ops), np.asarray(meta))
    np.testing.assert_array_equal(outs["int32"][0], outs["int16"][0])
    # finite distances equal; sentinel distances (none here) aside
    np.testing.assert_array_equal(outs["int32"][1], outs["int16"][1])
    assert outs["int32"][1][0, 1] == edge  # the ceiling really was hit


def test_packed_encode_roundtrip():
    codes, lens = encode_padded([b"ACGTACG", b"AC", b"ACGTNACG"], 12)
    assert packable(codes[:2], lens[:2])
    assert not packable(codes, lens)  # the N row
    packed = pack_2bit(codes[:2])
    assert packed.shape == (2, 3)
    back = np.asarray(unpack_2bit_jax(packed, 12, lens[:2]))
    np.testing.assert_array_equal(back, codes[:2])


def test_batch_aligner_pallas_identical_including_rejects():
    """BatchAligner end-to-end: use_pallas=True must produce the SAME
    per-pair result list as the XLA path — accepted runs, band-clip
    rejects (None), unbucketable pairs (None) — across mixed buckets,
    N-containing pairs (packed fallback) and the empty pair."""
    rng = random.Random(31)
    pairs = []
    for n in (100, 500, 600, 1500):
        t = bytes(rng.choice(ACGT) for _ in range(n))
        pairs.append((_mutate(rng, t, 0.1), t))
    t = bytes(rng.choice(ACGT) for _ in range(800))
    pairs.append((t[400:] + t[:400], t))          # rotation: rejected
    pairs.append((b"ACGNNNGT" * 40, b"ACGTACGT" * 40))  # N bases
    pairs.append((b"", b"ACGT"))                  # unbucketable
    pairs.append((b"A" * 99999, b"A" * 99999))    # beyond max bucket

    base = BatchAligner(max_length=2048, use_pallas=False).align(pairs)
    pal = BatchAligner(max_length=2048, use_pallas=True).align(pairs)
    assert pal == base
    assert base[-1] is None and base[-2] is None


def test_batch_aligner_dtype_and_packing_knobs_identical(monkeypatch):
    """RACON_TPU_DTYPE=int32 (the oracle) and RACON_TPU_PACK_BASES=0
    must not change a single result vs the shrunk/packed defaults."""
    rng = random.Random(7)
    pairs = []
    for n in (300, 700, 700):
        t = bytes(rng.choice(ACGT) for _ in range(n))
        pairs.append((_mutate(rng, t, 0.12), t))
    base = BatchAligner().align(pairs)
    monkeypatch.setenv("RACON_TPU_DTYPE", "int32")
    monkeypatch.setenv("RACON_TPU_PACK_BASES", "0")
    wide = BatchAligner().align(pairs)
    assert wide == base
    monkeypatch.setenv("RACON_TPU_DTYPE", "auto")
    monkeypatch.delenv("RACON_TPU_PACK_BASES")
    again = BatchAligner(use_pallas=True).align(pairs)
    assert again == base


def test_dtype_mode_resolution(monkeypatch):
    monkeypatch.delenv("RACON_TPU_DTYPE", raising=False)
    assert dtype_mode() == "auto"
    assert resolve_dtype(True) == "int16"
    assert resolve_dtype(False) == "int32"
    assert resolve_dtype(True, {"dtype": "int32"}) == "int32"
    monkeypatch.setenv("RACON_TPU_DTYPE", "int32")
    assert resolve_dtype(True) == "int32"
    monkeypatch.setenv("RACON_TPU_DTYPE", "int16")
    # forced narrow still respects the proof — and beats the table
    assert resolve_dtype(True, {"dtype": "int32"}) == "int16"
    assert resolve_dtype(False) == "int32"
    monkeypatch.setenv("RACON_TPU_DTYPE", "bogus")
    assert dtype_mode() == "auto"


def test_aligner_fits_vmem_envelope():
    """The aligner kernel's VMEM gate: small buckets resident, the
    giant ones fall back to XLA; int16 widens nothing the proof
    forbids."""
    assert align_pallas.fits_vmem(512, 64)
    assert align_pallas.fits_vmem(1024, 128)
    assert align_pallas.fits_vmem(4096, 512)
    assert not align_pallas.fits_vmem(16384, 1664)
    assert not align_pallas.fits_vmem(65536, 6656)
