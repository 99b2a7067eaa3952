"""Async dispatch pipeline tests (racon_tpu/pipeline).

The pipeline overlaps host pack, device compute, host unpack and
host-fallback work (the stream-overlap role of the reference's per-batch
CUDA streams, cudapolisher.cpp:165-199). The contracts tested here:

  - depth=0 (synchronous bisection path) and depth>=1 (threaded) produce
    BYTE-IDENTICAL output through every integration (fused device engine,
    host POA engine, device aligner, whole polisher);
  - a device chunk that raises mid-pipeline is routed to the host
    fallback, which completes every window (the per-window GPU->CPU
    discipline, cudapolisher.cpp:354-383) — unless RACON_TPU_STRICT;
  - per-stage wall-clock counters accumulate for every stage that ran.
"""

import gzip
import random
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from test_device_poa import _make_windows, _pack  # noqa: E402

from racon_tpu.native import nw_cigar_batch, poa_batch  # noqa: E402
from racon_tpu.ops.align import BatchAligner  # noqa: E402
from racon_tpu.ops.poa import BatchPOA  # noqa: E402
from racon_tpu.ops.poa_fused import FusedPOA  # noqa: E402
from racon_tpu.pipeline import DispatchPipeline, PipelineStats  # noqa: E402

ACGT = b"ACGT"


# ------------------------------------------------------------- unit level

@pytest.mark.parametrize("depth", [0, 1, 2, 3])
def test_stage_order_and_stats(depth):
    """Items traverse pack -> dispatch -> wait -> unpack in order at every
    depth; unpack order equals dispatch order (deterministic assembly)."""
    pl = DispatchPipeline(depth=depth)
    seen = []
    pl.run(range(9),
           pack=lambda i: i * 10,
           dispatch=lambda i, ops: ops + 1,
           wait=lambda h: h + 1,
           unpack=lambda i, r: seen.append((i, r)))
    pl.close()
    assert seen == [(i, i * 10 + 2) for i in range(9)]
    s = pl.stats.snapshot()
    assert s["chunks"] == 9 and s["errors"] == 0
    for k in ("pack_s", "device_s", "unpack_s", "fallback_s"):
        assert s[k] >= 0.0


def test_simulated_device_latency_env(monkeypatch):
    """RACON_TPU_DEVICE_LATENCY_S stalls each chunk's result wait by the
    configured round-trip (the device-dominated bench posture), charges
    the stall to device seconds, and strict-parses."""
    monkeypatch.setenv("RACON_TPU_DEVICE_LATENCY_S", "0.05")
    pl = DispatchPipeline(depth=0)
    assert pl.device_latency_s == 0.05
    seen = []
    t0 = time.perf_counter()
    pl.run(range(4), pack=lambda i: i, dispatch=lambda i, ops: ops,
           wait=lambda h: h, unpack=lambda i, r: seen.append(r))
    wall = time.perf_counter() - t0
    pl.close()
    assert seen == [0, 1, 2, 3]  # output untouched, only paced
    assert wall >= 0.2  # 4 chunks x 50 ms
    assert pl.stats.snapshot()["device_s"] >= 0.2

    from racon_tpu.errors import RaconError
    for bad in ("fast", "-1"):
        monkeypatch.setenv("RACON_TPU_DEVICE_LATENCY_S", bad)
        with pytest.raises(RaconError, match="DEVICE_LATENCY_S"):
            DispatchPipeline(depth=0)
    monkeypatch.delenv("RACON_TPU_DEVICE_LATENCY_S")
    assert DispatchPipeline(depth=0).device_latency_s == 0.0

    # the proportional twin: each chunk's dispatch is followed by a
    # sleep of X times its measured duration (a simulated device whose
    # round-trip scales with batch size)
    monkeypatch.setenv("RACON_TPU_DEVICE_LATENCY_X", "4")
    pl = DispatchPipeline(depth=0)
    assert pl.device_latency_x == 4.0
    seen = []
    t0 = time.perf_counter()
    pl.run(range(2), pack=lambda i: i,
           dispatch=lambda i, ops: time.sleep(0.05) or ops,
           wait=lambda h: h, unpack=lambda i, r: seen.append(r))
    wall = time.perf_counter() - t0
    pl.close()
    assert seen == [0, 1]
    assert wall >= 0.4  # 2 chunks x (50 ms dispatch + 4x sleep)
    monkeypatch.setenv("RACON_TPU_DEVICE_LATENCY_X", "no")
    with pytest.raises(RaconError, match="DEVICE_LATENCY_X"):
        DispatchPipeline(depth=0)


@pytest.mark.parametrize("depth", [0, 2])
def test_error_without_handler_propagates(depth):
    pl = DispatchPipeline(depth=depth)

    def bad_dispatch(i, ops):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        pl.run([1, 2], lambda i: i, bad_dispatch, lambda h: h,
               lambda i, r: None)
    pl.close()
    assert pl.stats.snapshot()["errors"] >= 1


@pytest.mark.parametrize("depth", [0, 2])
def test_error_handler_skips_chunk_and_continues(depth):
    pl = DispatchPipeline(depth=depth)
    failed, done = [], []

    def dispatch(i, ops):
        if i == 3:
            raise RuntimeError("chunk 3 died")
        return ops

    pl.run(range(6), lambda i: i, dispatch, lambda h: h,
           lambda i, r: done.append(i),
           on_error=lambda i, exc: failed.append(i))
    pl.close()
    assert failed == [3]
    assert sorted(done) == [0, 1, 2, 4, 5]
    assert pl.stats.snapshot()["errors"] == 1


@pytest.mark.parametrize("depth", [0, 2])
def test_fallback_pool(depth):
    """submit_fallback runs host work concurrently (inline at depth 0);
    drain re-raises the first failure; seconds accumulate."""
    pl = DispatchPipeline(depth=depth)
    futs = [pl.submit_fallback(lambda k=k: k * k) for k in range(4)]
    pl.drain_fallback()
    assert [f.result() for f in futs] == [0, 1, 4, 9]

    bad = pl.submit_fallback(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        pl.drain_fallback()
    assert bad.exception() is not None
    pl.drain_fallback(ignore_errors=True)  # nothing pending: no-op
    assert pl.stats.snapshot()["fallback_s"] >= 0.0

    # map_fallback: chunked submit half of the reject protocol
    fb = pl.map_fallback(list(range(10)), lambda sub: [i * 2 for i in sub],
                         chunk=4)
    pl.drain_fallback()
    assert [len(sub) for sub, _ in fb] == [4, 4, 2]
    got = [x for sub, fut in fb for x in fut.result()]
    assert got == [i * 2 for i in range(10)]
    pl.close()


def test_base_exception_mid_run_does_not_hang():
    """A BaseException escaping the dispatch loop (the Ctrl-C shape) with
    both bounded queues full must clean up and re-raise promptly instead
    of deadlocking on a worker blocked in a queue put."""
    pl = DispatchPipeline(depth=1)  # tightest queues: worst case

    def dispatch(i, ops):
        if i == 2:
            raise KeyboardInterrupt
        return ops

    t0 = time.perf_counter()
    with pytest.raises(KeyboardInterrupt):
        pl.run(range(50), lambda i: i, dispatch,
               lambda h: time.sleep(0.02), lambda i, r: None)
    assert time.perf_counter() - t0 < 10  # returned, did not hang
    pl.close()


def test_stats_shared_across_pipelines():
    """One PipelineStats instance aggregates several phases' pipelines —
    the polisher wires its align and consensus phases this way."""
    stats = PipelineStats()
    for _ in range(2):
        pl = DispatchPipeline(depth=2, stats=stats)
        pl.run(range(3), lambda i: i, lambda i, o: o, lambda h: h,
               lambda i, r: None)
        pl.close()
    assert stats.snapshot()["chunks"] == 6


def test_overlap_actually_happens():
    """At depth 2 a slow wait must overlap the next item's pack: total
    wall < sum of stage times. (Generous margin — CI boxes are noisy.)"""
    pl = DispatchPipeline(depth=2)
    t0 = time.perf_counter()
    pl.run(range(4),
           pack=lambda i: time.sleep(0.05),
           dispatch=lambda i, ops: i,
           wait=lambda h: time.sleep(0.05),
           unpack=lambda i, r: None)
    wall = time.perf_counter() - t0
    pl.close()
    s = pl.stats.snapshot()
    stage_sum = s["pack_s"] + s["device_s"] + s["unpack_s"]
    assert stage_sum >= 0.35  # 8 x 0.05s of stage work happened
    assert wall < stage_sum * 0.85  # ...in less wall time than its sum


# ------------------------------------------------------ engine integration

@pytest.fixture
def fused_fixture(monkeypatch):
    # one-device mesh so batch_rows=4 is not rounded up to the 8-virtual-
    # device width (chunk/launch counts below assume B=4); sharded-vs-
    # single equivalence is covered by test_fused_sharded_matches_single
    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    rng = random.Random(5)
    windows, _ = _make_windows(rng, 10, length=220, depth=7, rate=0.12)
    packed = [_pack(w) for w in windows]
    host = poa_batch(packed, 3, -5, -4, n_threads=2)
    kw = dict(max_nodes=768, max_len=384, batch_rows=4,
              depth_buckets=(4, 8))
    return packed, host, kw


def test_fused_depth0_vs_depth2_byte_identical(fused_fixture):
    packed, host, kw = fused_fixture
    outs = {}
    for depth in (0, 2):
        eng = FusedPOA(3, -5, -4, num_threads=2, **kw)
        with DispatchPipeline(depth=depth) as pl:
            res, st = eng.consensus([list(p) for p in packed], pipeline=pl)
            stats = pl.stats.snapshot()
        assert (st == 0).all(), st.tolist()
        assert stats["chunks"] == 3 and stats["launches"] == 6
        outs[depth] = res
    for (c0, v0), (c2, v2), (ch, vh) in zip(outs[0], outs[2], host):
        assert c0 == c2 == ch
        np.testing.assert_array_equal(v0, v2)
        np.testing.assert_array_equal(v0, vh)


def test_fused_chunk_failure_falls_back_to_host(fused_fixture, monkeypatch,
                                                capsys):
    """A device chunk raising mid-pipeline must not lose windows: the
    fallback pool completes every one, byte-identical to the host engine."""
    packed, host, kw = fused_fixture
    monkeypatch.delenv("RACON_TPU_STRICT", raising=False)
    eng = FusedPOA(3, -5, -4, num_threads=2, **kw)
    calls = {"n": 0}
    orig = eng._call

    def flaky(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 3:  # 2 chained calls per chunk: kill chunk 2
            raise RuntimeError("injected device fault")
        return orig(*args, **kwargs)

    monkeypatch.setattr(eng, "_call", flaky)
    with DispatchPipeline(depth=2) as pl:
        res, st = eng.consensus([list(p) for p in packed], pipeline=pl)
        stats = pl.stats.snapshot()
    assert "device chunk failed" in capsys.readouterr().err
    assert stats["errors"] == 1
    assert (st == 1).sum() == 4  # the failed chunk's windows, host-built
    assert (st == 0).sum() == 6
    assert eng.n_fallback == 4
    for (c, v), (ch, vh) in zip(res, host):  # nothing lost, nothing wrong
        assert c == ch
        np.testing.assert_array_equal(v, vh)


def test_fused_persistent_failure_trips_circuit_breaker(fused_fixture,
                                                        monkeypatch):
    """A device failing EVERY chunk (a lost device, OOM) must not burn a
    pack+dispatch attempt per chunk: after 3 consecutive chunk failures
    the device pass aborts — restoring the whole-batch fallback — and
    BatchPOA's non-strict catch still host-polishes every window."""
    from racon_tpu.ops import poa_fused

    packed, host, kw = fused_fixture
    monkeypatch.delenv("RACON_TPU_STRICT", raising=False)
    monkeypatch.setenv("RACON_TPU_ENGINE", "fused")
    monkeypatch.setenv("RACON_TPU_FUSED_FALLBACK", "host")

    calls = {"n": 0}

    class DeadDevice(poa_fused.FusedPOA):
        def __init__(self, *a, **k):
            k.update(kw)
            super().__init__(*a, **k)

        def _call(self, *a, **k):
            calls["n"] += 1
            raise RuntimeError("device gone")

    monkeypatch.setattr(poa_fused, "FusedPOA", DeadDevice)
    rng = random.Random(5)
    windows, _ = _make_windows(rng, 10, length=220, depth=7, rate=0.12)
    eng = BatchPOA(3, -5, -4, 220, num_threads=2, device_batches=1)
    eng.generate_consensus(windows, trim=False)
    assert calls["n"] == 3  # breaker tripped: not one attempt per chunk
    for w, (hc, _) in zip(windows, host):
        assert w.polished and w.consensus == hc


def test_fused_chunk_failure_strict_raises(fused_fixture, monkeypatch):
    packed, _, kw = fused_fixture
    monkeypatch.setenv("RACON_TPU_STRICT", "1")
    eng = FusedPOA(3, -5, -4, num_threads=2, **kw)
    monkeypatch.setattr(
        eng, "_call",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("injected")))
    with DispatchPipeline(depth=2) as pl:
        with pytest.raises(RuntimeError, match="injected"):
            eng.consensus([list(p) for p in packed], pipeline=pl)


def test_host_engine_depth0_vs_depth2_byte_identical():
    """BatchPOA's host chunk loop through the pipeline: same bytes at
    both depths (pack/native-call/trim really did stay independent)."""
    outs = {}
    for depth in (0, 2):
        rng = random.Random(17)
        windows, _ = _make_windows(rng, 12, length=220, depth=6, rate=0.1)
        with DispatchPipeline(depth=depth) as pl:
            eng = BatchPOA(3, -5, -4, 220, num_threads=2, pipeline=pl)
            eng.generate_consensus(windows, trim=False)
            stats = pl.stats.snapshot()
        assert stats["launches"] >= 1 and stats["device_s"] > 0
        outs[depth] = [(w.consensus, w.polished) for w in windows]
    assert outs[0] == outs[2]


def test_aligner_depth0_vs_depth2_with_reject_fallback():
    """BatchAligner through the pipeline: identical accept/reject results
    at both depths, on_reject fires for unbucketable AND band-clipped
    pairs, and the fallback pool host-aligns them concurrently — the
    polisher's exact wiring."""
    rng = np.random.default_rng(7)
    bases = np.frombuffer(ACGT, np.uint8)

    def rand(n):
        return bytes(rng.choice(bases, n))

    def mut(seq):
        out = bytearray()
        for ch in seq:
            r = rng.random()
            if r < 0.03:
                continue
            out.append(int(bases[rng.integers(4)]) if r < 0.08 else ch)
            if rng.random() < 0.03:
                out.append(int(bases[rng.integers(4)]))
        return bytes(out)

    pairs = []
    for _ in range(16):
        t = rand(int(rng.integers(200, 480)))
        pairs.append((mut(t), t))
    pairs.append((rand(900), rand(880)))  # beyond max_length: upfront reject
    long_idx = len(pairs) - 1

    outs = {}
    for depth in (0, 2):
        al = BatchAligner(band_width=64, max_length=512)
        fb = []
        reasons = {}
        with DispatchPipeline(depth=depth) as pl:
            def on_reject(idxs, reason, pl=pl, fb=fb, reasons=reasons):
                reasons.update(dict.fromkeys(idxs, reason))
                fb.extend(pl.map_fallback(
                    idxs, lambda sub: nw_cigar_batch(
                        [pairs[i] for i in sub], n_threads=2)))

            runs = al.align(list(pairs), pipeline=pl, on_reject=on_reject)
            pl.drain_fallback()
        rejected = sorted(i for sub, _ in fb for i in sub)
        assert long_idx in rejected
        # each rejected pair is reported once, under why it left the device
        assert sorted(reasons) == rejected
        assert reasons[long_idx] == "ladder"
        assert set(reasons.values()) <= {"ladder", "band", "cost"}
        cigars = {}
        for sub, fut in fb:
            for i, c in zip(sub, fut.result()):
                cigars[i] = c
        # complete coverage: every pair has device runs XOR a fallback CIGAR
        for i in range(len(pairs)):
            assert (runs[i] is not None) != (i in cigars)
            if i in cigars:
                assert cigars[i]
        outs[depth] = (runs, rejected, cigars)
    assert outs[0] == outs[2]


# --------------------------------------------------- polisher end-to-end

def _synth_dataset(tmp_path, rng):
    """Compact ONT-style synthetic polishing job (the test_ngs recipe)."""
    truth = bytes(rng.choice(ACGT) for _ in range(3000))

    def mutate(s, rate):
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

    draft = mutate(truth, 0.04)
    reads, paf = [], []
    read_len, step = 700, 120
    for start in range(0, len(truth) - read_len, step):
        read = mutate(truth[start:start + read_len], 0.05)
        name = f"r{start}"
        reads.append((name, read))
        t_begin = min(start, len(draft) - 1)
        t_end = min(start + read_len, len(draft))
        paf.append(f"{name}\t{len(read)}\t0\t{len(read)}\t+\tdraft\t"
                   f"{len(draft)}\t{t_begin}\t{t_end}\t{read_len}\t"
                   f"{read_len}\t60")
    reads_path = tmp_path / "reads.fasta.gz"
    with gzip.open(reads_path, "wb") as f:
        for name, read in reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    paf_path = tmp_path / "ovl.paf.gz"
    with gzip.open(paf_path, "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    draft_path = tmp_path / "draft.fasta.gz"
    with gzip.open(draft_path, "wb") as f:
        f.write(b">draft\n" + draft + b"\n")
    return reads_path, paf_path, draft_path


def test_polisher_depth0_vs_depth2_end_to_end(tmp_path):
    """The whole pipeline (host engine + device aligner + fallback pool)
    at depth 0 vs depth 2: identical FASTA out, and the stage counters
    populated — the acceptance contract, on synthetic data so it runs
    without the sample fixture."""
    from racon_tpu.core.polisher import PolisherType, create_polisher

    paths = _synth_dataset(tmp_path, random.Random(23))
    outs, stats = {}, {}
    for depth in (0, 2):
        p = create_polisher(*(str(x) for x in paths), PolisherType.kC,
                            500, -1.0, 0.3, num_threads=2,
                            tpu_aligner_batches=1,
                            tpu_pipeline_depth=depth)
        p.initialize()
        outs[depth] = [(s.name, s.data) for s in p.polish()]
        stats[depth] = p.stage_stats
        assert p.n_aligner_pairs > 0
        assert (p.n_aligner_device + p.n_aligner_host_fallback
                == p.n_aligner_pairs)
    assert outs[0] == outs[2]
    for depth in (0, 2):
        s = stats[depth]
        assert s["launches"] >= 1 and s["chunks"] >= 1
        assert s["device_s"] > 0  # a dead pipeline would read ~0 here


DATA = "/root/reference/test/data/"
sample_data = pytest.mark.skipif(
    not __import__("os").path.isdir(DATA),
    reason="reference sample data not available")


@sample_data
def test_sample_host_depth2_matches_committed_golden(monkeypatch):
    """Acceptance pin on the real sample: the depth-2 pipelined host run
    reproduces the committed synchronous golden byte-for-byte."""
    import os

    from racon_tpu.core.polisher import PolisherType, create_polisher

    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    p = create_polisher(
        DATA + "sample_reads.fastq.gz", DATA + "sample_overlaps.paf.gz",
        DATA + "sample_layout.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
        True, 5, -4, -8, num_threads=4, tpu_pipeline_depth=2)
    p.initialize()
    out = bytearray()
    for seq in p.polish():
        out += b">" + seq.name.encode() + b"\n" + seq.data + b"\n"
    golden = os.path.join(os.path.dirname(__file__), "data",
                          "sample_golden.fasta")
    with open(golden, "rb") as fh:
        assert bytes(out) == fh.read()


@sample_data
def test_sample_fused_depth0_vs_depth2(monkeypatch):
    """Fused engine on real data (the 24 shallowest sample windows, the
    affordable slice the default suite already compiles): depth 0 and
    depth 2 must agree byte-for-byte."""
    from racon_tpu.core.polisher import PolisherType, create_polisher

    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    p = create_polisher(DATA + "sample_reads.fastq.gz",
                        DATA + "sample_overlaps.paf.gz",
                        DATA + "sample_layout.fasta.gz", PolisherType.kC,
                        500, 10.0, 0.3, True, 5, -4, -8, num_threads=2)
    p.initialize()
    wins = sorted((w for w in p.windows if len(w.sequences) >= 3),
                  key=lambda w: len(w.sequences))[:24]
    packed = [[(w.sequences[i], w.qualities[i], w.positions[i][0],
                w.positions[i][1]) for i in range(len(w.sequences))]
              for w in wins]
    outs = {}
    for depth in (0, 2):
        eng = FusedPOA(5, -4, -8, num_threads=2, batch_rows=8)
        with DispatchPipeline(depth=depth) as pl:
            res, st = eng.consensus([list(p) for p in packed],
                                    fallback=False, pipeline=pl)
        assert (st == 0).all()
        outs[depth] = res
    for (c0, v0), (c2, v2) in zip(outs[0], outs[2]):
        assert c0 == c2
        np.testing.assert_array_equal(v0, v2)
