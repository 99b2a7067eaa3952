"""Occupancy-aware batch scheduler tests (racon_tpu/sched).

The scheduler's contract has three legs, each pinned here:

  - CORRECTNESS: adaptive ladders and sorted packing change only WHICH
    static shapes are compiled and HOW jobs are ordered into chunks —
    output is byte-identical with the scheduler on vs off, for all three
    device engines (aligner, session POA, fused POA) and end-to-end
    through the polisher at pipeline depths 0 and 2.
  - OPTIMALITY: the ladder DPs are exact under their cost models
    (checked against brute force on small histograms) and adaptive
    occupancy is >= static occupancy on skewed inputs.
  - ACCOUNTING: per-bucket occupancy counters sum to exactly the cells
    the device was asked to process, and the resilience layer's
    per-chunk fault hooks still route repacked chunks to
    fallback/quarantine correctly.
"""

import itertools
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from test_device_poa import _make_windows, _pack  # noqa: E402

from racon_tpu.native import nw_cigar_batch, poa_batch  # noqa: E402
from racon_tpu.ops.align import BatchAligner  # noqa: E402
from racon_tpu.ops.poa_graph import DeviceGraphPOA  # noqa: E402
from racon_tpu.ops.poa_fused import FusedPOA  # noqa: E402
from racon_tpu.pipeline import DispatchPipeline  # noqa: E402
from racon_tpu.sched import (BatchScheduler, OccupancyStats,  # noqa: E402
                             ladder_1d, ladder_2d, padded_cost_1d)

ACGT = b"ACGT"


# ------------------------------------------------------------ ladder DPs

def test_ladder_1d_exact_vs_brute_force():
    rng = random.Random(0)
    for _ in range(60):
        vals = [rng.randrange(1, 40) for _ in range(rng.randrange(1, 10))]
        k = rng.randrange(1, 5)
        edges = ladder_1d(vals, k)
        assert 1 <= len(edges) <= k
        assert max(edges) >= max(vals)  # every job covered
        got = padded_cost_1d(vals, edges)
        uniq = sorted(set(vals))
        best = min(
            padded_cost_1d(vals, comb)
            for r in range(1, min(k, len(uniq)) + 1)
            for comb in itertools.combinations(uniq, r)
            if comb[-1] == uniq[-1])
        assert got == pytest.approx(best)


def test_ladder_1d_quantum_and_empty():
    edges = ladder_1d([100, 600, 601, 4000], 3, quantum=256)
    assert all(e % 256 == 0 for e in edges)
    assert max(edges) >= 4000
    assert ladder_1d([], 4) == []


def test_ladder_2d_covers_and_beats_envelope():
    # bimodal: many small graphs, few envelope-sized ones — the adaptive
    # grid must cover everything with <= k shapes and cost far less than
    # one worst-case envelope for all
    shapes = [(300, 200)] * 50 + [(2000, 640)] * 5
    grid = ladder_2d(shapes, 4, quantum_a=64, quantum_b=64)
    assert 1 <= len(grid) <= 4
    for a, b in shapes:
        assert any(ga >= a and gb >= b for ga, gb in grid)
    cost = sum(min(ga * gb for ga, gb in grid if ga >= a and gb >= b)
               for a, b in shapes)
    assert cost < len(shapes) * 2048 * 640 / 3


def test_ladder_2d_splits_equal_a_runs():
    # jobs sharing `a` but split in `b` may belong to different buckets:
    # the low-b majority must not inherit the tall outlier's b edge
    shapes = [(100, 10)] * 30 + [(100, 500)]
    grid = ladder_2d(shapes, 2)
    assert (100, 10) in grid


# ------------------------------------------------- occupancy accounting

def _noisy_pairs(rng, n=18, lo=150, hi=700):
    bases = np.frombuffer(ACGT, np.uint8)

    def rand(m):
        return bytes(rng.choice(bases, m))

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
    for _ in range(n):
        t = rand(int(rng.integers(lo, hi)))
        pairs.append((mut(t), t))
    return pairs


@pytest.mark.parametrize("adaptive", [False, True])
def test_aligner_occupancy_counters_sum_to_job_cells(adaptive):
    """useful + padded == lanes * bucket capacity, and useful equals the
    independently recomputed per-pair DP cells — the counters account
    for every cell the device was asked to process."""
    rng = np.random.default_rng(3)
    pairs = _noisy_pairs(rng)
    sched = BatchScheduler(adaptive=adaptive)
    al = BatchAligner(band_width=64, max_length=1024, scheduler=sched)
    al.align(list(pairs))
    snap = sched.stats.snapshot()["aligner"]
    assert snap["buckets"], "no batches recorded"
    # band_width=64 is explicit: quantized to 64 for every bucket
    band = 64
    total_useful = sum(b["useful_cells"] for b in snap["buckets"].values())
    expect_useful = sum((len(q) + len(t) + 1) * band for q, t in pairs)
    assert total_useful == expect_useful
    total_jobs = sum(b["jobs"] for b in snap["buckets"].values())
    assert total_jobs == len(pairs)
    import ast

    for bucket_s, b in snap["buckets"].items():
        edge, bucket_band = ast.literal_eval(bucket_s)
        assert bucket_band == band
        capacity = (2 * edge + 1) * band  # n_waves * band per lane
        assert (b["useful_cells"] + b["padded_cells"]
                == b["lanes"] * capacity)
        assert 0 < b["occupancy_pct"] <= 100.0
    if adaptive:
        # data-derived shapes are new to this process: compile telemetry
        # must have charged them
        assert snap.get("compiles", 0) >= 1


def test_aligner_adaptive_occupancy_not_worse_and_results_identical():
    """Adaptive ladders on a skewed length histogram: occupancy >= the
    static ladder's, per-pair results identical and in input order."""
    rng = np.random.default_rng(11)
    pairs = _noisy_pairs(rng, n=24, lo=150, hi=500)
    pairs += _noisy_pairs(rng, n=2, lo=3000, hi=3500)
    rng_order = np.random.default_rng(1)
    rng_order.shuffle(pairs)  # arrival order decorrelated from length

    occ, res = {}, {}
    for adaptive in (False, True):
        sched = BatchScheduler(adaptive=adaptive)
        al = BatchAligner(band_width=64, scheduler=sched)
        res[adaptive] = al.align(list(pairs))
        occ[adaptive] = sched.stats.snapshot()["aligner"]["occupancy_pct"]
    # order restoration: identical per-index results despite shape-sorted
    # packing rebuilding every chunk in a different order
    assert res[False] == res[True]
    assert occ[True] >= occ[False]


def test_aligner_adaptive_reuse_matches_static_and_bounds_compiles():
    """A reused adaptive aligner must start every align() from the
    static ladder again (no state leaks between batches), and each call
    derives at most len(BUCKETS) compiled (edge, band) combos."""
    rng = np.random.default_rng(5)
    batches = [_noisy_pairs(rng, n=10, lo=150, hi=400),
               _noisy_pairs(rng, n=10, lo=300, hi=900)]
    static = BatchAligner(band_width=64,
                          scheduler=BatchScheduler(adaptive=False))
    expect = [static.align(list(b)) for b in batches]
    ad = BatchAligner(band_width=64,
                      scheduler=BatchScheduler(adaptive=True))
    got = [ad.align(list(b)) for b in batches]
    assert got == expect
    snap = ad.sched.stats.snapshot()["aligner"]
    assert len(snap["buckets"]) <= 2 * len(BatchAligner.BUCKETS)


# -------------------------------------------- per-engine byte identity

def test_session_adaptive_vs_static_byte_identical():
    rng = random.Random(5)
    windows, _ = _make_windows(rng, 12, length=80, depth=6)
    windows += _make_windows(rng, 6, length=90, depth=5,
                             spanning=False)[0]
    packed = [_pack(w) for w in windows]
    host = poa_batch(packed, 3, -5, -4, n_threads=2)
    outs = {}
    for adaptive in (False, True):
        eng = DeviceGraphPOA(3, -5, -4, num_threads=2, max_nodes=192,
                             max_len=128, buckets=((96, 96), (192, 128)),
                             batch_rows=8,
                             scheduler=BatchScheduler(adaptive=adaptive))
        dev, st = eng.consensus(packed)
        assert (st == 0).all(), st.tolist()
        outs[adaptive] = dev
        snap = eng.sched.stats.snapshot()["session"]
        for bucket_s, b in snap["buckets"].items():
            assert b["useful_cells"] + b["padded_cells"] > 0
            assert 0 < b["occupancy_pct"] <= 100.0
    for (c0, v0), (c1, v1), (ch, vh) in zip(outs[False], outs[True], host):
        assert c0 == c1 == ch  # adaptive == static == host engine
        np.testing.assert_array_equal(v0, v1)
        np.testing.assert_array_equal(v0, vh)


@pytest.fixture
def fused_setup(monkeypatch):
    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    rng = random.Random(5)
    windows, _ = _make_windows(rng, 10, length=220, depth=7, rate=0.12)
    packed = [_pack(w) for w in windows]
    host = poa_batch(packed, 3, -5, -4, n_threads=2)
    kw = dict(max_nodes=768, max_len=384, batch_rows=4,
              depth_buckets=(4, 8))
    return packed, host, kw


def test_fused_adaptive_vs_static_depth0_and_depth2(fused_setup):
    """Fused engine, scheduler on/off x pipeline depth 0/2: all four runs
    byte-identical to the host engine. The adaptive depth ladder derives
    from the actual chunk-max depths (7 here), replacing the (4, 8)
    static chain."""
    packed, host, kw = fused_setup
    outs = {}
    for adaptive in (False, True):
        for depth in (0, 2):
            eng = FusedPOA(3, -5, -4, num_threads=2,
                           scheduler=BatchScheduler(adaptive=adaptive),
                           **kw)
            if adaptive:
                # precompile-style pre-adaptation must be idempotent:
                # consensus()'s own derivation keeps the same ladder, so
                # warmed programs are the dispatched programs
                eng.adapt([list(p) for p in packed])
                assert eng.depth_buckets == (7,)
            with DispatchPipeline(depth=depth) as pl:
                res, st = eng.consensus([list(p) for p in packed],
                                        pipeline=pl)
            assert (st == 0).all(), st.tolist()
            outs[adaptive, depth] = res
            if adaptive:
                assert eng.depth_buckets == (7,)
                snap = eng.sched.stats.snapshot()["fused"]
                # layer accounting: useful layers == the windows' real
                # depth total; padded layers fill the rest of each call
                useful = sum(b["useful_cells"]
                             for b in snap["buckets"].values())
                assert useful == sum(len(p) - 1 for p in packed)
    ref = outs[False, 0]
    for key, res in outs.items():
        for (c, v), (cr, vr), (ch, vh) in zip(res, ref, host):
            assert c == cr == ch, key
            np.testing.assert_array_equal(v, vr)


# ------------------------------------------------ polisher end-to-end

def test_polisher_fasta_identical_sched_on_off_depth0_and_depth2(
        tmp_path, monkeypatch):
    """The acceptance pin: polished FASTA byte-identical with the
    scheduler on vs off, at pipeline depths 0 and 2, with the device
    aligner armed (the full pack -> dispatch -> unpack -> fallback
    path)."""
    from test_pipeline import _synth_dataset

    from racon_tpu.core.polisher import PolisherType, create_polisher

    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    paths = _synth_dataset(tmp_path, random.Random(23))
    outs = {}
    for adaptive in (False, True):
        for depth in (0, 2):
            p = create_polisher(*(str(x) for x in paths), PolisherType.kC,
                                500, -1.0, 0.3, num_threads=2,
                                tpu_aligner_batches=1,
                                tpu_pipeline_depth=depth,
                                tpu_adaptive_buckets=adaptive)
            p.initialize()
            outs[adaptive, depth] = [(s.name, s.data) for s in p.polish()]
            occ = p.occupancy_stats
            assert "aligner" in occ and occ["aligner"]["buckets"]
            assert p.scheduler.adaptive == adaptive
    ref = outs[False, 0]
    for key, out in outs.items():
        assert out == ref, f"FASTA diverged for sched/depth {key}"


# --------------------------------------- resilience interplay (repacked
# chunks still route through the per-chunk fault hooks)

def test_repacked_chunk_fault_still_falls_back(monkeypatch, capsys):
    """With adaptive buckets + sorted packing armed, an injected device-
    stage fault on a repacked chunk must still route its pairs to the
    host fallback — every pair aligned, none lost."""
    from racon_tpu.resilience import reset_fault_plan

    monkeypatch.delenv("RACON_TPU_STRICT", raising=False)
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", "device:chunk=0:raise")
    reset_fault_plan()
    try:
        rng = np.random.default_rng(7)
        pairs = _noisy_pairs(rng, n=12)
        sched = BatchScheduler(adaptive=True)
        al = BatchAligner(band_width=64, scheduler=sched)
        fb = []
        with DispatchPipeline(depth=2) as pl:
            def on_reject(idxs, reason, pl=pl, fb=fb):
                fb.extend(pl.map_fallback(
                    idxs, lambda sub: nw_cigar_batch(
                        [pairs[i] for i in sub], n_threads=2)))

            runs = al.align(list(pairs), pipeline=pl, on_reject=on_reject)
            pl.drain_fallback()
            stats = pl.stats.snapshot()
    finally:
        monkeypatch.delenv("RACON_TPU_FAULT_PLAN", raising=False)
        reset_fault_plan()
    assert stats["faults"] >= 1 and stats["errors"] >= 1
    cigars = {i: c for sub, fut in fb for i, c in zip(sub, fut.result())}
    for i in range(len(pairs)):  # complete coverage: device XOR fallback
        assert (runs[i] is not None) != (i in cigars)


def test_repacked_chunk_quarantine_still_works(monkeypatch):
    """Scheduler armed end-to-end: a window that fails consensus on the
    chunk pass AND its individual retry still quarantines (draft
    backbone kept, counter bumped) — the failure ladder is unaffected
    by repacking."""
    from racon_tpu.ops import poa as poa_mod

    monkeypatch.delenv("RACON_TPU_STRICT", raising=False)
    rng = random.Random(3)
    windows, _ = _make_windows(rng, 6, length=160, depth=5, rate=0.1)
    poison = windows[2].sequences[0]
    real_poa_batch = poa_mod.poa_batch

    def sabotaged(packed, *args, **kwargs):
        if any(win[0][0] == poison for win in packed):
            raise RuntimeError("poisoned window")
        return real_poa_batch(packed, *args, **kwargs)

    monkeypatch.setattr(poa_mod, "poa_batch", sabotaged)
    with DispatchPipeline(depth=2) as pl:
        eng = poa_mod.BatchPOA(3, -5, -4, 160, num_threads=2, pipeline=pl,
                               scheduler=BatchScheduler(adaptive=True))
        eng.generate_consensus(windows, trim=False)
        stats = pl.stats.snapshot()
    assert stats["quarantined"] == 1
    assert windows[2].consensus == poison and not windows[2].polished
    for w in windows[:2] + windows[3:]:
        assert w.polished and w.consensus


# --------------------------------------------------- compile cache knob

def test_enable_compile_cache_configures_jax(tmp_path, monkeypatch):
    from racon_tpu.sched import enable_compile_cache

    import os

    prev = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache = tmp_path / "xla-cache"
    try:
        # an operator's JAX_COMPILATION_CACHE_DIR is the only directory:
        # the option yields to it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "op"))
        assert enable_compile_cache(str(cache)) == str(tmp_path / "op")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "op")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache(str(cache)) == str(cache)
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(cache)
        assert jax.config.jax_compilation_cache_dir == str(cache)

        # a fresh-shaped jit compile must land an entry in the cache dir
        import jax.numpy as jnp

        @jax.jit
        def probe(x):
            return (x * 1.5 + jnp.arange(17, dtype=jnp.float32)).sum()

        probe(np.ones(17, np.float32)).block_until_ready()
        assert cache.is_dir() and any(cache.iterdir())
    finally:
        # restore: the suite's shared persistent cache must keep working
        # for the tests that follow
        if prev is not None:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", prev)
            enable_compile_cache()


def test_scheduler_from_env(monkeypatch):
    monkeypatch.delenv("RACON_TPU_ADAPTIVE_BUCKETS", raising=False)
    assert not BatchScheduler.from_env().adaptive
    monkeypatch.setenv("RACON_TPU_ADAPTIVE_BUCKETS", "1")
    assert BatchScheduler.from_env().adaptive
    # explicit argument (the CLI flag) wins over the environment
    assert not BatchScheduler.from_env(adaptive=False).adaptive


def test_occupancy_stats_snapshot_shape():
    st = OccupancyStats()
    st.record("eng", (64, 32), jobs=3, lanes=4, useful_cells=600,
              total_cells=1000)
    st.record("eng", (64, 32), jobs=1, lanes=4, useful_cells=100,
              total_cells=1000)
    st.record_compile("eng", 1.25)
    snap = st.snapshot()
    b = snap["eng"]["buckets"]["(64, 32)"]
    assert b == {"jobs": 4, "batches": 2, "lanes": 8, "useful_cells": 700,
                 "padded_cells": 1300, "occupancy_pct": 35.0}
    assert snap["eng"]["occupancy_pct"] == 35.0
    assert snap["eng"]["compiles"] == 1
    assert snap["eng"]["compile_s"] == 1.25
    assert st.summary() and "eng" in st.summary()


# --------------------------------------------------- lambda sample pin

DATA = "/root/reference/test/data/"
sample_data = pytest.mark.skipif(
    not __import__("os").path.isdir(DATA),
    reason="reference sample data not available")


@sample_data
def test_sample_adaptive_vs_static_all_engines(monkeypatch):
    """Lambda-fixture pin: on a real-data window slice, scheduler on vs
    off is byte-identical for the session and fused engines, and the
    device aligner's accepted/rejected results match pair-for-pair."""
    from racon_tpu.core.polisher import PolisherType, create_polisher

    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    p = create_polisher(DATA + "sample_reads.fastq.gz",
                        DATA + "sample_overlaps.paf.gz",
                        DATA + "sample_layout.fasta.gz", PolisherType.kC,
                        500, 10.0, 0.3, True, 5, -4, -8, num_threads=2)
    p.initialize()
    wins = sorted((w for w in p.windows if len(w.sequences) >= 3),
                  key=lambda w: len(w.sequences))[:24]
    packed = [_pack(w) for w in wins]
    for Engine, kw in ((FusedPOA, dict(batch_rows=8)),
                       (DeviceGraphPOA, dict())):
        outs = {}
        for adaptive in (False, True):
            eng = Engine(5, -4, -8, num_threads=2,
                         scheduler=BatchScheduler(adaptive=adaptive), **kw)
            if Engine is FusedPOA:
                res, st = eng.consensus([list(q) for q in packed],
                                        fallback=False)
            else:
                res, st = eng.consensus(packed)
            outs[adaptive] = (res, st.tolist())
        assert outs[False][1] == outs[True][1]
        for (c0, v0), (c1, v1) in zip(outs[False][0], outs[True][0]):
            if c0 is None or c1 is None:
                assert c0 is c1
                continue
            assert c0 == c1
            np.testing.assert_array_equal(v0, v1)


def test_pack_iteration_slab_contains_oldest():
    """The continuous feeder's incremental packing: shape-sorted slab,
    bounded by cap, always containing the oldest item."""
    from racon_tpu.sched import pack_iteration

    # (age, shape): oldest item has an extreme shape, so a naive
    # head-of-sorted slab would miss it
    items = [(age, shape) for age, shape in
             [(5, 10), (6, 11), (7, 12), (0, 99), (8, 13), (9, 98)]]
    batch, rest = pack_iteration(items, 2,
                                 shape_key=lambda e: e[1],
                                 age_key=lambda e: e[0])
    assert len(batch) == 2
    assert (0, 99) in batch  # the oldest always ships
    # the slab is contiguous in shape order: 99's neighbour is 98
    assert batch == [(9, 98), (0, 99)]
    assert sorted(batch + rest) == sorted(items)
    # cap larger than the pool: everything in one batch
    batch, rest = pack_iteration(items, 100,
                                 shape_key=lambda e: e[1],
                                 age_key=lambda e: e[0])
    assert len(batch) == 6 and not rest
    assert pack_iteration([], 4, shape_key=lambda e: e,
                          age_key=lambda e: e) == ([], [])
