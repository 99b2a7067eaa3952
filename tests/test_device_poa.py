"""Device POA engine tests (ops/poa_graph + native session + parallel/mesh).

Run on the CPU backend with 8 virtual devices (conftest.py), exercising the
same sharded code paths the TPU uses — the testing scheme SURVEY.md §4
prescribes in place of the reference's CPU-vs-GPU duality.

The central contract here is the one the engine's docstrings claim and the
reference never had: device-engine consensus is BYTE-IDENTICAL to the host
engine (the reference pins diverging GPU numbers separately,
test/racon_test.cpp:292-496; this design aligns every layer against the
evolving graph with host-identical DP and tie-breaking, so it must match
exactly). Coverage includes subgraph alignment, the banded clipped->full-DP
retry, and the unfit-window host fallback, with tiny forced envelopes so
XLA compiles stay fast.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from racon_tpu.core.window import Window, WindowType
from racon_tpu.native import PoaSession, edit_distance, poa_batch
from racon_tpu.ops.poa import BatchPOA
from racon_tpu.ops.poa_graph import (RING, DeviceGraphPOA, graph_aligner,
                                     live_nodes, swept_nodes)
from racon_tpu.parallel.mesh import BatchRunner

ACGT = b"ACGT"


def mutate(rng, s, rate):
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


def optimal_score(q, t, match, mismatch, gap):
    m, n = len(q), len(t)
    H = np.zeros((m + 1, n + 1), dtype=np.int32)
    H[0, :] = np.arange(n + 1) * gap
    H[:, 0] = np.arange(m + 1) * gap
    for i in range(1, m + 1):
        sub = np.where(np.frombuffer(t, np.uint8) == q[i - 1], match, mismatch)
        for j in range(1, n + 1):
            H[i, j] = max(H[i - 1, j - 1] + sub[j - 1], H[i - 1, j] + gap,
                          H[i, j - 1] + gap)
    return int(H[m, n])


def linear_graph_inputs(ts, qs, n_nodes, seq_len, max_pred=4):
    """Densify linear-chain graphs (sequence-as-graph) the way the session
    does, so the kernel can be tested directly against plain NW."""
    B = len(ts)
    codes = np.full((B, n_nodes), 5, dtype=np.int8)
    preds = np.full((B, n_nodes, max_pred), -1, dtype=np.int16)
    centers = np.zeros((B, n_nodes), dtype=np.int16)
    sinks = np.zeros((B, n_nodes), dtype=np.uint8)
    seqs = np.full((B, seq_len), 5, dtype=np.int8)
    lens = np.zeros(B, dtype=np.int32)
    band = np.zeros(B, dtype=np.int32)
    code_of = np.full(256, 4, dtype=np.int8)
    for i, b in enumerate(b"ACGT"):
        code_of[b] = i
    for k, (t, q) in enumerate(zip(ts, qs)):
        codes[k, :len(t)] = code_of[np.frombuffer(t, np.uint8)]
        preds[k, 0, 0] = 0
        for r in range(1, len(t)):
            preds[k, r, 0] = r
        centers[k, :len(t)] = np.arange(1, len(t) + 1)
        sinks[k, len(t) - 1] = 1
        seqs[k, :len(q)] = code_of[np.frombuffer(q, np.uint8)]
        lens[k] = len(q)
    return codes, preds, centers, sinks, seqs, lens, band


def kernel_path_score(ranks, q, t, n_nodes, match, mismatch, gap):
    """Score of the kernel's alignment of q against the linear graph of t:
    per-base match/mismatch (rank >= 0) or insertion gap, plus a gap for
    every chain node the path skipped."""
    score, matched = 0, 0
    for i, r in enumerate(ranks[:len(q)]):
        if r >= 0:
            score += match if q[i] == t[r] else mismatch
            matched += 1
        else:
            score += gap
    return score + gap * (len(t) - matched)


def test_graph_aligner_optimal_on_linear_graphs():
    """On a linear graph the graph-NW kernel must reproduce plain NW's
    optimal score (full DP, no band)."""
    rng = random.Random(2)
    fn = graph_aligner(64, 64, 4, 3, -5, -4)
    ts = [bytes(rng.choice(ACGT) for _ in range(rng.randrange(20, 60)))
          for _ in range(16)]
    qs = [mutate(rng, t, 0.25) or b"A" for t in ts]
    args = linear_graph_inputs(ts, qs, 64, 64)
    ranks = np.asarray(fn(*args))
    for k, (t, q) in enumerate(zip(ts, qs)):
        got = kernel_path_score(ranks[k], q, t, 64, 3, -5, -4)
        assert got == optimal_score(q, t, 3, -5, -4), k


def test_ring_and_full_carry_programs_identical():
    """The ring-carry variant (last RING rows resident) must be
    bit-identical to the full-carry program whenever predecessor
    distances fit the ring — including banded jobs."""
    from racon_tpu.ops.poa_graph import RING

    rng = random.Random(17)
    N, L = 192, 128
    ts = [bytes(rng.choice(ACGT) for _ in range(rng.randrange(100, 180)))
          for _ in range(8)]
    qs = [(mutate(rng, t, 0.15) or b"A")[:L] for t in ts]
    args = list(linear_graph_inputs(ts, qs, N, L))
    full = graph_aligner(N, L, 4, 5, -4, -8, ring=0)
    ringp = graph_aligner(N, L, 4, 5, -4, -8, ring=RING)
    np.testing.assert_array_equal(np.asarray(ringp(*args)),
                                  np.asarray(full(*args)))
    args[6] = np.full(len(ts), 32, dtype=np.int32)  # banded
    np.testing.assert_array_equal(np.asarray(ringp(*args)),
                                  np.asarray(full(*args)))


def test_ring_carry_boundary_distance():
    """A back-edge of exactly RING ranks is the last ring-safe distance:
    the ring program must still match the full program there, and the
    dispatcher's distance measure must flag RING+1 for full-carry."""
    from racon_tpu.ops.poa_graph import RING, max_pred_distance

    rng = random.Random(23)
    N, L = RING + 32, 96
    t = bytes(rng.choice(ACGT) for _ in range(N - 8))
    q = (mutate(rng, t, 0.1) or b"A")[:L]
    args = list(linear_graph_inputs([t], [q], N, L))
    # add a second pred with back-reach exactly RING: DP row k reads row
    # k - RING (a deletion-like long edge)
    k = RING + 4
    args[1][0, k - 1, 1] = k - RING
    assert max_pred_distance(args[1]) == RING
    full = graph_aligner(N, L, 4, 5, -4, -8, ring=0)
    ringp = graph_aligner(N, L, 4, 5, -4, -8, ring=RING)
    np.testing.assert_array_equal(np.asarray(ringp(*args)),
                                  np.asarray(full(*args)))
    # one rank further is out of the ring: the dispatcher must see it
    args[1][0, k - 1, 1] = k - RING - 1
    assert max_pred_distance(args[1]) == RING + 1
    eng = DeviceGraphPOA(5, -4, -8, max_nodes=N, max_len=L, max_pred=4,
                         buckets=((N, L),), batch_rows=2)
    fn_ring = eng._scan_kernel(N, L, ring_ok=True)
    fn_full = eng._scan_kernel(N, L, ring_ok=False)
    assert fn_full is full and fn_ring is not full


def _dag_batch(rng, n_list, n_nodes, seq_len, rows):
    """Densified jobs for chains of the given node counts with extra
    short back-edges (still topo-ordered), one mutated layer each, half
    of them banded; rows past len(n_list) are pad rows as the dispatcher
    fills them (codes 5, preds -1, no sink, length 0)."""
    ts = [bytes(rng.choice(ACGT) for _ in range(n)) for n in n_list]
    qs = [(mutate(rng, t, 0.15) or b"A")[:seq_len] for t in ts]
    args = linear_graph_inputs(ts, qs, n_nodes, seq_len)
    preds, band = args[1], args[6]
    for k, n in enumerate(n_list):
        for r in range(2, n):
            if rng.random() < 0.2:
                # rank r also follows rank r - d (DP row r - d + 1)
                preds[k, r, 1] = r - rng.randrange(2, min(r, 5) + 1) + 1
        band[k] = 16 * (k % 2)
    fill = (5, -1, 0, 0, 5, 0, 0)
    return [np.concatenate([a, np.full((rows - len(n_list),) + a.shape[1:],
                                       f, dtype=a.dtype)])
            for a, f in zip(args, fill)]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("score_dtype", ["int32", "int16"])
@pytest.mark.parametrize("ring", [0, RING])
def test_bounded_sweep_identical_to_full_sweep(monkeypatch, ring,
                                               score_dtype, packed,
                                               backend):
    """The node sweep stops at the batch's largest graph, and its ranks
    are byte-identical to a full sweep of the bucket: the same rows in
    a batch that also holds a bucket-sized graph (which forces the full
    sweep), and each real row alone in a batch (swept to its own size).
    Batches: rows from 2 nodes to exactly the bucket; fewer jobs than
    rows (all-pad rows); a largest graph that is not a multiple of the
    unroll; pad rows alone. `tpu` runs the chip's branch of the program,
    the sweep unrolled SWEEP_UNROLL steps an iteration, and first checks
    its full sweeps against the single-step program's."""
    from racon_tpu.ops.encode import pack_2bit

    N, L, B = 192, 64, 8
    kwargs = dict(ring=ring, score_dtype=score_dtype, packed_seq=packed)

    def run(fn, args):
        args = list(args)
        if packed:
            args[4] = pack_2bit(args[4])
        return np.asarray(fn(*args))

    rng = random.Random(29)
    cases = []
    for n_list, swept in (([2, 3, 17, 50, 101, 150, 191, N], N),
                          ([40, 90, 127], 128),
                          ([5, 20, 37, 33, 12, 8, 30, 37], 40),
                          ([], 0)):
        args = _dag_batch(rng, n_list, N, L, B)
        assert swept_nodes(int(live_nodes(args[0])), N) == swept
        full = [np.concatenate(x)
                for x in zip(args, _dag_batch(rng, [N], N, L, 1))]
        cases.append((n_list, args, full))
    fn = graph_aligner(N, L, 4, 5, -4, -8, **kwargs)
    refs = [run(fn, full)[:B] for _, _, full in cases]
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fn = graph_aligner.__wrapped__(N, L, 4, 5, -4, -8, **kwargs)
        for (_, _, full), ref in zip(cases, refs):
            np.testing.assert_array_equal(run(fn, full)[:B], ref)
    for (n_list, args, _), ref in zip(cases, refs):
        np.testing.assert_array_equal(run(fn, args), ref)
        for k in range(len(n_list)):
            solo = _dag_batch(rng, [], N, L, B)
            for a, src in zip(solo, args):
                a[0] = src[k]
            np.testing.assert_array_equal(run(fn, solo)[0], ref[k],
                                          err_msg=f"{n_list} row {k}")


def _make_windows(rng, n_windows, length=60, depth=6, rate=0.08,
                  spanning=True):
    windows = []
    truths = []
    for _ in range(n_windows):
        truth = bytes(rng.choice(ACGT) for _ in range(length))
        bb = mutate(rng, truth, rate)
        w = Window(0, 0, WindowType.kTGS, bb, b"!" * len(bb))
        for k in range(depth):
            if spanning:
                lay, b, e = mutate(rng, truth, rate), 0, len(bb) - 1
            else:
                # interior slice: exercises the bpos-subgraph path
                b = rng.randrange(0, len(bb) // 3)
                e = rng.randrange(2 * len(bb) // 3, len(bb) - 1)
                lay = mutate(rng, truth[b:e + 1], rate)
            w.add_layer(lay or b"A", None, b, e)
        windows.append(w)
        truths.append(truth)
    return windows, truths


def _pack(w):
    return [(w.sequences[i], w.qualities[i], w.positions[i][0],
             w.positions[i][1]) for i in range(len(w.sequences))]


def test_device_consensus_byte_identical_to_host():
    """>= 20 windows, spanning + subgraph layers: device-engine output must
    equal the host engine's byte-for-byte (consensus AND coverages)."""
    rng = random.Random(5)
    windows, _ = _make_windows(rng, 12, length=80, depth=6)
    sub_windows, _ = _make_windows(rng, 10, length=90, depth=5,
                                   spanning=False)
    windows += sub_windows
    packed = [_pack(w) for w in windows]

    eng = DeviceGraphPOA(3, -5, -4, num_threads=2, max_nodes=192,
                         max_len=128, buckets=((96, 96), (192, 128)),
                         batch_rows=8)
    dev, statuses = eng.consensus(packed)
    host = poa_batch(packed, 3, -5, -4, n_threads=2)

    assert (statuses == 0).all(), statuses.tolist()
    for i, ((dc, dcov), (hc, hcov)) in enumerate(zip(dev, host)):
        assert dc == hc, f"window {i} consensus diverged"
        np.testing.assert_array_equal(dcov, hcov, err_msg=f"window {i}")


def test_session_spans_nest_in_order(tmp_path):
    """The session engine's spans, in the order it works: start, the
    prepare/dispatch/commit rounds, finish, then the close that frees
    the window graphs."""
    from racon_tpu.obs import trace

    rng = random.Random(6)
    windows, _ = _make_windows(rng, 6, length=80, depth=5)
    packed = [_pack(w) for w in windows]
    eng = DeviceGraphPOA(3, -5, -4, num_threads=2, max_nodes=192,
                         max_len=128, buckets=((96, 96), (192, 128)),
                         batch_rows=8)
    rec = trace.configure(str(tmp_path / "t.json"))
    try:
        eng.consensus(packed)
    finally:
        trace.reset()
    names = [e["name"] for e in rec.events()
             if e["ph"] == "X" and e["name"].startswith("session.")]
    assert names[0] == "session.start"
    assert names[-2:] == ["session.finish", "session.close"]
    rounds = names[1:-2]
    assert {"session.prepare", "session.dispatch",
            "session.commit"} == set(rounds)
    assert rounds.count("session.dispatch") == rounds.count("session.commit")


def _block_swap_windows(rng):
    """Windows whose last layer is a homopolymer block swap: same length
    (so the 256-band is used) but the true path drifts ~300 columns off
    the band — the in-band result is mismatch soup, the exact case the
    clipped -> full-DP retry exists for."""
    windows = []
    for _ in range(3):
        bb = b"A" * 300 + b"C" * 300
        w = Window(0, 0, WindowType.kTGS, bb, b"!" * len(bb))
        w.add_layer(mutate(rng, bb, 0.05), None, 0, len(bb) - 1)
        w.add_layer(mutate(rng, bb, 0.05), None, 0, len(bb) - 1)
        w.add_layer(b"C" * 300 + b"A" * 300, None, 0, len(bb) - 1)
        windows.append(w)
    return windows


@pytest.mark.parametrize("buckets", [((96, 96), (192, 128)),
                                     ((130, 128),)])
def test_session_sweep_accounting(monkeypatch, buckets):
    """Each batch is accounted at the rows its program sweeps: the
    host's largest graph (the program's formula, and the session's node
    counts) rounded up to the unroll, or single steps in a bucket that
    is not a multiple of it; swept_steps and bucket_steps sum them per
    bucket, and useful + padded cells are B x swept x (len + 1). Batches
    of one bucket with different sweeps run one compiled program. The
    consensus stays the host engine's."""
    rng = random.Random(31)
    windows, _ = _make_windows(rng, 10, length=80, depth=6)
    packed = [_pack(w) for w in windows]
    nb_max, lb_max = buckets[-1]
    B = 8
    eng = DeviceGraphPOA(3, -5, -4, num_threads=2, max_nodes=nb_max,
                         max_len=lb_max, buckets=buckets, batch_rows=B)
    seen, programs = [], {}
    run_bucket, scan_kernel = eng._run_bucket, eng._scan_kernel

    def spy_bucket(nb, lb, codes, *rest):
        seen.append((nb, lb, codes.copy(), rest[-1].copy()))
        return run_bucket(nb, lb, codes, *rest)

    def spy_kernel(*a, **kw):
        fn = scan_kernel(*a, **kw)
        programs.setdefault(fn, (fn._cache_size(), set()))[1].add(
            len(seen) - 1)
        return fn

    monkeypatch.setattr(eng, "_run_bucket", spy_bucket)
    monkeypatch.setattr(eng, "_scan_kernel", spy_kernel)
    dev, st = eng.consensus(packed)
    host = poa_batch(packed, 3, -5, -4, n_threads=2)
    assert (st == 0).all(), st.tolist()
    assert [c for c, _ in dev] == [c for c, _ in host]

    live_on_device = jax.jit(live_nodes)
    expect: dict = {}
    for nb, lb, codes, nnodes in seen:
        n_live = int(nnodes.max())
        assert int(live_nodes(codes)) == n_live
        assert int(live_on_device(codes)) == n_live
        unit = 4 if nb % 4 == 0 else 1
        swept = -(-n_live // unit) * unit
        e = expect.setdefault(str((nb, lb)), [0, 0, 0])
        e[0] += swept
        e[1] += nb
        e[2] += B * swept * (lb + 1)
    snap = eng.sched.stats.snapshot()["session"]
    assert set(snap["buckets"]) == set(expect)
    for key, (swept, steps, cells) in expect.items():
        b = snap["buckets"][key]
        assert (b["swept_steps"], b["bucket_steps"]) == (swept, steps)
        assert b["useful_cells"] + b["padded_cells"] == cells
    assert snap["total_cells"] == sum(e[2] for e in expect.values())
    # batches of differing sweeps through one program compiled it once
    assert any(len({int(seen[i][3].max()) for i in idx}) > 1
               for _, idx in programs.values())
    for fn, (before, _) in programs.items():
        assert fn._cache_size() <= before + 1


def test_device_banded_retry_byte_identical():
    """The banded clipped -> full-DP retry must fire and the output must
    still match the host engine exactly."""
    rng = random.Random(11)
    windows = _block_swap_windows(rng)
    packed = [_pack(w) for w in windows]

    eng = DeviceGraphPOA(5, -4, -8, max_nodes=1280, max_len=640,
                         buckets=((1280, 640),), batch_rows=8)
    dev, statuses = eng.consensus(packed)
    host = poa_batch(packed, 5, -4, -8)

    assert (statuses == 0).all(), statuses.tolist()
    assert eng.last_stats["redos"] >= 3, eng.last_stats
    for i, ((dc, dcov), (hc, hcov)) in enumerate(zip(dev, host)):
        assert dc == hc, f"window {i} consensus diverged"
        np.testing.assert_array_equal(dcov, hcov, err_msg=f"window {i}")


def test_banded_only_mode_skips_retry():
    """-b / banded-only (the reference's --cuda-banded-alignment speed
    trade, cudabatch.cpp:56-59): banded results are trusted as-is — no
    full-DP retries — and the engine still polishes every window."""
    rng = random.Random(11)
    windows = _block_swap_windows(rng)
    packed = [_pack(w) for w in windows]

    eng = DeviceGraphPOA(5, -4, -8, max_nodes=1280, max_len=640,
                         buckets=((1280, 640),), batch_rows=8,
                         banded_only=True)
    dev, statuses = eng.consensus(packed)
    assert (statuses == 0).all(), statuses.tolist()
    assert eng.last_stats["redos"] == 0, eng.last_stats
    assert all(len(c) > 0 for c, _ in dev)


def test_device_unfit_windows_host_fallback_identical():
    """Windows outside a tiny forced envelope (too many nodes / layer too
    long) must be host-polished (status 1) with output identical to the
    host engine — the per-window GPU->CPU fallback discipline
    (cudapolisher.cpp:354-383)."""
    rng = random.Random(6)
    windows, _ = _make_windows(rng, 2, length=60)
    big = Window(0, 0, WindowType.kTGS, b"ACGT" * 25, b"!" * 100)
    big.add_layer(b"ACGT" * 25, None, 0, 99)
    big.add_layer(b"ACGTA" * 20, None, 0, 99)
    windows.append(big)  # 100 nodes > max_nodes=96 -> unfit
    packed = [_pack(w) for w in windows]

    eng = DeviceGraphPOA(3, -5, -4, max_nodes=96, max_len=96,
                         buckets=((96, 96),), batch_rows=8)
    dev, statuses = eng.consensus(packed)
    host = poa_batch(packed, 3, -5, -4)

    assert statuses.tolist() == [0, 0, 1]
    assert eng.last_stats["unfit"] == 1
    for i, ((dc, dcov), (hc, hcov)) in enumerate(zip(dev, host)):
        assert dc == hc, f"window {i} consensus diverged"
        np.testing.assert_array_equal(dcov, hcov, err_msg=f"window {i}")


def test_batch_poa_device_engine_end_to_end():
    rng = random.Random(7)
    windows, truths = _make_windows(rng, 4)
    engine = BatchPOA(3, -5, -4, 60, device_batches=1)
    engine.generate_consensus(windows, trim=False)
    for w, truth in zip(windows, truths):
        assert w.polished
        assert edit_distance(w.consensus, truth) <= \
            edit_distance(w.sequences[0], truth)


def test_precompile_covers_all_buckets():
    eng = DeviceGraphPOA(3, -5, -4, max_nodes=96, max_len=96,
                         buckets=((64, 64), (96, 96)), batch_rows=8)
    eng.precompile()  # must not raise; compiles both buckets
    assert set(eng.batch_rows) == {(64, 64), (96, 96)}


def test_sharded_matches_single_device():
    """Identical kernel outputs on 1 device vs the full 8-device mesh."""
    rng = random.Random(9)
    fn = graph_aligner(64, 64, 4, 3, -5, -4)
    ts = [bytes(rng.choice(ACGT) for _ in range(50)) for _ in range(16)]
    qs = [mutate(rng, t, 0.2) or b"A" for t in ts]
    args = linear_graph_inputs(ts, qs, 64, 64)

    single = BatchRunner(devices=jax.devices()[:1])
    multi = BatchRunner()
    assert multi.n_devices == 8, "conftest should provide 8 virtual devices"
    r1 = np.asarray(single.run(fn, *args))
    r8 = np.asarray(multi.run(fn, *args))
    np.testing.assert_array_equal(r1, r8)


def test_session_stats_counters():
    rng = random.Random(21)
    windows, _ = _make_windows(rng, 3, length=50, depth=4)
    packed = [_pack(w) for w in windows]
    session = PoaSession(packed, 3, -5, -4, 128, 8, 96, max_jobs=8)
    jobs = session.prepare()
    assert jobs is not None and jobs["n"] == 3
    stats = session.stats()
    assert stats["prepared"] == 3 and stats["committed"] == 0
    session.close()


def test_graft_entry_dryrun(capsys):
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    ranks = fn(*args)
    assert np.asarray(ranks).shape[0] == args[0].shape[0]
    __graft_entry__.dryrun_multichip(8)
    # the dryrun's assertions must actually have RUN: its success line
    # is the receipt. A skip sentinel (MULTICHIP_r01.json recorded one
    # passing with rc 0) must fail here, not slip through tier 1.
    out = capsys.readouterr().out
    assert "__GRAFT_DRYRUN_SKIP__" not in out
    assert "dryrun_multichip: 8-device batch-sharded POA + aligner + " \
           "fused kernels OK" in out


def test_max_nodes_env_knob_resolves_at_construction(monkeypatch, capsys):
    """RACON_TPU_MAX_NODES must take effect at ENGINE CONSTRUCTION (a
    late setenv — e.g. from a fixture or driver — must not be silently
    ignored as an import-time read would), be shared by both engines,
    and fall back with a warning on invalid values instead of crashing
    or degenerating the bucket ladder."""
    from racon_tpu.ops.poa_fused import FusedPOA
    from racon_tpu.ops.poa_graph import MAX_NODES, DeviceGraphPOA

    monkeypatch.setenv("RACON_TPU_MAX_NODES", "3072")
    sess = DeviceGraphPOA(5, -4, -8, batch_rows=8)
    fused = FusedPOA(5, -4, -8, batch_rows=8)
    assert sess.max_nodes == 3072
    assert sess.buckets[-1] == (3072, 640)
    assert fused.N == 3072

    for bad in ("bogus", "0", "-5", "999999999"):
        monkeypatch.setenv("RACON_TPU_MAX_NODES", bad)
        eng = DeviceGraphPOA(5, -4, -8, batch_rows=8)
        assert eng.max_nodes == MAX_NODES, bad
        assert "ignoring invalid" in capsys.readouterr().err

    # explicit constructor argument always beats the env var
    monkeypatch.setenv("RACON_TPU_MAX_NODES", "3072")
    eng = DeviceGraphPOA(5, -4, -8, max_nodes=768, batch_rows=8)
    assert eng.max_nodes == 768
