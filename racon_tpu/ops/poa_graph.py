"""Evolving-graph POA consensus with the graph DP on device.

The consensus role of GenomeWorks cudapoa (reference src/cuda/cudabatch.cpp)
rebuilt TPU-first. cudapoa keeps the whole POA — graph storage, DP, and
consensus — inside one CUDA block per window; that pointer-chasing design
has no good mapping onto the TPU's dense vector units or XLA's static-shape
model. The split here keeps the *irregular* graph bookkeeping on the host
(C++ session, native/src/session.cpp) and moves the *regular* hot loop — the
O(nodes x len) graph-banded NW DP plus traceback — onto the device as one
batched fixed-shape XLA program:

  - the host densifies each window's current graph into topo-ordered arrays
    (node codes, predecessor rank lists, band centers, sink flags);
  - the device kernel scans nodes in topological order, up to the batch's
    largest graph (a `lax.fori_loop` whose trip count the batch sets), each
    step computing one DP row for the whole batch: gather at most P
    predecessor rows, diagonal/vertical maxima, then the in-row gap
    recurrence as a running max (`lax.cummax`) — a formulation with no
    sequential dependence along the row, so every step is a wide vector op
    over [batch, len] lanes;
  - backpointers are derived from score equalities with the same tie order
    as the host engine (diagonal > vertical > horizontal, predecessors in
    edge order), and the traceback runs on device as a `lax.while_loop`
    (it exits as soon as every lane's path is complete rather than paying
    the worst-case node-count bound);
  - the resulting per-base node ranks are committed back into the C++
    session, which ingests them with the exact evolving-graph add_alignment
    the host engine uses.

Because each layer is aligned against the *evolving* graph — seeing every
earlier layer's insertions — and both DP and tie-breaking replicate the host
engine bit-for-bit (including the static-band masking and the clipped-band
full-DP retry), the device engine produces byte-identical consensus to the
host engine (tests/test_device_poa.py asserts this window-for-window). The
reference accepts backend divergence and pins its GPU numbers separately
(test/racon_test.cpp:292-496); this design does not have to.

Shape discipline (the cudapoa BatchConfig role, cudabatch.cpp:56-59): the
envelope is sized to what w=500 polishing actually needs — graphs beyond it
fall back to the host engine per window, the reference's GPU->CPU fallback
(cudapolisher.cpp:354-383). Jobs are padded into a FIXED set of
(nodes, len) buckets, each with ONE pinned batch size derived from the
device's free-memory query (the 90%-of-free-VRAM rule of
cudapolisher.cpp:169-173,230-239), and every program is compiled up front
by `precompile()` — so the steady-state loop never compiles.

The scheduling loop is pipelined: each round's batches are dispatched
asynchronously, and the host commits round k's results (mutating the POA
graphs) while round k+1 computes on device — the stream-overlap role of
cudapolisher.cpp:165-199. The batch axis is sharded across every device via
parallel/mesh.py — the multi-chip analogue of cudapoa's batch-per-GPU loop
(src/cuda/cudapolisher.cpp:228-345).
"""

from __future__ import annotations

import functools
import os
from collections import deque

import numpy as np

from ..obs import trace
from ..utils.logger import Logger, log_info

#: kernel shape envelope: max graph nodes, max layer len, max node
#: in-degree. Sized from measurement so w=500 ONT polishing fits entirely
#: (lambda sample, depth <= 38: graphs grow to ~2000 nodes with layer
#: insertions, layer slices <= 634 bp, in-degree <= 8 — envelope sweep in
#: round 4 gave 0/96 host fallbacks at 2048/640/8 vs 39/96 at 1280);
#: larger windows host-fallback per window. Round-5 measurement: at 30x
#: coverage the default envelope device-builds 98.7% of windows (500 kb
#: x 30x with exact overlap coordinates; a 2048-vs-3072 sweep changed
#: NOTHING — the once-suspected "node envelope binds at 30x" was a
#: synthbench coordinate-drift artifact, see PARITY.md). For workloads
#: whose graphs genuinely exceed the envelope, RACON_TPU_MAX_NODES
#: overrides it at ~linear per-row memory cost; the override resolves
#: at ENGINE CONSTRUCTION (like every other RACON_TPU_* knob), not at
#: import.
MAX_NODES = 2048
MAX_LEN = 640
MAX_PRED = 8


def env_max_nodes(default: int = MAX_NODES) -> int:
    """The node envelope both engines use when the caller doesn't pass
    one: RACON_TPU_MAX_NODES when set to a sane positive integer, else
    `default`. Invalid values warn and fall back instead of crashing
    the import or silently emptying the bucket ladder."""
    raw = os.environ.get("RACON_TPU_MAX_NODES")
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        v = -1
    # upper bound: beyond 32k nodes a single DP row costs ~100 MB and a
    # typo'd extra digit should warn, not OOM the device
    if v < 512 or v > 32768:
        log_info(f"[racon_tpu::env_max_nodes] warning: ignoring invalid "
                 f"RACON_TPU_MAX_NODES={raw!r} (want an integer in "
                 "[512, 32768])")
        return default
    return v

#: the full (nodes, len) bucket grid — every job shape is padded up into
#: one of these four compiled programs (plus one batch size each). Graphs
#: start at backbone size (~500) and grow as layers commit, so jobs climb
#: the ladder over a window's lifetime; (320, 256) catches NGS reads and
#: small subgraphs.
BUCKETS = ((320, 256), (768, 640), (1280, 640), (MAX_NODES, MAX_LEN))

#: jobs requested from the session per scheduling round (enough that every
#: ready window contributes a layer even on large inputs)
_CYCLE_JOBS = 1024

_NEG = -(1 << 29)  # matches the host engine's kNegInf (INT32_MIN / 4)


def _materialize(out) -> np.ndarray:
    """Block on one dispatched batch's results; multi-device pallas
    dispatches come back as a per-device list of shards."""
    if isinstance(out, list):
        return np.concatenate([np.asarray(o) for o in out])
    return np.asarray(out)


def _bytes_per_row(n_nodes: int, seq_len: int, max_pred: int) -> int:
    """Peak device bytes one batch row costs while its program runs: the
    H score carry, the backpointer stack (plus its traceback copy), and
    the densified inputs."""
    h = (n_nodes + 1) * (seq_len + 1) * 4
    bp = 2 * n_nodes * (seq_len + 1)
    inputs = n_nodes * (2 * max_pred + 4) + seq_len
    return h + bp + inputs


def pin_pow2_rows(budget: int, per_row: int, lo: int = 8,
                  hi: int = 128) -> int:
    """Shared batch-width pinning policy: the largest power of two whose
    rows fit `budget`, clamped to [lo, hi] — ONE size per program so the
    compile count stays fixed."""
    b = 1 << max(0, (budget // max(per_row, 1)).bit_length() - 1)
    return max(lo, min(hi, b))


def _device_budget(devices) -> int:
    """Free device memory to size batches from — queried from the chip
    like the reference's cudaMemGetInfo 90% rule
    (cudapolisher.cpp:169-173,230-239). A TPU that reports no memory
    stats fails the run: batch widths sized from a guess are how a
    program first meets the chip out of memory. The CPU test backend
    gets a small fixed budget."""
    dev = devices[0]
    if dev.platform != "tpu":
        return 64 << 20
    stats = dev.memory_stats() or {}
    if "bytes_limit" not in stats:
        from ..errors import RaconError

        raise RaconError("device_budget",
                         f"{dev.device_kind} reports no memory_stats(); "
                         "cannot size device batches")
    free = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
    return int(free * 0.9)


#: DP-carry ring depth for the ringed program variant: covers the
#: measured max predecessor rank distance across BOTH measured datasets
#: (lambda sample: 29, 99.95% of edges within 16; synthbench 250 kb x
#: 20x ONT-like: 72 — measured via RACON_TPU_ENVELOPE_STATS in round 5)
#: with ~1.8x headroom over the worst observation. Round 4 shipped
#: RING=64, which the second dataset EXCEEDED — that would have fired
#: the round-3 failure mode (lazy mid-run full-carry compile) on chip.
#: Batches that still exceed it are routed to the full-carry program —
#: compiled lazily on first occurrence (one-time, cache-persisted).
#: The fused engine fails >RING lanes to the host engine per window, so
#: this constant bounds its real-data eligibility too.
RING = 128


def max_pred_distance(preds: np.ndarray) -> int:
    """Max topological back-reach of any predecessor in densified job
    arrays ([B, N, P] DP-row indices, rank+1; 0 = virtual source, -1
    pad). Row k+1 reading row r is ring-safe iff k+1-r <= RING."""
    k1 = np.arange(1, preds.shape[1] + 1, dtype=np.int32)[None, :, None]
    return int(np.where(preds > 0, k1 - preds, 0).max(initial=0))


#: node steps per iteration of the XLA program's sweep on TPU; the sweep
#: length is rounded up to it
SWEEP_UNROLL = 4


def live_nodes(codes):
    """A batch's largest live graph in nodes: one past the last node that
    is not pad (code 5) in any row of densified codes [B, N] — the
    session pads each job's codes with 5 past its node count. Takes a
    host array or a traced one: the dispatcher's counters and the
    program read the same formula."""
    live = (codes != 5).any(axis=0)
    return (live * np.arange(1, codes.shape[-1] + 1, dtype=np.int32)).max()


def swept_nodes(n_live, n_nodes: int):
    """DP rows the XLA program sweeps for a batch whose largest graph has
    `n_live` nodes in a bucket of `n_nodes`: `n_live` rounded up to
    SWEEP_UNROLL (a bucket that is not a multiple of it steps singly, so
    the sweep never passes the bucket). Host ints and traced values
    alike."""
    u = SWEEP_UNROLL if n_nodes % SWEEP_UNROLL == 0 else 1
    return (n_live + u - 1) // u * u


def _mark_compiled(eng, nb: int, lb: int, ring_ok: bool, seconds: float,
                   kernel: str = "xla", dtype: str = "int32",
                   packed: bool = False) -> None:
    """First-dispatch compile telemetry (the shared OccupancyStats
    record_compile_once idiom): the key is the full program identity —
    bucket shape, pinned batch width, ring variant, scoring, engine,
    and the kernel-plane choices (pallas/xla, score dtype, packed
    operands) that each compile a distinct program."""
    eng.sched.stats.record_compile_once(
        "session",
        (nb, lb, eng.batch_rows.get((nb, lb)), bool(ring_ok),
         eng.match, eng.mismatch, eng.gap, eng.max_pred, kernel, dtype,
         packed),
        seconds)


@functools.lru_cache(maxsize=None)
def graph_aligner(n_nodes: int, seq_len: int, max_pred: int, match: int,
                  mismatch: int, gap: int, ring: int = 0,
                  score_dtype: str = "int32", packed_seq: bool = False):
    """Jitted batched graph-NW align + traceback for one shape bucket.

    Args (all leading dim B = batch; preds/centers ship as int16 — half
    the host->device bytes, upcast on device):
      codes   [B, N] int8   topo-ordered node base codes (pad 5)
      preds   [B, N, P] int16  predecessor DP-row indices (rank+1; 0 is the
                               virtual source row; -1 pad)
      centers [B, N] int16  band center column per node (bpos - origin + 1)
      sinks   [B, N] uint8  1 = sink node
      seq     [B, L] int8   layer base codes (pad 5)
      lens    [B]    int32  layer lengths
      band    [B]    int32  static band width (0 = exact full DP)

    Returns ranks [B, L] int16: for layer base i, the 0-based topo rank of
    the node it aligned to, or -1 for an insertion (-2 beyond lens).

    `ring > 0` carries only the last `ring` DP rows (plus the virtual
    source row) instead of all N+1 — a ~N/ring reduction of the scan
    carry's footprint — and is valid ONLY when every predecessor is
    within `ring` ranks of its node (the dispatcher checks the densified
    preds and falls back to the full-carry program otherwise). Results
    are bit-identical between the two variants; per-node sink scores are
    collected into a side carry as rows retire.

    `score_dtype='int16'` halves the DP carry and backpointer-source
    rows (legal only under ops/dtypes.poa_int16_ok's per-bucket
    overflow proof; bit-identical by construction). `packed_seq` takes
    the layer bases 2-bit packed ([B, L//4] uint8, encode.pack_2bit)
    and unpacks + pad-restores them on device from `lens` — a 4x cut in
    per-layer sequence traffic for ACGT-only windows.
    """
    import jax
    import jax.numpy as jnp

    N, L, P = n_nodes, seq_len, max_pred
    DT = jnp.int16 if score_dtype == "int16" else jnp.int32
    NEG = jnp.asarray(-(1 << 14) if score_dtype == "int16" else _NEG, DT)
    W = ring

    def align(codes, preds, centers, sinks, seq, lens, band):
        B = codes.shape[0]
        if packed_seq:
            from .encode import unpack_2bit_jax

            seq = unpack_2bit_jax(seq, L, lens)
        preds = preds.astype(jnp.int32)
        centers = centers.astype(jnp.int32)
        jidx = jnp.arange(L + 1, dtype=jnp.int32)
        jg = (jidx * gap).astype(DT)
        l32 = lens.astype(jnp.int32)
        band2 = (band // 2).astype(jnp.int32)

        # virtual source row: D[0][j] = j*gap within the layer, NEG beyond
        h0 = jnp.where(jidx[None, :] <= l32[:, None], jg[None, :], NEG)
        if W:
            # ring carry: slot 0 = virtual source (always resident), slot
            # 1 + (r-1) % W = DP row r; scores side-carry collects each
            # row's sink-column value as it is produced
            H = jnp.full((B, W + 1, L + 1), NEG, dtype=DT)
            H = H.at[:, 0, :].set(h0)
            scores0 = jnp.full((B, N), NEG, dtype=DT)
        else:
            H = jnp.full((B, N + 1, L + 1), NEG, dtype=DT)
            H = H.at[:, 0, :].set(h0)

        def step(carry, xs):
            if W:
                H, scores = carry
            else:
                H = carry
            code_k, preds_k, center_k, k = xs  # [B], [B,P], [B], scalar
            if W:
                pk = jnp.where(preds_k > 0,
                               1 + jax.lax.rem(preds_k - 1,
                                               jnp.int32(W)), 0)
                pk = jnp.clip(pk, 0, W)
            else:
                pk = jnp.clip(preds_k, 0, N)
            rows = jnp.take_along_axis(H, pk[:, :, None], axis=1)
            rows = jnp.where((preds_k >= 0)[:, :, None], rows, NEG)
            sub = jnp.where(seq == code_k[:, None], match,
                            mismatch).astype(DT)                 # [B, L]
            diag = rows[:, :, :-1] + sub[:, None, :]             # [B, P, L]
            vert = rows[:, :, 1:] + gap                          # [B, P, L]
            best = jnp.max(jnp.maximum(diag, vert), axis=1)      # [B, L]
            row0 = jnp.max(rows[:, :, 0], axis=1) + gap          # [B]

            # static-band masking, replicating the host engine exactly:
            # out-of-band cells are NEG, and the in-row gap recurrence only
            # propagates within the band (seeded from column 0 only when
            # the band touches it)
            use_band = band > 0
            jlo = jnp.where(use_band, jnp.maximum(1, center_k - band2), 1)
            jhi = jnp.where(use_band, jnp.minimum(l32, center_k + band2),
                            l32)
            inband = ((jidx[None, 1:] >= jlo[:, None]) &
                      (jidx[None, 1:] <= jhi[:, None]))          # [B, L]
            pre = jnp.where(inband, best, NEG)
            seed0 = jnp.where(jlo == 1, row0, NEG)
            cat = jnp.concatenate([seed0[:, None], pre], axis=1)
            run = jax.lax.cummax(cat - jg, axis=1) + jg
            hrow = jnp.where(inband, run[:, 1:], pre)
            new_row = jnp.concatenate([row0[:, None], hrow], axis=1)

            # backpointers from score equalities against the final row;
            # tie order matches the host traceback (poa.cpp align_nw):
            # diagonal first (predecessors in edge order), then vertical,
            # then horizontal. Encoding: p = diag via pred p; P+p = vert
            # via pred p; 2P = horizontal.
            nr = new_row[:, 1:]
            is_diag = nr[:, None, :] == diag
            is_vert = nr[:, None, :] == vert
            pd = jnp.argmax(is_diag, axis=1).astype(jnp.int32)
            pv = jnp.argmax(is_vert, axis=1).astype(jnp.int32)
            bpc = jnp.where(jnp.any(is_diag, axis=1), pd,
                            jnp.where(jnp.any(is_vert, axis=1), P + pv,
                                      2 * P))
            is_v0 = row0[:, None] == rows[:, :, 0] + gap         # [B, P]
            bp0 = P + jnp.argmax(is_v0, axis=1).astype(jnp.int32)
            bp_row = jnp.concatenate([bp0[:, None], bpc],
                                     axis=1).astype(jnp.int8)

            if W:
                slot = 1 + jax.lax.rem(k - 1, jnp.int32(W))
                H = jax.lax.dynamic_update_slice(
                    H, new_row[:, None, :],
                    (jnp.int32(0), slot, jnp.int32(0)))
                sc = jnp.take_along_axis(new_row, l32[:, None], axis=1)
                scores = jax.lax.dynamic_update_slice(
                    scores, sc, (jnp.int32(0), k - 1))
                return (H, scores), bp_row
            H = jax.lax.dynamic_update_slice(
                H, new_row[:, None, :], (jnp.int32(0), k, jnp.int32(0)))
            return H, bp_row

        # the node sweep stops at the batch's largest live graph: rows
        # past it are pad nodes nothing reads, so the trip count follows
        # the batch, not the bucket's N (one program per bucket still)
        n_swept = swept_nodes(live_nodes(codes), N)
        # unroll on TPU: the step is small relative to the While-loop
        # iteration overhead at N=2048 steps; CPU (tests) keeps compiles fast
        unroll = (SWEEP_UNROLL if jax.default_backend() == "tpu"
                  and N % SWEEP_UNROLL == 0 else 1)
        xs = (codes.T, preds.transpose(1, 0, 2), centers.T)
        # backpointer plane [N, B, L+1] int8, written in place at row k-1;
        # an unswept row reads as a vertical move to the first
        # predecessor, where a pad row's traceback (preds -1) ends
        bps = jnp.full((N, B, L + 1), P, dtype=jnp.int8)

        def sweep(i, st):
            carry, bps = st
            for u in range(unroll):
                k = i * unroll + (u + 1)
                x = tuple(jax.lax.dynamic_index_in_dim(a, k - 1, 0, False)
                          for a in xs)
                carry, bp_row = step(carry, x + (k,))
                bps = jax.lax.dynamic_update_index_in_dim(
                    bps, bp_row, k - 1, 0)
            return carry, bps

        carry, bps = jax.lax.fori_loop(
            0, n_swept // unroll, sweep, ((H, scores0) if W else H, bps))

        # best sink at the layer's final column; ties -> smallest rank
        # (host: ascending scan keeping strict improvements)
        if W:
            scores = carry[1]                                    # [B, N]
        else:
            scores = jnp.take_along_axis(
                carry[:, 1:, :], jnp.broadcast_to(l32[:, None, None],
                                                  (B, N, 1)),
                axis=2)[:, :, 0]                                 # [B, N]
        cand = jnp.where(sinks > 0, scores, NEG)
        best_rank = jnp.argmax(cand, axis=1).astype(jnp.int32)
        rows_b = jnp.arange(B)

        def cond(st):
            r, j, _ = st
            return jnp.any((r > 0) | (j > 0))

        def body(st):
            r, j, out = st
            active = (r > 0) | (j > 0)
            # point gathers straight from the [N, B, L+1] plane:
            # a batch-major flat copy of it makes the TPU compile time
            # grow with the batch width
            rc = jnp.clip(r - 1, 0, N - 1)
            code = bps[rc, rows_b, jnp.clip(j, 0, L)].astype(jnp.int32)
            code = jnp.where(r > 0, code, 2 * P)  # source row: horizontal
            is_diag = code < P
            is_vert = (code >= P) & (code < 2 * P)
            p = jnp.where(is_diag, code, code - P)
            pr = preds[rows_b, rc, jnp.clip(p, 0, P - 1)]
            consume = active & ~is_vert                # diag or horizontal
            jc = jnp.clip(j - 1, 0, L - 1)
            cur = jnp.take_along_axis(out, jc[:, None], axis=1)[:, 0]
            emit = jnp.where(is_diag, r - 1, -1).astype(jnp.int16)
            out = out.at[rows_b, jc].set(jnp.where(consume, emit, cur))
            r = jnp.where(active & (is_diag | is_vert), pr, r)
            j = jnp.where(consume, j - 1, j)
            return r, j, out

        # int16 output: rank < N <= 32767; halves the device->host bytes
        out0 = jnp.full((B, L), -2, dtype=jnp.int16)
        _, _, ranks = jax.lax.while_loop(
            cond, body, (best_rank + 1, l32, out0))
        return ranks

    return jax.jit(align)


class DeviceGraphPOA:
    """Orchestrates the session <-> device scheduling loop.

    Each round: ask the C++ session for the next ready layer of up to
    `_CYCLE_JOBS` windows, bucket the jobs by (graph size, layer length),
    pad each bucket to its pinned batch size and dispatch (async), then
    commit the OLDEST in-flight batch — so the host's graph ingest always
    overlaps the device's compute on the younger batches.

    The envelope/bucket/batch-size knobs exist so tests can force tiny
    shapes (and the unfit-fallback paths) without a real chip.
    """

    def __init__(self, match: int, mismatch: int, gap: int,
                 num_threads: int = 1, logger: Logger | None = None,
                 max_nodes: int | None = None, max_len: int = MAX_LEN,
                 max_pred: int = MAX_PRED, buckets=None,
                 batch_rows: int | None = None, cycle_jobs: int = _CYCLE_JOBS,
                 banded_only: bool = False, use_pallas: bool | None = None,
                 scheduler=None, runner=None):
        from ..parallel.mesh import BatchRunner
        from ..sched import BatchScheduler

        if max_nodes is None:
            max_nodes = env_max_nodes()
        # occupancy-aware scheduler (sched/): adaptive (nodes, len) grid
        # + sorted packing when armed, occupancy telemetry always
        self.sched = (scheduler if scheduler is not None
                      else BatchScheduler.from_env())
        #: RACON_TPU_PALLAS routes VMEM-sized buckets through the
        #: resident pallas window-sweep kernel (ops/poa_pallas.py)
        #: instead of the XLA scan program: `1` = always (when the VMEM
        #: envelope fits), `auto` = per-bucket via the persisted
        #: autotuner winner table (sched/autotune; no entry -> XLA,
        #: today's default), unset/0 = off. The constructor bool forces
        #: on/off for tests.
        from .poa_pallas import pallas_mode

        if use_pallas is None:
            self.pallas_posture = pallas_mode()
        else:
            self.pallas_posture = "on" if use_pallas else "off"
        self.use_pallas = self.pallas_posture != "off"
        #: per-bucket (use_pallas, score_dtype) dispatch plans, resolved
        #: lazily (the autotuner table / envelope proofs don't change
        #: within a run)
        self._plans: dict = {}

        self.match = match
        self.mismatch = mismatch
        self.gap = gap
        self.num_threads = num_threads
        self.logger = logger
        self.banded_only = banded_only
        # an explicit runner pins this engine to a sub-mesh (the serve
        # layer's worker lanes each pass their own); default is the full
        # auto-discovered mesh
        self.runner = runner if runner is not None else BatchRunner()
        self.max_nodes = max_nodes
        self.max_len = max_len
        self.max_pred = max_pred
        self.cycle_jobs = cycle_jobs
        self._forced_batch_rows = batch_rows
        self._set_buckets(tuple(buckets) if buckets is not None else tuple(
            b for b in BUCKETS if b[0] <= max_nodes and b[1] <= max_len))
        #: RACON_TPU_ENVELOPE_STATS=1: collect observed envelope maxima
        #: (nodes, len, pred distance, in-degree, depth) across the run —
        #: the measurement that justifies RING/MAX_* on new datasets
        self._env_stats = (
            {"max_nodes": 0, "max_len": 0, "max_pred_distance": 0,
             "max_in_degree": 0, "max_depth": 0}
            if os.environ.get("RACON_TPU_ENVELOPE_STATS") else None)

    def _set_buckets(self, buckets) -> None:
        """Install a bucket grid (envelope bucket appended as the safety
        net — every in-envelope job always fits SOME bucket) and pin one
        batch width per bucket."""
        self.buckets = tuple(buckets)
        if (not self.buckets or self.buckets[-1][0] < self.max_nodes
                or self.buckets[-1][1] < self.max_len):
            self.buckets = self.buckets + ((self.max_nodes, self.max_len),)
        self.batch_rows = {
            b: self._pin_batch(b, self._forced_batch_rows)
            for b in self.buckets}

    #: predicted graph growth per committed layer base: graphs start at
    #: backbone size and gain ~GROWTH nodes per aligned layer bp from
    #: insertions (lambda sample measurement: ~500 -> ~2000 nodes over
    #: 37 layers of ~550 bp, PARITY.md). The prediction only shapes the
    #: adaptive grid — a job outgrowing it first-fits a larger bucket or
    #: the envelope, so a wrong GROWTH costs padding, never correctness.
    GROWTH = 0.08

    def adapt(self, windows) -> None:
        """Derive the adaptive (nodes, len) grid from the window set (the
        job-shape histogram at run start: one predicted job per layer).
        No-op when the scheduler is off. Called by consensus() and by
        precompile(windows=...) so the bench can warm the same shapes the
        polish run will use."""
        if not self.sched.adaptive:
            return
        shapes: list[tuple[int, int]] = []
        for w in windows:
            if len(w) < 3:
                continue
            nodes = len(w[0][0]) + 1
            # host-engine visit order (begin-sorted, window.cpp:84-85):
            # early layers align small graphs, late ones the grown graph
            for seq, _, _, _ in sorted(w[1:], key=lambda s: s[2]):
                shapes.append((min(self.max_nodes, int(nodes)), len(seq)))
                nodes += self.GROWTH * len(seq)
        grid = self.sched.poa_grid(shapes, k=len(BUCKETS),
                                   max_nodes=self.max_nodes,
                                   max_len=self.max_len)
        if grid:
            self._set_buckets(grid)

    def _pin_batch(self, bucket, forced) -> int:
        """ONE batch size per bucket: the largest power of two whose peak
        footprint fits a quarter of the device budget (several batches are
        in flight while the pipeline is full), rounded to the device count."""
        n_dev = self.runner.n_devices
        if forced is not None:
            b = forced
        else:
            budget = _device_budget(self.runner.devices) // 4
            row = _bytes_per_row(bucket[0], bucket[1], self.max_pred)
            b = pin_pow2_rows(budget, row)
        return max(n_dev, (b // n_dev) * n_dev)

    def precompile(self, windows=None) -> None:
        """Compile every (bucket, pinned batch size) program up front so
        the scheduling loop never stalls on XLA (VERDICT r3: mid-run
        compiles were the prime suspect in the on-chip failure).

        With the adaptive scheduler armed, pass the window set so the
        DERIVED grid is what gets compiled — the ladder is a pure
        function of the windows, so a later engine instance adapting to
        the same windows reuses these programs via the jit cache."""
        import time

        if windows is not None:
            self.adapt(windows)
        for (nb, lb) in self.buckets:
            B = self.batch_rows[(nb, lb)]
            # a valid tiny problem: linear 2-node chain, 2-base layer
            codes = np.full((B, nb), 5, dtype=np.int8)
            codes[:, :2] = 0
            preds = np.full((B, nb, self.max_pred), -1, dtype=np.int16)
            preds[:, 0, 0] = 0
            preds[:, 1, 0] = 1
            centers = np.zeros((B, nb), dtype=np.int16)
            centers[:, :2] = (1, 2)
            sinks = np.zeros((B, nb), dtype=np.uint8)
            sinks[:, 1] = 1
            seq = np.full((B, lb), 5, dtype=np.int8)
            seq[:, :2] = 0
            lens = np.full(B, 2, dtype=np.int32)
            band = np.zeros(B, dtype=np.int32)
            # through the run's own dispatch entry point, so the warmed
            # program (kernel choice, dtype, packing) is EXACTLY the one
            # the scheduling loop will request
            nnodes = np.full(B, 2, dtype=np.int32)
            out = self._run_bucket(nb, lb, codes, preds, centers, sinks,
                                   seq, lens, band, nnodes)
            _materialize(out)  # block
            from .encode import pack_bases_enabled

            if pack_bases_enabled():
                # the ACGT-only job above warmed the packed-operand
                # program; real data carries N/IUPAC windows whose
                # batches request the UNPACKED variant — a distinct
                # program that must not compile cold mid-run
                seq_n = seq.copy()
                seq_n[:, 1] = 4
                out = self._run_bucket(nb, lb, codes, preds, centers,
                                       sinks, seq_n, lens, band, nnodes)
                _materialize(out)

    def _bucket(self, n_nodes: int, length: int) -> tuple[int, int]:
        return next((nb, lb) for nb, lb in self.buckets
                    if n_nodes <= nb and length <= lb)

    def consensus(self, windows):
        """windows: list of lists of (seq, qual|None, begin, end), element 0
        the backbone. Returns (results, statuses): results like poa_batch's
        [(consensus bytes, coverages)], statuses int array (0 device,
        1 host fallback, 2 backbone-only)."""
        from ..native import PoaSession

        # adaptive grid from the run's own job-shape histogram (no-op
        # when the scheduler is off — the static grid stays)
        with trace.span("session.start", windows=len(windows)):
            self.adapt(windows)
            session = PoaSession(windows, self.match, self.mismatch,
                                 self.gap, self.max_nodes, self.max_pred,
                                 self.max_len, max_jobs=self.cycle_jobs,
                                 banded_only=self.banded_only,
                                 n_threads=self.num_threads)
        bar = self.logger.bar if self.logger is not None else None
        total_layers = sum(max(0, len(w) - 1) for w in windows)
        if self.logger is not None and total_layers:
            self.logger.bar_total(total_layers)

        # split-half pipelining: each prepare() pulls at most HALF the
        # active windows (round-robin), so while half A's results are
        # committed (mutating graphs), half B computes on device — and
        # every batch stays large (few device calls, few round trips)
        # instead of fragmenting to whatever the last commit freed.
        import os

        n_active = sum(1 for w in windows if len(w) >= 3)
        # RACON_TPU_SCHED_HALVES: windows per prepare = active/H. H=2
        # overlaps host ingest with device compute; H=1 minimizes device
        # round trips (serial rounds) — tune per link latency
        halves = max(1, int(os.environ.get("RACON_TPU_SCHED_HALVES", "2")))
        half = max(8, min(self.cycle_jobs, max(1, n_active // halves)))
        # how many dispatched batches to keep queued: enough to hide the
        # host's commit+prepare time behind device compute, small enough
        # to bound queued transfers on large inputs
        depth = 4
        # prepare only in BURSTS — once enough windows have been freed by
        # commits to fill a decent batch — otherwise each commit's handful
        # of freed windows would round-trip as a tiny fragment batch
        threshold = 1
        freed = 1
        inflight: deque = deque()
        while True:
            if freed >= threshold or not inflight:
                burst = 0
                while len(inflight) < depth:
                    with trace.span("session.prepare") as sp:
                        jobs = session.prepare(half)
                        sp.set(jobs=jobs["n"] if jobs is not None else 0)
                    if jobs is None:
                        break
                    burst += jobs["n"]
                    inflight.extend(self._dispatch_round(jobs))
                if burst:
                    freed = 0
                    threshold = max(8, burst // 2)
            if not inflight:
                break
            # commit the oldest batch (blocks only on ITS device result;
            # younger batches keep computing via async dispatch)
            win, layer, band, npart, lb, out, rows = inflight.popleft()
            with trace.span("session.commit", engine="session",
                            jobs=npart):
                # gather by the dispatch scatter's row map (job j is on
                # row rows[j], not row j)
                ranks = _materialize(out)[rows][:, :lb]
                session.commit(win, layer, band, ranks)
            freed += npart
            if bar is not None:
                for _ in range(npart):
                    bar("[racon_tpu::Polisher.polish] "
                        "aligning layers to graphs on device")
        self.last_stats = session.stats()
        if self._env_stats is not None:
            self._env_stats["max_depth"] = max(
                (len(w) - 1 for w in windows), default=0)
            log_info(f"[racon_tpu::DeviceGraphPOA] envelope stats: "
                     f"{self._env_stats} (envelope: nodes {self.max_nodes}, "
                     f"len {self.max_len}, pred {self.max_pred}, "
                     f"RING {RING})")
        with trace.span("session.finish"):
            out = session.finish(self.num_threads)
        # free the window graphs here, inside a span, rather than
        # whenever the session is collected (tens of ms on a 0.1 Mb job)
        with trace.span("session.close"):
            session.close()
        return out

    #: bucket groups smaller than this merge upward into the next larger
    #: nonempty bucket: a slightly longer scan for a few jobs beats paying
    #: another device round trip for a nearly-empty batch
    MIN_FILL = 16

    def _dispatch_round(self, jobs):
        """Bucket one prepare() round and dispatch every batch async.
        Returns [(win, layer, band, n_jobs, len_bucket, device_out)] —
        everything needed for commit is snapshotted so the session's
        prepare buffers can be reused immediately."""
        n = jobs["n"]
        if self._env_stats is not None:
            # RACON_TPU_ENVELOPE_STATS: record the run's observed maxima
            # so the RING/MAX_NODES/MAX_LEN/MAX_PRED envelope constants
            # can be justified against more datasets than the lambda
            # sample (round-4 verdict #7)
            st = self._env_stats
            st["max_nodes"] = max(st["max_nodes"],
                                  int(jobs["nnodes"][:n].max(initial=0)))
            st["max_len"] = max(st["max_len"],
                                int(jobs["len"][:n].max(initial=0)))
            st["max_pred_distance"] = max(
                st["max_pred_distance"],
                max_pred_distance(jobs["preds"][:n]))
            st["max_in_degree"] = max(
                st["max_in_degree"],
                int((jobs["preds"][:n] >= 0).sum(axis=2).max(initial=0)))
        groups: dict[tuple[int, int], list[int]] = {}
        for i in range(n):
            b = self._bucket(int(jobs["nnodes"][i]), int(jobs["len"][i]))
            groups.setdefault(b, []).append(i)

        # merge under-filled groups upward (jobs always fit any larger
        # bucket) so each round dispatches few, well-filled batches
        order = sorted(groups)
        for gi, b in enumerate(order[:-1]):
            if len(groups.get(b, ())) < self.MIN_FILL:
                for nb in order[gi + 1:]:
                    if groups.get(nb) and nb[0] >= b[0] and nb[1] >= b[1]:
                        groups[nb] = groups.pop(b) + groups[nb]
                        break

        batches = []
        for (nb, lb), idx in sorted(groups.items()):
            # sorted packing: shape-homogeneous batches within the bucket
            # (commits key on (win, layer), so cross-window dispatch
            # order is free); identity when the scheduler is off
            idx = self.sched.order(
                idx, key=lambda i: (int(jobs["nnodes"][i]),
                                    int(jobs["len"][i])))
            B = self.batch_rows[(nb, lb)]
            for s in range(0, len(idx), B):
                part = idx[s:s + B]
                sel = np.asarray(part, dtype=np.int64)
                meta = (jobs["win"][sel].copy(), jobs["layer"][sel].copy(),
                        jobs["band"][sel].copy())
                with trace.span("session.dispatch", engine="session",
                                bucket=f"{nb}x{lb}", jobs=len(part)):
                    out, rows = self._dispatch(jobs, sel, nb, lb, B)
                # occupancy recorded AFTER the dispatch call returned
                # (the aligner's discipline: a batch killed before the
                # device saw it must not be accounted as device work)
                use_pallas, dtype = self._plan(nb, lb)
                # mesh view: job j landed on shard j % n_devices (the
                # _dispatch round-robin scatter), so per-shard useful
                # cells — the balance the scale curve gates on — come
                # from strided sums. The batch is always padded to the
                # pinned width B (a per-tail program shape would
                # compile cold mid-run), so the full-mesh baseline
                # equals the dispatched capacity.
                n_dev = self.runner.n_devices
                row_cells = (jobs["nnodes"][sel].astype(np.int64)
                             * (jobs["len"][sel].astype(np.int64) + 1))
                shard_useful = [int(row_cells[s::n_dev].sum())
                                for s in range(n_dev)]
                # the XLA program sweeps to the batch's largest graph
                # (the program's own formula); the pallas sweep is
                # accounted at the bucket's rows
                swept = nb if use_pallas else int(swept_nodes(
                    live_nodes(jobs["codes"][sel, :nb]), nb))
                self.sched.stats.record(
                    "session", (nb, lb), jobs=len(part), lanes=B,
                    useful_cells=int(row_cells.sum()),
                    total_cells=B * swept * (lb + 1),
                    kernel="pallas" if use_pallas else "xla", dtype=dtype,
                    n_devices=n_dev, shard_useful=shard_useful,
                    full_mesh_cells=B * swept * (lb + 1),
                    swept_steps=swept, bucket_steps=nb)
                batches.append(meta + (len(part), lb, out, rows))
        return batches

    def _plan(self, nb, lb) -> tuple[bool, str]:
        """(use_pallas, score_dtype) for one bucket — the kernel-plane
        dispatch decision: the Pallas posture (forced / env / the
        persisted autotuner winner table under `auto`), the corrected
        VMEM envelope gate, and the dtype-shrinking proof
        (ops/dtypes.poa_int16_ok; int32 whenever it fails)."""
        plan = self._plans.get((nb, lb))
        if plan is None:
            from .dtypes import kernel_plan, poa_int16_ok
            from .poa_pallas import fits_vmem

            plan = self._plans[(nb, lb)] = kernel_plan(
                self.pallas_posture, "session", (nb, lb),
                (self.match, self.mismatch, self.gap, self.max_pred),
                poa_int16_ok(nb, lb, self.match, self.mismatch, self.gap),
                lambda dt: fits_vmem(nb, lb, self.max_pred, dt))
        return plan

    def _scan_kernel(self, nb, lb, ring_ok: bool = True,
                     score_dtype: str = "int32",
                     packed_seq: bool = False):
        """The XLA scan program for a bucket: ring-carried (last RING rows
        only, ~nb/RING smaller carry) when every predecessor in the batch
        is within RING ranks, full-carry otherwise (lazy-compiled; see
        RING)."""
        ring = RING if (ring_ok and nb > RING) else 0
        if not ring_ok and not getattr(self, "_warned_full", False):
            self._warned_full = True
            log_info("[racon_tpu::DeviceGraphPOA] long back-edge batch: "
                     "using the full-carry DP program")
        # default-valued kwargs are omitted so the lru key (and thus the
        # jit cache entry) is shared with plain graph_aligner(...) calls
        kwargs: dict = {}
        if score_dtype != "int32":
            kwargs["score_dtype"] = score_dtype
        if packed_seq:
            kwargs["packed_seq"] = True
        return graph_aligner(nb, lb, self.max_pred, self.match,
                             self.mismatch, self.gap, ring=ring, **kwargs)

    def _run_bucket(self, nb, lb, codes, preds, centers, sinks, seqs,
                    lens, band, nnodes):
        """Dispatch ONE padded batch through the bucket's planned
        program — the single device entry point shared by precompile()
        and the scheduling loop, so the programs warmed up front are
        exactly the programs the run requests. Handles the kernel
        choice (pallas/XLA), the score dtype, 2-bit operand packing
        (ACGT-only batches; the XLA path packs the layer bases, the
        pallas path additionally packs the node codes — it carries the
        per-job node counts the restore needs) and the first-dispatch
        compile telemetry."""
        import time

        import jax

        from .encode import pack_2bit, pack_bases_enabled, packable

        use_pallas, dtype = self._plan(nb, lb)
        can_pack = pack_bases_enabled() and packable(seqs, lens)
        t0 = time.perf_counter()
        if use_pallas:
            from .poa_pallas import window_sweep

            packed = can_pack and packable(codes, nnodes)
            # default kwargs omitted: lru/jit keys shared with direct
            # window_sweep(...) calls (profiling, tests)
            kwargs: dict = {}
            if dtype != "int32":
                kwargs["score_dtype"] = dtype
            if packed:
                kwargs["packed"] = True
            fn = window_sweep(nb, lb, self.max_pred, self.match,
                              self.mismatch, self.gap,
                              interpret=jax.default_backend() != "tpu",
                              **kwargs)
            c = pack_2bit(codes) if packed else codes
            s = pack_2bit(seqs) if packed else seqs
            # pallas path: per-job real node count bounds its row sweep
            out = self._run_pallas(fn, c, preds, centers, sinks, s,
                                   lens, band, nnodes)
            _mark_compiled(self, nb, lb, True,
                           time.perf_counter() - t0, kernel="pallas",
                           dtype=dtype, packed=packed)
            return out
        # ring validity: every predecessor within RING ranks of its node
        # (measured: 29 lambda / 72 synthbench, see RING; the full-carry
        # program covers the rare batch that exceeds it)
        ring_ok = max_pred_distance(preds) <= RING
        fn = self._scan_kernel(nb, lb, ring_ok=ring_ok, score_dtype=dtype,
                               packed_seq=can_pack)
        s = pack_2bit(seqs) if can_pack else seqs
        out = self.runner.run(fn, codes, preds, centers, sinks, s,
                              lens, band)
        _mark_compiled(self, nb, lb, ring_ok,
                       seconds=time.perf_counter() - t0, dtype=dtype,
                       packed=can_pack)
        return out

    def _dispatch(self, jobs, sel, nb, lb, B):
        """Pad/scatter one bucket batch and dispatch it. Returns
        (device_out, rows): `rows[j]` is the batch row job j landed on —
        round-robin across the mesh's per-device shards, so each device
        carries an even share of the real (and of the padding) rows
        instead of the last shard eating all the pad. Per-row results
        are position-independent; commit gathers by `rows`."""
        n_dev = self.runner.n_devices
        per = B // n_dev
        j = np.arange(len(sel), dtype=np.int64)
        rows = (j % n_dev) * per + j // n_dev

        def take(arr, fill):
            out = np.full((B,) + arr.shape[1:], fill, dtype=arr.dtype)
            out[rows] = arr[sel]
            return out

        return self._run_bucket(
            nb, lb, take(jobs["codes"][:, :nb], 5),
            take(jobs["preds"][:, :nb, :self.max_pred], -1),
            take(jobs["centers"][:, :nb], 0),
            take(jobs["sinks"][:, :nb], 0),
            take(jobs["seqs"][:, :lb], 5),
            take(jobs["len"], 0), take(jobs["band"], 0),
            take(jobs["nnodes"], 0)), rows

    def _run_pallas(self, fn, *args):
        """Run the pallas sweep across every device (the batch width is
        already a multiple of n_devices, _pin_batch) — the shared
        per-device split both kernel planes use."""
        return self.runner.run_split(fn, *args)
