"""Batched banded global alignment (edit distance + CIGAR path).

TPU-native replacement for both edlib (reference src/overlap.cpp:205-224) and
GenomeWorks cudaaligner (src/cuda/cudaaligner.cpp): many pairwise global
alignments are computed at once as one fixed-shape XLA program.

Design
------
Anti-diagonal wavefront DP: cells (i, j) with i+j == d depend only on
wavefronts d-1 and d-2, so each wavefront is a single vector op — no
horizontal dependency chain. A static band of width B tracks the main
diagonal: on wavefront d only query rows i in [offset[d], offset[d] + B) are
kept. Offsets are precomputed on the host per lane (they advance by 0/1 per
wavefront) and shared by the DP and the traceback, so the two can never
disagree. Unit costs (match 0, mismatch 1, indel 1, minimize), mirroring
edlib's edit-distance NW mode that the reference relies on.

The kernel keeps 2-bit backpointers packed 4-per-byte on the device and
traces back there too, vectorized across lanes; only the op paths come
back to the host. Lengths are bucketed by the caller
(`BatchAligner`) into a handful of static shapes to avoid recompilation.

Determinism: tie-breaking is fixed (diagonal < up/I < left/D), so output is
bit-stable across runs and backends — the property the reference's golden
CI test demands (ci/gpu/cuda_test.sh:30-44).
"""

from __future__ import annotations

import functools

import numpy as np

from ..obs import trace

INF = np.int32(1 << 28)

# backpointer codes
BP_DIAG, BP_UP, BP_LEFT = 0, 1, 2  # M, I (consume query), D (consume target)


def band_offsets(q_len: int, t_len: int, band: int, n_waves: int) -> np.ndarray:
    """Per-wavefront band start rows for one lane (host side).

    Wavefront d holds query rows i in [off[d], off[d]+band). The band tracks
    the ideal diagonal i ~= d * M / (M+N) and is clamped so (0,0) and (M,N)
    are always inside. Offsets are nondecreasing with steps in {0, 1}.
    """
    m, n = q_len, t_len
    d = np.arange(n_waves, dtype=np.int64)
    center = (d * m) // (m + n) if (m + n) else d * 0
    lo = np.maximum(0, d - n)
    hi = np.minimum(d, m)
    off = np.clip(center - band // 2, lo, np.maximum(lo, hi - band + 1))
    off = np.maximum.accumulate(off)            # enforce monotone
    off = np.minimum(off, np.maximum(0, m - 0))  # safety clamp
    # steps must be 0/1 for the DP gather to stay in-range; enforce
    steps = np.diff(off)
    if (steps > 1).any():
        # smooth: cumulative min walk backwards
        for idx in np.where(steps > 1)[0][::-1]:
            off[idx] = off[idx + 1] - 1
    return off.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _kernel_for(band: int, n_waves: int, score_dtype: str = "int32",
                packed: bool = False):
    """jitted banded DP for one static (band, n_waves) shape; jax is
    imported lazily so the module loads without a device runtime.
    `score_dtype` narrows the wavefront state (legal only under
    ops/dtypes.aligner_int16_ok); `packed` takes 2-bit packed operands
    (encode.pack_2bit) and unpacks them on device — both variants are
    byte-identical to the int32/int8 program by construction."""
    import jax

    return jax.jit(functools.partial(_banded_nw_kernel, band=band,
                                     n_waves=n_waves,
                                     score_dtype=score_dtype,
                                     packed=packed))


def _banded_nw_kernel(q, t, q_len, t_len, offsets, band: int, n_waves: int,
                      score_dtype: str = "int32", packed: bool = False):
    """Batched banded edit-distance DP.

    Args:
      q, t: [B, Lq], [B, Lt] int8 codes (PAD beyond length), or 2-bit
        packed [B, Lq // 4] uint8 when `packed` (ACGT-only operands;
        PAD is restored from the lengths on device).
      q_len, t_len: [B] int32.
      offsets: [B, n_waves] int32 band starts.
      band: static band width (multiple of 4).
      n_waves: static number of wavefronts (>= max(q_len+t_len) + 1).
      score_dtype: 'int32' (sentinel 1<<28) or 'int16' (sentinel 1<<14,
        legal iff 2*edge+1 < 1<<14 — every cell is min-clamped at the
        sentinel per wavefront, so values never exceed sentinel + 1).

    Returns:
      ops: [2 * edge, B] uint8 — each lane's backpointer path in
        traceback order (rows past the lane's count are padding).
      meta: [B, 3] int32 — (path length, edit distance at (M, N),
        touched-band-edge flag).

    The traceback runs on the device too, walking the 2-bit backpointer
    plane (packed 4 per byte) every lane at once, so only the paths
    leave the chip, not the ~n_waves * band / 4 bytes per lane of the
    plane.
    """
    import jax
    import jax.numpy as jnp

    DT = jnp.int16 if score_dtype == "int16" else jnp.int32
    INFD = jnp.asarray((1 << 14) if score_dtype == "int16" else INF, DT)
    if packed:
        from .encode import unpack_2bit_jax

        # unpack once per program: without the barrier XLA sinks the
        # shifts into the wavefront scan, whose state then carries the
        # packed operands and re-unpacks both of them every wavefront
        q, t = jax.lax.optimization_barrier(
            (unpack_2bit_jax(q, q.shape[1] * 4, q_len),
             unpack_2bit_jax(t, t.shape[1] * 4, t_len)))

    batch = q.shape[0]
    ks = jnp.arange(band, dtype=jnp.int32)
    lq, lt = q.shape[1], t.shape[1]
    inf_col = jnp.full((batch, 1), INFD, dtype=DT)

    def shift(s, by):
        """s[:, k + by] for 0 <= k + by < band, INF elsewhere."""
        if by > 0:
            return jnp.concatenate([s[:, by:]] + [inf_col] * by, axis=1)
        if by < 0:
            return jnp.concatenate([inf_col] * -by + [s[:, :by]], axis=1)
        return s

    def at(seq, idx):
        """seq[lane, clip(idx)] for one index per lane."""
        return jnp.take_along_axis(
            seq, jnp.clip(idx, 0, seq.shape[1] - 1)[:, None], axis=1)

    # the sequence windows of wavefront d: qwin[k] = q[clip(i - 1)] and
    # twin[k] = t[clip(j - 1)] with i = off[d] + k, j = d - i. The band
    # start advances by da = off[d] - off[d-1] in {0, 1} (band_offsets),
    # so wavefront d's windows are the previous ones shifted by one lane
    # (q when da = 1, t when da = 0) plus one fresh element per lane —
    # the same clipped indices as a per-cell gather, without one
    a_first = offsets[:, 0]
    qwin0 = jnp.take_along_axis(
        q, jnp.clip(a_first[:, None] + ks[None, :] - 1, 0, lq - 1), axis=1)
    twin0 = jnp.take_along_axis(
        t, jnp.clip(-a_first[:, None] - ks[None, :] - 1, 0, lt - 1), axis=1)

    def step(carry, xs):
        s1, s2, a1, a2, qwin, twin, dist = carry
        d, a0 = xs                                 # scalar, [B]

        i = a0[:, None] + ks[None, :]              # [B, band] query row
        j = d - i                                  # target col
        valid = (i >= 0) & (i <= q_len[:, None]) & (j >= 0) & (j <= t_len[:, None])

        # neighbours from the banded wavefronts: da = a0 - a1 in {0, 1},
        # db = a0 - a2 in {0, 1, 2} — static lane shifts selected per lane
        da = (a0 - a1)[:, None]
        db = (a0 - a2)[:, None]
        fresh = d > 0
        qwin = jnp.where(fresh & (da == 1),
                         jnp.concatenate(
                             [qwin[:, 1:], at(q, a0 + band - 2)], axis=1),
                         qwin)
        twin = jnp.where(fresh & (da == 0),
                         jnp.concatenate(
                             [at(t, d - a0 - 1), twin[:, :-1]], axis=1),
                         twin)
        up = jnp.where(i >= 1, jnp.where(da == 0, shift(s1, -1), s1),
                       INFD)                       # consume q[i-1]
        left = jnp.where(j >= 1, jnp.where(da == 0, s1, shift(s1, 1)),
                         INFD)                     # consume t[j-1]
        diag = jnp.where((i >= 1) & (j >= 1),
                         jnp.where(db == 0, shift(s2, -1),
                                   jnp.where(db == 1, s2, shift(s2, 1))),
                         INFD)
        sub = jnp.where(qwin == twin, 0, 1).astype(DT)

        cd = diag + sub
        cu = up + jnp.asarray(1, DT)
        cl = left + jnp.asarray(1, DT)

        # fixed tie order: diag, up, left
        score = cd
        bp = jnp.zeros_like(score, dtype=jnp.uint8) + BP_DIAG
        bp = jnp.where(cu < score, BP_UP, bp).astype(jnp.uint8)
        score = jnp.minimum(score, cu)
        bp = jnp.where(cl < score, BP_LEFT, bp).astype(jnp.uint8)
        score = jnp.minimum(score, cl)

        # seed origin
        origin = (i == 0) & (j == 0)
        score = jnp.where(origin, jnp.asarray(0, DT), score)
        score = jnp.where(valid, jnp.minimum(score, INFD), INFD)

        # record final distance when this wavefront crosses (M, N)
        at_end = (i == q_len[:, None]) & (j == t_len[:, None])
        dist = jnp.where(at_end.any(axis=1),
                         jnp.where(at_end, score, INFD).min(axis=1), dist)

        # pack 2-bit backpointers 4 per byte
        b4 = bp.reshape(batch, band // 4, 4).astype(jnp.uint8)
        packed_bp = (b4[..., 0] | (b4[..., 1] << 2) | (b4[..., 2] << 4)
                     | (b4[..., 3] << 6))

        return (score, s1, a0, a1, qwin, twin, dist), packed_bp

    s_init = jnp.full((batch, band), INFD, dtype=DT)
    a_init = jnp.zeros((batch,), dtype=jnp.int32)
    dist_init = jnp.full((batch,), INFD, dtype=DT)

    (_, _, _, _, _, _, dist), bp_packed = jax.lax.scan(
        step, (s_init, s_init, a_init, a_init, qwin0, twin0, dist_init),
        (jnp.arange(n_waves, dtype=jnp.int32),
         offsets.T.astype(jnp.int32)))

    # traceback from (M, N) to (0, 0), every lane at once. A lane's
    # active steps are a prefix, so step s of every lane lands in row s.
    lanes = jnp.arange(batch)
    ql = q_len.astype(jnp.int32)
    tl = t_len.astype(jnp.int32)
    offs = offsets.astype(jnp.int32)

    def tb_cond(st):
        i, j = st[1], st[2]
        return jnp.any((i > 0) | (j > 0))

    def tb_body(st):
        s, i, j, cnt, touched, ops = st
        active = (i > 0) | (j > 0)
        d = jnp.minimum(i + j, n_waves - 1)
        off = jnp.take_along_axis(offs, d[:, None], axis=1)[:, 0]
        k = i - off
        # a band-boundary cell marks possible clipping, but only when
        # the matrix actually continues past the boundary on that side
        row_lo = jnp.maximum(0, d - tl)
        row_hi = jnp.minimum(d, ql)
        touched = touched | (active & (k <= 0) & (off > row_lo))
        touched = touched | (active & (k >= band - 1)
                             & (off + band - 1 < row_hi))
        kc = jnp.clip(k, 0, band - 1)
        byte = bp_packed[d, lanes, kc >> 2].astype(jnp.int32)
        code = (byte >> (2 * (kc & 3))) & 3
        # boundary overrides: on i==0 only D possible; on j==0 only I
        code = jnp.where(i == 0, BP_LEFT, code)
        code = jnp.where(j == 0, BP_UP, code)
        ops = jax.lax.dynamic_update_slice(
            ops, code.astype(jnp.uint8)[None, :], (s, jnp.int32(0)))
        i = jnp.where(active & (code != BP_LEFT), i - 1, i)
        j = jnp.where(active & (code != BP_UP), j - 1, j)
        return s + 1, i, j, cnt + active, touched, ops

    _, _, _, cnt, touched, ops = jax.lax.while_loop(
        tb_cond, tb_body,
        (jnp.int32(0), ql, tl, jnp.zeros((batch,), jnp.int32),
         jnp.zeros((batch,), bool),
         jnp.zeros((max(1, lq + lt), batch), jnp.uint8)))
    meta = jnp.stack([cnt, dist.astype(jnp.int32),
                      touched.astype(jnp.int32)], axis=1)
    return ops, meta


def decode_paths(ops: np.ndarray, counts: np.ndarray) -> list:
    """Per-lane op runs in forward order from device paths: `ops[lane]`
    holds lane's backpointer codes in traceback order, `counts[lane]`
    of them valid."""
    return [_runs_of(ops[lane, :counts[lane]][::-1])
            for lane in range(len(counts))]


_CODE_TO_OP = {BP_DIAG: "M", BP_UP: "I", BP_LEFT: "D"}


def _runs_of(seq: np.ndarray) -> list[tuple[int, str]]:
    """Forward-order op codes -> CIGAR-style run list — the ONE decoding
    shared by both kernels' device tracebacks, so their outputs compare
    (and render) identically."""
    runs: list[tuple[int, str]] = []
    if len(seq):
        change = np.nonzero(np.diff(seq))[0]
        starts = np.concatenate(([0], change + 1))
        ends = np.concatenate((change + 1, [len(seq)]))
        runs = [(int(e - s), _CODE_TO_OP[int(seq[s])])
                for s, e in zip(starts, ends)]
    return runs


class BatchAligner:
    """Buckets (query, target) pairs into static shapes and aligns each bucket
    on the device — the orchestration analogue of CUDABatchAligner
    (src/cuda/cudaaligner.cpp) with XLA instead of CUDA streams.

    band_width=0 means auto: 10% of the mean pair length, forced even —
    the reference's auto band rule (src/cuda/cudapolisher.cpp:158-174) —
    quantized up to a multiple of 128 so each bucket compiles exactly once.

    Rejection statuses mirror cudaaligner (src/cuda/cudaaligner.cpp:63-71):
    pairs beyond the largest bucket, pairs whose traceback rode the band
    boundary, and pairs whose in-band cost is beyond what a <=30%-error
    overlap can produce (both signs of band clipping) return None, and the
    caller host-aligns them (the GPU->CPU fallback,
    cudapolisher.cpp:203-213) — no overlap is ever dropped.
    """

    #: length bucket edges (sequences are padded to the bucket edge)
    BUCKETS = (512, 1024, 2048, 4096, 8192, 16384, 32768, 65536)
    #: target bytes of packed backpointers per device batch
    MAX_BP_BYTES = 192 * 1024 * 1024

    def __init__(self, band_width: int = 0, max_length: int | None = None,
                 runner=None, scheduler=None,
                 use_pallas: bool | None = None):
        import os

        from ..sched import BatchScheduler

        self.band_width = band_width
        #: Pallas wavefront-kernel posture: True/False force it on/off
        #: (tests), None defers to RACON_TPU_PALLAS (`1` = always when
        #: the VMEM envelope fits, `auto` = per-bucket winner table,
        #: unset/0 = XLA programs only — today's behavior)
        self.use_pallas = use_pallas
        # the cudaaligner max-length envelope (exceeded_max_length ->
        # CPU, cudaaligner.cpp:63-68); RACON_TPU_ALIGNER_MAXLEN trims it
        # e.g. for time-capped smoke runs on slow links
        if max_length is None:
            max_length = int(os.environ.get("RACON_TPU_ALIGNER_MAXLEN",
                                            65536))
        self.max_length = max_length
        self.runner = runner
        # occupancy-aware scheduler (sched/): adaptive length ladder +
        # sorted packing when armed, per-bucket occupancy telemetry always
        self.sched = (scheduler if scheduler is not None
                      else BatchScheduler.from_env())
        #: pairs whose banded distance hit the band-adequacy limit and were
        #: sent back for exact host alignment (observability, SURVEY.md §5)
        self.n_band_rejects = 0

    def _bucket_of(self, length: int) -> int | None:
        for edge in self.BUCKETS:
            if length <= edge and edge <= self.max_length:
                return edge
        return None

    def _band_for(self, pairs, idxs) -> int:
        """Auto band for one bucket: 10% of the bucket's mean pair length
        (the reference's auto rule, cudapolisher.cpp:158-174), quantized up
        to a multiple of 128 — one compiled shape per bucket, cached across
        runs. An explicit band_width is honored as given (rounded up to a
        multiple of 4 for backpointer packing).

        Length differences need no band floor: band_offsets tracks the
        (0,0)->(M,N) ideal line, so a uniformly-stretched skewed pair fits
        a narrow band, and a pair with concentrated indels is caught by the
        edge-touch/cost signals and host-realigned. A floor keyed to the
        bucket's worst pair would let one chimeric outlier balloon the
        whole bucket's backpointer memory."""
        if self.band_width > 0:
            return (self.band_width + 3) // 4 * 4
        mean_len = sum(max(len(pairs[i][0]), len(pairs[i][1]))
                       for i in idxs) / len(idxs)
        return max(128, (int(mean_len * 0.1) + 127) // 128 * 128)

    def _lane_cap(self, n_waves: int, band: int, n_dev: int) -> int:
        """Most lanes one chunk of this shape may hold: the backpointer
        bytes of a lane against MAX_BP_BYTES, at least one per device."""
        return max(n_dev, self.MAX_BP_BYTES // (n_waves * (band // 4)))

    def _plan(self, pairs, n_dev: int):
        """Bucket, band and chunk the pairs. Returns (chunks, unbucketed):
        chunks as (edge, band, n_waves, pair indices) in dispatch order,
        and the pairs no bucket takes (the host aligner's)."""
        def shape_of(idx: int) -> int:
            return max(len(pairs[idx][0]), len(pairs[idx][1]))

        # device eligibility and the AUTO band are ALWAYS decided by the
        # static ladder, adaptive mode included. The band is algorithmic,
        # not padding — it changes which equal-cost path the banded DP
        # can see — so it must not move when the scheduler regroups jobs;
        # pinning both to the static rule makes scheduler-on vs -off
        # byte-identity structural, not a fixture property.
        static_groups: dict[int, list[int]] = {}
        unbucketed: list[int] = []
        for idx, (qs, ts) in enumerate(pairs):
            edge = self._bucket_of(max(len(qs), len(ts)))
            if edge is None or not qs or not ts:
                unbucketed.append(idx)  # host aligner handles these
                continue
            static_groups.setdefault(edge, []).append(idx)

        band_of: dict[int, int] = {}  # pair -> band, the static rule's
        for edge, idxs in static_groups.items():
            band = self._band_for(pairs, idxs)
            for i in idxs:
                band_of[i] = band

        # regroup by (compiled edge, band). Static mode: the original
        # one-band-per-bucket grouping, unchanged. Adaptive mode: a
        # sub-ladder INSIDE each occupied static bucket (the run's
        # length histogram, compile budget K = len(BUCKETS) split across
        # buckets by job count), so jobs move to a tighter edge but keep
        # their static band — the per-lane DP (band + offsets) is
        # bit-identical, only the compiled wavefront count shrinks, and
        # the total (edge, band) combo count stays <= K because band is
        # constant within a static bucket. Static edges are multiples of
        # the ladder quantum, so a derived edge never exceeds its static
        # bucket's. All derivation state is local: a reused aligner
        # starts every align() from the static ladder again.
        groups: dict[tuple[int, int], list[int]] = {}
        if self.sched.adaptive and static_groups:
            k_of = {edge: 1 for edge in static_groups}
            spare = len(self.BUCKETS) - len(static_groups)
            by_load = sorted(static_groups,
                             key=lambda e: -len(static_groups[e]))
            i = 0
            while spare > 0:
                k_of[by_load[i % len(by_load)]] += 1
                spare -= 1
                i += 1
            for edge, idxs in static_groups.items():
                sub = self.sched.aligner_ladder(
                    [shape_of(i) for i in idxs], k=k_of[edge],
                    max_length=self.max_length) or (edge,)
                for i in idxs:
                    e = next((x for x in sub if x >= shape_of(i)), edge)
                    groups.setdefault((e, band_of[i]), []).append(i)
        else:
            for edge, idxs in static_groups.items():
                for i in idxs:
                    groups.setdefault((edge, band_of[i]), []).append(i)

        from ..sched import shard_interleave

        chunks: list[tuple[int, int, int, list[int]]] = []
        for (edge, band), idxs in sorted(groups.items()):
            # sorted packing: shape-homogeneous chunks instead of arrival
            # order (results land by original index, so output order is
            # unaffected); identity when the scheduler is off
            idxs = self.sched.order(idxs, key=shape_of)
            n_waves = 2 * edge + 1
            max_lanes = self._lane_cap(n_waves, band, n_dev)
            if n_dev > 1:
                # device-aware chunking: BODY chunks are multiples of
                # the mesh width (zero round_batch padding lanes, rows
                # interleaved so each shard carries an even share of
                # the sorted lengths) and the remainder dispatches as
                # its own small chunk on a sub-mesh (for_batch) instead
                # of padding whole lanes up to the full device count
                stride = max(n_dev, (max_lanes // n_dev) * n_dev)
                body = (len(idxs) // n_dev) * n_dev
                for s in range(0, body, stride):
                    part = idxs[s:s + min(stride, body - s)]
                    chunks.append((edge, band, n_waves,
                                   shard_interleave(part, n_dev)))
                if body < len(idxs):
                    chunks.append((edge, band, n_waves, idxs[body:]))
            else:
                for s in range(0, len(idxs), max_lanes):
                    chunks.append((edge, band, n_waves,
                                   idxs[s:s + max_lanes]))

        return chunks, unbucketed

    def align(self, pairs: list[tuple[bytes, bytes]], progress=None,
              pipeline=None,
              on_reject=None) -> list[list[tuple[int, str]] | None]:
        """Globally align each (query, target) pair. Returns per-pair op runs,
        or None for rejected pairs (see class docstring).

        `pipeline` (pipeline.DispatchPipeline) overlaps host pack (operand
        encoding + band offsets) and unpack (backpointer traceback) with
        device compute; omitted, the stages run synchronously as before.
        `on_reject(idx_list, reason)` fires as soon as pairs are known to
        need the host aligner — unbucketable pairs up front (`ladder`),
        band-clipped pairs per chunk as tracebacks land (`band` when the
        traceback rode the band's edge, `cost` when the in-band cost is
        past what a <=30%-error overlap gives) — so the caller can start
        fallback work concurrently with the device pass instead of
        scanning for None afterwards. With `on_reject` armed and strict
        mode off, a device chunk that still fails after the pipeline's
        watchdog/retry policy is routed the same way (`device_failure`) —
        its pairs host-align and the device pass continues
        (chunk-granularity GPU->CPU discipline, cudapolisher.cpp:354-383)
        instead of aborting the whole phase.
        """
        import jax

        from . import align_pallas
        from .dtypes import aligner_int16_ok, kernel_plan
        from .encode import (encode, encode_padded, pack_2bit,
                             pack_bases_enabled)
        from ..parallel.mesh import BatchRunner
        from ..pipeline import DispatchPipeline
        from ..resilience import strict_mode
        from ..utils.logger import warn_dedup

        runner = self.runner if self.runner is not None else BatchRunner()
        pl = pipeline if pipeline is not None else DispatchPipeline(depth=0)
        results: list[list[tuple[int, str]] | None] = [None] * len(pairs)

        with trace.span("aligner.plan", pairs=len(pairs)) as sp:
            chunks, unbucketed = self._plan(pairs, runner.n_devices)
            sp.set(chunks=len(chunks))
        if on_reject is not None and unbucketed:
            on_reject(unbucketed, "ladder")

        # per-bucket kernel/dtype plan, resolved once: the Pallas posture
        # (constructor override, else RACON_TPU_PALLAS incl. the `auto`
        # winner-table consult), the score dtype (int16 iff the bucket's
        # overflow proof holds — ops/dtypes), and the VMEM envelope gate
        # with fallback to the XLA program
        if self.use_pallas is True:
            mode = "on"
        elif self.use_pallas is False:
            mode = "off"
        else:
            from .poa_pallas import pallas_mode

            mode = pallas_mode()
        plans: dict[tuple[int, int], tuple[str, str]] = {}

        def plan_for(edge: int, band: int) -> tuple[str, str]:
            plan = plans.get((edge, band))
            if plan is None:
                use, dtype = kernel_plan(
                    mode, "aligner", (edge, band), (),
                    aligner_int16_ok(edge),
                    lambda dt: align_pallas.fits_vmem(edge, band, dt))
                plan = plans[(edge, band)] = (
                    "pallas" if use else "xla", dtype)
            return plan

        def packs(idx) -> bool:
            """2-bit base packing: ACGT-only chunks ship a quarter of the
            sequence bytes and unpack on device (byte-identical; any N
            in the chunk keeps the int8 operands, and padding lanes are
            ACGT). From the pairs alone, so the span args, taken before
            pack() runs, can say it too."""
            return pack_bases_enabled() and all(
                bool((encode(s) < 4).all()) for i in idx for s in pairs[i])

        def pack(chunk):
            edge, band, n_waves, idx = chunk
            kern, dtype = plan_for(edge, band)
            qs = [pairs[i][0] for i in idx]
            ts = [pairs[i][1] for i in idx]
            # tail batches smaller than the mesh dispatch on a SUB-MESH
            # (largest device count <= batch) instead of padding whole
            # lanes up to the full device count; for_batch is
            # deterministic in len(idx), so dispatch() resolves the
            # same runner
            lanes = runner.for_batch(len(idx)).round_batch(len(idx))
            q_arr, q_lens = encode_padded(qs + [b"A"] * (lanes - len(idx)),
                                          edge)
            t_arr, t_lens = encode_padded(ts + [b"A"] * (lanes - len(idx)),
                                          edge)
            offs = np.stack([band_offsets(int(ql), int(tl), band, n_waves)
                             for ql, tl in zip(q_lens, t_lens)])
            do_pack = packs(idx)
            if kern == "pallas":
                q_op, t_op = align_pallas.build_ext(q_arr, t_arr, band)
                if do_pack:
                    q_op, t_op = pack_2bit(q_op), pack_2bit(t_op)
            elif do_pack:
                q_op, t_op = pack_2bit(q_arr), pack_2bit(t_arr)
            else:
                q_op, t_op = q_arr, t_arr
            return kern, dtype, do_pack, q_op, t_op, q_lens, t_lens, offs

        def dispatch(chunk, ops):
            import time

            edge, band, n_waves, idx = chunk
            kern, dtype, do_pack, q_op, t_op, q_lens, t_lens, offs = ops
            # the sub-mesh pack() sized the lanes for (zero padding
            # lanes on tails smaller than the mesh)
            r = runner.for_batch(len(idx))
            # compile telemetry: the first dispatch of a new shape blocks
            # through trace + XLA build (near-zero when the persistent
            # compile cache is warm) — charge that wall to the shape.
            # The lane count is part of the program identity: a tail
            # chunk narrower than its siblings compiles separately.
            t0 = time.perf_counter()
            if kern == "pallas":
                fn = align_pallas.wavefront_align(
                    edge, band, dtype, do_pack,
                    interpret=jax.default_backend() != "tpu")
                out = r.run_split(fn, q_op, t_op,
                                  q_lens.astype(np.int32),
                                  t_lens.astype(np.int32), offs)
            else:
                kernel = _kernel_for(band, n_waves, dtype, do_pack)
                out = r.run(
                    kernel, q_op, t_op, q_lens.astype(np.int32),
                    t_lens.astype(np.int32), offs,
                    out_batch_axes=(1, 0))  # ops is [steps, B]
            self.sched.stats.record_compile_once(
                "aligner",
                (band, n_waves, offs.shape[0], kern, dtype, do_pack),
                time.perf_counter() - t0)
            # occupancy telemetry, recorded at dispatch (a chunk killed
            # by a fault or the circuit breaker must not be accounted as
            # device work): useful DP cells = per-pair wave count x band
            # vs the batch's full n_waves x band x lanes — plus the mesh
            # view (per-shard useful split; what full-mesh round_batch
            # rounding would have dispatched)
            from .device_program import shard_useful_split

            row_cells = [(len(pairs[i][0]) + len(pairs[i][1]) + 1) * band
                         for i in idx]
            self.sched.stats.record(
                "aligner", (edge, band), jobs=len(idx),
                lanes=offs.shape[0],
                useful_cells=sum(row_cells),
                total_cells=offs.shape[0] * n_waves * band,
                kernel=kern, dtype=dtype, n_devices=r.n_devices,
                shard_useful=shard_useful_split(row_cells, offs.shape[0],
                                                r.n_devices),
                full_mesh_cells=(runner.round_batch(len(idx))
                                 * n_waves * band))
            pl.stats.bump("launches")
            return kern, out, q_lens, t_lens, offs

        def wait(handle):
            kern, out, q_lens, t_lens, offs = handle
            if kern == "pallas":
                shards = out if isinstance(out, list) else [out]
                op_arr = np.concatenate(
                    [np.asarray(jax.device_get(s[0])) for s in shards])
                meta = np.concatenate(
                    [np.asarray(jax.device_get(s[1])) for s in shards])
                return kern, (op_arr, meta), q_lens, t_lens, offs
            ops, meta = out
            return (kern, (np.asarray(jax.device_get(ops)),
                           np.asarray(jax.device_get(meta))),
                    q_lens, t_lens, offs)

        def unpack(chunk, res):
            breaker.ok()  # a chunk came all the way back: device alive
            edge, band, n_waves, idx = chunk
            kern, out, q_lens, t_lens, offs = res
            # both kernels trace back on the device: each lane's path
            # (traceback order), its length, distance and edge flag
            op_arr, meta = out
            if kern != "pallas":
                op_arr = op_arr.T                 # [steps, B] -> [B, steps]
            counts = meta[:, 0]
            dist = meta[:, 1].astype(np.int64)
            touched = meta[:, 2] > 0
            runs = decode_paths(op_arr, counts)
            # second clipping signal: an in-band cost far above what a
            # <=30%-error overlap can produce means the true (off-band)
            # path was clipped — e.g. a large balanced indel whose
            # in-band "alignment" is a run of mismatches
            suspicious = dist > 0.4 * np.maximum(q_lens, t_lens)
            accepted = 0
            rejected: dict[str, list[int]] = {"band": [], "cost": []}
            for lane, i_pair in enumerate(idx):
                if touched[lane] or suspicious[lane]:
                    self.n_band_rejects += 1  # clipped: host re-aligns
                    rejected["band" if touched[lane] else "cost"].append(
                        i_pair)
                else:
                    results[i_pair] = runs[lane]
                    accepted += 1
            if on_reject is not None:
                for reason, rej in rejected.items():
                    if rej:
                        on_reject(rej, reason)
            if progress is not None:
                # rejected pairs tick when the host fallback aligns them
                progress(accepted)

        #: consecutive-chunk-failure circuit breaker — the shared seam
        #: implementation (ops/device_program.ChunkBreaker): one flaky
        #: chunk degrades to the host fallback, but a wedged device must
        #: not cost a watchdog deadline + retry per chunk for the whole
        #: phase — past the streak the pass aborts and the polisher's
        #: whole-phase host fallback runs
        from .device_program import ChunkBreaker

        breaker = ChunkBreaker("BatchAligner", pl.stats,
                               "the device alignment pass")

        def chunk_error(chunk, exc):
            # a chunk dead after watchdog/retry: its pairs host-align via
            # the reject protocol; results stay complete, never crash.
            # Deduplicated: on a wedged device this fires once per chunk
            # with near-identical text — the first prints, repeats are
            # counted (RACON_TPU_LOG_LEVEL=debug shows each)
            edge, band, n_waves, idx = chunk
            breaker.failed(exc, f"{len(idx)} pairs to host fallback")
            on_reject(list(idx), "device_failure")

        def describe(chunk):
            edge, band, n_waves, idx = chunk
            return {"engine": "aligner", "bucket": f"{edge}x{band}",
                    "jobs": len(idx), "edge": edge, "band": band,
                    "lanes": runner.for_batch(len(idx)).round_batch(
                        len(idx)),
                    "lane_cap": self._lane_cap(n_waves, band,
                                               runner.n_devices),
                    "kernel": plan_for(edge, band)[0],
                    "packed": packs(idx)}

        pl.run(chunks, pack, dispatch, wait, unpack,
               on_error=(chunk_error if on_reject is not None
                         and not strict_mode() else None),
               label="aligner",
               describe=describe)
        return results


def edit_distance(a: bytes, b: bytes) -> int:
    """Plain (unbanded) edit distance on host — numpy row DP. Used by tests
    as the reference metric (the reference uses edlib in
    test/racon_test.cpp:16-25)."""
    if not a:
        return len(b)
    if not b:
        return len(a)
    pa = np.frombuffer(a, dtype=np.uint8)
    pb = np.frombuffer(b, dtype=np.uint8)
    prev = np.arange(len(pb) + 1, dtype=np.int32)
    for i in range(1, len(pa) + 1):
        cur = np.empty_like(prev)
        cur[0] = i
        # vertical + diagonal candidates
        np.minimum(prev[:-1] + (pb != pa[i - 1]), prev[1:] + 1, out=cur[1:])
        # horizontal propagation: cur[j] = min_k<=j (cand[k] + (j - k))
        ar = np.arange(len(cur), dtype=np.int32)
        cur = np.minimum.accumulate(cur - ar) + ar
        prev = cur
    return int(prev[-1])
