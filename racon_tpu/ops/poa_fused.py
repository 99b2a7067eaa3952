"""Single-launch whole-window POA on device (experimental engine).

The cudapoa-shaped design (reference src/cuda/cudabatch.cpp:77-270: add
windows until the batch is full, then ONE generate_poa() builds every
window's whole graph on device) rebuilt TPU-first. Where the session
engine (ops/poa_graph.py) round-trips host<->device once per layer wave,
this engine runs ALL layers of a window batch in a single jitted call —
the POA graph itself lives in fixed-shape device arrays and is mutated by
vectorized scatters:

  - the graph's topological order is maintained WITHOUT graph traversal:
    every aligned column owns a 64-bit ORDER KEY; node order is
    `argsort(column key, node id)` — one vectorized sort per layer instead
    of a sequential topo walk. Insertion columns get keys strictly between
    their path neighbours' keys (run-partitioned equal spacing), with the
    low 8 bits salted by layer index so keys are globally unique (equal
    keys would let node-id tie-breaking reorder columns under later
    in-column allocations);
  - per layer: graph-NW DP + traceback on device (the same formulation as
    ops/poa_graph.graph_aligner, full DP), then a fully VECTORIZED ingest
    — target resolution (same base -> existing node, mismatch -> aligned
    alternate or new node in column, insertion -> new node + new column),
    prefix-sum node allocation, and conflict-free scatter wiring of edges,
    edge weights (w[i-1] + w[i], the endpoint-sum convention of
    native/src/poa.cpp add_alignment) and sequence counts.
    No sequential walk anywhere in the ingest;
  - windows that exceed any envelope (nodes, columns, in-degree P, key
    spacing) raise a per-window `failed` flag and fall back to the host
    engine — the per-window GPU->CPU fallback discipline
    (cudapolisher.cpp:354-383);
  - consensus runs on host from the fetched arrays via the SAME C++
    heaviest-bundle the host engine uses (native rh_poa_finish_arrays).

Accuracy contract: the engine replicates the host's layer order
(begin-sorted, window.cpp:84-85), band rule (256 when the layer fits,
exact DP otherwise), the banded clipped->full-DP retry (the host
band_clipped rule, run on device under `lax.cond` so unclipped layers —
the typical case — pay nothing) and ingest semantics; tests assert
BYTE-IDENTITY to the host engine on spanning, non-spanning and
band-clipping synthetic windows. On real data the guarantee is
measurably weaker than the session engine's: deep windows can hit
topo-order tie cases where the argsort-key order and the host graph's
walk order rank equal-scoring paths differently (lambda sample: 95/96
windows byte-equal, 1 diverges with identical aggregate quality —
distance 1352 == host; pinned by tests/test_fused_poa.py). The session
engine (ops/poa_graph.py) remains the byte-identical-everywhere engine;
the reference itself pins diverging GPU numbers separately
(racon_test.cpp:292-496). With `banded_only` (-b) the retry is skipped,
the reference's GPU-only speed/accuracy trade (cudabatch.cpp:56-59).

Non-spanning layers (reference window.cpp:87-103's subgraph case) are
handled by MASKING, not extraction: every node carries its backbone
position (`bpos`, inherited exactly like the host engine's), and a layer
with range [begin, end] aligns against only the in-range nodes — preds
filtered to in-range (a node with no in-range pred becomes a subgraph
source), sinks recomputed as in-range nodes without in-range successors.
This reproduces the host's bpos-range-induced subgraph
(native/src/poa.cpp Graph::subgraph) without materializing it.

Depth is bucketed ((8, 16, 32, 64) layers per call) and deeper windows
CHAIN calls: the state arrays stream out of one call and into the next
with a layer-index base, so arbitrary depth costs no extra host work
beyond the fetch/feed of the fixed-size state.

FUSED single-launch mode (RACON_TPU_FUSED=auto|0|1, default auto):
instead of the chained per-bucket calls with host-side window slicing,
one device program runs a chunk's WHOLE chain — banded graph alignment,
the window-slicing decisions (spanning / bpos-range subgraph bounds /
the static-band rule, derived on device from the raw layer coordinates)
and the POA row-update ingest — as one jitted scan with donated state
buffers, so aligned coordinates never leave the chip between stages and
per-chunk Python dispatch collapses to one launch + one fetch.
Bit-identical to the split path by construction (integer-exact slicing,
same layer scan); `auto` arbitrates fused-vs-split per depth bucket via
the persisted autotuner winner table (sched/autotune, engine
"fused_loop") under the same identity veto as the kernel plane, and a
fused chunk that faults falls back to the split chained path — its
DECLARED fallback — byte-identically before anything reaches the host
engine tail.

Requires jax x64 (the order keys are int64); enabled at kernel build.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from ..resilience import strict_mode
from ..utils.logger import Logger, log_info, warn_dedup
#: envelope shared with the session engine (ONE source of truth, incl.
#: the construction-time RACON_TPU_MAX_NODES override; measured: ~2000
#: nodes at depth 38 on the lambda sample, and the default envelope
#: device-builds 98.7% of windows at 30x coverage — see
#: poa_graph.MAX_NODES and PARITY.md)
from .poa_graph import (MAX_LEN, MAX_NODES, MAX_PRED, RING,
                        env_max_nodes)

#: layers per call; deeper windows chain calls with carried state
DEPTH_BUCKETS = (8, 16, 32, 64)

#: deepest chunk the FUSED single-launch program takes (beyond it the
#: split chained path runs — one compiled program per distinct chunk
#: total-depth must stay bounded, and chain-sums past this are rare
#: tails, not the hot path)
FUSED_LOOP_MAX_DEPTH = 128

_NEG = -(1 << 29)


def fused_mode() -> str:
    """RACON_TPU_FUSED posture for the single-launch fused
    align→window-slice→POA program: '1' = fused whenever the chunk
    fits FUSED_LOOP_MAX_DEPTH, '0' = always the split chained path
    (the pre-fusion behavior), 'auto' (default) = per-bucket via the
    persisted autotuner winner table (sched/autotune engine
    "fused_loop"; a cold table dispatches split). Invalid values fall
    back to auto — never crash a run over a typo'd knob. Inside an
    audit oracle_scope (ops/oracle.py) the posture is pinned '0' on
    that thread — the shadow oracle runs the split chained path, the
    fused program's declared byte-identical fallback."""
    from .oracle import oracle_active

    if oracle_active():
        return "0"
    raw = (os.environ.get("RACON_TPU_FUSED") or "auto").strip().lower()
    return raw if raw in ("auto", "0", "1") else "auto"


@functools.lru_cache(maxsize=None)
def fused_raw(n_nodes: int, seq_len: int, depth: int, max_pred: int,
              match: int, mismatch: int, gap: int,
              banded_only: bool = False, score_dtype: str = "int32",
              device_slice: bool = False):
    """Raw (traceable, un-jitted) whole-window POA builder for one
    (N, L, D, P) shape — `fused_builder` jits it for single-device
    dispatch; FusedPOA's BatchRunner shard_maps it for multi-chip
    dispatch (the batch-per-GPU loop of cudapolisher.cpp:228-240, as one
    batch-sharded program per chip over the mesh).

    State arrays (leading dim B): codes [B,N] i8 (-1 free), preds [B,N,P]
    i16 node ids (-1 empty), predw [B,N,P] i32, nseq [B,N] i32,
    col_of [B,N] i16, colkey [B,N] i64, colnodes [B,N,5] i16,
    bpos [B,N] i16, n_nodes/n_cols [B] i32. Layer inputs: seqs [B,D,L] i8
    (pad 5), lens [B,D] i32 (0 = no layer), wts [B,D,L] i8 (Phred-33
    weights <= 93; upcast on device — a quarter of the host->device
    bytes), rlo/rhi [B,D] i16 (the layer's bpos range; -32768/32767 =
    spanning, full graph), lbase [B] i32 (per-row layer-index base, so
    every operand is batch-leading and shardable). Returns the updated
    state + failed [B] bool.
    """
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)

    N, L, D, P = n_nodes, seq_len, depth, max_pred
    C = N  # column capacity
    #: DP score dtype — int16 halves the per-layer DP carry when the
    #: envelope proof holds (ops/dtypes.poa_int16_ok; the graph/ingest
    #: arrays keep their own dtypes — only the alignment DP narrows)
    DT = jnp.int16 if score_dtype == "int16" else jnp.int32
    NEG = jnp.asarray(-(1 << 14) if score_dtype == "int16" else _NEG, DT)
    MAXKEY = jnp.int64(1) << 44  # composite (key << 11 | id) must fit i64

    def dp_align(codes_r, preds_r, sinks_r, centers_r, band, seq, slen, B,
                 kmax):
        # ring carry: only the last RING DP rows stay resident (slot 0 =
        # virtual source) — valid because the caller fails any lane whose
        # predecessor distance exceeds the ring (measured: 29 on the
        # lambda sample, 72 on synthbench 250 kb — see poa_graph.RING);
        # the score at each lane's sink column is collected
        # into a side carry as rows retire
        W = RING
        jidx = jnp.arange(L + 1, dtype=jnp.int32)
        jg = (jidx * gap).astype(DT)
        h0 = jnp.where(jidx[None, :] <= slen[:, None], jg[None, :], NEG)
        H = jnp.full((B, W + 1, L + 1), NEG, dtype=DT)
        H = H.at[:, 0, :].set(h0)
        scores0 = jnp.full((B, N), NEG, dtype=DT)
        band2 = (band // 2).astype(jnp.int32)

        def step(carry, xs):
            H, scores = carry
            code_k, preds_k, center_k, k = xs
            pk = jnp.where(preds_k > 0,
                           1 + jax.lax.rem(preds_k - 1, jnp.int32(W)), 0)
            pk = jnp.clip(pk, 0, W)
            rows = jnp.take_along_axis(H, pk[:, :, None], axis=1)
            rows = jnp.where((preds_k >= 0)[:, :, None], rows, NEG)
            sub = jnp.where(seq == code_k[:, None], match,
                            mismatch).astype(DT)
            diag = rows[:, :, :-1] + sub[:, None, :]
            vert = rows[:, :, 1:] + gap
            best = jnp.max(jnp.maximum(diag, vert), axis=1)
            row0 = jnp.max(rows[:, :, 0], axis=1) + gap
            # static-band masking around each node's expected diagonal,
            # exactly like the host engine (band 0 = full DP)
            use_band = band > 0
            jlo = jnp.where(use_band, jnp.maximum(1, center_k - band2), 1)
            jhi = jnp.where(use_band, jnp.minimum(slen, center_k + band2),
                            slen)
            inb = ((jidx[None, 1:] >= jlo[:, None]) &
                   (jidx[None, 1:] <= jhi[:, None]))
            pre = jnp.where(inb, best, NEG)
            seed0 = jnp.where(jlo == 1, row0, NEG)
            cat = jnp.concatenate([seed0[:, None], pre], axis=1)
            run = jax.lax.cummax(cat - jg, axis=1) + jg
            hrow = jnp.where(inb, run[:, 1:], pre)
            new_row = jnp.concatenate([row0[:, None], hrow], axis=1)

            nr = new_row[:, 1:]
            is_diag = nr[:, None, :] == diag
            is_vert = nr[:, None, :] == vert
            pd = jnp.argmax(is_diag, axis=1).astype(jnp.int32)
            pv = jnp.argmax(is_vert, axis=1).astype(jnp.int32)
            bpc = jnp.where(jnp.any(is_diag, axis=1), pd,
                            jnp.where(jnp.any(is_vert, axis=1), P + pv,
                                      2 * P))
            is_v0 = row0[:, None] == rows[:, :, 0] + gap
            bp0 = P + jnp.argmax(is_v0, axis=1).astype(jnp.int32)
            bp_row = jnp.concatenate([bp0[:, None], bpc],
                                     axis=1).astype(jnp.int8)
            slot = 1 + jax.lax.rem(k - 1, jnp.int32(W))
            H = jax.lax.dynamic_update_slice(
                H, new_row[:, None, :], (jnp.int32(0), slot, jnp.int32(0)))
            sc = jnp.take_along_axis(new_row, slen[:, None], axis=1)
            scores = jax.lax.dynamic_update_slice(
                scores, sc, (jnp.int32(0), k - 1))
            return (H, scores), bp_row

        # row loop bounded by the batch's real node count (graphs start at
        # backbone size ~N/4 and grow layer by layer — a static N-step
        # scan would pay for every pad row on every layer)
        bps0 = jnp.zeros((N, B, L + 1), dtype=jnp.int8)

        def row(k, carry):
            hs, bps = carry
            code_k = jax.lax.dynamic_slice_in_dim(
                codes_r, k - 1, 1, axis=1)[:, 0]
            preds_k = jax.lax.dynamic_slice_in_dim(
                preds_r, k - 1, 1, axis=1)[:, 0]
            center_k = jax.lax.dynamic_slice_in_dim(
                centers_r, k - 1, 1, axis=1)[:, 0]
            hs, bp_row = step(hs, (code_k, preds_k, center_k, k))
            bps = jax.lax.dynamic_update_slice(
                bps, bp_row[None], (k - 1, jnp.int32(0), jnp.int32(0)))
            return hs, bps

        (_, scores), bps = jax.lax.fori_loop(
            jnp.int32(1), kmax + 1, row, ((H, scores0), bps0))

        cand = jnp.where(sinks_r, scores, NEG)
        best_rank = jnp.argmax(cand, axis=1).astype(jnp.int32)

        rows_b = jnp.arange(B)

        def cond(st):
            r, j, _ = st
            return jnp.any((r > 0) | (j > 0))

        def body(st):
            r, j, out = st
            active = (r > 0) | (j > 0)
            # point gathers from the [N, B, L+1] plane, as graph_aligner
            # does (a batch-major flat copy slows the TPU compile)
            rc = jnp.clip(r - 1, 0, N - 1)
            code = bps[rc, rows_b, jnp.clip(j, 0, L)].astype(jnp.int32)
            code = jnp.where(r > 0, code, 2 * P)
            is_diag = code < P
            is_vert = (code >= P) & (code < 2 * P)
            p = jnp.where(is_diag, code, code - P)
            pr = preds_r[rows_b, rc, jnp.clip(p, 0, P - 1)]
            consume = active & ~is_vert
            jc = jnp.clip(j - 1, 0, L - 1)
            cur = jnp.take_along_axis(out, jc[:, None], axis=1)[:, 0]
            emit = jnp.where(is_diag, r - 1, -1)
            out = out.at[rows_b, jc].set(jnp.where(consume, emit, cur))
            r = jnp.where(active & (is_diag | is_vert), pr, r)
            j = jnp.where(consume, j - 1, j)
            return r, j, out

        out0 = jnp.full((B, L), -2, dtype=jnp.int32)
        _, _, ranks = jax.lax.while_loop(cond, body,
                                         (best_rank + 1, slen, out0))
        return ranks

    def fwd(a, b):
        return jnp.where(b[1], b[0], a[0]), (a[1] | b[1])

    def bwd_seg(a, b):
        return (jnp.where(b[1], b[0], jnp.maximum(a[0], b[0])),
                (a[1] | b[1]))

    def one_layer(state, layer):
        (codes, preds, predw, nseq, col_of, colkey, colnodes,
         bpos, n_nodes, n_cols, failed) = state
        seq, slen, wts, rlo, rhi, band, lidx = layer
        B = codes.shape[0]
        rows_b = jnp.arange(B)
        active = (slen > 0) & ~failed

        # topo order from column keys (argsort; node-id tiebreak)
        alloc = codes >= 0
        nkey = jnp.where(
            alloc,
            (jnp.take_along_axis(
                colkey, jnp.clip(col_of, 0, C - 1).astype(jnp.int32),
                axis=1) << 11) | jnp.arange(N, dtype=jnp.int64)[None, :],
            jnp.int64(1) << 62)
        order = jnp.argsort(nkey, axis=1).astype(jnp.int32)
        rank_of = jnp.zeros((B, N), dtype=jnp.int32)
        rank_of = rank_of.at[rows_b[:, None], order].set(
            jnp.arange(N, dtype=jnp.int32)[None, :])

        # the layer's bpos-range-induced subgraph, by masking (the host's
        # Graph::subgraph semantics): out-of-range nodes become dead rows,
        # in-range nodes keep only in-range preds (none left -> subgraph
        # source), sinks = in-range nodes with no in-range successor
        in_range = (alloc & (bpos >= rlo[:, None]) &
                    (bpos <= rhi[:, None]))
        in_range_r = jnp.take_along_axis(in_range, order, axis=1)

        codes_r = jnp.take_along_axis(codes, order, axis=1)
        codes_r = jnp.where(in_range_r, codes_r, 5).astype(jnp.int8)
        pr_nodes = jnp.take_along_axis(preds, order[:, :, None], axis=1)
        pr_clip = jnp.clip(pr_nodes, 0, N - 1).reshape(B, -1)
        pr_ok = (pr_nodes >= 0) & jnp.take_along_axis(
            in_range, pr_clip, axis=1).reshape(B, N, P)
        pr_rank = jnp.where(
            pr_ok,
            jnp.take_along_axis(rank_of, pr_clip,
                                axis=1).reshape(B, N, P) + 1,
            -1).astype(jnp.int32)
        no_pred = (~pr_ok).all(axis=2) & in_range_r
        pr_rank = pr_rank.at[:, :, 0].set(
            jnp.where(no_pred, 0, pr_rank[:, :, 0]))
        # dp_align's carry holds only the last RING rows — a lane with a
        # longer predecessor reach would read retired rows; fail it to
        # the host engine (measured: 29 lambda / 72 synthbench, both
        # within RING=128 — see poa_graph.RING)
        kk1 = jnp.arange(1, N + 1, dtype=jnp.int32)[None, :, None]
        ring_fail = ((pr_rank > 0) &
                     (kk1 - pr_rank > RING)).any(axis=(1, 2))

        has_succ = jnp.zeros((B, N + 2), dtype=bool)
        succ_pos = jnp.where(pr_ok & in_range_r[:, :, None],
                             pr_clip.reshape(B, N, P), N + 1)
        has_succ = has_succ.at[
            rows_b[:, None, None], succ_pos].set(True, mode="drop")
        sinks_r = in_range_r & ~jnp.take_along_axis(
            has_succ[:, :N], order, axis=1)

        # band centers: bpos relative to the layer's range origin
        origin = jnp.maximum(rlo.astype(jnp.int32), 0)
        centers_r = (jnp.take_along_axis(bpos, order, axis=1).astype(
            jnp.int32) - origin[:, None] + 1)

        kmax = jnp.max(n_nodes).astype(jnp.int32)
        ranks = dp_align(codes_r, pr_rank, sinks_r, centers_r,
                         band.astype(jnp.int32), seq, slen, B, kmax)

        if not banded_only:
            # banded clipped -> full-DP retry, the host engine's rule
            # (native/src/poa.cpp band_clipped): fewer than half the
            # aligned columns matching means the in-band path is mismatch
            # soup from band clipping; redo those lanes with the exact
            # full DP. lax.cond skips the redo entirely on the (typical)
            # layer where nothing clipped.
            node_c = jnp.take_along_axis(
                codes_r, jnp.clip(ranks, 0, N - 1), axis=1)
            al = ranks >= 0
            n_al = al.sum(axis=1)
            n_ma = (al & (node_c == seq)).sum(axis=1)
            clipped = (active & (band > 0) &
                       ((n_al == 0) | (2 * n_ma < n_al)))

            def _redo(_):
                full = dp_align(codes_r, pr_rank, sinks_r, centers_r,
                                jnp.zeros_like(band, jnp.int32), seq,
                                slen, B, kmax)
                return jnp.where(clipped[:, None], full, ranks)

            ranks = jax.lax.cond(jnp.any(clipped), _redo,
                                 lambda _: ranks, None)

        # ---- vectorized ingest
        iidx = jnp.arange(L, dtype=jnp.int32)
        inlen = (iidx[None, :] < slen[:, None]) & active[:, None]
        base = seq.astype(jnp.int32)
        aligned = (ranks >= 0) & inlen
        node_at = jnp.where(
            aligned,
            jnp.take_along_axis(order, jnp.clip(ranks, 0, N - 1), axis=1),
            -1)
        col0 = jnp.where(
            aligned,
            jnp.take_along_axis(col_of, jnp.clip(node_at, 0, N - 1),
                                axis=1).astype(jnp.int32),
            -1)
        same = aligned & (jnp.take_along_axis(
            codes, jnp.clip(node_at, 0, N - 1), axis=1) == base)
        alt = jnp.where(
            aligned,
            colnodes.reshape(B, -1)[
                rows_b[:, None],
                jnp.clip(col0, 0, C - 1) * 5 + jnp.clip(base, 0, 4)],
            -1).astype(jnp.int32)
        use_alt = aligned & ~same & (alt >= 0)
        new_in_col = aligned & ~same & (alt < 0)
        insertion = inlen & ~aligned

        # per-run anchor keys: prev (forward) / next (backward); anchor
        # bpos propagated the same way for insertion-node bpos inheritance
        # (host: insertions take the previous column's bpos, leading
        # insertions backfill from the next aligned column)
        akey = jnp.where(
            aligned,
            jnp.take_along_axis(
                colkey, jnp.clip(col0, 0, C - 1).astype(jnp.int32),
                axis=1),
            0)
        abpos = jnp.where(
            aligned,
            jnp.take_along_axis(bpos, jnp.clip(node_at, 0, N - 1),
                                axis=1).astype(jnp.int64),
            0)
        pkey, pflag = jax.lax.associative_scan(fwd, (akey, aligned),
                                               axis=1)
        pkey_prev = jnp.concatenate(
            [jnp.zeros((B, 1), jnp.int64), pkey[:, :-1]], axis=1)
        has_prev = jnp.concatenate(
            [jnp.zeros((B, 1), bool), pflag[:, :-1]], axis=1)
        pbp = jax.lax.associative_scan(fwd, (abpos, aligned), axis=1)[0]
        pbp_prev = jnp.concatenate(
            [jnp.zeros((B, 1), jnp.int64), pbp[:, :-1]], axis=1)
        nk = jax.lax.associative_scan(
            fwd, (jnp.flip(akey, 1), jnp.flip(aligned, 1)), axis=1)[0]
        nkey_next = jnp.flip(nk, 1)
        nbp_next = jnp.flip(jax.lax.associative_scan(
            fwd, (jnp.flip(abpos, 1), jnp.flip(aligned, 1)), axis=1)[0], 1)
        nkey_next = jnp.where(
            jnp.flip(jax.lax.associative_scan(
                jnp.logical_or, jnp.flip(aligned, 1), axis=1), 1),
            nkey_next, MAXKEY)
        ins_bpos = jnp.where(has_prev, pbp_prev, nbp_next).astype(
            jnp.int16)

        # position within insertion run and run length
        ins_i = jnp.cumsum(insertion.astype(jnp.int32), axis=1)
        run_start_ins = jax.lax.associative_scan(
            fwd, (ins_i.astype(jnp.int64), aligned), axis=1)[0]
        run_start_ins = jnp.concatenate(
            [jnp.zeros((B, 1), jnp.int64), run_start_ins[:, :-1]],
            axis=1).astype(jnp.int32)
        jrun = jnp.where(insertion, ins_i - run_start_ins, 0)
        mrev = jax.lax.associative_scan(
            bwd_seg, (jnp.flip(jnp.where(insertion, jrun, 0), 1),
                      jnp.flip(aligned, 1)), axis=1)[0]
        mrun = jnp.flip(mrev, 1)

        # insertion column keys: run-partitioned equal spacing, low 8 bits
        # replaced with the layer salt for global uniqueness (lidx is per
        # row, [B])
        span = nkey_next - pkey_prev
        spacing = span // (mrun.astype(jnp.int64) + 1)
        grid = pkey_prev + span * jrun.astype(jnp.int64) // (
            mrun.astype(jnp.int64) + 1)
        salt = ((lidx.astype(jnp.int64) + 1) & 0xFF)[:, None]
        ikey = (grid & ~jnp.int64(0xFF)) | salt
        key_bad = insertion & ((spacing <= 512) |
                               (ikey <= pkey_prev) | (ikey >= nkey_next))

        new_node = new_in_col | insertion
        nid = (n_nodes[:, None] +
               jnp.cumsum(new_node.astype(jnp.int32), axis=1) - 1)
        cid = (n_cols[:, None] +
               jnp.cumsum(insertion.astype(jnp.int32), axis=1) - 1)
        overflow = (new_node & (nid >= N)) | (insertion & (cid >= C))
        layer_fail = (key_bad.any(axis=1) | overflow.any(axis=1)
                      | ring_fail)
        ok = active & ~layer_fail
        okm = ok[:, None]

        target = jnp.where(same, node_at,
                           jnp.where(use_alt, alt,
                                     jnp.where(new_node, nid, -1)))
        tcol = jnp.where(insertion, cid, col0)

        sn = jnp.where(new_node & okm, nid, N + 1)
        codes = codes.at[rows_b[:, None], sn].set(
            base.astype(jnp.int8), mode="drop")
        col_of = col_of.at[rows_b[:, None], sn].set(
            tcol.astype(col_of.dtype), mode="drop")
        tbpos = jnp.where(insertion, ins_bpos,
                          jnp.take_along_axis(
                              bpos, jnp.clip(node_at, 0, N - 1),
                              axis=1)).astype(jnp.int16)
        bpos = bpos.at[rows_b[:, None], sn].set(tbpos, mode="drop")
        sc = jnp.where(insertion & okm, cid, C + 1)
        colkey = colkey.at[rows_b[:, None], sc].set(ikey, mode="drop")
        flat_cn = colnodes.reshape(B, C * 5)
        cnpos = jnp.where(new_node & okm,
                          jnp.clip(tcol, 0, C - 1) * 5 + base, C * 5 + 1)
        flat_cn = flat_cn.at[rows_b[:, None], cnpos].set(
            nid.astype(colnodes.dtype), mode="drop")
        colnodes = flat_cn.reshape(B, C, 5)

        st = jnp.where((inlen & (target >= 0)) & okm, target, N + 1)
        nseq = nseq.at[rows_b[:, None], st].add(1, mode="drop")

        # edges between consecutive path positions
        tails = target[:, :-1]
        heads = target[:, 1:]
        epresent = inlen[:, 1:] & inlen[:, :-1] & okm
        w32 = wts.astype(jnp.int32)
        ew = w32[:, :-1] + w32[:, 1:]
        hclip = jnp.clip(heads, 0, N - 1)
        hpred = jnp.take_along_axis(preds, hclip[:, :, None], axis=1)
        match_slot = (hpred == tails[:, :, None]) & (tails[:, :, None] >= 0)
        empty_slot = hpred < 0
        has_match = match_slot.any(axis=2)
        slot = jnp.where(has_match, jnp.argmax(match_slot, axis=2),
                         jnp.argmax(empty_slot, axis=2))
        slot_ok = has_match | empty_slot.any(axis=2)
        edge_fail = (epresent & ~slot_ok).any(axis=1)
        failed = failed | (active & (layer_fail | edge_fail))
        eok = epresent & slot_ok & (~edge_fail)[:, None]

        flat_p = preds.reshape(B, N * P)
        flat_w = predw.reshape(B, N * P)
        ppos = jnp.where(eok, hclip * P + slot, N * P + 1)
        flat_p = flat_p.at[rows_b[:, None], ppos].set(
            tails.astype(preds.dtype), mode="drop")
        flat_w = flat_w.at[rows_b[:, None], ppos].add(ew, mode="drop")
        preds = flat_p.reshape(B, N, P)
        predw = flat_w.reshape(B, N, P)
        n_nodes = jnp.where(
            ok, n_nodes + new_node.sum(axis=1, dtype=jnp.int32), n_nodes)
        n_cols = jnp.where(
            ok, n_cols + insertion.sum(axis=1, dtype=jnp.int32), n_cols)
        return ((codes, preds, predw, nseq, col_of, colkey,
                 colnodes, bpos, n_nodes, n_cols, failed), None)

    def run(codes, preds, predw, nseq, col_of, colkey, colnodes,
            bpos, n_nodes, n_cols, failed, seqs, lens, wts, rlo, rhi,
            band, lbase):
        state = (codes, preds, predw, nseq, col_of, colkey,
                 colnodes, bpos, n_nodes, n_cols, failed)
        # per-step layer indices [D, B]: row base + step offset
        lidx_all = (lbase[None, :].astype(jnp.int32)
                    + jnp.arange(D, dtype=jnp.int32)[:, None])
        state, _ = jax.lax.scan(
            one_layer, state,
            (seqs.transpose(1, 0, 2), lens.T, wts.transpose(1, 0, 2),
             rlo.T, rhi.T, band.T, lidx_all))
        return state

    def run_sliced(codes, preds, predw, nseq, col_of, colkey, colnodes,
                   bpos, n_nodes, n_cols, failed, seqs, lens, wts,
                   begins, ends, bblen, offs, lbase):
        """The FUSED variant: window slicing runs ON DEVICE. Layers
        arrive as raw (begin, end) backbone coordinates plus per-row
        backbone length / spanning offset, and each scan step derives
        the bpos-range subgraph bounds (rlo/rhi) and the static-band
        rule exactly as the host packer does (`_pack_chunk`) — integer
        arithmetic only, so the derived operands are bit-identical to
        the host-sliced ones and the aligned coordinates never leave
        the chip between the slicing, alignment and ingest stages."""
        state = (codes, preds, predw, nseq, col_of, colkey,
                 colnodes, bpos, n_nodes, n_cols, failed)
        lidx_all = (lbase[None, :].astype(jnp.int32)
                    + jnp.arange(D, dtype=jnp.int32)[:, None])
        bb32 = bblen.astype(jnp.int32)
        of32 = offs.astype(jnp.int32)

        def sliced(state, xs):
            seq, slen, w, b, e, lidx = xs
            b32 = b.astype(jnp.int32)
            e32 = e.astype(jnp.int32)
            # the host packer's spanning rule (reference
            # window.cpp:97-102): offset precomputed per row on host
            # (int(0.01 * bb_len) — float-truncation-exact)
            spanning = (b32 < of32) & (e32 > bb32 - of32)
            span = jnp.where(spanning, bb32, e32 - b32 + 1)
            rlo = jnp.where(spanning, -32768, b32).astype(jnp.int16)
            rhi = jnp.where(spanning, 32767, e32).astype(jnp.int16)
            # the host engine's static-band rule: band 256 when the
            # layer fits, exact DP otherwise
            band = jnp.where(jnp.abs(slen - span) < 256 // 2 - 16,
                             256, 0).astype(jnp.int32)
            return one_layer(state, (seq, slen, w, rlo, rhi, band,
                                     lidx))

        state, _ = jax.lax.scan(
            sliced, state,
            (seqs.transpose(1, 0, 2), lens.T, wts.transpose(1, 0, 2),
             begins.T, ends.T, lidx_all))
        return state

    return run_sliced if device_slice else run


@functools.lru_cache(maxsize=None)
def fused_builder(n_nodes: int, seq_len: int, depth: int, max_pred: int,
                  match: int, mismatch: int, gap: int,
                  banded_only: bool = False, score_dtype: str = "int32",
                  device_slice: bool = False):
    """Single-device jitted variant of `fused_raw` (multi-chip dispatch
    goes through BatchRunner.run on the raw function instead)."""
    import jax

    run = fused_raw(n_nodes, seq_len, depth, max_pred, match, mismatch,
                    gap, banded_only=banded_only, score_dtype=score_dtype,
                    device_slice=device_slice)
    # donate the state buffers on accelerators so chained calls mutate in
    # place instead of allocating a second copy of the graph arrays (the
    # CPU test backend can't donate and would warn on every call)
    donate = tuple(range(11)) if jax.default_backend() == "tpu" else ()
    return jax.jit(run, donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def _pinned_rows(n_nodes: int, seq_len: int, max_pred: int) -> int:
    """ONE pinned batch width per envelope from the device free-memory
    query (the 90%-of-free-VRAM rule, cudapolisher.cpp:169-173,230-239).
    Wider batches are nearly free on the VPU — the whole workload should
    fit ONE chunk when memory allows, because sequential depth (layers x
    graph rows) and launch count are the real costs; /3 keeps two
    pipelined chunks' DP state plus slack in flight. Cached per process:
    jit programs are shape-keyed on B, so the width the bench precompiles
    must be the width the polish run uses even though precompile's own
    buffers shrink the free-memory reading in between."""
    import jax

    from .poa_graph import _device_budget, pin_pow2_rows

    h = (n_nodes + 1) * (seq_len + 1) * 4       # DP score carry, per row
    bps = n_nodes * (seq_len + 1)               # backpointer stack, per row
    state = n_nodes * (2 * max_pred * 3 + 30)   # graph arrays, per row
    return pin_pow2_rows(_device_budget(jax.devices()) // 3,
                         h + bps + state)


def _weights_of(qual, length):
    if qual:
        w = np.frombuffer(qual, np.uint8).astype(np.int32) - 33
        return np.clip(w, 0, 127)  # Phred <= 93; int8-safe by contract
    return np.ones(length, dtype=np.int32)


class FusedPOA:
    """Whole-window device POA engine (see module docstring).

    consensus(windows) has the same contract as DeviceGraphPOA.consensus:
    windows are lists of (seq, qual|None, begin, end) with element 0 the
    backbone; returns (results, statuses) with statuses 0 = device-built,
    1 = host fallback, 2 = backbone-only.
    """

    def __init__(self, match: int, mismatch: int, gap: int,
                 num_threads: int = 1, logger: Logger | None = None,
                 max_nodes: int | None = None, max_len: int = MAX_LEN,
                 max_pred: int = MAX_PRED, batch_rows: int | None = None,
                 depth_buckets=DEPTH_BUCKETS, banded_only: bool = False,
                 runner=None, scheduler=None,
                 use_fused: bool | None = None):
        from ..parallel.mesh import BatchRunner
        from ..sched import BatchScheduler

        if max_nodes is None:
            max_nodes = env_max_nodes()
        # occupancy-aware scheduler (sched/): adaptive depth ladder when
        # armed, per-depth-bucket occupancy telemetry always
        self.sched = (scheduler if scheduler is not None
                      else BatchScheduler.from_env())
        self.match = match
        self.mismatch = mismatch
        self.gap = gap
        self.num_threads = num_threads
        self.logger = logger
        self.N = max_nodes
        self.L = max_len
        self.P = max_pred
        # batch axis sharded over every device (the reference's
        # batch-per-GPU loop, cudapolisher.cpp:228-240): B is sized PER
        # DEVICE from the free-memory pin, times the mesh width, so each
        # chip carries the width one chip's memory affords
        self.runner = runner if runner is not None else BatchRunner()
        if batch_rows:
            self.B = self.runner.round_batch(batch_rows)
        else:
            self.B = self._pin_rows() * self.runner.n_devices
        self.depth_buckets = tuple(depth_buckets)
        #: compile budget for the adaptive depth ladder — pinned to the
        #: construction-time ladder size so adapt() is idempotent (a
        #: precompile-then-consensus double derivation must yield the
        #: SAME ladder, or the precompiled programs would be discarded)
        self._depth_k = len(self.depth_buckets)
        self.last_stats = {"chunks": 0, "launches": 0, "pack_s": 0.0,
                           "device_s": 0.0, "unpack_s": 0.0,
                           "fused_chunks": 0, "fused_fallbacks": 0}
        # -b / banded-only: trust banded DP results (skip the clipped ->
        # full-DP retry), the reference's GPU-only speed/accuracy trade
        self.banded_only = banded_only
        #: fused single-launch posture (see fused_mode): the constructor
        #: bool forces it on/off for tests, None defers to
        #: RACON_TPU_FUSED; per-depth-bucket winner lookups cache here
        if use_fused is None:
            self.fused_posture = fused_mode()
        else:
            self.fused_posture = "1" if use_fused else "0"
        self._fused_plans: dict[int, bool] = {}
        # score-dtype plan for this engine's single (N, L) envelope:
        # int16 when the overflow proof holds (ops/dtypes; the third
        # engine dispatcher consulting the autotuner table — the fused
        # engine has no pallas variant, so only the dtype half applies)
        from .dtypes import kernel_plan, poa_int16_ok
        from .poa_pallas import pallas_mode

        _, self.score_dtype = kernel_plan(
            pallas_mode(), "fused", (self.N, self.L),
            (self.match, self.mismatch, self.gap, self.P),
            poa_int16_ok(self.N, self.L, self.match, self.mismatch,
                         self.gap),
            lambda dt: False)  # no pallas variant: dtype half only
        self._code_of = np.full(256, 4, dtype=np.int8)
        for i, b in enumerate(b"ACGT"):
            self._code_of[b] = i

    def _pin_rows(self) -> int:
        return _pinned_rows(self.N, self.L, self.P)

    def _call(self, d: int, state, seqs, lens, wts, rlo, rhi, band,
              done: int):
        """One chained builder call for depth bucket `d`: shard_mapped
        over the mesh when one exists, plain donated jit on one device."""
        import time

        t0 = time.perf_counter()
        lbase = np.full(self.B, done, dtype=np.int32)
        if self.runner.sharding is not None:
            raw = fused_raw(self.N, self.L, d, self.P, self.match,
                            self.mismatch, self.gap,
                            banded_only=self.banded_only,
                            score_dtype=self.score_dtype)
            out = self.runner.run(raw, *state, seqs, lens, wts, rlo,
                                  rhi, band, lbase,
                                  donate_argnums=tuple(range(11)))
        else:
            fn = fused_builder(self.N, self.L, d, self.P, self.match,
                               self.mismatch, self.gap,
                               banded_only=self.banded_only,
                               score_dtype=self.score_dtype)
            out = fn(*state, seqs, lens, wts, rlo, rhi, band, lbase)
        # first-dispatch compile telemetry (shared record_compile_once
        # idiom); the key is the full program identity
        self.sched.stats.record_compile_once(
            "fused",
            (self.N, self.L, d, self.P, self.match, self.mismatch,
             self.gap, self.banded_only, self.B,
             self.runner.sharding is not None, self.score_dtype),
            time.perf_counter() - t0)
        return out

    def _call_fused(self, D: int, state, seqs, lens, wts, begins, ends,
                    bblen, offs):
        """ONE single-launch fused align→window-slice→POA call covering
        a chunk's whole chain depth `D`: window slicing (spanning /
        bpos-range / band rule) runs on device from the raw layer
        coordinates, and the layer loop is one device-resident scan —
        no chained Python dispatch, no intermediate state fetch.
        Bit-identical to the split chained path by construction (the
        slicing arithmetic is integer-exact; pinned by tests)."""
        import time

        t0 = time.perf_counter()
        lbase = np.zeros(self.B, dtype=np.int32)
        if self.runner.sharding is not None:
            raw = fused_raw(self.N, self.L, D, self.P, self.match,
                            self.mismatch, self.gap,
                            banded_only=self.banded_only,
                            score_dtype=self.score_dtype,
                            device_slice=True)
            out = self.runner.run(raw, *state, seqs, lens, wts, begins,
                                  ends, bblen, offs, lbase,
                                  donate_argnums=tuple(range(11)))
        else:
            fn = fused_builder(self.N, self.L, D, self.P, self.match,
                               self.mismatch, self.gap,
                               banded_only=self.banded_only,
                               score_dtype=self.score_dtype,
                               device_slice=True)
            out = fn(*state, seqs, lens, wts, begins, ends, bblen, offs,
                     lbase)
        self.sched.stats.record_compile_once(
            "fused",
            (self.N, self.L, D, self.P, self.match, self.mismatch,
             self.gap, self.banded_only, self.B,
             self.runner.sharding is not None, self.score_dtype,
             "loop"),
            time.perf_counter() - t0)
        return out

    def _fused_plan(self, plan) -> bool:
        """Arbitrate FUSED single-launch vs SPLIT chained dispatch for
        a chunk whose chain plan is `plan` (see fused_mode): forced
        postures win; `auto` consults the persisted autotuner winner
        table per depth bucket (engine "fused_loop", keyed by the
        chunk's leading — largest — chain bucket at this engine's
        envelope and scoring; a cold table dispatches split, exactly
        the pre-fusion behavior). Chunks deeper than
        FUSED_LOOP_MAX_DEPTH always split: one compiled program per
        distinct total depth must stay bounded."""
        if not plan or sum(plan) > FUSED_LOOP_MAX_DEPTH:
            return False
        if self.fused_posture == "0":
            return False
        if self.fused_posture == "1":
            return True
        key = plan[0]
        cached = self._fused_plans.get(key)
        if cached is None:
            from ..sched.autotune import get_autotuner

            ent = get_autotuner().winner(
                "fused_loop", (self.N, self.L, key),
                (self.match, self.mismatch, self.gap, self.P))
            cached = self._fused_plans[key] = (
                (ent or {}).get("kernel") == "fused")
        return cached

    def _eligible(self, win) -> bool:
        bb_len = len(win[0][0])
        if bb_len + 1 > self.N:
            return False
        for seq, _, b, e in win[1:]:
            if not seq or len(seq) > self.L:
                return False
        return True

    def _adapt_depths(self, windows, fused_idx) -> None:
        """Adaptive depth ladder from the ACTUAL chunk-max depths — known
        exactly once windows are depth-sorted, since chunks are carved
        from that list in B-strides; every padded layer costs B * L
        device work, so tight edges are the whole occupancy story.
        No-op when the scheduler is off."""
        if not self.sched.adaptive or not fused_idx:
            return
        maxima = [len(windows[fused_idx[s]]) - 1
                  for s in range(0, len(fused_idx), self.B)]
        ladder = self.sched.depth_ladder(maxima, k=self._depth_k)
        if ladder:
            self.depth_buckets = ladder

    def _fused_order(self, windows) -> list[int]:
        """Eligible window indices, deepest first — the ONE definition of
        which windows the device pass takes and in what order, shared by
        consensus() and adapt() so a precompile-derived depth ladder is
        always the ladder the run dispatches."""
        idx = [i for i, w in enumerate(windows)
               if len(w) >= 3 and self._eligible(w)]
        idx.sort(key=lambda i: -len(windows[i]))
        return idx

    def adapt(self, windows) -> None:
        """Derive the adaptive depth ladder ahead of consensus(), so
        precompile(windows=...) warms exactly the programs the run will
        dispatch (the ladder is a pure function of the window set)."""
        self._adapt_depths(windows, self._fused_order(windows))

    def _chain_plan(self, depth: int) -> list[int]:
        """The greedy chained-call depth sequence for one chunk depth."""
        plan, done = [], 0
        while done < depth:
            rem = depth - done
            fits = [b for b in self.depth_buckets if b <= rem]
            d = max(fits) if fits else min(
                b for b in self.depth_buckets if b >= rem)
            plan.append(d)
            done += d
        return plan

    def precompile(self, max_depth: int | None = None,
                   windows=None) -> None:
        """Compile the depth-bucket programs up front. `max_depth` (the
        deepest window that will be polished) restricts compilation to the
        buckets the chaining algorithm can actually pick — the caller
        knows the windows, so the bench/polisher need not pay for unused
        programs. With the adaptive scheduler armed, pass `windows` (the
        packed window set) so the DERIVED depth ladder is what gets
        compiled instead of the static one the run would then discard."""
        if windows is not None:
            self.adapt(windows)
        fused_totals: set[int] = set()
        if max_depth is None:
            needed = set(self.depth_buckets)
            plans = [self._chain_plan(b) for b in self.depth_buckets]
        else:
            needed = set()
            plans = [self._chain_plan(depth)
                     for depth in range(1, max(1, max_depth) + 1)]
        for plan in plans:
            if self._fused_plan(plan):
                fused_totals.add(sum(plan))
            needed.update(plan)  # split programs stay warm: they are
            # the fused program's declared fallback
        for d in sorted(needed):
            state = self._init_state([b"AC"], [np.ones(2, np.int32)])
            seqs = np.full((self.B, d, self.L), 5, np.int8)
            lens = np.zeros((self.B, d), np.int32)
            wts = np.zeros((self.B, d, self.L), np.int8)
            rlo = np.full((self.B, d), -32768, np.int16)
            rhi = np.full((self.B, d), 32767, np.int16)
            band = np.zeros((self.B, d), np.int32)
            out = self._call(d, state, seqs, lens, wts, rlo, rhi, band, 0)
            np.asarray(out[0])  # block
        for D in sorted(fused_totals):
            state = self._init_state([b"AC"], [np.ones(2, np.int32)])
            seqs = np.full((self.B, D, self.L), 5, np.int8)
            lens = np.zeros((self.B, D), np.int32)
            wts = np.zeros((self.B, D, self.L), np.int8)
            begins = np.zeros((self.B, D), np.int32)
            ends = np.zeros((self.B, D), np.int32)
            bblen = np.full(self.B, 2, np.int32)
            offs = np.zeros(self.B, np.int32)
            out = self._call_fused(D, state, seqs, lens, wts, begins,
                                   ends, bblen, offs)
            np.asarray(out[0])  # block

    def _init_state(self, backbones, bweights):
        B, N, P, C = self.B, self.N, self.P, self.N
        codes = np.full((B, N), -1, dtype=np.int8)
        preds = np.full((B, N, P), -1, dtype=np.int16)
        predw = np.zeros((B, N, P), dtype=np.int32)
        nseq = np.zeros((B, N), dtype=np.int32)
        col_of = np.full((B, N), -1, dtype=np.int16)
        colkey = np.zeros((B, C), dtype=np.int64)
        colnodes = np.full((B, C, 5), -1, dtype=np.int16)
        bpos = np.zeros((B, N), dtype=np.int16)
        n_nodes = np.zeros(B, dtype=np.int32)
        n_cols = np.zeros(B, dtype=np.int32)
        failed = np.zeros(B, dtype=bool)
        for k, (bb, w) in enumerate(zip(backbones, bweights)):
            m = len(bb)
            codes[k, :m] = self._code_of[np.frombuffer(bb, np.uint8)]
            col_of[k, :m] = np.arange(m)
            colkey[k, :m] = (np.arange(m, dtype=np.int64) + 1) << 32
            colnodes[k, np.arange(m), codes[k, :m]] = np.arange(m)
            bpos[k, :m] = np.arange(m)
            preds[k, 1:m, 0] = np.arange(m - 1)
            predw[k, 1:m, 0] = w[:-1] + w[1:]
            nseq[k, :m] = 1
            n_nodes[k] = m
            n_cols[k] = m
        return (codes, preds, predw, nseq, col_of, colkey,
                colnodes, bpos, n_nodes, n_cols, failed)

    def consensus(self, windows, fallback: bool = True, pipeline=None):
        """fallback=False leaves ineligible/failed windows as (None,
        status 1) for the caller to polish (e.g. with the session engine,
        which handles non-spanning layers via subgraphs).

        `pipeline` (pipeline.DispatchPipeline) drives the chunk loop:
        while chunk k's chained calls compute on device, a pack worker
        builds chunk k+1's layer operands, an unpack worker fetches and
        C++-finalizes chunk k-1, and fused-ineligible windows are host-
        polished on the fallback pool concurrently with the device pass —
        the stream-overlap role of the reference's per-batch CUDA streams
        (cudapolisher.cpp:165-199). Omitted, an internal depth-1 pipeline
        reproduces the engine's historical one-chunk lookahead. A chunk
        whose device call raises is routed to the host fallback (per-chunk
        GPU->CPU discipline, cudapolisher.cpp:354-383) unless
        RACON_TPU_STRICT is set, in which case the error propagates.
        """
        from ..native import poa_batch
        from ..pipeline import DispatchPipeline

        n = len(windows)
        results: list = [None] * n
        statuses = np.ones(n, dtype=np.int32)
        for i, w in enumerate(windows):
            if len(w) < 3:
                statuses[i] = 2
                results[i] = (w[0][0], np.zeros(len(w[0][0]), np.uint32))
        # windows are processed deepest-first so each batch chunk chains
        # a similar number of calls (padding layers are not free);
        # _fused_order is the one shared definition of the device set
        fused_idx = self._fused_order(windows)
        fused_set = set(fused_idx)
        self._adapt_depths(windows, fused_idx)

        bar = self.logger.bar if self.logger is not None else None
        if self.logger is not None and fused_idx:
            self.logger.bar_total(len(fused_idx))

        self.last_stats = stats = {"chunks": 0, "launches": 0,
                                   "pack_s": 0.0, "device_s": 0.0,
                                   "unpack_s": 0.0, "fused_chunks": 0,
                                   "fused_fallbacks": 0}
        own_pipeline = pipeline is None
        pl = pipeline if pipeline is not None else DispatchPipeline(depth=1)

        # upfront-known host work overlaps the device pass: windows the
        # fused engine cannot take are submitted to the fallback pool NOW
        # instead of serialized after every device chunk retires;
        # concurrent jobs split the thread budget so the pool never
        # oversubscribes the host beyond num_threads
        prefall: list[tuple[list[int], object]] = []
        if fallback and pl.depth > 0:
            ineligible = [i for i in range(n)
                          if statuses[i] == 1 and i not in fused_set]
            fb_threads = max(1, self.num_threads // pl.fallback_workers)
            prefall = pl.map_fallback(
                ineligible,
                lambda sub: poa_batch([windows[i] for i in sub],
                                      self.match, self.mismatch, self.gap,
                                      n_threads=fb_threads))

        def chunk_plan(chunk):
            # deterministic in the chunk (env/posture/table stable for
            # the run), so pack and on_error always agree on which
            # path a chunk took
            return self._chain_plan(max(len(windows[i]) - 1
                                        for i in chunk))

        def pack(chunk):
            plan = chunk_plan(chunk)
            if self._fused_plan(plan):
                D = sum(plan)
                return ("fused", D) + self._pack_chunk_fused(
                    windows, chunk, D)
            return ("split",) + self._pack_chunk(windows, chunk)

        def dispatch(chunk, packed):
            from .device_program import shard_useful_split

            depths = [len(windows[i]) - 1 for i in chunk]
            n_dev = self.runner.n_devices
            if packed[0] == "fused":
                # the FUSED single-launch program: window slicing +
                # every chained layer step in ONE device-resident scan
                # — one launch, one fetch per chunk
                _, D, state, ops = packed
                state = self._call_fused(D, state, *ops)
                row_layers = [min(dep, D) for dep in depths]
                self.sched.stats.record(
                    "fused", D, jobs=len(chunk), lanes=self.B,
                    useful_cells=sum(row_layers),
                    total_cells=self.B * D,
                    kernel="fused", dtype=self.score_dtype,
                    n_devices=n_dev,
                    shard_useful=shard_useful_split(row_layers, self.B,
                                                    n_dev),
                    full_mesh_cells=self.B * D)
                pl.stats.bump("launches")
                stats["fused_chunks"] += 1
                return state
            _, state, calls = packed
            # state stays on device across chained calls (a fetch here
            # would round-trip ~5 MB of graph arrays per call); only the
            # final state is materialized for the host finalizer
            for d, ops, done in calls:
                state = self._call(d, state, *ops, done)
                # occupancy in LAYER units, recorded AFTER the call
                # returned (a faulted chunk must not be accounted as
                # device work): every lane pays all d layer steps of
                # every chained call, real or padded. Each window counts
                # as a job ONCE (on its chunk's first call) so jobs
                # totals stay comparable across engines. The mesh view
                # splits the chunk's rows into per-device shards; B is
                # pinned (no sub-mesh tails), so the full-mesh baseline
                # equals the dispatched capacity.
                row_layers = [min(max(0, dep - done), d)
                              for dep in depths]
                self.sched.stats.record(
                    "fused", d, jobs=len(chunk) if done == 0 else 0,
                    lanes=self.B,
                    useful_cells=sum(row_layers),
                    total_cells=self.B * d,
                    kernel="xla", dtype=self.score_dtype,
                    n_devices=n_dev,
                    shard_useful=shard_useful_split(row_layers, self.B,
                                                    n_dev),
                    full_mesh_cells=self.B * d)
            pl.stats.bump("launches", len(calls))
            return state

        def wait(state):
            return tuple(np.asarray(x) for x in state)

        def _tick(chunk):
            if bar is not None:
                for _ in chunk:
                    bar("[racon_tpu::Polisher.polish] "
                        "building whole-window POA graphs on device")

        def unpack(chunk, np_state):
            self._finalize_chunk(chunk, np_state, results, statuses)
            breaker.ok()
            _tick(chunk)

        # consecutive-chunk-failure circuit breaker — the shared seam
        # implementation (ops/device_program.ChunkBreaker)
        from .device_program import ChunkBreaker

        breaker = ChunkBreaker("FusedPOA", pl.stats, "the device pass")

        def on_error(chunk, exc):
            # a FUSED single-launch chunk gets its DECLARED fallback
            # first: re-run through the split chained path, which is
            # byte-identical by construction (the host tail is not —
            # the host engine may resolve topo-order ties differently,
            # so falling past split would move bytes under a fault)
            if self._fused_plan(chunk_plan(chunk)):
                try:
                    self._split_chunk_inline(windows, chunk, results,
                                             statuses,
                                             watchdog=pl.watchdog,
                                             stats=pl.stats)
                except Exception as split_exc:  # noqa: BLE001 — both
                    # paths dead: count the streak on the SPLIT failure
                    # and leave the windows to the host tail below
                    exc = split_exc
                else:
                    stats["fused_fallbacks"] += 1
                    breaker.ok()
                    warn_dedup(
                        "FusedPOA.fused_chunk_fell_back",
                        "[racon_tpu::FusedPOA] warning: fused program "
                        f"failed ({type(exc).__name__}: {exc}); chunk "
                        "re-ran on the split chained path")
                    _tick(chunk)
                    return
            # the chunk's windows stay unbuilt; the fallback tail below
            # polishes every one of them on host
            breaker.failed(exc, f"{len(chunk)} windows to fallback")
            _tick(chunk)

        # mesh balance: within each FULL chunk, windows round-robin
        # across the per-device row shards (the chunk list IS the row
        # order and B/n_dev rows per shard align exactly with the
        # strided groups), so the depth-sorted deep windows spread over
        # the mesh instead of loading the first shard; pure permutation
        # — per-window results are row-position-independent. The tail
        # chunk keeps sorted order: its graph-state rows are contiguous
        # from row 0, so a strided reorder would NOT line up with the
        # shard boundaries anyway (the padding rows are pinned to the
        # end of the batch by _init_state).
        from ..sched import shard_interleave

        n_dev = self.runner.n_devices
        chunk_items = [
            (shard_interleave(chunk, n_dev) if len(chunk) == self.B
             else chunk)
            for chunk in (fused_idx[s:s + self.B]
                          for s in range(0, len(fused_idx), self.B))]
        strict = strict_mode()
        try:
            # the pipeline already counts and times every stage callback;
            # this run's share is the delta against the (possibly
            # phase-shared) counters — nothing else runs on the pipeline
            # meanwhile
            base = pl.stats.snapshot()
            pl.run(chunk_items, pack, dispatch, wait, unpack,
                   on_error=None if strict else on_error,
                   label="fused",
                   describe=lambda c: {"engine": "fused",
                                       "jobs": len(c)})
            after = pl.stats.snapshot()
            for key in ("pack_s", "device_s", "unpack_s", "chunks",
                        "launches"):
                stats[key] = after[key] - base[key]

            pl.drain_fallback(ignore_errors=not strict)
            for sub, fut in prefall:
                try:
                    sub_res = fut.result()
                except Exception as exc:
                    # this fallback job died even after its bounded
                    # retry: its windows stay None for the caller's
                    # per-window quarantine path
                    warn_dedup(
                        "FusedPOA.fallback_job_failed",
                        "[racon_tpu::FusedPOA] warning: fallback job "
                        f"failed ({type(exc).__name__}: {exc}); "
                        f"{len(sub)} windows left to the caller")
                    continue
                for i, r in zip(sub, sub_res):
                    results[i] = r
                    statuses[i] = 1
        finally:
            if own_pipeline:
                pl.close()

        # everything left is ineligible (depth-0 path) or device-failed
        rest = [i for i in range(n) if results[i] is None]
        self.n_fallback = len(rest) + sum(len(s) for s, _ in prefall)
        if rest and fallback:
            try:
                host = poa_batch([windows[i] for i in rest], self.match,
                                 self.mismatch, self.gap,
                                 n_threads=self.num_threads)
            except Exception as exc:
                # the host batch itself died: leave the unbuilt windows
                # as None for the caller's per-window quarantine path
                # instead of losing the whole device pass's results
                if strict:
                    raise
                log_info("[racon_tpu::FusedPOA] warning: host fallback "
                         f"batch failed ({type(exc).__name__}: {exc}); "
                         f"{len(rest)} windows left to the caller")
            else:
                for i, r in zip(rest, host):
                    results[i] = r
                    statuses[i] = 1
        return results, statuses

    def _pack_chunk(self, windows, chunk):
        """Host-only packing for one window chunk: the init state plus
        every chained call's padded layer operands. Returns (state,
        [(depth_bucket, operand_arrays, layer_base), ...]) — no device
        interaction, so a pipeline pack worker can run it while an older
        chunk computes."""
        backbones = [windows[i][0][0] for i in chunk]
        bweights = [_weights_of(windows[i][0][1], len(windows[i][0][0]))
                    for i in chunk]
        state = self._init_state(backbones, bweights)
        depth = max(len(windows[i]) - 1 for i in chunk)
        done = 0
        plan = self._chain_plan(depth)
        # per-window constants, hoisted out of the chained-call loop:
        # layer order is a stable sort by begin, the host engine's visit
        # order (reference window.cpp:84-85)
        metas = [(sorted(windows[i][1:], key=lambda s: s[2]),
                  len(windows[i][0][0])) for i in chunk]
        calls = []
        for d in plan:
            seqs = np.full((self.B, d, self.L), 5, np.int8)
            lens = np.zeros((self.B, d), np.int32)
            wts = np.zeros((self.B, d, self.L), np.int8)
            rlo = np.full((self.B, d), -32768, np.int16)
            rhi = np.full((self.B, d), 32767, np.int16)
            band = np.zeros((self.B, d), np.int32)
            for k, (layers, bb_len) in enumerate(metas):
                offset = int(0.01 * bb_len)
                for dd in range(d):
                    li = done + dd
                    if li >= len(layers):
                        break
                    seq, qual, b, e = layers[li]
                    seqs[k, dd, :len(seq)] = self._code_of[
                        np.frombuffer(seq, np.uint8)]
                    lens[k, dd] = len(seq)
                    wts[k, dd, :len(seq)] = _weights_of(qual, len(seq))
                    spanning = b < offset and e > bb_len - offset
                    span = bb_len if spanning else e - b + 1
                    if not spanning:
                        # non-spanning: bpos-range subgraph (reference
                        # window.cpp:97-102)
                        rlo[k, dd] = b
                        rhi[k, dd] = e
                    # the host engine's static-band rule (band 256 when
                    # the layer fits, exact DP otherwise)
                    if abs(len(seq) - span) < 256 // 2 - 16:
                        band[k, dd] = 256
            calls.append((d, (seqs, lens, wts, rlo, rhi, band), done))
            done += d
        return state, calls

    def _pack_chunk_fused(self, windows, chunk, D: int):
        """Host packing for one FUSED single-launch chunk: the init
        state plus ONE set of layer operands covering the whole chain
        depth `D` — raw (begin, end) coordinates and the per-row
        backbone length / spanning offset instead of host-derived
        rlo/rhi/band (that slicing now runs on device, `_call_fused`).
        Cheaper than the split packer by construction: no per-layer
        band/spanning Python work and one operand set instead of one
        per chained call."""
        backbones = [windows[i][0][0] for i in chunk]
        bweights = [_weights_of(windows[i][0][1], len(windows[i][0][0]))
                    for i in chunk]
        state = self._init_state(backbones, bweights)
        seqs = np.full((self.B, D, self.L), 5, np.int8)
        lens = np.zeros((self.B, D), np.int32)
        wts = np.zeros((self.B, D, self.L), np.int8)
        begins = np.zeros((self.B, D), np.int32)
        ends = np.zeros((self.B, D), np.int32)
        bblen = np.zeros(self.B, np.int32)
        offs = np.zeros(self.B, np.int32)
        for k, i in enumerate(chunk):
            layers = sorted(windows[i][1:], key=lambda s: s[2])
            bb_len = len(windows[i][0][0])
            bblen[k] = bb_len
            # float truncation kept bit-exact with the split packer
            offs[k] = int(0.01 * bb_len)
            for dd, (seq, qual, b, e) in enumerate(layers[:D]):
                seqs[k, dd, :len(seq)] = self._code_of[
                    np.frombuffer(seq, np.uint8)]
                lens[k, dd] = len(seq)
                wts[k, dd, :len(seq)] = _weights_of(qual, len(seq))
                begins[k, dd] = b
                ends[k, dd] = e
        return state, (seqs, lens, wts, begins, ends, bblen, offs)

    def _split_chunk_inline(self, windows, chunk, results, statuses,
                            watchdog=None, stats=None) -> None:
        """The DECLARED fallback of the fused single-launch program: a
        chunk whose fused dispatch failed (injected fault, watchdog
        timeout, real device error) is re-run through the SPLIT chained
        path — byte-identical to the fused program by construction,
        unlike the host-engine tail (which may resolve topo-order ties
        differently). Runs synchronously on the calling (pipeline
        error-handler) thread, with the pipeline's `watchdog` deadline
        (single attempt, no retry) guarding every device interaction —
        a chunk whose fused dispatch DeadlineTimed-out on a wedged
        device must not hang forever in its own fallback. Compile
        telemetry still flows through `_call`; occupancy is not
        recorded for the retry (the existing discipline: a faulted
        chunk is never accounted as clean device work)."""
        state, calls = self._pack_chunk(windows, chunk)
        for d, ops, done in calls:
            dispatch = functools.partial(self._call, d, state, *ops,
                                         done)
            state = (watchdog.call(dispatch, stats=stats, retry=False,
                                   deadline=True)
                     if watchdog is not None else dispatch())

        def fetch():
            return tuple(np.asarray(x) for x in state)

        np_state = (watchdog.call(fetch, stats=stats, retry=False)
                    if watchdog is not None else fetch())
        self._finalize_chunk(chunk, np_state, results, statuses)

    def _finalize_chunk(self, chunk, state, results, statuses):
        from ..native import poa_finish_arrays

        (codes, preds, predw, nseq, col_of, colkey, colnodes,
         bpos, n_nodes, n_cols, failed) = (np.asarray(x) for x in state)
        okrows = [k for k in range(len(chunk)) if not failed[k]]
        if okrows:
            sel = np.asarray(okrows)
            fin = poa_finish_arrays(
                codes[sel], preds[sel], predw[sel], nseq[sel],
                col_of[sel], colkey[sel], n_nodes[sel],
                n_threads=self.num_threads)
            for k, r in zip(okrows, fin):
                results[chunk[k]] = r
                statuses[chunk[k]] = 0
