"""Pallas TPU kernel for whole-window graph-banded NW + traceback.

The within-kernel half of GenomeWorks cudapoa, TPU-shaped. cudapoa runs
one POA group per CUDA block with the working set in shared memory
(SURVEY.md §2c-6); this kernel runs one (window, layer) job per
sequential grid step with the ENTIRE job resident in VMEM:

  - the full score matrix H [N+1, L+1] (~6.3 MB of int32 rows at the
    largest bucket) and the packed backpointer plane live in VMEM
    scratch — the row sweep never touches HBM;
  - the virtual source is H row 0, and predecessor rows are dynamic
    row loads whose indices come from the job's node table in SMEM (one
    window per step means predecessor ranks are scalars — no per-lane
    gather problem);
  - the row loop runs to THIS job's real node count (dynamic bound), not
    the bucket's padded N;
  - the traceback is in-kernel (scalar pointer chase over the VMEM
    backpointers), so the kernel's only output is the final per-base
    node ranks — nothing else leaves the chip.

DP values, band masking and tie-breaking replicate
ops/poa_graph.graph_aligner exactly (same formulas, same int32
arithmetic), so consensus byte-identity is preserved;
tests/test_pallas_poa.py fuzzes this kernel against the XLA one in
interpret mode. The trade against the XLA kernel: the XLA program
vectorizes one DP row across the whole batch ([B, L] per step) but pays
HBM for every row and ~N+L while-loop steps of traceback per batch; this
kernel's vectors are [L]-wide but every access is VMEM and the whole
sweep is one fused loop. Which wins is a hardware question — the kernel
is enabled with RACON_TPU_PALLAS=1 or =auto (the autotuner's measured
winner), default off; the dispatcher falls back to the XLA program for
shapes the VMEM budget cannot hold. tests/test_chip_compile.py compiles
it for v5e; it never runs in interpret mode on a TPU.
"""

from __future__ import annotations

import functools
import os

_NEG = -(1 << 29)
_NEG16 = -(1 << 14)

#: VMEM the resident job may use (scores + backpointers + operand
#: blocks + slack), under the 16 MiB default scoped-VMEM limit the TPU
#: compiler gives a kernel; the largest session bucket (2048, 640)
#: needs ~8.5 MB of it
VMEM_BUDGET = 14 << 20
#: SMEM the double-buffered per-job node tables may use (1 MiB per
#: core on v5e; the rest is left to the compiler's own scalars)
SMEM_BUDGET = 512 << 10
#: backpointer codes (<= 2P) packed per int32 word of the plane
_BP_PER_WORD = 4


def _round128(n: int) -> int:
    return (n + 127) // 128 * 128


def _round_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _h_rows(n_nodes: int, score_dtype: str) -> int:
    """int32 scratch rows holding the N+1 DP rows (two per row when the
    scores are int16)."""
    per = 2 if score_dtype == "int16" else 1
    return _round_to((n_nodes + 1 + per - 1) // per, 8)


def _bp_rows(n_nodes: int) -> int:
    return _round_to((n_nodes + _BP_PER_WORD - 1) // _BP_PER_WORD, 8)


def pallas_mode() -> str:
    """RACON_TPU_PALLAS posture shared by every engine dispatcher:
    'off' (unset/0 — XLA programs only, today's default), 'on' (`1` —
    the Pallas kernel whenever the VMEM envelope fits), or 'auto'
    (consult the persisted per-bucket winner table, sched/autotune;
    buckets without a measured entry dispatch XLA exactly as off).
    Inside an audit oracle_scope (ops/oracle.py) the posture is pinned
    'off' on that thread — the shadow re-execution's ground truth is
    the XLA program whatever the environment says."""
    from .oracle import oracle_active

    if oracle_active():
        return "off"
    raw = (os.environ.get("RACON_TPU_PALLAS") or "").strip().lower()
    if not raw or raw == "0":
        return "off"
    if raw == "auto":
        return "auto"
    return "on"


def fits_vmem(n_nodes: int, seq_len: int, max_pred: int = 8,
              score_dtype: str = "int32") -> bool:
    """True when one (window, layer) job is resident-on-chip feasible.

    Budgets what `window_sweep` places in VMEM at the chip's tiling, not
    only the nominal array bytes: the H score matrix (int32 rows, two
    int16 DP rows per row when narrow, padded to 8 sublanes and 128
    lanes), the packed backpointer
    plane, and the double-buffered [1, W] operand and output blocks
    (each a full 8-sublane tile). The node table lives in SMEM and has
    its own budget. The aligner kernel's envelope check
    (ops/align_pallas.fits_vmem) shares this discipline and the same
    budget constant."""
    w = _round128(seq_len + 1)
    h = _h_rows(n_nodes, score_dtype) * w * 4
    bps = _bp_rows(n_nodes) * w * 4
    blocks = 2 * 8 * (w + _round128(seq_len)) * 4
    smem = 2 * n_nodes * (max_pred + 3) * 4
    return (h + bps + blocks + (1 << 20) <= VMEM_BUDGET
            and smem <= SMEM_BUDGET)


@functools.lru_cache(maxsize=None)
def window_sweep(n_nodes: int, seq_len: int, max_pred: int, match: int,
                 mismatch: int, gap: int, interpret: bool = False,
                 score_dtype: str = "int32", packed: bool = False):
    """Jitted fn(codes, preds, centers, sinks, seq, lens, band, nnodes)
    -> ranks [B, L] i32, one grid step per batch row.

    Argument layouts match graph_aligner's (codes [B,N] i8, preds
    [B,N,P] i16 rank+1 with 0 = virtual source / -1 pad, centers [B,N]
    i16, sinks [B,N] u8, seq [B,L] i8, lens/band [B] i32) plus nnodes
    [B] i32 — the per-job real node count. Returns graph_aligner's rank
    encoding (node rank, -1 insertion, -2 beyond lens).

    Layout on chip: the per-row scalars (lens, band, nnodes) arrive by
    scalar prefetch; each job's node table (code, center, sink, P
    predecessors per node) is one SMEM block, because the row sweep
    reads it a scalar at a time; the layer bases and the rank output
    are lane-dense [1, W] VMEM rows in DP-column space (lane j = column
    j, W = L+1 rounded up to 128 lanes). Band-shifted neighbours are
    lane rolls, and the backpointer plane packs four 8-bit codes per
    int32 word (`_BP_PER_WORD` DP rows per scratch row) so every store
    is a whole int32 row.

    `score_dtype='int16'` stores the resident H matrix as int16 pairs (legal
    only under ops/dtypes.poa_int16_ok's per-bucket overflow proof, so
    the int32 arithmetic never leaves int16 range — bit-identical
    results by construction). `packed` takes 2-bit packed codes/seq
    ([B, N//4] / [B, L//4] uint8, encode.pack_2bit) and unpacks +
    pad-restores them with XLA ops before the kernel — a 4x cut in
    node/sequence transfer for ACGT-only windows.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, L, P = n_nodes, seq_len, max_pred
    W, WL = _round128(L + 1), _round128(L)
    REC = P + 3                       # node record: code, center, sink, preds
    narrow = score_dtype == "int16"
    NEG = _NEG16 if narrow else _NEG
    i32 = jnp.int32

    def kernel(scal_ref, node_ref, seq_ref, out_ref, H, bps):
        b = pl.program_id(0)
        slen = scal_ref[3 * b]
        band = scal_ref[3 * b + 1]
        nn = scal_ref[3 * b + 2]
        jidx = jax.lax.broadcasted_iota(i32, (1, W), 1)
        jg = jidx * gap
        negv = jnp.full((1, W), NEG, i32)

        def vec(s):
            return jnp.full((1, W), s, i32)

        # int16 scores pack two DP rows per int32 word (row 2m in the low
        # half): the chip stores whole 32-bit rows at a dynamic offset
        def load_row(r):
            if not narrow:
                return H[pl.ds(r, 1), :]
            w = H[pl.ds(r // 2, 1), :]
            return jnp.where(vec(r % 2) == 0, w << 16, w) >> 16

        def store_row(r, v):
            if not narrow:
                H[pl.ds(r, 1), :] = v
                return
            lo = H[pl.ds(r // 2, 1), :] & 0xFFFF
            H[pl.ds(r // 2, 1), :] = jnp.where(vec(r % 2) == 0,
                                               v & 0xFFFF, lo | (v << 16))

        # virtual source row: D[0][j] = j*gap within the layer
        store_row(0, jnp.where(jidx <= slen, jg, NEG))
        seq = seq_ref[...]                                      # [1, W]
        band2 = band // 2
        use_band = band > 0

        def row(k, carry):
            best_s, best_r = carry
            base = (k - 1) * REC
            code_k = node_ref[0, base]
            center_k = node_ref[0, base + 1]
            sink_k = node_ref[0, base + 2]
            sub = jnp.where(seq == code_k, match, mismatch)     # [1, W]
            diags, verts = [], []
            for p in range(P):                       # static P, unrolled
                pr = node_ref[0, base + 3 + p]
                prow = load_row(jnp.maximum(pr, 0))
                prow = jnp.where(vec(pr) >= 0, prow, negv)
                # diagonal: column j reads predecessor column j-1
                diags.append(pltpu.roll(prow, 1, 1) + sub)
                verts.append(prow + gap)
            best = jnp.maximum(diags[0], verts[0])
            r0 = verts[0]                            # lane 0: row0
            for p in range(1, P):
                best = jnp.maximum(best, jnp.maximum(diags[p], verts[p]))
                r0 = jnp.maximum(r0, verts[p])

            # static-band masking, replicating graph_aligner exactly
            jlo = jnp.where(use_band, jnp.maximum(1, center_k - band2), 1)
            jhi = jnp.where(use_band, jnp.minimum(slen, center_k + band2),
                            slen)
            inb = (jidx >= vec(jlo)) & (jidx <= vec(jhi))
            pre = jnp.where(inb, best, negv)
            seed0 = jnp.where(vec(jlo) == 1, r0, negv)
            # in-row gap recurrence: running max via Hillis-Steele
            # doubling over lane rolls (log2(L+1) steps)
            x = jnp.where(jidx == 0, seed0, pre) - jg
            s = 1
            while s <= L:
                x = jnp.maximum(x, jnp.where(jidx >= s,
                                             pltpu.roll(x, s, 1), negv))
                s <<= 1
            new_row = jnp.where(jidx == 0, r0,
                                jnp.where(inb, x + jg, pre))

            # backpointers, graph_aligner's encoding and tie order:
            # diagonal via pred p -> p; vertical via pred p -> P+p;
            # horizontal -> 2P; column 0 takes the first vertical pred
            code = jnp.where(jidx == 0, P, 2 * P)
            for p in reversed(range(P)):
                code = jnp.where(verts[p] == new_row, P + p, code)
            for p in reversed(range(P)):
                code = jnp.where((jidx > 0) & (diags[p] == new_row), p,
                                 code)
            store_row(k, new_row)
            w = (k - 1) // _BP_PER_WORD
            sh = 8 * ((k - 1) % _BP_PER_WORD)
            old = jnp.where(vec(sh) == 0, 0, bps[pl.ds(w, 1), :])
            bps[pl.ds(w, 1), :] = old | (code << sh)

            # best sink at the layer's final column: strict improvement
            # in ascending rank = graph_aligner's first-max argmax
            upd = (jidx == vec(slen)) & (vec(sink_k) > 0) & (new_row > best_s)
            return (jnp.where(upd, new_row, best_s),
                    jnp.where(upd, k - 1, best_r))

        _, best_r = jax.lax.fori_loop(
            1, nn + 1, row, (negv, jnp.zeros((1, W), i32)))
        best_rank = jnp.max(jnp.where(jidx == slen, best_r, 0))
        lidx = jax.lax.broadcasted_iota(i32, (1, WL), 1)

        def tb_cond(st):
            r, j, _ = st
            return (r > 0) | (j > 0)

        def tb_body(st):
            r, j, out = st
            rr = jnp.maximum(r - 1, 0)
            word = bps[pl.ds(rr // _BP_PER_WORD, 1), :]
            word = jnp.max(jnp.where(jidx == j, word, 0))
            code = jnp.where(r > 0,
                             (word >> (8 * (rr % _BP_PER_WORD))) & 0xFF,
                             2 * P)
            is_diag = code < P
            is_vert = (code >= P) & (code < 2 * P)
            p = jnp.where(is_diag, code, code - P)
            pr = node_ref[0, rr * REC + 3 + jnp.clip(p, 0, P - 1)]
            consume = jnp.logical_not(is_vert)     # diag or horizontal
            at = jnp.where(consume & (j > 0), j - 1, -1)
            out = jnp.where(lidx == at, jnp.where(is_diag, r - 1, -1), out)
            r = jnp.where(is_diag | is_vert, pr, r)
            j = jnp.where(consume, j - 1, j)
            return r, j, out

        # empty rows (nnodes == 0: batch padding) wrote no bps rows — the
        # traceback must not start, or it would chase uninitialized
        # scratch; start it pre-terminated instead
        _, _, out = jax.lax.while_loop(
            tb_cond, tb_body,
            (jnp.where(nn > 0, best_rank + 1, 0), jnp.where(nn > 0, slen, 0),
             jnp.full((1, WL), -2, i32)))
        out_ref[...] = out

    def call(codes, preds, centers, sinks, seq, lens, band, nnodes):
        if packed:
            from .encode import unpack_2bit_jax

            codes = unpack_2bit_jax(codes, N, nnodes)
            seq = unpack_2bit_jax(seq, L, lens)
        B = codes.shape[0]
        scal = jnp.stack([lens.astype(i32), band.astype(i32),
                          nnodes.astype(i32)], axis=1).reshape(3 * B)
        nodes = jnp.concatenate(
            [codes.astype(i32)[:, :, None], centers.astype(i32)[:, :, None],
             sinks.astype(i32)[:, :, None], preds.astype(i32)],
            axis=2).reshape(B, 1, N * REC)
        # layer bases in DP-column space: lane j holds base j-1
        seqw = jnp.pad(seq.astype(i32), ((0, 0), (1, W - L - 1)),
                       constant_values=5)[:, None, :]
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((None, 1, N * REC), lambda b, s: (b, 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((None, 1, W), lambda b, s: (b, 0, 0)),
                ],
                out_specs=pl.BlockSpec((None, 1, WL),
                                       lambda b, s: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((_h_rows(N, score_dtype), W), i32),  # H
                    pltpu.VMEM((_bp_rows(N), W), jnp.int32),      # bps
                ]),
            out_shape=jax.ShapeDtypeStruct((B, 1, WL), i32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(scal, nodes, seqw)
        return out[:, 0, :L]

    return jax.jit(call)
