"""Pallas TPU kernel for banded global alignment with in-kernel traceback.

The alignment-side half of the device-kernel plane (the POA side is
ops/poa_pallas.window_sweep): the anti-diagonal wavefront of
ops/align._banded_nw_kernel as a hand-tiled kernel, one pair per
sequential grid step with the WHOLE job resident in VMEM:

  - the two rolling wavefronts live in VMEM scratch as int32 rows; the
    XLA program instead carries them through a `lax.scan` whose state
    round-trips HBM every anti-diagonal;
  - the backpointer plane (2-bit codes, 16 per int32 word, so every
    store is a whole 32-bit row) lives in VMEM scratch, where the XLA
    program's plane is an HBM buffer;
  - the traceback runs in-kernel (scalar pointer chase over the VMEM
    backpointers, mirroring window_sweep), so the kernel's outputs are
    only the op-code path (<= m+n entries), its length, the final
    distance and the band-edge flag — what the XLA program's device
    traceback returns too.

DP values, band tracking and tie order replicate _banded_nw_kernel
EXACTLY (same formulas, same INF clamp, same diag < up < left order),
and the band-shifted neighbour reads are lane rolls because the host
pre-extends the operands (`build_ext`): q_ext[p] =
q[clip(p-1, 0, edge-1)] and t_ext[p] = t[clip(2*edge-1-p, 0, edge-1)],
so wavefront d of lane offset a0 reads q at slice start a0 and t at
slice start 2*edge + a0 - d — including the exact clip values the XLA
program's `take_along_axis(clip(...))` produces, cell for cell.
tests/test_pallas_align.py fuzzes the kernel against the XLA program in
interpret mode; `BatchAligner` dispatches it per bucket under
RACON_TPU_PALLAS=1 (always, when the envelope fits) or =auto (when the
persisted autotuner table says it measured faster), with the XLA
program as the fallback for shapes the VMEM budget cannot hold.
"""

from __future__ import annotations

import functools

import numpy as np

#: VMEM the resident job may use — shared budget with the POA kernel
from .poa_pallas import SMEM_BUDGET, VMEM_BUDGET, _round128

BP_DIAG, BP_UP, BP_LEFT = 0, 1, 2  # ops/align.py's codes


#: 2-bit backpointer codes packed per int32 word of the plane
_BP_PER_WORD = 16


def _bp_rows(n_waves: int) -> int:
    return ((n_waves + _BP_PER_WORD - 1) // _BP_PER_WORD + 7) // 8 * 8


def ext_widths(edge: int, band: int) -> tuple[int, int]:
    """(q_ext, t_ext) operand widths for one bucket (128-padded)."""
    return _round128(1 + edge + band), _round128(2 * edge + band)


def fits_vmem(edge: int, band: int, dtype: str = "int32") -> bool:
    """True when one lane of bucket (edge, band) is resident-on-chip
    feasible, counted at the chip's tiling: the packed backpointer plane,
    the two wavefront rows, and the double-buffered operand and output
    blocks (each [1, X] block a full 8-sublane int32 tile) fit the
    shared VMEM budget with slack, and the double-buffered band offsets
    fit the SMEM budget. The kernel computes in int32 whatever `dtype`
    says, so the footprint does not depend on it."""
    del dtype
    n_waves = 2 * edge + 1
    nw_pad = _round128(n_waves)
    lq, lt = ext_widths(edge, band)
    bw = _round128(band) + 128
    bp = _bp_rows(n_waves) * bw * 4
    tiles = 8 * 4 * (2 * bw + 2 * (lq + bw + lt + bw + nw_pad + 128))
    return (bp + tiles + (1 << 20) <= VMEM_BUDGET
            and 2 * nw_pad * 4 <= SMEM_BUDGET)


def build_ext(q_arr: np.ndarray, t_arr: np.ndarray,
              band: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side operand extension (see module docstring): [B, edge]
    int8 code arrays (PAD beyond length, from encode_padded) ->
    (q_ext [B, Lq], t_ext [B, Lt]) int8 such that every wavefront's
    neighbour reads become contiguous dynamic slices that reproduce the
    XLA program's clipped gathers exactly."""
    edge = q_arr.shape[1]
    lq, lt = ext_widths(edge, band)
    qi = np.clip(np.arange(lq) - 1, 0, edge - 1)
    ti = np.clip(2 * edge - 1 - np.arange(lt), 0, edge - 1)
    return np.ascontiguousarray(q_arr[:, qi]), \
        np.ascontiguousarray(t_arr[:, ti])


@functools.lru_cache(maxsize=None)
def wavefront_align(edge: int, band: int, score_dtype: str = "int32",
                    packed: bool = False, interpret: bool = False):
    """Jitted fn(q_ext, t_ext, q_lens, t_lens, offsets) ->
    (ops [B, nw_pad] i32, meta [B, 128] i32), one pair per grid step.

    `ops[k, :meta[k, 0]]` is lane k's backpointer path in traceback
    order (reverse it for the forward CIGAR); meta[k] = (count, dist,
    touched_edge, 0...). `packed` takes 2-bit packed q_ext/t_ext
    ([B, Lx//4] uint8, from encode.pack_2bit over build_ext's output)
    and unpacks + PAD-restores them with XLA ops before the kernel —
    a 4x cut in host->device sequence traffic, byte-identical by
    construction. `score_dtype` picks the wavefront value range; int16
    is only legal under ops/dtypes.aligner_int16_ok's envelope proof
    (the kernel computes in int32 either way: the chip has no 16-bit
    vector ALU, and inside the proof the two agree bit for bit).

    Layout on chip: the pair lengths arrive by scalar prefetch and the
    band offsets as an SMEM block (read one per wavefront); wavefronts
    are [1, BW] int32 VMEM rows (BW = band rounded up to 128 lanes, plus
    128 lanes of INF). The band-shifted
    neighbour reads and the sequence windows are lane rolls: a
    128-aligned window of q_ext/t_ext is loaded at a dynamic offset and
    rolled by the remainder. Backpointers pack 16 2-bit codes per int32
    word, so every store is a whole int32 row.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_waves = 2 * edge + 1
    nw_pad = _round128(n_waves)
    lq, lt = ext_widths(edge, band)
    BW = _round128(band) + 128
    LQ, LT = lq + BW, lt + BW
    INF = (1 << 14) if score_dtype == "int16" else (1 << 28)
    i32 = jnp.int32

    def kernel(scal_ref, offs_ref, qx_ref, tx_ref, ops_ref, meta_ref, bps,
               s1_ref, s2_ref):
        b = pl.program_id(0)
        m = scal_ref[2 * b]
        n = scal_ref[2 * b + 1]
        ks = jax.lax.broadcasted_iota(i32, (1, BW), 1)
        infv = jnp.full((1, BW), INF, i32)

        def vec(s):
            return jnp.full((1, BW), s, i32)

        def shifted(s, by):
            """s[k + by] for 0 <= k + by < band, INF elsewhere."""
            src = ks + by
            return jnp.where((src >= 0) & (src < band),
                             pltpu.roll(s, (BW - by) % BW, 1), infv)

        def window(ref, at):
            """ref[at + k] for k < BW - 127: an aligned load rolled by
            the remainder."""
            st = pl.multiple_of(at // 128 * 128, 128)
            w = ref[:, pl.ds(st, BW)]
            return pltpu.roll(w, (BW - (at - st)) % BW, 1)

        def wave(d, carry):
            # the loop index arrives as int64 when another kernel build
            # (poa_fused) has flipped jax_enable_x64 for the process;
            # every index expression below must stay int32
            d = jnp.asarray(d, i32)
            a1, a2, dist = carry
            s1 = s1_ref[...]
            s2 = s2_ref[...]
            a0 = offs_ref[0, d]
            i = vec(a0) + ks
            j = vec(d) - i
            # neighbour reads from the rolling wavefronts: up (d-1, i-1)
            # = s1[k + da - 1], left (d-1, i) = s1[k + da], diag
            # (d-2, i-1) = s2[k + db - 1] — INF outside the band, as the
            # XLA program's clipped gather gives
            up = jnp.where(i >= 1, shifted(s1, a0 - a1 - 1), infv)
            left = jnp.where(j >= 1, shifted(s1, a0 - a1), infv)
            diag = jnp.where((i >= 1) & (j >= 1),
                             shifted(s2, a0 - a2 - 1), infv)
            qi = window(qx_ref, a0)
            tj = window(tx_ref, 2 * edge + a0 - d)
            sub = jnp.where(qi == tj, 0, 1)

            cd = diag + sub
            cu = up + 1
            cl = left + 1
            # fixed tie order: diag, up, left (ops/align.py)
            score = cd
            bp = jnp.where(cu < score, BP_UP, BP_DIAG)
            score = jnp.minimum(score, cu)
            bp = jnp.where(cl < score, BP_LEFT, bp)
            score = jnp.minimum(score, cl)
            score = jnp.where((i == 0) & (j == 0), 0, score)
            valid = ((i >= 0) & (i <= vec(m)) & (j >= 0) & (j <= vec(n))
                     & (ks < band))
            score = jnp.where(valid, jnp.minimum(score, INF), INF)

            at_end = (i == vec(m)) & (j == vec(n)) & (ks < band)
            end = jnp.min(jnp.where(at_end, score, infv))
            dist = jnp.where(jnp.max(at_end.astype(i32)) > 0, end, dist)

            w = d // _BP_PER_WORD
            sh = 2 * (d % _BP_PER_WORD)
            old = jnp.where(vec(sh) == 0, 0, bps[pl.ds(w, 1), :])
            bps[pl.ds(w, 1), :] = old | (bp << sh)
            s2_ref[...] = s1
            s1_ref[...] = score
            return a0, a1, dist

        s1_ref[...] = infv
        s2_ref[...] = infv
        _, _, dist = jax.lax.fori_loop(
            0, n_waves, wave, (i32(0), i32(0), i32(INF)))

        # in-kernel traceback: the XLA program's walk, one lane;
        # the op path is carried as whole 128-lane vectors and written
        # at the end
        oidx = jax.lax.broadcasted_iota(i32, (1, nw_pad), 1)

        def tb_cond(st):
            i, j, cnt, touched, path = st
            return (i > 0) | (j > 0)

        def tb_body(st):
            i, j, cnt, touched, path = st
            d = i + j
            off = offs_ref[0, d]
            k = i - off
            row_lo = jnp.maximum(0, d - n)
            row_hi = jnp.minimum(d, m)
            # band-boundary marks (possible clipping) only when the
            # matrix continues past the boundary on that side
            touched = jnp.where((k <= 0) & (off > row_lo), 1, touched)
            touched = jnp.where((k >= band - 1)
                                & (off + band - 1 < row_hi), 1, touched)
            kc = jnp.clip(k, 0, band - 1)
            word = jnp.max(jnp.where(ks == kc,
                                     bps[pl.ds(d // _BP_PER_WORD, 1), :],
                                     jnp.iinfo(i32).min))
            code = (word >> (2 * (d % _BP_PER_WORD))) & 3
            # boundary overrides: on i==0 only D possible; on j==0 only I
            code = jnp.where(i == 0, BP_LEFT, code)
            code = jnp.where(j == 0, BP_UP, code)
            di = jnp.where(code != BP_LEFT, 1, 0)
            dj = jnp.where(code != BP_UP, 1, 0)
            path = jnp.where(oidx == cnt, code, path)
            return i - di, j - dj, cnt + 1, touched, path

        _, _, cnt, touched, path = jax.lax.while_loop(
            tb_cond, tb_body, (m, n, i32(0), i32(0),
                               jnp.zeros((1, nw_pad), i32)))
        ops_ref[...] = path
        midx = jax.lax.broadcasted_iota(i32, (1, 128), 1)
        meta_ref[...] = jnp.where(
            midx == 0, cnt,
            jnp.where(midx == 1, dist, jnp.where(midx == 2, touched, 0)))

    def call(q_ext, t_ext, q_lens, t_lens, offsets):
        B = offsets.shape[0]
        if packed:
            from .encode import PAD, unpack_2bit_jax

            pos_q = jnp.arange(lq, dtype=i32)[None, :]
            pos_t = jnp.arange(lt, dtype=i32)[None, :]
            ql = q_lens.astype(i32)[:, None]
            tl = t_lens.astype(i32)[:, None]
            qx = unpack_2bit_jax(q_ext, lq)
            tx = unpack_2bit_jax(t_ext, lt)
            # PAD restore along the clip maps build_ext baked in:
            # q_ext[p] = q[clip(p-1, 0, edge-1)] is PAD iff that clipped
            # index lands at or past q_len (only possible when the pair
            # does not fill its bucket), and symmetrically for t_ext
            qx = jnp.where((pos_q >= 1 + ql) & (ql < edge),
                           jnp.int8(PAD), qx)
            tx = jnp.where((pos_t <= 2 * edge - 1 - tl) & (tl < edge),
                           jnp.int8(PAD), tx)
        else:
            qx, tx = q_ext, t_ext
        scal = jnp.stack([q_lens.astype(i32), t_lens.astype(i32)],
                         axis=1).reshape(2 * B)
        offs = jnp.pad(offsets.astype(i32),
                       ((0, 0), (0, nw_pad - offsets.shape[1])))[:, None, :]
        # the rolled windows may read up to BW lanes past the operand's
        # end; those lanes only ever land outside the band
        qx = jnp.pad(qx.astype(i32), ((0, 0), (0, LQ - lq)))[:, None, :]
        tx = jnp.pad(tx.astype(i32), ((0, 0), (0, LT - lt)))[:, None, :]
        row = lambda b, s: (b, 0, 0)  # noqa: E731
        ops, meta = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((None, 1, nw_pad), row,
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((None, 1, LQ), row),
                    pl.BlockSpec((None, 1, LT), row),
                ],
                out_specs=(
                    pl.BlockSpec((None, 1, nw_pad), row),
                    pl.BlockSpec((None, 1, 128), row),
                ),
                scratch_shapes=[
                    pltpu.VMEM((_bp_rows(n_waves), BW), i32),  # bps
                    pltpu.VMEM((1, BW), i32),      # wavefront d-1
                    pltpu.VMEM((1, BW), i32),      # wavefront d-2
                ]),
            out_shape=(
                jax.ShapeDtypeStruct((B, 1, nw_pad), i32),
                jax.ShapeDtypeStruct((B, 1, 128), i32),
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(scal, offs, qx, tx)
        return ops[:, 0, :], meta[:, 0, :]

    return jax.jit(call)
