"""The plain reference that decides `correct`: the polished output of a
job against the genome its reads were simulated from.

Polishing (kC) and read correction (kF) both aim at the sequence the
reads came from, and the generator knows it. So the reference answer of
every target is its truth, and what is compared is the edit distance
of each polished record to it. Nothing here imports the program.

The distance is exact dynamic programming, cut into pieces so that it
is cheap at 0.5 Mb: unique 24-mers of the truth, about every
`ANCHOR_STEP` bases, are located in the polished record; consecutive
anchors bound one piece, and the pieces' distances are summed. Each
piece is aligned inside a diagonal band of +-`BAND`; a piece whose
lengths differ by more counts the longer length. Both cuts can only
add edits, so the sum is an upper bound on the global edit distance.

Contig jobs compare the whole contig (the generator covers its ends
as those of a circular chromosome); truth that no anchor reaches counts
as wrong. Fragment jobs compare the whole record, and let it begin and
end anywhere in its truth read span, because racon trims corrected
reads where their coverage ends. Anchors lie about half a window
apart, so that one wrong window fills at least one piece.
"""

from __future__ import annotations

import numpy as np

ANCHOR_K = 24
ANCHOR_STEP = 250
ANCHOR_TRIES = 8
#: how far (bases) an anchor may drift from where the previous one
#: predicts it
ANCHOR_SLACK = 400
BAND = 48
#: pieces shorter than this are left out of the worst piece's share
MIN_PIECE = 200
_INF = 1 << 28


def banded_distances(pieces, free_end: list[bool] | None = None,
                     band: int = BAND) -> list[int]:
    """Edit distance of each (p, t) in `pieces`, over a diagonal band of
    +-`band`, all pieces one row of the DP matrix at a time. With
    `free_end[k]`, piece k's p is aligned to the best prefix of its t
    (t's tail is free). A distance the band cannot reach counts
    max(len(p), len(t))."""
    k = len(pieces)
    if k == 0:
        return []
    free_end = free_end or [False] * k
    m = np.array([len(p) for p, _ in pieces])
    n = np.array([len(t) for _, t in pieces])
    w = 2 * band + 1
    rows = int(m.max())
    pad = band + 1
    P = np.zeros((k, rows), dtype=np.uint8)
    T = np.full((k, pad + int(n.max()) + rows + pad), 255, dtype=np.uint8)
    for i, (p, t) in enumerate(pieces):
        P[i, :len(p)] = np.frombuffer(p, dtype=np.uint8)
        T[i, pad:pad + len(t)] = np.frombuffer(t, dtype=np.uint8)
    o = np.arange(-band, band + 1)
    # row 0: j = o columns of t consumed by gaps
    cur = np.where(o >= 0, o, _INF).astype(np.int64)[None, :].repeat(k, 0)
    out = np.full(k, -1, dtype=np.int64)

    def finish(i: int, d) -> None:
        for q in np.nonzero(m == i)[0]:
            if free_end[q]:
                ok = (i + o >= 0) & (i + o <= n[q])
                out[q] = d[q][ok].min() if ok.any() else _INF
            else:
                off = n[q] - i
                out[q] = d[q, off + band] if abs(off) <= band else _INF

    finish(0, cur)
    inf_col = np.full((k, 1), _INF, dtype=np.int64)
    for i in range(1, rows + 1):
        # t index of cell (i, j = i + o) is j - 1 = i - 1 + o
        tcol = T[:, pad + i - 1 - band:pad + i + band]
        sub = (P[:, i - 1:i] != tcol).astype(np.int64)
        diag = cur + sub
        diag[:, (i + o) < 1] = _INF
        up = np.concatenate([cur[:, 1:], inf_col], axis=1) + 1
        x = np.minimum(diag, up)
        cur = np.minimum.accumulate(x - o, axis=1) + o
        cur[:, (i + o) < 0] = _INF
        np.minimum(cur, _INF, out=cur)
        finish(i, cur)
    return [int(min(d, max(a, b))) for d, a, b in zip(out, m, n)]


def anchors(polished: bytes, truth: bytes, lo: int = 0,
            hi: int | None = None) -> list[tuple[int, int]]:
    """(truth position, polished position) pairs of 24-mers of
    truth[lo:hi] that occur once in their search range of the polished
    record, increasing in both coordinates."""
    hi = len(truth) if hi is None else hi
    found: list[tuple[int, int]] = []
    b = lo
    while b + ANCHOR_K <= hi:
        for tries in range(ANCHOR_TRIES):
            tb = b + tries * (ANCHOR_STEP // ANCHOR_TRIES)
            if tb + ANCHOR_K > hi:
                break
            kmer = truth[tb:tb + ANCHOR_K]
            if found:
                guess = found[-1][1] + (tb - found[-1][0])
                s_lo = max(found[-1][1] + ANCHOR_K, guess - ANCHOR_SLACK)
                s_hi = guess + ANCHOR_SLACK + ANCHOR_K
            else:
                s_lo, s_hi = 0, len(polished)
            pos = polished.find(kmer, s_lo, s_hi)
            if pos >= 0 and polished.find(kmer, pos + 1, s_hi) < 0:
                found.append((tb, pos))
                break
        b += ANCHOR_STEP
    # close the stretch: an anchor as near its end as one can be found
    for tries in range(ANCHOR_TRIES):
        tb = hi - ANCHOR_K - tries * (ANCHOR_STEP // ANCHOR_TRIES)
        if not found or tb <= found[-1][0] + ANCHOR_K:
            break
        guess = found[-1][1] + (tb - found[-1][0])
        s_lo = max(found[-1][1] + ANCHOR_K, guess - ANCHOR_SLACK)
        s_hi = guess + ANCHOR_SLACK + ANCHOR_K
        kmer = truth[tb:tb + ANCHOR_K]
        pos = polished.find(kmer, s_lo, s_hi)
        if pos >= 0 and polished.find(kmer, pos + 1, s_hi) < 0:
            found.append((tb, pos))
            break
    return found


def contig_pieces(polished: bytes, truth: bytes, margin: int):
    """Pieces between consecutive anchors of truth[margin:-margin], and
    the bases of that stretch that lie outside every piece."""
    lo, hi = margin, len(truth) - margin
    an = anchors(polished, truth, lo, hi)
    if not an:
        return [], max(0, hi - lo)
    pieces = [(polished[pa:pb], truth[ta:tb])
              for (ta, pa), (tb, pb) in zip(an, an[1:])]
    return pieces, (an[0][0] - lo) + max(0, hi - an[-1][0] - ANCHOR_K)


def fragment_pieces(polished: bytes, truth: bytes):
    """Cut a corrected read and its truth span at shared anchors.
    Returns [(p, t, free_end)]: inner pieces are global; the head is
    reversed so that its free start in the truth becomes a free end,
    and the tail's end is free."""
    an = anchors(polished, truth)
    if not an:
        if max(len(polished), len(truth)) > 4 * ANCHOR_STEP:
            # nothing of the truth is recognisable: every base is wrong
            return [(polished, b"", False)]
        return [(polished, truth, False)]
    t0, p0 = an[0]
    out = [(polished[:p0][::-1], truth[:t0][::-1], True)]
    for (ta, pa), (tb, pb) in zip(an, an[1:]):
        out.append((polished[pa:pb], truth[ta:tb], False))
    tl, pl = an[-1]
    out.append((polished[pl:], truth[tl:], True))
    return out


def parse_fasta(data: bytes) -> list[tuple[str, bytes]]:
    recs = []
    for block in data.split(b">")[1:]:
        head, _, body = block.partition(b"\n")
        recs.append((head.decode(), body.replace(b"\n", b"")))
    return recs


def compare(job, fasta: bytes, fragment: bool, margin: int) -> dict:
    """The job's polished FASTA against its truth: `edits` (an upper
    bound) over `bases` (the compared truth stretch of a contig, the
    whole record of a corrected read); `worst_piece_pct`, the largest
    share of edits in one piece between two anchors of at least
    `MIN_PIECE` truth bases (a wrong window shows there, diluted
    nowhere); and how many targets have no record or a record that is
    no target. `margin` bases are cut from both ends of a contig's
    truth."""
    got = {}
    for head, seq in parse_fasta(fasta):
        name = head.split(" ", 1)[0]
        if fragment and name.endswith("r"):
            name = name[:-1]
        got[name] = seq
    missing = [t for t in job.target_names if t not in got]
    extra = [n for n in got if n not in job.truth]
    pieces, free = [], []
    edits = bases = 0
    for name in job.target_names:
        if name not in got:
            continue
        truth = job.truth[name]
        if fragment:
            for p, t, fe in fragment_pieces(got[name], truth):
                pieces.append((p, t))
                free.append(fe)
            bases += len(got[name])
        else:
            ps, unanchored = contig_pieces(got[name], truth, margin)
            pieces += ps
            free += [False] * len(ps)
            edits += unanchored
            bases += max(0, len(truth) - 2 * margin)
    dist = banded_distances(pieces, free)
    edits += sum(dist)
    # a corrected read's free ends are where its coverage runs out: they
    # count in `edits` but say more about the layout than the program
    worst = max((100.0 * d / len(t) for d, (_, t), fe in
                 zip(dist, pieces, free)
                 if len(t) >= MIN_PIECE and not fe), default=0.0)
    return {"edits": edits, "bases": bases, "worst_piece_pct": worst,
            "missing": len(missing), "extra": len(extra)}
