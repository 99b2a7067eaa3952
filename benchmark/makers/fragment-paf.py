"""racon kF with PAF overlaps: one read set, its dual all-vs-all PAF,
and one job: the first byte-bounded chunk of targets (consecutive reads
in file order, the way `rampler.split` cuts them) against every read
and that chunk's overlap rows. Every job draws a read set of its own.

The read set holds `n_reads` reads and `total_read_bp` bases, as the
source's does: the layout (lengths scaled to that total, and where each
read lies) comes from the configuration's `layout_seed`, and the seed
draws the genome, the errors and the strands. The targets are the
chunk's reads that have at least one overlap row."""

import numpy as np

import gen


def _read_span(seg, start: int, a: int, b: int, rev: bool, rlen: int):
    """Truth span [a, b) as coordinates on a read simulated from truth
    position `start`, on the read's own strand."""
    fb, fe = int(seg[a - start]), int(seg[b - start])
    return (rlen - fe, rlen - fb) if rev else (fb, fe)


def make(seed: int, name: str, cfg: dict, traffic: dict) -> gen.Job:
    g = int(cfg["genome_bp"])
    n = int(cfg["n_reads"])
    total = int(cfg["total_read_bp"])
    min_ovl = int(cfg["min_overlap_bp"])
    lrng = np.random.default_rng(int(cfg["layout_seed"]))
    lens = gen.read_lengths(lrng, cfg, total / n, n)
    lens = np.clip(np.rint(lens * (total / lens.sum())),
                   int(cfg["min_read_bp"]), g).astype(np.int64)
    starts = lrng.integers(0, g - lens + 1)
    ends = starts + lens
    nrng = np.random.default_rng(seed)
    truth = gen.random_genome(nrng, g)
    strands = nrng.random(n) < 0.5
    reads, segs, names, truths = [], [], [], {}
    for i in range(n):
        s, e = int(starts[i]), int(ends[i])
        fwd, seg = gen.mutate_fast(nrng, truth[s:e], cfg["read_err"])
        rev = bool(strands[i])
        reads.append(gen.revcomp(fwd) if rev else fwd)
        segs.append(seg)
        names.append(f"read{i}")
        truths[names[-1]] = gen.revcomp(truth[s:e]) if rev else truth[s:e]
    # every ordered pair (q, t) of distinct reads whose true spans share
    # at least min_ovl bases: both rows of a pair are emitted (dual)
    lo = np.maximum(starts[:, None], starts[None, :])
    hi = np.minimum(ends[:, None], ends[None, :])
    qi, ti = np.nonzero((hi - lo >= min_ovl) & ~np.eye(n, dtype=bool))
    rows_by_target: dict[int, list[str]] = {}
    for q, t in zip(qi.tolist(), ti.tolist()):
        a, b = int(lo[q, t]), int(hi[q, t])
        qs, qe = _read_span(segs[q], int(starts[q]), a, b, bool(strands[q]),
                            len(reads[q]))
        ts, te = _read_span(segs[t], int(starts[t]), a, b, bool(strands[t]),
                            len(reads[t]))
        rows_by_target.setdefault(t, []).append(
            f"{names[q]}\t{len(reads[q])}\t{qs}\t{qe}\t"
            f"{'-' if strands[q] != strands[t] else '+'}\t"
            f"{names[t]}\t{len(reads[t])}\t{ts}\t{te}\t{b - a}\t{b - a}\t60")
    chunk, size = [], 0
    for i in range(n):
        if chunk and size + len(reads[i]) > int(traffic["split_bytes"]):
            break
        chunk.append(i)
        size += len(reads[i])
    rows = [r for i in chunk for r in rows_by_target.get(i, [])]
    chunk = [i for i in chunk if i in rows_by_target]
    targets = [names[i] for i in chunk]
    return gen.Job(name, gen.fasta(zip(names, reads)),
                   ("\n".join(rows) + "\n").encode(),
                   gen.fasta((names[i], reads[i]) for i in chunk),
                   {t: truths[t] for t in targets}, targets)
