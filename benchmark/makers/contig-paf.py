"""racon kC with PAF overlaps: one genome, its draft, and reads onto it.

The layout comes from the configuration's `layout_seed`: read lengths
drawn until they reach `coverage` x the genome, and for each read a
start anywhere from half its length before the contig's start to half
its length before its end. A read that runs past either end is cut
there, as a mapper cuts the parts of a read that spans the origin of a
circular chromosome; so every base of the contig has at least half the
coverage, and the whole contig is compared."""

import numpy as np

import gen


def make(seed: int, name: str, cfg: dict, traffic: dict) -> gen.Job:
    g = int(cfg["genome_bp"])
    mean = int(cfg["read_len"])
    want = g * int(cfg["coverage"])
    lrng = np.random.default_rng(int(cfg["layout_seed"]))
    lens = gen.read_lengths(lrng, cfg, mean, 2 * want // mean + 16)
    lens = lens[:int(np.searchsorted(np.cumsum(lens), want)) + 1]
    starts = lrng.integers(-(lens // 2), g - lens // 2)
    ends = np.minimum(g, starts + lens)
    starts = np.maximum(0, starts)
    nrng = np.random.default_rng(seed)
    order = nrng.permutation(len(lens))
    truth = gen.random_genome(nrng, g)
    draft, t_seg = gen.mutate_fast(nrng, truth, cfg["draft_err"])
    strands = nrng.random(len(lens)) < 0.5
    reads, paf = [], []
    for i, k in enumerate(order.tolist()):
        start, end = int(starts[k]), int(ends[k])
        fwd, _ = gen.mutate_fast(nrng, truth[start:end], cfg["read_err"])
        read = gen.revcomp(fwd) if strands[i] else fwd
        rname = f"read{i}"
        reads.append((rname, read))
        paf.append(f"{rname}\t{len(read)}\t0\t{len(read)}\t"
                   f"{'-' if strands[i] else '+'}\tdraft\t{len(draft)}\t"
                   f"{int(t_seg[start])}\t{int(t_seg[end])}\t"
                   f"{end - start}\t{end - start}\t60")
    return gen.Job(name, gen.fasta(reads), ("\n".join(paf) + "\n").encode(),
                   gen.fasta([("draft", draft)]), {"draft": truth},
                   ["draft"])
