"""Seeded job generator for the benchmark: one general generator that a
configuration file (the deployment's shapes) and a traffic file (how
the jobs are cut and how many a run may use) parameterise.

A configuration's `mode` and `overlaps` name its job maker,
`benchmark/makers/<mode>-<overlaps>.py`, found by name: a new kind of
job is a new maker file, and a configuration naming a maker that does
not exist is refused. Each maker's `make(seed, name, cfg, traffic)`
returns one `Job`. The makers here:

- `contig-paf` (racon's kC): a random genome, a draft at `draft_err`,
  and reads at `read_err` up to `coverage` x, with PAF overlaps of
  every read onto the draft taken from the simulation's own
  coordinate maps.
- `fragment-paf` (racon's kF, `-f`): one genome, `n_reads` reads
  holding `total_read_bp` bases, and the dual all-vs-all PAF of every
  pair whose true spans share `min_overlap_bp` or more. The targets
  are split into byte-bounded chunks of `split_bytes` (the wrapper's
  `--split`); one job is the first chunk against all reads and that
  chunk's overlap rows.

Read lengths follow a gamma distribution of mean `read_len` (kC) or
`total_read_bp / n_reads` (kF) and standard deviation `read_len_sd`,
none shorter than `min_read_bp`. The layout, each read's length and
where it lies, is drawn once from the configuration's `layout_seed`, so
that every seed gives the program the same amount of work: the aligner
runs a length bucket in batches of a few long lanes, so one overlap
that a seed moves across a bucket's edge or a batch's last lane costs
a whole batch (chip runs with a layout drawn per seed spread 13 %
between seeds). The run seed draws the genome, the draft's and the
reads' errors, the strands, and the order of the reads in their file.

Every job carries its truth: the genome segment each target should
polish to, in the target's own orientation. The error model is a copy
of the repository's (`tools/synthbench.mutate_fast`), kept here so that
no later change to the program can change the inputs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ACGT = b"ACGT"
_COMP = bytes.maketrans(b"ACGT", b"TGCA")


def revcomp(s: bytes) -> bytes:
    return s.translate(_COMP)[::-1]


def mutate_fast(nrng, s: bytes, rate: float):
    """ONT-like errors at `rate`: deletions, insertions (placed before
    the kept base) and substitutions each at rate/3. Returns the mutated
    bytes and `seg`, with seg[i] the output start of input base i's
    segment and seg[n] the output length, so input span [b, e) lands on
    output span [seg[b], seg[e])."""
    arr = np.frombuffer(s, dtype=np.uint8).copy()
    n = len(arr)
    u = nrng.random(n)
    dele = u < rate / 3
    ins = (u >= rate / 3) & (u < 2 * rate / 3)
    sub = (u >= 2 * rate / 3) & (u < rate)
    bases = np.frombuffer(ACGT, dtype=np.uint8)
    arr[sub] = bases[nrng.integers(0, 4, int(sub.sum()))]
    out_len = np.where(dele, 0, np.where(ins, 2, 1))
    off = np.zeros(n, dtype=np.int64)
    np.cumsum(out_len[:-1], out=off[1:])
    total = int(off[-1] + out_len[-1]) if n else 0
    out = np.empty(total, dtype=np.uint8)
    keep = ~dele
    out[off[keep] + ins[keep]] = arr[keep]
    ins_keep = ins & keep
    out[off[ins_keep]] = bases[nrng.integers(0, 4, int(ins_keep.sum()))]
    return out.tobytes(), np.append(off, total)


def random_genome(nrng, n: int) -> bytes:
    return np.frombuffer(ACGT, dtype=np.uint8)[nrng.integers(0, 4, n)] \
        .tobytes()


def read_lengths(lrng, cfg: dict, mean: float, n: int) -> np.ndarray:
    """`n` read lengths: a gamma distribution of that mean and
    `read_len_sd`, none under `min_read_bp`."""
    shape = (mean / cfg["read_len_sd"]) ** 2
    return np.maximum(int(cfg["min_read_bp"]),
                      np.rint(lrng.gamma(shape, mean / shape, n))
                      ).astype(np.int64)


@dataclasses.dataclass
class Job:
    """One polishing job: the three input files' bytes in the argv
    positionals' order (reads, overlaps, targets), the overlaps' file
    extension, and for each target name the sequence it should polish
    to."""
    name: str
    reads: bytes
    overlaps: bytes
    targets: bytes
    truth: dict[str, bytes]
    target_names: list[str]
    overlaps_ext: str = "paf"

    def write(self, d: str) -> list[str]:
        paths = [os.path.join(d, f"{self.name}.{ext}")
                 for ext in ("reads.fasta", self.overlaps_ext,
                             "targets.fasta")]
        for path, data in zip(paths, (self.reads, self.overlaps,
                                      self.targets)):
            with open(path, "wb") as f:
                f.write(data)
        return paths


def fasta(records) -> bytes:
    return b"".join(b">" + n.encode() + b"\n" + s + b"\n" for n, s in records)


def maker(cfg: dict):
    """The `make` of the configuration's maker file."""
    name = f"{cfg['mode']}-{cfg['overlaps']}"
    path = os.path.join(HERE, "makers", f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no job maker {name!r} (benchmark/makers/"
                         f"{name}.py) for mode {cfg['mode']!r} with "
                         f"overlaps {cfg['overlaps']!r}")
    spec = importlib.util.spec_from_file_location(f"bench_maker_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make


def make_jobs(seed: int, cfg: dict, traffic: dict, n_jobs: int,
              prefix: str = "job") -> list[Job]:
    """`n_jobs` jobs of the cell, all drawn from `seed`, each from its own
    sub-seed: the same seed always gives the same jobs."""
    make = maker(cfg)
    return [make(int(ss.generate_state(1)[0]), f"{prefix}{k}", cfg, traffic)
            for k, ss in enumerate(np.random.SeedSequence(seed)
                                   .spawn(n_jobs))]
