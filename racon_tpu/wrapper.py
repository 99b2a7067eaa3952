"""Wrapper: subsample / split / multi-chunk polishing driver.

The capability of the reference's `racon_wrapper`
(scripts/racon_wrapper.py:57-147): optionally subsample the reads to a
target coverage, optionally split the target sequences into byte-bounded
chunks, then polish chunk by chunk so peak memory stays bounded — the
reference's only scale-out mechanism beyond one process (SURVEY.md §2c-7).

Differences from the reference, both deliberate:
  - rampler is replaced by the in-package racon_tpu.rampler (no external
    binary, gzip-transparent);
  - chunks are polished in-process (create_polisher per chunk) instead of
    shelling out, so device runtimes and compiled kernels are reused
    across chunks.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

from . import rampler
from .errors import RaconError


def run(sequences: str, overlaps: str, target_sequences: str,
        split: int | None = None, subsample: tuple[int, int] | None = None,
        include_unpolished: bool = False, fragment_correction: bool = False,
        window_length: int = 500, quality_threshold: float = 10.0,
        error_threshold: float = 0.3, match: int = 5, mismatch: int = -4,
        gap: int = -8, threads: int = 1, tpu_poa_batches: int = 0,
        tpu_aligner_batches: int = 0, tpu_banded_alignment: bool = False,
        num_shards: int = 1, shard_id: int = 0, out=None) -> None:
    """Polish `target_sequences`, optionally subsampled/split, writing
    FASTA to `out` (default stdout).

    `num_shards`/`shard_id` implement the multi-host scale-out story
    (SURVEY.md §5): the window workload is embarrassingly parallel and
    needs no inter-device communication, so hosts scale by FILE-LEVEL
    scatter/gather over DCN — each host polishes a contiguous block of the
    target chunks (chunks are byte-bounded, so blocks are balanced), and
    concatenating the shard outputs in shard order reproduces the
    unsharded output byte-for-byte. Requires --split so there is more
    than one unit to scatter."""
    from .core.polisher import create_polisher, PolisherType
    from .obs import jax_profile

    if not (0 <= shard_id < num_shards):
        raise RaconError(
            "wrapper", f"shard_id {shard_id} outside [0, {num_shards})")
    out = out if out is not None else sys.stdout.buffer
    work = tempfile.mkdtemp(prefix="racon_tpu_work_")
    try:
        if subsample is not None:
            ref_len, coverage = subsample
            print("[racon_tpu::wrapper] subsampling sequences", file=sys.stderr)
            sequences = rampler.subsample(sequences, ref_len, coverage, work)

        if split is not None:
            print("[racon_tpu::wrapper] splitting target sequences",
                  file=sys.stderr)
            targets = rampler.split(target_sequences, split, work)
            print(f"[racon_tpu::wrapper] total number of splits: "
                  f"{len(targets)}", file=sys.stderr)
        else:
            targets = [target_sequences]

        if num_shards > 1:
            if len(targets) < num_shards:
                # every shard must have work: silently-empty shard output
                # looks like a failed run to gather scripts
                raise RaconError(
                    "wrapper",
                    f"num_shards {num_shards} exceeds the {len(targets)} "
                    "target chunk(s); " +
                    ("use a smaller --split size or fewer shards"
                     if split is not None else
                     "--num-shards needs --split to make chunks to scatter"))
            lo = shard_id * len(targets) // num_shards
            hi = (shard_id + 1) * len(targets) // num_shards
            print(f"[racon_tpu::wrapper] shard {shard_id}/{num_shards}: "
                  f"chunks [{lo}, {hi}) of {len(targets)}", file=sys.stderr)
            targets = targets[lo:hi]

        with jax_profile():
            for part in targets:
                polisher = create_polisher(
                    sequences, overlaps, part,
                    PolisherType.kF if fragment_correction
                    else PolisherType.kC,
                    window_length, quality_threshold, error_threshold, True,
                    match, mismatch, gap, threads, tpu_poa_batches,
                    tpu_banded_alignment, tpu_aligner_batches)
                polisher.initialize()
                for seq in polisher.polish(not include_unpolished):
                    out.write(b">" + seq.name.encode() + b"\n" + seq.data
                              + b"\n")
                out.flush()
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="racon_tpu_wrapper",
        description="racon_tpu wrapper adding sequence subsampling and "
                    "target splitting for bounded memory/runtime")
    parser.add_argument("sequences")
    parser.add_argument("overlaps")
    parser.add_argument("target_sequences")
    parser.add_argument("--split", type=int,
                        help="split target sequences into chunks of given "
                             "size in bytes")
    parser.add_argument("--subsample", nargs=2, type=int,
                        metavar=("REFERENCE_LENGTH", "COVERAGE"),
                        help="subsample sequences to coverage given the "
                             "reference length")
    parser.add_argument("-u", "--include-unpolished", action="store_true")
    parser.add_argument("-f", "--fragment-correction", action="store_true")
    parser.add_argument("-w", "--window-length", type=int, default=500)
    parser.add_argument("-q", "--quality-threshold", type=float, default=10.0)
    parser.add_argument("-e", "--error-threshold", type=float, default=0.3)
    parser.add_argument("-m", "--match", type=int, default=5)
    parser.add_argument("-x", "--mismatch", type=int, default=-4)
    parser.add_argument("-g", "--gap", type=int, default=-8)
    parser.add_argument("-t", "--threads", type=int, default=1)
    parser.add_argument("-c", "--tpupoa-batches", type=int, default=0)
    parser.add_argument("--tpualigner-batches", type=int, default=0)
    parser.add_argument("-b", "--tpu-banded-alignment", action="store_true")
    parser.add_argument("--num-shards", type=int, default=1,
                        help="multi-host scale-out: total hosts polishing "
                             "this workload (file-level scatter over the "
                             "--split chunks; cat shard outputs in shard "
                             "order to gather)")
    parser.add_argument("--shard-id", type=int, default=0,
                        help="this host's shard index in [0, num_shards)")

    args = parser.parse_args(argv)
    try:
        run(args.sequences, args.overlaps, args.target_sequences,
            split=args.split,
            subsample=tuple(args.subsample) if args.subsample else None,
            include_unpolished=args.include_unpolished,
            fragment_correction=args.fragment_correction,
            window_length=args.window_length,
            quality_threshold=args.quality_threshold,
            error_threshold=args.error_threshold,
            match=args.match, mismatch=args.mismatch, gap=args.gap,
            threads=args.threads, tpu_poa_batches=args.tpupoa_batches,
            tpu_aligner_batches=args.tpualigner_batches,
            tpu_banded_alignment=args.tpu_banded_alignment,
            num_shards=args.num_shards, shard_id=args.shard_id)
    except RaconError as exc:
        print(str(exc), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
