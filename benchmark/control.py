"""Readings behind the limits of `correct`, at the cell's own size, in
one process on the chip.

    python3 benchmark/control.py --workload <cell> --sound 1,2,3 \
        --control 4,5,6 --fault altered:7,8,9

For each seed it polishes the seed's first job of the cell through the
cell's own entry and argv, and prints one JSON line with the numbers
that `correct` compares:

- `--sound`: the job as the window runs it (the lower readings);
- `--control`: the control. The configuration states that every read
  overlapping a window gives it a layer; the control breaks that
  guarantee the way a cap on a window's depth would, keeping the
  overlaps of one read in `KEEP` (about eight layers a window at 30x)
  (the upper readings);
- `--fault NAME:SEEDS`: a fault of `faults.py` planted in the program
  (repeatable).

The benchmark's own runs never run this. Compiles are not timed here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import run

KEEP = 4


def thin(job, keep: int):
    """The job with only the overlaps of reads whose index is a multiple
    of `keep` (and, for contigs, only those reads)."""
    def kept(name: bytes) -> bool:
        return int(name[len(b"read"):]) % keep == 0

    rows = [r for r in job.overlaps.split(b"\n")
            if r and kept(r.split(b"\t", 1)[0])]
    reads = job.reads
    if job.target_names == ["draft"]:
        recs = reads.split(b">")[1:]
        reads = b"".join(b">" + r for r in recs
                         if kept(r.split(b"\n", 1)[0]))
    return dataclasses.replace(job, name=job.name + "_control",
                               reads=reads,
                               overlaps=b"\n".join(rows) + b"\n")


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", action="append", default=[])
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, cfg, traffic, _, _ = run.cell_spec(bench, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.cache_dir()
    sys.path.insert(0, run.ROOT)
    import jax

    import faults
    import gen
    from racon_tpu.sched import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        run.log("FAIL: JAX found no TPU")
        return 1
    enable_compile_cache()
    plan = ([("sound", None, s) for s in seeds(args.sound)]
            + [(f"control_keep{KEEP}", None, s) for s in seeds(args.control)]
            + [(f"fault_{name}", name, s) for f in args.fault
               for name, _, text in [f.partition(":")] for s in seeds(text)])
    threads = len(os.sched_getaffinity(0))
    with tempfile.TemporaryDirectory(prefix="racon_control_") as d:
        for kind, fault, seed in plan:
            job = gen.make_jobs(seed, cfg, traffic, 1)[0]
            if kind.startswith("control"):
                job = thin(job, KEEP)
            t0 = time.perf_counter()
            with faults.planted(fault):
                res = run.run_job(job.name, run.job_argv(job.write(d), cfg,
                                                         threads))
            checks = run.check([job], [res], cfg)
            print(json.dumps({"kind": kind, "seed": seed, "job": job.name,
                              "ok": res.ok, "why": res.why,
                              "windows": res.windows,
                              "wall_s": time.perf_counter() - t0,
                              **{k: v["value"] for k, v in checks.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
