"""Synthetic scale benchmark: ONT-style polishing at arbitrary genome size.

The BASELINE.md north star is E. coli 30x ONT polishing throughput; the
packaged sample is only 48.5 kb. This tool simulates the same shape of
workload at any scale — a random genome, a noisy draft, long reads with
ONT-like errors, and PAF overlaps derived from the simulation's true
coordinates — then polishes it and reports wall-clock, windows/sec, and
polished identity vs the simulated truth.

    python tools/synthbench.py --genome-kb 200 --coverage 30 [-c 1]

Unlike bench.py (the driver's one-line contract on the reference sample),
this is an engineering tool for scale/perf work.
"""

from __future__ import annotations

import argparse
import gzip
import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ACGT = b"ACGT"


def mutate(rng, s, rate):
    out = bytearray()
    for c in s:
        r = rng.random()
        if r < rate / 3:
            continue
        if r < 2 * rate / 3:
            out.append(rng.choice(ACGT))
            out.append(c)
            continue
        if r < rate:
            out.append(rng.choice(ACGT))
            continue
        out.append(c)
    return bytes(out)


def mutate_fast(nrng, s, rate, with_offsets=False):
    """Vectorized mutate() twin (numpy RNG, different stream — only used
    under --fast-sim, never for the seed-pinned goldens): same error
    model, dels/ins/subs each at rate/3, insertions placed before the
    kept base like mutate(). `with_offsets` additionally returns the
    exact input→output coordinate maps — land[i] = output position of
    input base i itself (for a deleted base: where it would have been)
    and seg[i] = output start of base i's segment with seg[n] = total
    output length, so seg[e] is the exclusive output end of span
    [b, e). Callers use these to emit drift-free coordinates — at
    multi-Mb scale the global-length-ratio approximation drifts by
    hundreds of bases (indel-count fluctuation grows with length) and
    distorts every derived overlap."""
    import numpy as np

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
    if with_offsets:
        land = off + (ins & keep)
        seg = np.append(off, total)
        return out.tobytes(), land, seg
    return out.tobytes()


def simulate_fast(seed, genome_len, coverage, read_len, read_err,
                  draft_err):
    """Vectorized simulate() for multi-Mb genomes (numpy RNG stream;
    deterministic for a seed but NOT byte-compatible with simulate())."""
    import numpy as np

    nrng = np.random.default_rng(seed)
    bases = np.frombuffer(ACGT, dtype=np.uint8)
    truth = bases[nrng.integers(0, 4, genome_len)].tobytes()
    # exact truth→draft coordinate map: PAF coordinates must be the
    # draft positions where the read's truth span actually lands, not a
    # global-length-ratio guess (which drifts ±hundreds of bases at
    # multi-Mb scale and distorts every window layer derived from it)
    draft, t_land, t_seg = mutate_fast(nrng, truth, draft_err,
                                       with_offsets=True)

    comp = bytes.maketrans(b"ACGT", b"TGCA")
    reads, paf = [], []
    n_reads = genome_len * coverage // read_len
    starts = nrng.integers(0, max(1, genome_len - read_len // 2), n_reads)
    strands = nrng.random(n_reads) < 0.5
    for i in range(n_reads):
        start = int(starts[i])
        end = min(genome_len, start + read_len)
        fwd = mutate_fast(nrng, truth[start:end], read_err)
        read = fwd.translate(comp)[::-1] if strands[i] else fwd
        name = f"read{i}"
        t_begin = int(t_land[start])
        t_end = int(t_seg[end]) if end > start else t_begin
        reads.append((name, read))
        paf.append(f"{name}\t{len(read)}\t0\t{len(read)}\t"
                   f"{'-' if strands[i] else '+'}\tdraft\t{len(draft)}\t"
                   f"{t_begin}\t{t_end}\t{end - start}\t{end - start}\t60")
    return truth, draft, reads, paf


def simulate(rng, genome_len, coverage, read_len, read_err, draft_err):
    truth = bytes(rng.choice(ACGT) for _ in range(genome_len))
    draft = mutate(rng, truth, draft_err)

    reads, paf = [], []
    n_reads = genome_len * coverage // read_len
    scale = len(draft) / len(truth)
    for i in range(n_reads):
        start = rng.randrange(0, max(1, genome_len - read_len // 2))
        end = min(genome_len, start + read_len)
        fwd = mutate(rng, truth[start:end], read_err)
        strand = rng.random() < 0.5
        if strand:
            comp = bytes.maketrans(b"ACGT", b"TGCA")
            read = fwd.translate(comp)[::-1]
        else:
            read = fwd
        name = f"read{i}"
        t_begin = int(start * scale)
        t_end = min(len(draft), int(end * scale))
        reads.append((name, read))
        paf.append(f"{name}\t{len(read)}\t0\t{len(read)}\t"
                   f"{'-' if strand else '+'}\tdraft\t{len(draft)}\t"
                   f"{t_begin}\t{t_end}\t{end - start}\t{end - start}\t60")
    return truth, draft, reads, paf


def _scale_child_env(repo: str, n_devices: int) -> dict:
    """An environment pinning the child to a CPU mesh of `n_devices`
    virtual devices (the __graft_entry__ dryrun discipline: platform
    forced before jax init)."""
    env = dict(os.environ)
    keep = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
            if p and p != repo]
    env["PYTHONPATH"] = os.pathsep.join([repo] + keep)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["RACON_TPU_MAX_DEVICES"] = str(n_devices)
    from racon_tpu.sched import default_cache_dir

    env["JAX_COMPILATION_CACHE_DIR"] = default_cache_dir()
    return env


def _scale_point(n_devices: int, doc: dict, sha: str) -> dict:
    """One scale-curve point from a child artifact: throughput plus the
    mesh-waste view aggregated across every device engine's buckets —
    per-shard useful-cell balance (max/min; 1.0 = perfectly even) and
    the padded-cell fraction vs the full-mesh round_batch baseline (the
    sub-mesh tail dispatch win)."""
    from racon_tpu.sched.telemetry import accumulate_cells

    shards: list[int] = []
    useful = total = fm_cells = fm_useful = 0
    for engine in (doc.get("occupancy") or {}).values():
        # the engine-level raw sums OccupancyStats.snapshot() publishes
        # — summed across engines here (fractions cannot be combined,
        # raw cells can)
        accumulate_cells(shards, engine.get("shard_useful", ()))
        useful += engine.get("useful_cells", 0)
        total += engine.get("total_cells", 0)
        fm_cells += engine.get("full_mesh_cells", 0)
        fm_useful += engine.get("full_mesh_useful", 0)
    synth = doc.get("synth") or {}
    point = {"n_devices": n_devices,
             "windows_per_s": synth.get("windows_per_s"),
             "windows": synth.get("windows"),
             "polish_s": synth.get("polish_s"),
             "golden_sha": sha}
    if shards:
        point["shard_useful"] = shards
        if min(shards) > 0:
            point["shard_balance"] = round(max(shards) / min(shards), 4)
    if total:
        point["padded_frac"] = round((total - useful) / total, 6)
    if fm_cells:
        point["padded_frac_full_mesh"] = round(
            (fm_cells - fm_useful) / fm_cells, 6)
    return point


def run_scale_curve(args) -> int:
    """--scale-curve N1,N2,...: re-run the SAME workload once per mesh
    size (subprocess per point — the virtual device count must be
    pinned before jax initializes), assert the polished FASTA is
    byte-identical at every size, and emit a `scale` block in the
    --json artifact: windows/s, per-shard useful-cell balance, and the
    padded-cell fraction vs the full-mesh-rounding baseline per point —
    the numbers tools/perfgate.py gates mesh regressions on."""
    import hashlib
    import json
    import subprocess

    sizes = sorted({int(s) for s in args.scale_curve.split(",")
                    if s.strip()})
    if not sizes or min(sizes) < 1:
        print("[synthbench] --scale-curve wants positive device counts",
              file=sys.stderr)
        return 2
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    me = os.path.abspath(__file__)
    curve, shas = [], []
    with tempfile.TemporaryDirectory(prefix="racon_scale_") as d:
        for n in sizes:
            child_json = os.path.join(d, f"scale_{n}.json")
            golden = os.path.join(d, f"golden_{n}.fasta")
            cmd = [sys.executable, me,
                   "--genome-kb", str(args.genome_kb),
                   "--coverage", str(args.coverage),
                   "--read-len", str(args.read_len),
                   "--read-err", str(args.read_err),
                   "--draft-err", str(args.draft_err),
                   "-w", str(args.window_length),
                   "-t", str(args.threads),
                   "-c", str(args.tpupoa_batches),
                   "--tpualigner-batches", str(args.tpualigner_batches),
                   "--seed", str(args.seed),
                   "--json", child_json, "--golden-out", golden]
            if args.adaptive_buckets:
                cmd.append("--adaptive-buckets")
            if args.fast_sim:
                cmd.append("--fast-sim")
            print(f"[synthbench] scale point: {n} device(s) ...",
                  file=sys.stderr)
            proc = subprocess.run(cmd, env=_scale_child_env(repo, n),
                                  capture_output=True, text=True,
                                  timeout=3600)
            if proc.returncode != 0:
                sys.stderr.write(proc.stderr[-4000:])
                print(f"[synthbench] scale point {n} FAILED "
                      f"(rc {proc.returncode})", file=sys.stderr)
                return 1
            with open(child_json) as fh:
                doc = json.load(fh)
            with open(golden, "rb") as fh:
                sha = hashlib.sha256(fh.read()).hexdigest()
            shas.append(sha)
            point = _scale_point(n, doc, sha)
            curve.append(point)
            print(f"[synthbench]   {n} device(s): "
                  f"{point['windows_per_s']} windows/s, shard balance "
                  f"{point.get('shard_balance', 'n/a')}, padded "
                  f"{point.get('padded_frac', 'n/a')} (full-mesh "
                  f"baseline {point.get('padded_frac_full_mesh', 'n/a')})"
                  f", sha {sha[:12]}", file=sys.stderr)
    identical = len(set(shas)) == 1
    print(f"[synthbench] scale curve: polished FASTA "
          f"{'byte-identical' if identical else 'DIVERGED'} across mesh "
          f"sizes {sizes}", file=sys.stderr)
    if args.json:
        head = curve[-1]
        artifact = {
            "mode": "synth",
            "synth": {"windows_per_s": head["windows_per_s"],
                      "windows": head["windows"],
                      "polish_s": head["polish_s"],
                      "genome_kb": args.genome_kb,
                      "coverage": args.coverage,
                      "seed": args.seed},
            "scale": {"curve": curve, "identical": identical},
            # describes the headline (largest-mesh) CHILD, not this
            # orchestrator process — the one artifact whose mesh block
            # cannot come from the shared mesh_info() helper
            "mesh": {"n_devices": head["n_devices"],
                     "worker_lanes": 1,
                     "max_devices_env": str(head["n_devices"])},
        }
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=1, sort_keys=True)
        print(f"[synthbench] wrote artifact {args.json}",
              file=sys.stderr)
    return 0 if identical else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--genome-kb", type=int, default=200)
    ap.add_argument("--coverage", type=int, default=30)
    ap.add_argument("--read-len", type=int, default=8000)
    ap.add_argument("--read-err", type=float, default=0.12)
    ap.add_argument("--draft-err", type=float, default=0.10)
    ap.add_argument("-w", "--window-length", type=int, default=500)
    ap.add_argument("-t", "--threads", type=int, default=os.cpu_count() or 1)
    ap.add_argument("-c", "--tpupoa-batches", type=int, default=0)
    ap.add_argument("--tpualigner-batches", type=int, default=0)
    ap.add_argument("--engine", choices=("session", "fused"),
                    default=None,
                    help="device consensus engine (with -c > 0); "
                         "default session — the fused engine is the "
                         "one the RACON_TPU_FUSED single-launch "
                         "program applies to")
    ap.add_argument("--dispatch-overhead", action="store_true",
                    help="A/B the fused single-launch dispatch "
                         "(RACON_TPU_FUSED=1) against the split "
                         "chained path (=0) on the same workload: "
                         "windows/s, measured host overhead (host_s = "
                         "polish wall - device-stage seconds) and "
                         "launch counts per mode, byte-identity "
                         "asserted; implies --engine fused and "
                         "requires -c > 0")
    ap.add_argument("--adaptive-buckets", action="store_true",
                    help="arm the occupancy-aware batch scheduler "
                         "(adaptive shape ladders + sorted packing); "
                         "the occupancy report below A/Bs the win")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--scale-curve", default=None, metavar="N1,N2,...",
                    help="mesh-scaling sweep: re-run this workload once "
                         "per virtual-CPU mesh size (e.g. '1,2,4,8'), "
                         "assert byte-identical polished FASTA across "
                         "sizes, and record windows/s + per-shard "
                         "useful-cell balance + padded-cell fraction "
                         "vs the full-mesh-rounding baseline per point "
                         "in the --json artifact (gated by "
                         "tools/perfgate.py --scale-balance-max)")
    ap.add_argument("--fast-sim", action="store_true",
                    help="vectorized simulator for multi-Mb genomes "
                         "(deterministic per seed, but a different RNG "
                         "stream than the default — goldens pin the "
                         "default)")
    ap.add_argument("--golden-out", default=None,
                    help="write the polished FASTA here (golden artifact; "
                         "deterministic for a given seed/params)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write a machine-readable artifact (mode "
                         "'synth': windows_per_s, phase seconds, "
                         "identity, per-bucket occupancy incl. the "
                         "dispatched kernel/dtype choice) — the shape "
                         "tools/perfgate.py gates with "
                         "--windows-per-s-min / --against")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record a Chrome trace (Perfetto) of the polish "
                         "to PATH and report trace-recording overhead vs "
                         "an untraced baseline run of the same workload "
                         "(target: < 2%%)")
    ap.add_argument("--flight", action="store_true",
                    help="A/B the always-on flight recorder "
                         "(obs/flight.py, the bounded ring the serve "
                         "layer installs) against an unrecorded "
                         "baseline run of the same workload "
                         "(target: < 2%% — the serve-mode overhead "
                         "budget)")
    ap.add_argument("--progress-journal", action="store_true",
                    help="A/B the serve-mode live-progress hook plus a "
                         "JSONL event journal write per progress event "
                         "(obs/journal.py) against an uninstrumented "
                         "baseline run of the same workload "
                         "(target: < 2%% — the serve-mode overhead "
                         "budget)")
    args = ap.parse_args(argv)

    if args.dispatch_overhead:
        if args.tpupoa_batches <= 0:
            print("[synthbench] --dispatch-overhead needs device "
                  "consensus (-c > 0)", file=sys.stderr)
            return 2
        args.engine = "fused"

    if args.scale_curve:
        return run_scale_curve(args)

    from racon_tpu.core.polisher import create_polisher, PolisherType
    from racon_tpu.native import edit_distance

    rng = random.Random(args.seed)
    genome_len = args.genome_kb * 1000
    print(f"[synthbench] simulating {args.genome_kb} kb genome at "
          f"{args.coverage}x ...", file=sys.stderr)
    if args.fast_sim:
        truth, draft, reads, paf = simulate_fast(
            args.seed, genome_len, args.coverage, args.read_len,
            args.read_err, args.draft_err)
    else:
        truth, draft, reads, paf = simulate(rng, genome_len, args.coverage,
                                            args.read_len, args.read_err,
                                            args.draft_err)

    with tempfile.TemporaryDirectory() as d:
        reads_path = os.path.join(d, "reads.fasta.gz")
        with gzip.open(reads_path, "wb", compresslevel=1) as f:
            for name, read in reads:
                f.write(b">" + name.encode() + b"\n" + read + b"\n")
        paf_path = os.path.join(d, "ovl.paf.gz")
        with gzip.open(paf_path, "wb", compresslevel=1) as f:
            f.write(("\n".join(paf) + "\n").encode())
        draft_path = os.path.join(d, "draft.fasta.gz")
        with gzip.open(draft_path, "wb", compresslevel=1) as f:
            f.write(b">draft\n" + draft + b"\n")

        dispatch_ab = None
        fused_mode_label = None  # the mode the MEASURED run dispatched

        def run_polish(instrument=None):
            t0 = time.perf_counter()
            polisher = create_polisher(
                reads_path, paf_path, draft_path, PolisherType.kC,
                args.window_length, 10.0, 0.3, True, 5, -4, -8,
                num_threads=args.threads,
                tpu_poa_batches=args.tpupoa_batches,
                tpu_aligner_batches=args.tpualigner_batches,
                tpu_engine=args.engine,
                tpu_adaptive_buckets=args.adaptive_buckets or None)
            if instrument is not None:
                instrument(polisher)
            polisher.initialize()
            t1 = time.perf_counter()
            n_windows = len(polisher.windows)
            polished = polisher.polish()
            t2 = time.perf_counter()
            return polisher, polished, n_windows, t1 - t0, t2 - t1

        if args.trace:
            # overhead A/B on the SAME workload: a discarded warmup run
            # first, so one-time process-wide costs (XLA jit compiles,
            # compile telemetry, lazy imports) are paid before EITHER
            # measured run — a cold baseline vs warm traced comparison
            # would systematically understate the overhead — then the
            # untraced baseline, then the traced run (whose outputs the
            # identity metrics below use; all runs are deterministic)
            from racon_tpu.obs import trace as obs_trace

            run_polish()  # warmup, discarded
            _, _, _, _, base_polish_s = run_polish()
            # configure with NO path: polish()'s own end-of-run save is
            # then a no-op, so the timed region measures pure recording
            # overhead — serialization happens once, below, off-clock
            rec = obs_trace.configure(None)
            polisher, polished, n_windows, init_s, polish_s = run_polish()
            n_events = len(rec.events())
            rec.save(os.path.abspath(args.trace))
            obs_trace.reset()
            print(f"[synthbench] trace written to {args.trace}",
                  file=sys.stderr)
            overhead = ((polish_s - base_polish_s) / base_polish_s * 100
                        if base_polish_s > 0 else 0.0)
            print(f"[synthbench] trace overhead: {overhead:+.2f}% "
                  f"(baseline {base_polish_s:.2f}s, traced "
                  f"{polish_s:.2f}s, {n_events} events) "
                  f"[{'OK' if overhead < 2.0 else 'OVER'} 2% target]",
                  file=sys.stderr)
        elif args.flight:
            # same A/B discipline as --trace (warmup discarded, then
            # baseline, then recorded), but with the serve layer's
            # bounded FlightRecorder installed — the number that backs
            # the "always-on costs <2%" claim in README "Serving"
            from racon_tpu.obs import flight as obs_flight
            from racon_tpu.obs import trace as obs_trace

            run_polish()  # warmup, discarded
            _, _, _, _, base_polish_s = run_polish()
            rec = obs_trace.install(obs_flight.FlightRecorder())
            polisher, polished, n_windows, init_s, polish_s = run_polish()
            n_events = len(rec.events())
            obs_trace.reset()
            overhead = ((polish_s - base_polish_s) / base_polish_s * 100
                        if base_polish_s > 0 else 0.0)
            print(f"[synthbench] flight-recorder overhead: "
                  f"{overhead:+.2f}% (baseline {base_polish_s:.2f}s, "
                  f"recorded {polish_s:.2f}s, {n_events} ring events) "
                  f"[{'OK' if overhead < 2.0 else 'OVER'} 2% target]",
                  file=sys.stderr)
        elif args.progress_journal:
            # same A/B discipline as --trace / --flight, but with the
            # serve-mode progress hook armed AND every progress event
            # journaled — the number behind the "<2% for
            # progress+journal enabled" serve claim (README
            # "End-to-end tracing & progress")
            from racon_tpu.obs.journal import Journal

            run_polish()  # warmup, discarded
            _, _, _, _, base_polish_s = run_polish()
            journal = Journal(os.path.join(d, "journal.jsonl"))
            n_events = [0]

            def hook(ev, _j=journal, _n=n_events):
                _n[0] += 1
                _j.record("progress", job="synth", **ev)

            polisher, polished, n_windows, init_s, polish_s = run_polish(
                instrument=lambda p: setattr(p, "progress_hook", hook))
            journal.close()
            overhead = ((polish_s - base_polish_s) / base_polish_s * 100
                        if base_polish_s > 0 else 0.0)
            print(f"[synthbench] progress+journal overhead: "
                  f"{overhead:+.2f}% (baseline {base_polish_s:.2f}s, "
                  f"instrumented {polish_s:.2f}s, {n_events[0]} events "
                  f"journaled) "
                  f"[{'OK' if overhead < 2.0 else 'OVER'} 2% target]",
                  file=sys.stderr)
        elif args.dispatch_overhead:
            # A/B the two dispatch modes on the SAME workload (the
            # --trace discipline: a discarded warmup run per mode
            # absorbs that mode's compiles before its measured run).
            # Byte-identity across modes is asserted — the fused
            # program may move every perf number, never a byte.
            saved_mode = os.environ.get("RACON_TPU_FUSED")
            dispatch_ab = {}
            try:
                for mode, label in (("0", "split"), ("1", "fused")):
                    os.environ["RACON_TPU_FUSED"] = mode
                    run_polish()  # warmup, discarded
                    polisher, polished, n_windows, init_s, polish_s = \
                        run_polish()
                    ss = polisher.stage_stats
                    dispatch_ab[label] = {
                        "windows_per_s": round(n_windows / polish_s, 3)
                        if polish_s > 0 else 0.0,
                        "polish_s": round(polish_s, 3),
                        "device_s": round(ss["device_s"], 3),
                        "host_s": round(
                            max(0.0, polish_s - ss["device_s"]), 3),
                        "launches": ss["launches"],
                        "chunks": ss["chunks"],
                        "_fasta": [(s.name, s.data) for s in polished],
                    }
            finally:
                if saved_mode is None:
                    os.environ.pop("RACON_TPU_FUSED", None)
                else:
                    os.environ["RACON_TPU_FUSED"] = saved_mode
            fused_mode_label = "1"  # the headline run dispatched fused
            dispatch_ab["identical"] = (
                dispatch_ab["split"].pop("_fasta")
                == dispatch_ab["fused"].pop("_fasta"))
            sp, fu = dispatch_ab["split"], dispatch_ab["fused"]
            print(f"[synthbench] dispatch A/B: split "
                  f"{sp['windows_per_s']} w/s (host {sp['host_s']}s, "
                  f"{sp['launches']} launches) vs fused "
                  f"{fu['windows_per_s']} w/s (host {fu['host_s']}s, "
                  f"{fu['launches']} launches), FASTA "
                  f"{'identical' if dispatch_ab['identical'] else 'DIVERGED'}",
                  file=sys.stderr)
        else:
            polisher, polished, n_windows, init_s, polish_s = run_polish()
        # occupancy report: the per-bucket padding-waste metric the
        # adaptive scheduler moves (see README "Batch scheduling &
        # occupancy"); printed per bucket so a ladder change is
        # attributable, not just a single blended number
        for engine, e in polisher.occupancy_stats.items():
            if not e.get("buckets"):
                continue
            print(f"[synthbench] {engine} occupancy "
                  f"{e['occupancy_pct']:.1f}% (adaptive="
                  f"{'on' if polisher.scheduler.adaptive else 'off'})",
                  file=sys.stderr)
            for bucket, b in e["buckets"].items():
                plan = ""
                if "kernel" in b or "dtype" in b:
                    plan = (f", kernel {b.get('kernel', '?')}"
                            f"/{b.get('dtype', '?')}")
                print(f"[synthbench]   bucket {bucket}: {b['jobs']} jobs "
                      f"/ {b['batches']} batches, occupancy "
                      f"{b['occupancy_pct']:.1f}%{plan}", file=sys.stderr)

    if args.golden_out:
        with open(args.golden_out, "wb") as fh:
            for seq in polished:
                fh.write(b">" + seq.name.encode() + b"\n" + seq.data + b"\n")
        print(f"[synthbench] wrote golden {args.golden_out}", file=sys.stderr)

    # measured dispatch overhead: host_s = polish wall minus the
    # device-stage seconds (dispatch + result wait; clamped at 0 when
    # deep pipelining makes the stage sums exceed the wall) — the
    # number the fused single-launch program exists to shrink,
    # published in the artifact's `fused` block for perfgate
    fused_block = None
    if args.tpupoa_batches > 0:
        ss = polisher.stage_stats
        host_s = max(0.0, polish_s - ss["device_s"])
        fused_block = {
            "mode": (fused_mode_label
                     or os.environ.get("RACON_TPU_FUSED") or "auto"),
            "engine": args.engine or "session",
            "launches": ss["launches"],
            "chunks": ss["chunks"],
            "device_s": round(ss["device_s"], 3),
            "host_s": round(host_s, 3),
            "host_frac": round(host_s / polish_s, 4)
            if polish_s > 0 else 0.0,
        }
        print(f"[synthbench] dispatch: {fused_block['launches']} "
              f"launches / {fused_block['chunks']} chunks "
              f"(mode {fused_block['mode']}), host overhead "
              f"{fused_block['host_s']}s "
              f"({fused_block['host_frac'] * 100:.1f}% of polish wall)",
              file=sys.stderr)

    # throughput first: the identity metric below costs O(genome^2/64)
    # Myers time at multi-Mb scale, and the perf number must survive a
    # wall-cap hitting mid-metric
    print(f"[synthbench] init {init_s:.1f}s  polish {polish_s:.1f}s  "
          f"({n_windows} windows, {n_windows / polish_s:.1f} windows/s)",
          file=sys.stderr)
    d_draft = edit_distance(draft, truth)
    d_pol = edit_distance(polished[0].data, truth)
    print(f"[synthbench] draft error {d_draft / genome_len * 100:.2f}%  "
          f"polished error {d_pol / genome_len * 100:.2f}%  "
          f"(identity {100 - d_pol / genome_len * 100:.3f}%)",
          file=sys.stderr)
    if args.json:
        import json

        from racon_tpu.parallel.mesh import mesh_info

        artifact = {
            "mode": "synth",
            "synth": {
                "windows_per_s": round(n_windows / polish_s, 3)
                if polish_s > 0 else 0.0,
                "windows": n_windows,
                "init_s": round(init_s, 3),
                "polish_s": round(polish_s, 3),
                "identity_pct": round(100 - d_pol / genome_len * 100, 4),
                "genome_kb": args.genome_kb,
                "coverage": args.coverage,
                "seed": args.seed,
            },
            # per-bucket occupancy INCLUDING the dispatched kernel/dtype
            # choice — the autotuner's decision made visible per run
            "occupancy": polisher.occupancy_stats,
            # the mesh this number was measured on: perfgate refuses
            # cross-mesh comparisons (1-chip vs 8-chip windows/s is a
            # different machine, not a regression)
            "mesh": mesh_info(),
        }
        if fused_block is not None:
            # measured dispatch-loop numbers (host overhead fraction,
            # launch counts) — perfgate gates fused.host_frac whenever
            # this block is present
            artifact["fused"] = fused_block
        if dispatch_ab is not None:
            artifact["dispatch_overhead"] = dispatch_ab
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=1, sort_keys=True)
        print(f"[synthbench] wrote artifact {args.json}", file=sys.stderr)
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        print(f"[synthbench] peak host RSS {rss_kb / 1024:.0f} MiB",
              file=sys.stderr)
    except Exception:
        pass
    if dispatch_ab is not None and not dispatch_ab["identical"]:
        return 1  # the fused program moved a byte: that is a bug
    return 0


if __name__ == "__main__":
    sys.exit(main())
