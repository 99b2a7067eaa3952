"""Bring-up smoke of racon-tpu on the chip.

One process, which imports JAX once and owns the chip. It checks the
Pallas kernels against the XLA programs on the chip; runs a small
seeded job (100 kb) through the normal CLI entry (`racon_tpu.cli.main`)
with the session engine, byte-compares it with the host engine, and has
a warm in-process `PolishServer` answer three submits of it; runs it
with the fused engine; reruns both from the persistent compile cache;
then simulates the BASELINE.json north-star job (30x coverage, ~8 kb
reads at 12 % error, a draft at 10 % error, w=500) at the largest
genome length that fits the time budget (4.6 Mb down to 1 Mb) and
polishes it with both engines, checking identity against the simulated
truth. Every phase must pass; the last stdout line is then

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

With `--chips 4` it runs only the multi-chip phase: the session-engine
polish through a 4-chip BatchRunner and through a 1-chip runner, which
must agree byte for byte, with useful work on every chip.

    python chip_smoke.py [--chips 1|4] [--genome-mb MB] [--seed S]

Exits non-zero, printing no result line, when JAX finds no TPU or when
any phase fails. Diagnostics go to stderr; the per-phase readings go to
stdout, each labelled with the device kind.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
_T_START = time.perf_counter()

#: the BASELINE.json north star: E. coli-sized genome, 30x ONT
NORTH_STAR_MB = 4.6
#: the genome may be cut (never coverage, read length or error profile)
#: only as far as the chip tool's time limit forces, and no further
MIN_GENOME_MB = 1.0
COVERAGE, READ_LEN, READ_ERR, DRAFT_ERR = 30, 8000, 0.12, 0.10
#: the small job: server submits, host comparison, compile warm-up
SMALL_KB = 100
#: the one-chip run ends inside the driver's 1200 s, compile included
TIME_BUDGET_S = 1000.0
IDENTITY_MIN = 99.5
DEGRADATION_KEYS = ("faults", "retries", "timeouts", "breaker_trips",
                    "quarantined")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class Compiles:
    """Counts the XLA compiles of this process through jax.monitoring:
    every backend compile request, per program name, with its seconds,
    and how many of them the persistent compile cache answered."""

    def __init__(self):
        import jax

        self.n = 0
        self.s = 0.0
        self.hits = 0
        self.programs: dict[str, list] = {}

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.s += duration
                rec = self.programs.setdefault(kw.get("fun_name", "?"),
                                               [0, 0.0])
                rec[0] += 1
                rec[1] += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[int, float, int]:
        return self.n, self.s, self.hits

    def since(self, mark) -> tuple[int, float, int]:
        """(compile requests, their seconds, cache hits among them)."""
        return self.n - mark[0], self.s - mark[1], self.hits - mark[2]


def write_job(d: str, name: str, seed: int, genome_len: int):
    """Simulate one seeded job (tools/synthbench.simulate_fast) and
    write its FASTA/PAF triple under `d`. Returns (paths, truth)."""
    from tools.synthbench import simulate_fast

    truth, draft, reads, paf = simulate_fast(
        seed, genome_len, COVERAGE, READ_LEN, READ_ERR, DRAFT_ERR)
    paths = tuple(os.path.join(d, f"{name}.{ext}")
                  for ext in ("reads.fasta", "paf", "draft.fasta"))
    with open(paths[0], "wb") as f:
        f.writelines(b">" + n.encode() + b"\n" + r + b"\n"
                     for n, r in reads)
    with open(paths[1], "w") as f:
        f.write("\n".join(paf) + "\n")
    with open(paths[2], "wb") as f:
        f.write(b">draft\n" + draft + b"\n")
    return paths, truth


def run_cli(argv: list[str], env: dict | None = None):
    """`racon_tpu.cli.main(argv)` in this process. Returns (FASTA bytes,
    the polisher it built, wall seconds)."""
    from racon_tpu import cli
    from racon_tpu.core import polisher as polisher_mod

    built = []
    real = polisher_mod.create_polisher

    def capture(*a, **kw):
        p = real(*a, **kw)
        built.append(p)
        return p

    out = io.TextIOWrapper(io.BytesIO(), encoding="latin-1")
    saved = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    polisher_mod.create_polisher = capture
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            rc = cli.main(argv)
        out.flush()
    finally:
        polisher_mod.create_polisher = real
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    wall = time.perf_counter() - t0
    check(rc == 0, f"racon_tpu {' '.join(argv)} exited {rc}")
    return out.buffer.getvalue(), built[0], wall


def contig(fasta: bytes) -> bytes:
    lines = fasta.split(b"\n")
    check(len(lines) >= 2 and lines[0].startswith(b">"),
          "polished output is not one FASTA record")
    return b"".join(lines[1:])


def identity_pct(polished: bytes, truth: bytes, chunk: int = 20000) -> float:
    """Identity of `polished` against the simulated truth. A global
    edit distance costs O(genome^2) here, so both sequences are cut at
    unique 32-mer anchors about every `chunk` bases and the pieces'
    distances summed — an upper bound on the global distance, so the
    identity is a lower bound."""
    from racon_tpu.native import edit_distance

    ratio = len(polished) / max(1, len(truth))
    cuts = [(0, 0)]
    for b in range(chunk, len(truth) - chunk, chunk):
        for shift in range(0, 4000, 41):
            k = truth[b + shift:b + shift + 32]
            guess = int((b + shift) * ratio)
            lo, hi = max(cuts[-1][1] + 1, guess - 5000), guess + 5000
            pos = polished.find(k, lo, hi)
            if pos >= 0 and polished.find(k, pos + 1, hi) < 0:
                cuts.append((b + shift, pos))
                break
    cuts.append((len(truth), len(polished)))
    dist = sum(edit_distance(polished[p0:p1], truth[t0:t1])
               for (t0, p0), (t1, p1) in zip(cuts, cuts[1:]))
    return 100.0 * (1.0 - dist / len(truth))


def device_args(threads: int, engine: str | None = None) -> list[str]:
    args = ["-t", str(threads), "-c", "1", "--tpualigner-batches", "1",
            "--tpu-strict"]
    return args + (["--tpu-engine", engine] if engine else [])


def polish_report(kind: str, label: str, polisher, wall: float,
                  compiles) -> dict:
    """Print one engine run's readings and check the device did the
    work: no degradation, and no window the device envelope fits built
    on the host."""
    ss = polisher.stage_stats
    wc = polisher.window_counts
    n_win = sum(wc.values())
    rep = {"polish_s": wall, "windows": n_win,
           "windows_per_s": n_win / wall if wall > 0 else 0.0,
           "windows_device": wc.get("device", 0),
           "windows_host_envelope": wc.get("host_envelope", 0),
           "windows_host_other": wc.get("host", 0),
           "windows_backbone": wc.get("backbone", 0),
           "overlaps_device": polisher.n_aligner_device,
           "overlaps_host": polisher.n_aligner_host_fallback,
           "compiles_in_polish": compiles[0],
           "compile_s_in_polish": compiles[1],
           "cache_hits_in_polish": compiles[2]}
    rep.update({k: ss.get(k, 0) for k in DEGRADATION_KEYS})
    print(f"[{kind}] {label}: " + " ".join(f"{k}={v}" for k, v in rep.items()),
          flush=True)
    bad = {k: ss.get(k, 0) for k in DEGRADATION_KEYS if ss.get(k, 0)}
    check(not bad, f"{label}: degradation counters nonzero: {bad}")
    check(wc.get("host", 0) == 0,
          f"{label}: {wc.get('host')} windows inside the device envelope "
          "were polished on the host")
    check(wc.get("device", 0) > 0, f"{label}: no window was built on the "
          "device")
    return rep


def small_run(kind, label, engine, small, threads, compiles) -> bytes:
    """One engine's first run, on the small job: its cold compiles."""
    m = compiles.mark()
    out, _, wall = run_cli(device_args(threads, engine) + list(small))
    n, s, hits = compiles.since(m)
    print(f"[{kind}] {label} cold: small_job_s={wall} compiles={n} "
          f"compile_s={s} cache_hits={hits}", flush=True)
    return out


def big_run(kind, label, engine, big, truth, threads, compiles) -> None:
    """The timed polish of the big job (compiles inside counted), with
    identity against the simulated truth."""
    m = compiles.mark()
    out, pol, wall = run_cli(device_args(threads, engine) + list(big))
    polish_report(kind, label, pol, wall, compiles.since(m))
    ident = identity_pct(contig(out), truth)
    print(f"[{kind}] {label}: identity_pct={ident}", flush=True)
    check(ident >= IDENTITY_MIN, f"{label}: identity {ident:.4f}% is below "
          f"{IDENTITY_MIN}%")


def server_phase(kind, small, expected: bytes, threads: int, d: str) -> None:
    """Three submits of the small job to a warm in-process server on the
    session engine; each answer byte-identical to the one-shot CLI."""
    from racon_tpu.serve import PolishClient, PolishServer

    sock = os.path.join(d, "serve.sock")
    srv = PolishServer(socket_path=sock, workers=1, job_threads=threads,
                       tpu_poa_batches=1, tpu_aligner_batches=1,
                       tpu_engine="session").start()
    try:
        cl = PolishClient(socket_path=sock)
        for i in range(3):
            t0 = time.perf_counter()
            res = cl.submit(*small, strict=True)
            wall = time.perf_counter() - t0
            same = res.fasta == expected
            print(f"[{kind}] server submit {i + 1}: job_s={wall} "
                  f"identical_to_cli={same}", flush=True)
            check(same, f"server answer {i + 1} differs from the one-shot "
                  "CLI output")
    finally:
        srv.drain(timeout=60)


def pallas_phase(kind: str, d: str) -> None:
    """Both Pallas kernels (every dtype variant) against the XLA
    programs, on the chip, through the autotuner's identity veto."""
    from racon_tpu.ops.poa_graph import MAX_PRED
    from racon_tpu.sched.autotune import Autotuner

    at = Autotuner(os.path.join(d, "autotune.json"))
    for name, (ent, _) in (
            ("session (320, 256)",
             at.profile_session_bucket(320, 256, MAX_PRED, 3, -5, -4,
                                       rows=16)),
            ("aligner (1024, 128)", at.profile_aligner_bucket(1024, 128))):
        print(f"[{kind}] pallas {name}: identical={ent['identical']} "
              f"ms={ent['ms']}", flush=True)
        check(ent["identical"] and any(k.startswith("pallas")
                                       for k in ent["ms"]),
              f"pallas {name}: a kernel disagrees with the XLA program")


def warm_cache_phase(kind: str, small, threads: int, compiles) -> float:
    """Every program is in the persistent compile cache by now: drop the
    in-memory caches and rerun each engine's small job, so its programs
    load from the cache — the warm-cache compile seconds. Returns the
    two runs' wall seconds, the per-job cost the genome is sized from."""
    import jax

    total = 0.0
    for label, engine in (("session", None), ("fused", "fused")):
        jax.clear_caches()
        m = compiles.mark()
        _, _, wall = run_cli(device_args(threads, engine) + list(small))
        n, s, hits = compiles.since(m)
        print(f"[{kind}] {label} warm cache: small_job_s={wall} "
              f"compiles={n} compile_s={s} cache_hits={hits}", flush=True)
        total += wall
    return total


def genome_mb_for(kind: str, t_start: float, small_s: float) -> float:
    """The largest genome, from the north star down to MIN_GENOME_MB,
    whose two engine polishes fit what is left of TIME_BUDGET_S — sized
    from the small job's warm-cache cost, scaled linearly with margin."""
    left = TIME_BUDGET_S - (time.perf_counter() - t_start)
    per_mb = 1.5 * small_s / (SMALL_KB / 1000)
    mb = max(MIN_GENOME_MB, min(NORTH_STAR_MB, int(left / per_mb * 10) / 10))
    print(f"[{kind}] sizing: {left:.0f} s left of {TIME_BUDGET_S:.0f} s, "
          f"~{per_mb:.0f} s per Mb for both engines -> {mb} Mb",
          flush=True)
    return mb


def one_chip(args, kind: str, threads: int, compiles, d: str,
             t_start: float) -> None:
    small, _ = write_job(d, "small", args.seed + 1, SMALL_KB * 1000)
    pallas_phase(kind, d)
    session_small = small_run(kind, "session", None, small, threads,
                              compiles)
    host_small, _, host_s = run_cli(["-t", str(threads)] + list(small))
    same = host_small == session_small
    print(f"[{kind}] host vs session on {SMALL_KB} kb: identical={same} "
          f"host_s={host_s}", flush=True)
    check(same, "session engine output differs from the host engine")
    server_phase(kind, small, session_small, threads, d)
    small_run(kind, "fused", "fused", small, threads, compiles)
    small_s = warm_cache_phase(kind, small, threads, compiles)

    mb = args.genome_mb or genome_mb_for(kind, t_start, small_s)
    if mb < NORTH_STAR_MB:
        print(f"[{kind}] dataset: genome cut {NORTH_STAR_MB} Mb -> {mb} Mb "
              "(chip tool time limit); coverage, read length and error "
              "profile as published", flush=True)
    t0 = time.perf_counter()
    big, truth = write_job(d, "big", args.seed, int(mb * 1e6))
    print(f"[{kind}] dataset: genome_bp={int(mb * 1e6)} coverage={COVERAGE} "
          f"read_len={READ_LEN} read_err={READ_ERR} draft_err={DRAFT_ERR} "
          f"w=500 simulate_s={time.perf_counter() - t0}", flush=True)
    big_run(kind, "session", None, big, truth, threads, compiles)
    big_run(kind, "fused", "fused", big, truth, threads, compiles)


def four_chips(args, kind: str, threads: int, compiles, d: str) -> None:
    import jax

    genome = int((args.genome_mb or MIN_GENOME_MB) * 1e6)
    big, _ = write_job(d, "big", args.seed, genome)
    print(f"[{kind}] dataset: genome_bp={genome} coverage={COVERAGE}",
          flush=True)
    outs = {}
    for n in (4, 1):
        m = compiles.mark()
        out, pol, wall = run_cli(device_args(threads) + list(big),
                                 env={"RACON_TPU_MAX_DEVICES": str(n)})
        rep = polish_report(kind, f"session {n}-chip", pol, wall,
                            compiles.since(m))
        shards = pol.occupancy_stats.get("session", {}).get(
            "shard_useful", [])
        print(f"[{kind}] session {n}-chip: shard_useful={shards}",
              flush=True)
        if n == 4:
            check(len(shards) == 4 and min(shards) > 0,
                  f"4-chip run left a chip without useful work: {shards}")
        outs[n] = out
        del rep
    same = outs[4] == outs[1]
    print(f"[{kind}] 4-chip vs 1-chip FASTA identical={same} "
          f"devices={[str(x) for x in jax.devices()]}", flush=True)
    check(same, "4-chip output differs from the 1-chip output")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--genome-mb", type=float, default=None,
                    help="genome length (default: on one chip the largest "
                         f"that fits {TIME_BUDGET_S:.0f} s, from "
                         f"{NORTH_STAR_MB} down to {MIN_GENOME_MB}; on "
                         f"four {MIN_GENOME_MB})")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    if args.genome_mb is not None and args.genome_mb < MIN_GENOME_MB:
        log(f"refusing a genome below {MIN_GENOME_MB} Mb")
        return 2
    sys.path.insert(0, REPO)
    try:
        import racon_tpu
        import tools.synthbench  # noqa: F401
    except ImportError as exc:
        log(f"FAIL: the racon_tpu checkout is not next to this script "
            f"({exc})")
        return 1
    if not os.path.abspath(racon_tpu.__file__).startswith(REPO + os.sep):
        log(f"FAIL: racon_tpu comes from {racon_tpu.__file__}, not from "
            "the checkout next to this script")
        return 1
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"FAIL: JAX found no TPU (platform {platform!r})")
        return 1
    if len(devices) < args.chips:
        log(f"FAIL: {args.chips} chips asked, {len(devices)} found")
        return 1
    kind = devices[0].device_kind
    from racon_tpu.sched import enable_compile_cache

    print(f"[{kind}] compile cache: {enable_compile_cache()}", flush=True)
    compiles = Compiles()
    threads = os.cpu_count() or 1
    t0 = _T_START
    try:
        with tempfile.TemporaryDirectory(prefix=".smoke_", dir=REPO) as d:
            if args.chips == 1:
                one_chip(args, kind, threads, compiles, d, t0)
            else:
                four_chips(args, kind, threads, compiles, d)
    except SmokeFailure as exc:
        log(f"FAIL: {exc}")
        return 1
    peak = devices[0].memory_stats().get("peak_bytes_in_use")
    for name, (n, s) in sorted(compiles.programs.items(),
                               key=lambda kv: -kv[1][1]):
        print(f"[{kind}] compile {name}: requests={n} s={s}", flush=True)
    print(f"[{kind}] total_s={time.perf_counter() - t0} "
          f"compiles={compiles.n} compile_s={compiles.s} "
          f"cache_hits={compiles.hits} peak_hbm_bytes={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
