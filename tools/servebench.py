"""Serve-mode benchmark: warm server submits vs cold one-shot CLI runs.

Starts a `PolishServer` (warmed on the benchmark's own inputs, so job
shapes hit the warm jit caches exactly), submits N concurrent synthetic
jobs through `PolishClient`, and compares against N sequential COLD CLI
runs — fresh `python -m racon_tpu.cli` subprocesses, each paying
interpreter + import + engine construction + compile, which is precisely
the per-run tax the serve subsystem amortizes.

Two warm phases measure two different claims:

  - SEQUENTIAL warm submits (one at a time — the like-for-like twin of
    the sequential cold runs, same machine utilization): their p50 is
    the headline warm latency and must beat the cold p50;
  - a CONCURRENT wave of N submits: cross-job batch rounds, queue-wait
    vs execution breakdown, and batch occupancy — the multiplexing
    story (concurrent p50 embeds queueing on an oversubscribed host, so
    it is reported, not gated).

Exit status is the acceptance check: 0 only when sequential warm p50
beats cold p50, no warm job compiled anything (sched compile telemetry:
the warm path recompiles NOTHING), every warm job's FASTA equals the
cold CLI bytes, every wave job saw at least one live progress frame AND
one streamed `result_part` frame before its result (time-to-first-
progress and time-to-first-BYTE are reported as their own columns), and
the serve event journal — enabled for the measured run — passes its
consistency check (every job exactly one terminal state,
started/terminal pairs balanced). `--json PATH` writes the summary as a
bench-style artifact with `occupancy` / `metrics` / `slo` / `journal`
fields alongside the serve numbers (the same field names bench.py
publishes; tools/perfgate.py gates warm p50, p99, ttfb_p50 and
slo.miss_rate from it).

FLEET MODE (`--fleet N`): run N in-process server replicas, round-robin
the warm wave across them, and let the fleet aggregator (obs/fleet.py)
poll every replica's scrape+healthz MID-WAVE. The artifact gains a
`fleet` block — aggregator lag (poll wall) percentiles and the
scrape-overhead percentage — which tools/perfgate.py gates at the
established <2% observability budget.

ROUTER MODE (`--router N`): start N warm replicas behind the
shard-aware router (racon_tpu/serve/router.py) and sweep the same
concurrent wave through it at 1, 2, 4 ... replicas (capped at N). The
artifact becomes a `router` block — jobs/s per replica count, requeue
count (zero on a healthy fleet, any requeue fails the bench), the
router's merge overhead (job wall minus slowest-shard exec) and
byte-identity vs a direct single-replica submit — plus `scaling_x`
(jobs/s at N over jobs/s at 1), which tools/perfgate.py gates via
`router.identical` and `--router-scaling-min`. The block also
carries the routed time-to-first-part (`ttfb_s`) and, at the top
count, a `trace` block A/Bing the same job traced vs untraced —
`trace.overhead_pct`, gated by perfgate's `--trace-overhead-max`
at the same <2% budget as every other observability tax.
Sequential single-job
submits per count additionally measure `range_scaling_x` — how much
faster ONE job finishes when the router window-range-shards its
contig across the fleet (a `--contigs 1` workload makes every
multi-replica point range-shard) — gated via `--range-scaling-min`.

RAMP MODE (`--ramp N`): elastic autoscaling under a ramped open-loop
load. One warm replica behind the router, the autoscaler armed with
ceiling N, Poisson arrivals climbing from well inside one replica's
capacity to far outside it, then a slow trickle while the idle fleet
drains back to the floor. The artifact gains an `autoscale` block
(replicas over time, scale up/down counts, `gold_p99_flat` = ramp
p99 over idle p99, `jobs_lost`) which tools/perfgate.py gates via
`autoscale.jobs_lost` == 0 and `autoscale.gold_p99_flat`
(default-when-present; `--ramp-p99-flat-max` makes it mandatory).

AUDIT MODE (`--audit-rate R`): arm the identity-audit sentinel
(racon_tpu/obs/audit.py) on every replica, keep it armed through the
measured warm phases, and A/B the same sequential workload with the
sentinel muted on the same warm server — the wall delta is the real
audit cost. The artifact gains an `audit` block (sampled fraction,
shadow device seconds, mismatch/demotion counts, overhead_pct) which
tools/perfgate.py gates at the <2% observability budget and at ZERO
mismatches (a mismatch on a clean bench workload is a corruption bug,
and also fails the bench directly).

ROUNDS MODE (`--rounds N`): serve-native iterative polishing with the
content-addressed window cache. One warm cache-OFF server runs a
`rounds=N` job (the no-cache per-round walls and the byte-identity
reference), then one warm cache-ON server (serve/wincache.py armed,
optionally with the audit sentinel riding at `--audit-rate`) runs the
SAME job twice — the first submit measures convergence hits (later
rounds re-polish windows whose content already stabilized, so they
skip device dispatch), the second measures the identical-resubmit
ceiling (everything hits). The artifact gains `rounds` (per-round
walls cache-on vs cache-off, `round2_speedup_x` = mean no-cache
round-2+ wall over mean cached round-2+ wall) and `cache`
(`identical` byte-equality cache-on vs cache-off, hit rates, the
cache snapshot) blocks; tools/perfgate.py gates `cache.identical`
whenever the block is present and `rounds.round2_speedup_x` via
`--round2-speedup-min`.

FLOOD MODE (`--flood N`): preemptive-QoS isolation. N free-tenant
submitter threads flood a 2-replica routed fabric in a closed loop
while gold-priority waves measure p99 three ways — idle fabric, flood
with preemption off, flood with preemption on — then a doomed-abort
phase arms the speculative deadline-abort and submits unmeetable
deadlines that must be rejected typed at admission. The artifact gains
a `qos` block (`gold_p99_flat` = gold p99 under flood-with-preemption
over idle, `doomed_abort_saved_s` = EMA-predicted device seconds the
aborts saved) which tools/perfgate.py gates via `qos.gold_p99_flat`
(default-when-present) and `--doomed-abort-min` (mandatory once
requested).

OPEN-LOOP ARRIVAL MODE (`--qps`, optionally a `--qps-curve` sweep):
instead of firing the whole wave at once (closed-loop, back-pressure
hides the queueing), jobs arrive by a Poisson process at the target
rate and the bench reports p50/p95/p99 end-to-end latency,
time-to-first-byte (the first streamed `result_part`), achieved vs
offered throughput per rate, and the SATURATION KNEE — the highest
swept rate the server still absorbs (achieved >= 90% of offered). The
curve rides the `--json` artifact under `openloop` so perfgate can gate
the latency tail round over round. `--baseline PATH` embeds a prior
measurement (e.g. the round-barrier design's curve) and prints the
comparison.

    python tools/servebench.py --jobs 4 [--genome-kb 20] [--json out.json]
    python tools/servebench.py --qps 2 --qps-jobs 8 --qps-curve 0.5,1,2,4
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from racon_tpu.sched import default_cache_dir  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = default_cache_dir()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def build_dataset(tmpdir: str, genome_kb: int, coverage: int,
                  read_len: int, seed: int, contigs: int = 1):
    """Synthetic ONT-style workload via synthbench's simulator (same
    error model as the scale bench, so serve numbers are comparable).
    `contigs` > 1 splits the genome budget across independent contigs
    — the shape that exercises per-contig result streaming: the first
    contig's bytes hit the wire while later contigs still polish."""
    import random

    all_reads, all_paf, drafts = [], [], []
    per_contig = max(1, genome_kb // max(1, contigs))
    for c in range(max(1, contigs)):
        rng = random.Random(seed + 1000 * c)
        _, draft, reads, paf = simulate_contig(
            rng, per_contig * 1000, coverage, read_len)
        tag = f"c{c}_" if contigs > 1 else ""
        cname = f"draft{c}" if contigs > 1 else "draft"
        drafts.append((cname, draft))
        for name, read in reads:
            all_reads.append((tag + name, read))
        for line in paf:
            fields = line.split("\t")
            fields[0] = tag + fields[0]
            fields[5] = cname
            all_paf.append("\t".join(fields))
    paths = (os.path.join(tmpdir, "reads.fasta.gz"),
             os.path.join(tmpdir, "ovl.paf.gz"),
             os.path.join(tmpdir, "draft.fasta.gz"))
    with gzip.open(paths[0], "wb", compresslevel=1) as f:
        for name, read in all_reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    with gzip.open(paths[1], "wb", compresslevel=1) as f:
        f.write(("\n".join(all_paf) + "\n").encode())
    with gzip.open(paths[2], "wb", compresslevel=1) as f:
        for cname, draft in drafts:
            f.write(b">" + cname.encode() + b"\n" + draft + b"\n")
    return paths


def simulate_contig(rng, genome_len, coverage, read_len):
    from synthbench import simulate

    return simulate(rng, genome_len, coverage, read_len, 0.12, 0.10)


def merge_fleet_snaps(snaps: list[dict]) -> dict:
    """Aggregate N replicas' stats snapshots into one artifact view:
    queue/SLO counters SUM (the gated slo.miss_rate must see every
    replica's deadlines, not replica 0's), batcher activity counters
    sum, high-water marks take the max, and lane rows concatenate
    tagged with their replica. Non-additive detail (occupancy,
    latency percentiles, tenants) stays replica 0's."""
    if len(snaps) == 1:
        return snaps[0]
    out = json.loads(json.dumps(snaps[0]))  # deep copy, JSON-shaped
    q, b, slo = out["queue"], out["batcher"], out["slo"]
    q_sum = ("submitted", "admitted", "rejected_full",
             "rejected_draining", "rejected_quota", "expired",
             "completed", "failed", "deadline_hit", "deadline_miss",
             "depth")
    b_sum = ("iterations", "shared_iterations", "solo_iterations",
             "jobs", "windows", "host_s", "compiles", "compile_s")
    for i, lane in enumerate(b.get("lanes") or []):
        lane["replica"] = 0
    for r, s in enumerate(snaps[1:], start=1):
        for k in q_sum:
            if k in s["queue"]:
                q[k] = q.get(k, 0) + s["queue"][k]
        for k in ("deadline_hit", "deadline_miss", "expired"):
            slo[k] += s["slo"][k]
        sb = s["batcher"]
        for k in b_sum:
            if k in sb:
                b[k] = b.get(k, 0) + sb[k]
        for k, v in sb.items():
            if k.startswith("max_"):
                b[k] = max(b.get(k, 0), v)
        b["lanes"] = (b.get("lanes") or []) + [
            dict(lane, replica=r) for lane in (sb.get("lanes") or [])]
        out["inflight"] += s.get("inflight", 0)
    deadlined = slo["deadline_hit"] + slo["deadline_miss"]
    slo["miss_rate"] = (round(slo["deadline_miss"] / deadlined, 4)
                        if deadlined else 0.0)
    return out


def _mesh_block(batcher_snap: dict) -> dict:
    """The shared mesh-block schema (parallel/mesh.py), with the serve
    batcher's actual lane count riding in."""
    from racon_tpu.parallel.mesh import mesh_info

    return mesh_info(
        worker_lanes=batcher_snap.get("worker_lanes", 1))


def spawn_replica(sock: str, args):
    """One REAL `racon_tpu serve` replica subprocess. The fleet benches
    (--router / --ramp) spawn replicas as processes, not in-process
    threads: N PolishServers in one interpreter share a single GIL, so
    thread-replicas can only ever measure overhead, never scaling."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [q for q in env.get("PYTHONPATH", "").split(os.pathsep)
                  if q])
    if getattr(args, "device_latency_ms", 0):
        # the device-dominated posture: every replica pipeline stalls a
        # simulated accelerator round-trip per chunk (off-CPU, so waits
        # overlap across replica processes even on a small host)
        env["RACON_TPU_DEVICE_LATENCY_S"] = str(
            args.device_latency_ms / 1000.0)
    if getattr(args, "device_latency_x", 0):
        env["RACON_TPU_DEVICE_LATENCY_X"] = str(args.device_latency_x)
    if getattr(args, "host_poa_chunk", 0):
        # smaller chunks -> per-chunk latency paces proportionally to a
        # job's window count (a range shard carries fewer windows, so
        # it pays proportionally less simulated device time)
        env["RACON_TPU_HOST_POA_CHUNK"] = str(args.host_poa_chunk)
    return subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve",
         "--socket", sock, "--workers", str(args.workers),
         "--no-warmup", "-t", str(args.threads),
         "-c", str(args.tpupoa_batches),
         "--tpualigner-batches", str(args.tpualigner_batches)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def wait_replica(PolishClient, sock: str,
                 deadline_s: float = 120.0) -> None:
    probe = PolishClient(socket_path=sock, timeout=10)
    deadline = time.perf_counter() + deadline_s
    while time.perf_counter() < deadline:
        try:
            probe.request({"type": "ping"})
            return
        except Exception:  # noqa: BLE001 — still starting
            time.sleep(0.2)
    raise RuntimeError(f"replica {sock} never came up")


def stop_replica(proc) -> None:
    try:
        proc.terminate()
    except Exception:  # noqa: BLE001 — already gone
        pass
    try:
        proc.wait(timeout=30)
    except Exception:  # noqa: BLE001 — escalate
        try:
            proc.kill()
            proc.wait(timeout=5)
        except Exception:  # noqa: BLE001 — nothing left to do
            pass


def cold_cli_run(paths, args) -> tuple[float, bytes]:
    """One fresh-process CLI run: the full cold tax, wall-clocked."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    cmd = [sys.executable, "-m", "racon_tpu.cli",
           "-t", str(args.threads)]
    if args.tpupoa_batches:
        cmd += ["-c", str(args.tpupoa_batches)]
    if args.tpualigner_batches:
        cmd += ["--tpualigner-batches", str(args.tpualigner_batches)]
    cmd += list(paths)
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, env=env, capture_output=True)
    dt = time.perf_counter() - t0
    if proc.returncode != 0:
        print(proc.stderr.decode(errors="replace"), file=sys.stderr)
        raise SystemExit(f"[servebench] cold CLI run failed "
                         f"(rc {proc.returncode})")
    return dt, proc.stdout


def check_slo(args, PolishClient, PolishServer) -> int:
    """`--check-slo`: one warm server, one concurrent wave with per-job
    deadlines, three gated cells printed as a faultcheck-style row —
    p99 end-to-end latency, deadline-miss rate (from the server's OWN
    SLO accounting, the same numbers admission control uses), and a
    live `scrape` that must return Prometheus text with populated
    latency histograms. Exit 0 only when every cell passes."""
    with tempfile.TemporaryDirectory(prefix="racon_slo_") as tmp:
        print(f"[servebench] SLO gate: {args.jobs} jobs, deadline "
              f"{args.deadline:.0f}s, p99<= {args.slo_p99:.1f}s, "
              f"miss-rate<= {args.slo_miss_rate:.2f}", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)
        sock = os.path.join(tmp, "serve.sock")
        server = PolishServer(
            socket_path=sock, workers=args.workers, warmup=False,
            job_threads=args.threads,
            flight_dir=os.path.join(tmp, "flight"),
            tpu_poa_batches=args.tpupoa_batches,
            tpu_aligner_batches=args.tpualigner_batches)
        server.warmup(paths=paths)
        server.start()
        client = PolishClient(socket_path=sock)

        latencies = [None] * args.jobs

        def submit(i):
            t0 = time.perf_counter()
            try:
                client.submit(*paths, deadline_s=args.deadline,
                              retries=5)
            except Exception as exc:
                print(f"[servebench] job {i} failed: {exc}",
                      file=sys.stderr)
                return
            latencies[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(args.jobs)]
        for t in threads:
            t.start()
        # scrape mid-wave: the live-exposition contract is part of the
        # gate (must answer while jobs are executing)
        live = client.scrape()
        for t in threads:
            t.join()
        snap = client.stats()
        server.drain(timeout=30)

    from racon_tpu.serve.queue import nearest_rank

    cells = []
    done = sorted(v for v in latencies if v is not None)
    if len(done) < args.jobs:
        cells.append(("completed", False,
                      f"{len(done)}/{args.jobs} jobs"))
    p99 = nearest_rank(done, 0.99) if done else float("inf")
    cells.append(("p99", p99 <= args.slo_p99,
                  f"{p99:.2f}s <= {args.slo_p99:.1f}s"))
    slo = snap.get("slo") or {}
    miss_rate = float(slo.get("miss_rate", 1.0))
    cells.append(("miss-rate", miss_rate <= args.slo_miss_rate,
                  f"{miss_rate:.2f} <= {args.slo_miss_rate:.2f} "
                  f"({slo.get('deadline_miss', '?')} missed, "
                  f"{slo.get('expired', '?')} expired)"))
    hist_lines = [ln for ln in live.splitlines()
                  if "_bucket{" in ln]
    populated = any(not ln.rstrip().endswith(" 0")
                    for ln in hist_lines)
    cells.append(("scrape", bool(hist_lines) and populated,
                  f"{len(live.splitlines())} lines, "
                  f"{len(hist_lines)} buckets, "
                  f"{'populated' if populated else 'EMPTY'}"))
    row = "  ".join(f"{name} {'pass' if ok else 'FAIL'} ({detail})"
                    for name, ok, detail in cells)
    failures = sum(not ok for _, ok, _ in cells)
    print(f"[servebench] slo  {row}", file=sys.stderr)
    print(f"[servebench] SLO gate "
          f"{'PASS' if not failures else 'FAIL'}: "
          f"{len(cells) - failures}/{len(cells)} cells green",
          file=sys.stderr)
    return 1 if failures else 0


def run_router_bench(args, PolishClient, PolishServer) -> int:
    """`--router N`: job throughput through the shard-aware router
    (racon_tpu/serve/router.py) vs replica count. Starts N warm
    replica SUBPROCESSES once (real processes — in-process
    thread-replicas share one GIL and cannot scale), then for each
    swept count c (1, 2, 4 ...
    capped at N; N always included) fronts the first c replicas with a
    PolishRouter and fires the same concurrent wave through it.
    Reports jobs/s per count, the requeue count (zero on a healthy
    fleet — any requeue here is a real replica loss and fails the
    bench), the router's merge overhead (job wall minus the slowest
    shard's exec seconds: the fan-out + merge + ledger tax) and
    byte-identity vs a direct single-replica submit. Each swept count
    also times SEQUENTIAL single-job submits: with a single-contig
    workload (`--contigs 1`) the router splits the one contig by
    window range across every routable replica, so the per-job wall
    drops as replicas join — `range_scaling_x` (single-job wall at 1
    replica over the wall at N) is that claim, reported whenever the
    top point actually range-sharded. `--json` rides the curve out as
    a `router` artifact block with `scaling_x` (jobs/s at N replicas
    over jobs/s at 1) which tools/perfgate.py gates via
    `router.identical` (always, when the block is present),
    `--router-scaling-min` and `--range-scaling-min` (each mandatory
    once requested). The sequential submits also stream parts, so the
    block carries the routed `ttfb_s` (submit start to the first
    part-routed frame — the router twin of the direct-submit ttfb),
    and the top count A/Bs the same job with the distributed-trace
    plane armed (submit_traced: client + router spans, per-replica
    trace_pull, clock-chained merge) vs untraced into a `trace`
    artifact block whose `overhead_pct` perfgate holds to its <=2%
    budget (`--trace-overhead-max`)."""
    from racon_tpu.serve.queue import nearest_rank
    from racon_tpu.serve.router import PolishRouter

    n_max = max(1, args.router)
    counts = sorted({c for c in (1, 2, 4) if c < n_max} | {n_max})
    fail: list[str] = []
    curve: list[dict] = []
    with tempfile.TemporaryDirectory(prefix="racon_routerbench_") as tmp:
        print(f"[servebench] router bench: {n_max} replica(s), sweep "
              f"{counts}, {args.jobs} jobs per wave", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)
        procs, socks = [], []
        try:
            t0 = time.perf_counter()
            for k in range(n_max):
                sock = os.path.join(tmp, f"rep{k}.sock")
                procs.append(spawn_replica(sock, args))
                socks.append(sock)
            for sock in socks:
                wait_replica(PolishClient, sock)
                # one direct job warms this replica's engines on the
                # bench's own shapes before anything is timed
                PolishClient(socket_path=sock).submit(*paths)
            print(f"[servebench] {n_max} replica subprocess(es) warm "
                  f"in {time.perf_counter() - t0:.2f}s",
                  file=sys.stderr)
            # the identity reference: one direct submit to a single
            # replica — every routed job must reproduce these bytes
            solo = PolishClient(socket_path=socks[0]).submit(*paths)

            for c in counts:
                router = PolishRouter(
                    replicas=socks[:c],
                    socket_path=os.path.join(tmp, f"router{c}.sock"),
                    journal=os.path.join(tmp, f"router{c}.jsonl"))
                router.start()
                results: list = [None] * args.jobs

                def submit(i):
                    try:
                        cl = PolishClient(
                            socket_path=router.config.socket_path)
                        results[i] = cl.submit(*paths, retries=5)
                    except Exception as exc:
                        print(f"[servebench] router job {i} "
                              f"({c} replicas) failed: {exc}",
                              file=sys.stderr)

                threads = [threading.Thread(target=submit, args=(i,))
                           for i in range(args.jobs)]
                t_wave = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t_wave
                # sequential single-JOB latency: the number window-range
                # sharding moves. The wave above measures fleet
                # THROUGHPUT (more replicas, more concurrent jobs);
                # these submits measure how much faster ONE job
                # finishes when the router can split a contig by
                # window range across every routable replica
                seq_cl = PolishClient(
                    socket_path=router.config.socket_path)
                seq_walls: list[float] = []
                ttfbs: list[float] = []
                r_seq = None
                for _ in range(3):
                    t_seq = time.perf_counter()
                    marks: list[float] = []
                    r_seq = seq_cl.submit(
                        *paths, retries=5,
                        on_part=lambda f: marks.append(
                            time.perf_counter()))
                    seq_walls.append(time.perf_counter() - t_seq)
                    # routed time-to-first-part: submit start to the
                    # first result_part frame the router forwarded —
                    # the router twin of the direct-submit ttfb the
                    # latency sweep reports
                    if marks:
                        ttfbs.append(marks[0] - t_seq)
                    if r_seq.fasta != solo.fasta:
                        fail.append(f"router x{c}: sequential job "
                                    "FASTA diverged from the direct "
                                    "single-replica bytes")
                # trace overhead A/B at the top count: the same
                # sequential job with the full distributed-trace
                # plane armed (client spans + router spans + replica
                # trace_pull + merge) vs the untraced walls above —
                # min-of-3 on both sides, the steady-state number
                # perfgate gates as trace.overhead_pct
                trace_pt = None
                if c == n_max:
                    tr_walls: list[float] = []
                    for _ in range(3):
                        t_tr = time.perf_counter()
                        r_tr, _doc = seq_cl.submit_traced(*paths,
                                                          retries=5)
                        tr_walls.append(time.perf_counter() - t_tr)
                        if r_tr.fasta != solo.fasta:
                            fail.append(
                                f"router x{c}: traced job FASTA "
                                "diverged from the direct "
                                "single-replica bytes")
                    base_w = min(seq_walls) if seq_walls else 0.0
                    traced_w = min(tr_walls)
                    trace_pt = {
                        "untraced_wall_s": round(base_w, 3),
                        "traced_wall_s": round(traced_w, 3),
                        "overhead_pct": round(
                            (traced_w - base_w)
                            / max(base_w, 1e-9) * 100.0, 2)}
                requeues = router.counters["requeues"]
                router.drain(timeout=30)
                done = [r for r in results if r is not None]
                identical = bool(done) and all(r.fasta == solo.fasta
                                               for r in done)
                # merge overhead: what the router ADDED on top of the
                # slowest shard — fan-out, part forwarding, contig-order
                # merge and the journal ledger
                ov = [(r.router["wall_s"] - r.router["shard_exec_max_s"])
                      / max(r.router["wall_s"], 1e-9) * 100.0
                      for r in done
                      if r.router.get("wall_s")]
                shards = [r.router.get("shards", 1) for r in done]
                rb = r_seq.router if r_seq is not None else {}
                pt = {"replicas": c, "jobs": args.jobs,
                      "completed": len(done),
                      "wall_s": round(wall, 3),
                      "jobs_per_s": round(len(done) / max(wall, 1e-9),
                                          3),
                      "shards_mean": round(statistics.mean(shards), 2)
                      if shards else 0,
                      "job_wall_s": round(min(seq_walls), 3)
                      if seq_walls else None,
                      "ttfb_s": round(min(ttfbs), 3)
                      if ttfbs else None,
                      "range": bool(rb.get("range")),
                      "range_shards": rb.get("range_shards"),
                      "requeues": requeues,
                      "merge_overhead_pct": round(
                          nearest_rank(sorted(ov), 0.50), 2)
                      if ov else None,
                      "identical": identical}
                curve.append(pt)
                print(f"[servebench] router x{c}: "
                      f"{pt['completed']}/{args.jobs} jobs in "
                      f"{wall:.2f}s ({pt['jobs_per_s']:.3f} jobs/s, "
                      f"{pt['shards_mean']:.1f} shards/job, "
                      f"merge overhead "
                      f"{pt['merge_overhead_pct'] or 0:.2f}%, "
                      f"{requeues} requeues), single job "
                      f"{pt['job_wall_s']:.2f}s"
                      + (f" range-sharded x{pt['range_shards']}"
                         if pt["range"] else "")
                      + f" [{'OK' if identical else 'FAIL'} identity]",
                      file=sys.stderr)
                if len(done) < args.jobs:
                    fail.append(f"router x{c}: only {len(done)}/"
                                f"{args.jobs} jobs completed")
                if not identical:
                    fail.append(f"router x{c}: routed FASTA diverged "
                                "from the direct single-replica bytes")
                if requeues:
                    fail.append(f"router x{c}: {requeues} requeues on "
                                "a healthy fleet (a replica dropped "
                                "mid-shard)")
        finally:
            for proc in procs:
                stop_replica(proc)

    scaling_x = (curve[-1]["jobs_per_s"]
                 / max(curve[0]["jobs_per_s"], 1e-9)) if curve else 0.0
    router_block = {
        "replicas_max": n_max,
        "jobs": args.jobs,
        "curve": curve,
        "jobs_per_s": curve[-1]["jobs_per_s"] if curve else 0.0,
        "job_wall_s": curve[-1]["job_wall_s"] if curve else None,
        "ttfb_s": curve[-1]["ttfb_s"] if curve else None,
        "range": bool(curve) and bool(curve[-1].get("range")),
        "requeues": sum(pt["requeues"] for pt in curve),
        "merge_overhead_pct": max(
            (pt["merge_overhead_pct"] for pt in curve
             if pt["merge_overhead_pct"] is not None), default=None),
        "identical": bool(curve) and all(pt["identical"]
                                         for pt in curve),
        "scaling_x": round(scaling_x, 3),
        "device_latency_ms": args.device_latency_ms,
        "device_latency_x": args.device_latency_x,
        "host_poa_chunk": args.host_poa_chunk,
    }
    print(f"[servebench] router scaling: x{scaling_x:.2f} jobs/s at "
          f"{n_max} replica(s) vs 1 "
          f"({router_block['requeues']} requeues total)",
          file=sys.stderr)
    # single-JOB scaling, reported only when the highest-count point
    # actually range-sharded (a multi-contig workload at few replicas
    # splits whole contigs instead — no sub-contig claim to make there)
    if router_block["range"] and curve[0].get("job_wall_s"):
        router_block["range_shards"] = curve[-1].get("range_shards")
        router_block["range_scaling_x"] = round(
            curve[0]["job_wall_s"]
            / max(curve[-1]["job_wall_s"], 1e-9), 3)
        print(f"[servebench] range scaling: one job "
              f"x{router_block['range_scaling_x']:.2f} faster at "
              f"{n_max} replica(s) vs 1 "
              f"({curve[0]['job_wall_s']:.2f}s -> "
              f"{curve[-1]['job_wall_s']:.2f}s, "
              f"{router_block['range_shards']} window-range shards — "
              "perfgate gates router.range_scaling_x)",
              file=sys.stderr)
    if args.json:
        artifact = {"mode": "router", "jobs": args.jobs,
                    "router": router_block, "pass": not fail}
        if trace_pt is not None:
            artifact["trace"] = trace_pt
            print(f"[servebench] trace overhead: "
                  f"{trace_pt['overhead_pct']:+.2f}% "
                  f"({trace_pt['untraced_wall_s']:.2f}s untraced -> "
                  f"{trace_pt['traced_wall_s']:.2f}s traced — "
                  "perfgate gates trace.overhead_pct)",
                  file=sys.stderr)
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)
    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


def run_rounds_bench(args, PolishClient, PolishServer) -> int:
    """`--rounds N`: iterative serve-native polishing with and without
    the content-addressed window cache. Three submits, two warm
    servers:

      1. cache OFF, `rounds=N`  -> byte-identity reference + the
         no-cache per-round walls;
      2. cache ON,  `rounds=N`  -> convergence hits: rounds whose
         windows stopped changing skip device dispatch;
      3. cache ON,  `rounds=N` again -> the identical-resubmit
         ceiling (every window hits, zero device iterations).

    Gates (exit status): all three FASTAs byte-identical, every submit
    completed all N rounds, the cached run saw a NONZERO hit rate, and
    — when `--audit-rate` armed the sentinel on the cached server —
    zero audit mismatches. The `--json` artifact carries `rounds` /
    `cache` blocks for tools/perfgate.py (`cache.identical`,
    `rounds.round2_speedup_x` via `--round2-speedup-min`)."""
    n = max(1, args.rounds)
    fail: list[str] = []
    with tempfile.TemporaryDirectory(prefix="racon_roundsbench_") as tmp:
        print(f"[servebench] rounds bench: {n} rounds, cache off vs "
              f"on (+ resubmit)", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)
        base_kw = dict(workers=args.workers, warmup=False,
                       job_threads=args.threads,
                       tpu_poa_batches=args.tpupoa_batches,
                       tpu_aligner_batches=args.tpualigner_batches)

        off = PolishServer(socket_path=os.path.join(tmp, "off.sock"),
                           **base_kw)
        off.warmup(paths=paths)
        off.start()
        try:
            r_off = PolishClient(
                socket_path=off.config.socket_path).submit(
                *paths, rounds=n)
        finally:
            off.drain(timeout=30)

        on_kw = dict(base_kw, wincache=True)
        if args.audit_rate is not None:
            on_kw["audit_rate"] = args.audit_rate
        on = PolishServer(socket_path=os.path.join(tmp, "on.sock"),
                          **on_kw)
        on.warmup(paths=paths)
        on.start()
        try:
            client = PolishClient(socket_path=on.config.socket_path)
            r_on = client.submit(*paths, rounds=n)
            r_on2 = client.submit(*paths, rounds=n)
            cache_snap = on.batcher.wincache.snapshot()
            audit_snap = (on.auditor.snapshot()
                          if on.auditor is not None else None)
        finally:
            on.drain(timeout=30)

    identical = (r_on.fasta == r_off.fasta
                 and r_on2.fasta == r_off.fasta)
    if not identical:
        fail.append("cached rounds FASTA diverged from the cache-off "
                    "bytes")
    for tag, r in (("off", r_off), ("on", r_on), ("resubmit", r_on2)):
        if r.rounds.get("completed") != n:
            fail.append(f"{tag} submit completed "
                        f"{r.rounds.get('completed')}/{n} rounds")

    def _walls(res):
        return [p["wall_s"] for p in res.rounds.get("per_round", [])]

    def _rate(res):
        c = res.rounds.get("cache") or {}
        total = c.get("hits", 0) + c.get("misses", 0)
        return round(c.get("hits", 0) / total, 4) if total else 0.0

    off_w, on_w, on2_w = _walls(r_off), _walls(r_on), _walls(r_on2)
    # round-2+ speedup: round 1 always pays full dispatch (and, warmed
    # on the bench's own shapes, may hit warmup-populated entries) —
    # the cache's claim is about LATER rounds, where converged windows
    # repeat verbatim
    off_r2 = statistics.mean(off_w[1:]) if len(off_w) > 1 else None
    on_r2 = statistics.mean(on_w[1:]) if len(on_w) > 1 else None
    speedup = (round(off_r2 / max(on_r2, 1e-9), 3)
               if off_r2 is not None and on_r2 is not None else None)
    resub_x = (round(statistics.mean(off_w)
                     / max(statistics.mean(on2_w), 1e-9), 3)
               if off_w and on2_w else None)
    hit_rate, hit_rate2 = _rate(r_on), _rate(r_on2)
    if hit_rate2 <= 0.0:
        fail.append("cached resubmit saw a zero hit rate — the cache "
                    "never engaged")
    if audit_snap is not None and audit_snap["mismatches"]:
        fail.append(f"audit sentinel caught "
                    f"{audit_snap['mismatches']} mismatches with the "
                    "window cache armed")

    print(f"[servebench] rounds x{n} cache-off walls: "
          + " ".join(f"{w:.2f}" for w in off_w), file=sys.stderr)
    print(f"[servebench] rounds x{n} cache-on  walls: "
          + " ".join(f"{w:.2f}" for w in on_w)
          + f"  (hit rate {hit_rate * 100:.1f}%)", file=sys.stderr)
    print(f"[servebench] rounds x{n} resubmit  walls: "
          + " ".join(f"{w:.2f}" for w in on2_w)
          + f"  (hit rate {hit_rate2 * 100:.1f}%)", file=sys.stderr)
    if speedup is not None:
        print(f"[servebench] round-2+ mean: {off_r2:.3f}s no-cache vs "
              f"{on_r2:.3f}s cached — x{speedup:.2f} "
              f"[{'OK' if speedup > 1.0 else 'FAIL'}]; resubmit "
              f"x{resub_x:.2f}", file=sys.stderr)
    if audit_snap is not None:
        print(f"[servebench] audit over cached rounds: "
              f"{audit_snap['audited']} audited "
              f"({audit_snap['mismatches']} mismatches) "
              f"[{'OK' if not audit_snap['mismatches'] else 'FAIL'}]",
              file=sys.stderr)
    print(f"[servebench] identity cache-on vs cache-off: "
          f"[{'OK' if identical else 'FAIL'}]", file=sys.stderr)

    if args.json:
        rounds_block = {
            "requested": n,
            "completed": r_on.rounds.get("completed"),
            "per_round": r_on.rounds.get("per_round"),
            "per_round_nocache": r_off.rounds.get("per_round"),
            "round2plus_nocache_mean_s": (round(off_r2, 4)
                                          if off_r2 is not None
                                          else None),
            "round2plus_cached_mean_s": (round(on_r2, 4)
                                         if on_r2 is not None
                                         else None),
            "round2_speedup_x": speedup,
        }
        cache_block = {
            "identical": identical,
            "hit_rate": hit_rate,
            "resubmit": {"hit_rate": hit_rate2,
                         "per_round": r_on2.rounds.get("per_round"),
                         "speedup_x": resub_x},
            "snapshot": cache_snap,
        }
        cb = r_on.rounds.get("cache") or {}
        cache_block.update(hits=cb.get("hits"), misses=cb.get("misses"))
        artifact = {"mode": "rounds", "jobs": 3,
                    "rounds": rounds_block, "cache": cache_block,
                    "pass": not fail}
        if audit_snap is not None:
            artifact["audit"] = {"rate": args.audit_rate,
                                 "windows": audit_snap["windows"],
                                 "sampled": audit_snap["sampled"],
                                 "audited": audit_snap["audited"],
                                 "mismatches": audit_snap["mismatches"],
                                 "repaired": audit_snap["repaired"]}
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)

    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


def run_fragment_bench(args, PolishClient, PolishServer) -> int:
    """`--fragment N`: serve-native fragment error correction (the
    read-vs-read mode, `mode: "fragment"` on the wire). One warm
    server, three measurements:

      1. identity: one fragment submit vs a solo kF run on the same
         files — byte-identical, the gate that makes the throughput
         numbers meaningful;
      2. fragment wave: N concurrent fragment jobs, closed loop ->
         jobs/s, latency percentiles, streamed parts per job (the
         server runs with a small `frag_group` so every job really
         streams multiple bounded read groups);
      3. contig wave: the standard contig workload through the SAME
         warm server -> the comparison row. Fragment jobs are
         per-read-pile corrections with no contig assembly, so their
         jobs/s must land ABOVE the contig rate at a flat p99 — that
         ratio is the `fragment.vs_contig_x` column.

    Gates (exit status): byte-identity, every wave job completed, and
    vs_contig_x > 1. The `--json` artifact carries a `fragment` block
    for tools/perfgate.py (`fragment.identical` whenever the block is
    present, `--fragment-jobs-min` as the mandatory absolute floor on
    `fragment.jobs_per_s`)."""
    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.serve.queue import nearest_rank
    from racon_tpu.serve.server import make_fragment_dataset

    n_jobs = max(2, args.fragment)
    fail: list[str] = []
    with tempfile.TemporaryDirectory(prefix="racon_fragbench_") as tmp:
        print(f"[servebench] fragment bench: {n_jobs} fragment jobs "
              "vs the contig workload, one warm server",
              file=sys.stderr)
        frag_dir = os.path.join(tmp, "frag")
        os.makedirs(frag_dir)
        frag_paths = make_fragment_dataset(frag_dir)
        contig_paths = build_dataset(tmp, args.genome_kb,
                                     args.coverage, args.read_len,
                                     args.seed, contigs=args.contigs)

        # the solo oracle: same files, same kF parameters the serve
        # path uses (ServeConfig defaults) — one process, no serving
        solo_p = create_polisher(*frag_paths, PolisherType.kF, 500,
                                 10.0, 0.3, num_threads=args.threads)
        solo_p.initialize()
        solo = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                        for s in solo_p.polish(True))
        n_reads = solo.count(b">")

        srv = PolishServer(socket_path=os.path.join(tmp, "serve.sock"),
                           workers=args.workers, warmup=False,
                           job_threads=args.threads,
                           tpu_poa_batches=args.tpupoa_batches,
                           tpu_aligner_batches=args.tpualigner_batches,
                           frag_group=8)
        srv.warmup(paths=contig_paths)
        srv.start()
        try:
            client = PolishClient(socket_path=srv.config.socket_path)

            # ---- identity + streamed decomposition, one warm job each
            parts: list[dict] = []
            r = client.submit(*frag_paths, fragment=True,
                              on_part=parts.append)
            identical = r.fasta == solo
            if not identical:
                fail.append("serve fragment FASTA diverged from the "
                            "solo kF bytes")
            client.submit(*contig_paths)  # warm the contig job path too

            def wave(paths, n, label, **kw):
                lat: list = [None] * n
                nparts = [0] * n

                def submit(i):
                    t0 = time.perf_counter()

                    def on_part(_frame, _i=i):
                        nparts[_i] += 1

                    try:
                        client.submit(*paths, retries=8,
                                      on_part=on_part, **kw)
                    except Exception as exc:
                        print(f"[servebench] {label} job {i} failed: "
                              f"{exc}", file=sys.stderr)
                        return
                    lat[i] = time.perf_counter() - t0

                threads = [threading.Thread(target=submit, args=(i,))
                           for i in range(n)]
                t_start = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                duration = time.perf_counter() - t_start
                done = sorted(v for v in lat if v is not None)
                out = {"jobs": n, "completed": len(done),
                       "duration_s": round(duration, 3),
                       "jobs_per_s": round(
                           len(done) / max(duration, 1e-9), 3),
                       "parts_per_job": round(
                           sum(nparts) / max(n, 1), 2)}
                if done:
                    out.update(
                        p50_s=round(nearest_rank(done, 0.50), 4),
                        p95_s=round(nearest_rank(done, 0.95), 4),
                        p99_s=round(nearest_rank(done, 0.99), 4))
                return out

            frag_wave = wave(frag_paths, n_jobs, "fragment",
                             fragment=True)
            contig_wave = wave(contig_paths,
                               max(2, min(n_jobs, args.jobs)),
                               "contig")
        finally:
            srv.drain(timeout=30)

    for label, w in (("fragment", frag_wave), ("contig", contig_wave)):
        if w["completed"] != w["jobs"]:
            fail.append(f"{label} wave completed "
                        f"{w['completed']}/{w['jobs']} jobs")
    vs_contig = round(frag_wave["jobs_per_s"]
                      / max(contig_wave["jobs_per_s"], 1e-9), 3)
    if vs_contig <= 1.0:
        fail.append(f"fragment jobs/s x{vs_contig:.2f} of contig — "
                    "must be above 1 (a per-read-pile correction "
                    "cheaper than contig assembly)")

    print(f"[servebench] fragment identity vs solo kF "
          f"({n_reads} reads): [{'OK' if identical else 'FAIL'}]",
          file=sys.stderr)
    print(f"[servebench] fragment wave: "
          f"{frag_wave['jobs_per_s']:.2f} jobs/s "
          f"(p99 {frag_wave.get('p99_s', 0):.2f}s, "
          f"{frag_wave['parts_per_job']:.1f} parts/job)",
          file=sys.stderr)
    print(f"[servebench] contig wave:   "
          f"{contig_wave['jobs_per_s']:.2f} jobs/s "
          f"(p99 {contig_wave.get('p99_s', 0):.2f}s) — fragment "
          f"x{vs_contig:.2f} [{'OK' if vs_contig > 1.0 else 'FAIL'}] "
          "(perfgate gates fragment.identical / "
          "--fragment-jobs-min)", file=sys.stderr)

    if args.json:
        fragment_block = {
            "identical": identical,
            "reads": n_reads,
            "jobs_per_s": frag_wave["jobs_per_s"],
            "p50_s": frag_wave.get("p50_s"),
            "p99_s": frag_wave.get("p99_s"),
            "parts_per_job": frag_wave["parts_per_job"],
            "vs_contig_x": vs_contig,
            "wave": frag_wave,
            "contig": contig_wave,
        }
        artifact = {"mode": "fragment", "jobs": n_jobs,
                    "fragment": fragment_block, "pass": not fail}
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)

    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


def run_flood_bench(args, PolishClient, PolishServer) -> int:
    """`--flood N`: preemptive-QoS isolation under load. Two warm
    replicas behind the shard-aware router; N free-tenant submitter
    threads flood the fabric in a closed loop while a gold-priority
    wave runs through it. Three gold waves measure three points:

      1. idle fabric            -> gold p99 baseline
      2. flood, preemption OFF  -> gold p99 degraded by head-of-line
                                   free work (reported, not gated)
      3. flood, preemption ON   -> gold p99 must stay FLAT: each gold
                                   shard preempts the free job on its
                                   replica, runs, and the free job
                                   resumes byte-identically

    then a doomed-abort phase arms the speculative deadline-abort
    (`abort_margin` 0) on every replica and submits free jobs with an
    unmeetable deadline: each must come back typed `deadline-doomed`
    at ADMISSION — before any device dispatch — and the sum of their
    EMA-predicted service seconds is the device time the abort saved.
    The `--json` artifact gains a `qos` block (`gold_p99_flat` = gold
    p99 flood-with-preemption over idle, `doomed_abort_saved_s`)
    which tools/perfgate.py gates via `qos.gold_p99_flat`
    (default-when-present) and `--doomed-abort-min` (mandatory once
    requested). Exit status: every gold job byte-identical to a
    direct submit in every phase, preemptions actually fired in
    phase 3, and every unmeetable-deadline job was aborted doomed."""
    from racon_tpu.serve import DeadlineDoomed
    from racon_tpu.serve.queue import nearest_rank
    from racon_tpu.serve.router import PolishRouter

    n_flood = max(1, args.flood)
    n_gold = max(2, args.jobs)
    fail: list[str] = []
    with tempfile.TemporaryDirectory(prefix="racon_floodbench_") as tmp:
        print(f"[servebench] flood bench: {n_flood} free submitter(s) "
              f"vs {n_gold}-job gold waves, 2 replicas", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)
        servers, socks = [], []
        router = None
        try:
            t0 = time.perf_counter()
            for k in range(2):
                sock = os.path.join(tmp, f"flood_rep{k}.sock")
                srv = PolishServer(
                    socket_path=sock, workers=args.workers,
                    warmup=False, job_threads=args.threads,
                    tpu_poa_batches=args.tpupoa_batches,
                    tpu_aligner_batches=args.tpualigner_batches)
                srv.warmup(paths=paths)
                srv.start()
                servers.append(srv)
                socks.append(sock)
            router = PolishRouter(
                replicas=socks,
                socket_path=os.path.join(tmp, "flood_router.sock"),
                journal=os.path.join(tmp, "flood_router.jsonl")).start()
            client = PolishClient(
                socket_path=router.config.socket_path)
            print(f"[servebench] fabric warm in "
                  f"{time.perf_counter() - t0:.2f}s", file=sys.stderr)
            # the identity reference — and the submit that seeds every
            # replica's service-time EMA for the doomed phase
            solo = client.submit(*paths, tenant="gold", priority=10)

            def gold_wave(tag: str) -> float:
                lat: list[float] = []
                for _ in range(n_gold):
                    t = time.perf_counter()
                    r = client.submit(*paths, tenant="gold",
                                      priority=10, retries=8)
                    lat.append(time.perf_counter() - t)
                    if r.fasta != solo.fasta:
                        fail.append(f"{tag}: gold FASTA diverged from "
                                    "the direct submit bytes")
                return nearest_rank(sorted(lat), 0.99)

            def flood_phase(tag: str, preempt: bool) -> tuple[float,
                                                              int]:
                for srv in servers:
                    srv.config.preempt = preempt
                stop = threading.Event()
                flood_done = [0] * n_flood
                flood_bad: list[str] = []

                def flood(slot: int):
                    mine = PolishClient(
                        socket_path=router.config.socket_path)
                    while not stop.is_set():
                        try:
                            r = mine.submit(*paths, tenant="free",
                                            priority=0, retries=8)
                        except Exception as exc:  # noqa: BLE001
                            flood_bad.append(
                                f"{type(exc).__name__}: {exc}")
                            return
                        if r.fasta != solo.fasta:
                            flood_bad.append("free FASTA diverged")
                            return
                        flood_done[slot] += 1

                threads = [threading.Thread(target=flood, args=(i,))
                           for i in range(n_flood)]
                for t in threads:
                    t.start()
                time.sleep(1.0)  # the flood owns the fabric first
                p99 = gold_wave(tag)
                stop.set()
                for t in threads:
                    t.join(timeout=180)
                for srv in servers:
                    srv.config.preempt = False
                if flood_bad:
                    fail.append(f"{tag}: flood submitter died "
                                f"({flood_bad[0]})")
                print(f"[servebench] {tag}: gold p99 {p99:.2f}s "
                      f"({sum(flood_done)} free jobs completed "
                      "under the wave)", file=sys.stderr)
                return p99, sum(flood_done)

            p99_idle = gold_wave("flood idle-baseline")
            print(f"[servebench] flood idle-baseline: gold p99 "
                  f"{p99_idle:.2f}s", file=sys.stderr)
            p99_nopre, _ = flood_phase("flood preempt-off", False)
            pre0 = sum(s.qos["preemptions"] for s in servers)
            p99_pre, free_done = flood_phase("flood preempt-on", True)
            preemptions = sum(s.qos["preemptions"]
                              for s in servers) - pre0
            if preemptions < 1:
                fail.append("preempt-on flood phase fired zero "
                            "preemptions — gold never displaced free")

            # doomed-abort phase: arm admission-time speculative abort
            # on every replica (margin 0) and submit free jobs whose
            # deadline the populated EMA says is unmeetable — the
            # typed reject must arrive BEFORE any device dispatch
            for srv in servers:
                srv.queue.abort_margin = 0.0
            doomed_n, doomed_saved = 0, 0.0
            try:
                for _ in range(n_gold):
                    try:
                        client.submit(*paths, tenant="free",
                                      deadline_s=0.05)
                        fail.append("unmeetable-deadline job was NOT "
                                    "aborted doomed (it ran to "
                                    "completion)")
                    except DeadlineDoomed as exc:
                        doomed_n += 1
                        doomed_saved += max(exc.predicted_s, 0.0)
            finally:
                for srv in servers:
                    srv.queue.abort_margin = None
            aborted = sum(s.qos["aborted_doomed"] for s in servers)
            print(f"[servebench] doomed-abort: {doomed_n}/{n_gold} "
                  f"unmeetable jobs aborted at admission, "
                  f"~{doomed_saved:.2f} predicted device-seconds "
                  f"saved ({aborted} replica-side aborts)",
                  file=sys.stderr)
        finally:
            if router is not None:
                router.drain(timeout=30)
            for srv in servers:
                srv.drain(timeout=30)

    flat = round(p99_pre / max(p99_idle, 1e-9), 3)
    nopre_x = round(p99_nopre / max(p99_idle, 1e-9), 3)
    qos_block = {
        "replicas": 2,
        "flood_submitters": n_flood,
        "gold_jobs": n_gold,
        "free_jobs_completed": free_done,
        "gold_p99_idle_s": round(p99_idle, 3),
        "gold_p99_flood_nopreempt_s": round(p99_nopre, 3),
        "gold_p99_flood_preempt_s": round(p99_pre, 3),
        "gold_p99_flat": flat,
        "gold_p99_nopreempt_x": nopre_x,
        "preemptions": preemptions,
        "doomed_submitted": n_gold,
        "doomed_aborted": doomed_n,
        "doomed_abort_saved_s": round(doomed_saved, 3),
    }
    print(f"[servebench] gold p99: idle {p99_idle:.2f}s, flood "
          f"no-preempt {p99_nopre:.2f}s (x{nopre_x:.2f}), flood "
          f"preempt {p99_pre:.2f}s (x{flat:.2f} — "
          "perfgate gates qos.gold_p99_flat)", file=sys.stderr)
    if args.json:
        artifact = {"mode": "flood", "jobs": n_gold,
                    "qos": qos_block, "pass": not fail}
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)
    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


def run_ramp_bench(args, PolishClient, PolishServer) -> int:
    """`--ramp N`: elastic autoscaling under a ramped open-loop load.
    The fabric starts at ONE warm replica behind the router with the
    autoscaler (serve/autoscale.py) armed, ceiling N. Poisson arrivals
    ramp the offered rate linearly from 1x to 10x over the wave — the
    1x base rate sits well inside one replica's capacity (measured, or
    `--ramp-qps0`), the 10x peak far outside it, so the loop MUST
    scale up to hold latency. Every job's FASTA must equal a direct
    submit's bytes (with a single-contig workload the scaled-up points
    exercise window-range sharding on every job).

    After the ramp a slow trickle keeps jobs arriving while the idle
    fleet scales back down to the 1-replica floor: a job lost in that
    phase is the scale-down race the unroute-then-drain handshake
    exists to prevent. The bench FAILS on any lost job, any byte
    divergence, a ramp that never scaled up, or a fleet that did not
    drain back to the floor. `--json` writes a `"mode": "ramp"`
    artifact whose `autoscale` block (replicas over time, scale
    up/down counts, gold p99 idle vs ramp as `gold_p99_flat`,
    `jobs_lost`) tools/perfgate.py gates via `autoscale.jobs_lost`
    == 0 (always, when the block is present) and
    `autoscale.gold_p99_flat` (default 2.0; `--ramp-p99-flat-max`
    makes it mandatory)."""
    import random

    from racon_tpu.serve.autoscale import AutoscaleConfig, Autoscaler
    from racon_tpu.serve.queue import nearest_rank
    from racon_tpu.serve.router import PolishRouter

    n_max = max(2, args.ramp)
    n_jobs = max(8, args.ramp_jobs)
    fail: list[str] = []
    samples: list[dict] = []
    with tempfile.TemporaryDirectory(prefix="racon_rampbench_") as tmp:
        print(f"[servebench] ramp bench: 1->{n_max} replicas, "
              f"{n_jobs} Poisson jobs ramping 1x->10x", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)
        # one warm base replica + warm SPARES on the exact spec sockets
        # the autoscaler will ask for (autoscale_1.sock, ...) — all
        # real subprocesses (one GIL per replica), so a scale-up adds
        # genuine capacity and its latency is the healthz handshake,
        # not an interpreter start or a compile
        t0 = time.perf_counter()
        base_sock = os.path.join(tmp, "ramp_base.sock")
        base = spawn_replica(base_sock, args)
        pool: dict = {}
        for i in range(1, n_max):
            spec = os.path.join(tmp, f"autoscale_{i}.sock")
            pool[spec] = spawn_replica(spec, args)
        for sock in [base_sock, *pool]:
            wait_replica(PolishClient, sock)
            PolishClient(socket_path=sock).submit(*paths)  # warm it
        print(f"[servebench] base + {len(pool)} warm spare "
              f"subprocess(es) in {time.perf_counter() - t0:.2f}s",
              file=sys.stderr)
        router = PolishRouter(
            replicas=base_sock,
            socket_path=os.path.join(tmp, "ramp_router.sock"),
            journal=os.path.join(tmp, "ramp_router.jsonl"),
            # under ramped CONCURRENT load, unbounded range fan-out
            # couples every job to every replica (one busy replica
            # gates all merges); two shards per job keeps the
            # sub-contig speedup while the fleet spreads whole jobs
            max_shards=2,
            health_interval_s=0.25).start()
        live: dict = {}

        def spawn(spec):
            proc = pool.pop(spec, None)
            if proc is None:  # past the prebuilt pool: cold spawn
                proc = spawn_replica(spec, args)
            live[spec] = proc
            return spec

        def stop(handle):
            proc = live.pop(handle, None)
            if proc is not None:
                stop_replica(proc)

        scaler = None
        try:
            client = PolishClient(
                socket_path=router.config.socket_path)
            # identity reference; also seeds the service-time EMA
            solo = client.submit(*paths, tenant="gold")
            # idle gold baseline on the 1-replica floor
            idle: list[float] = []
            for _ in range(3):
                t = time.perf_counter()
                r = client.submit(*paths, tenant="gold")
                idle.append(time.perf_counter() - t)
                if r.fasta != solo.fasta:
                    fail.append("idle-baseline FASTA diverged")
            p99_idle = nearest_rank(sorted(idle), 0.99)
            qps0 = args.ramp_qps0 or \
                0.35 / max(statistics.mean(idle), 1e-9)
            print(f"[servebench] idle gold p99 {p99_idle:.2f}s; "
                  f"offered rate {qps0:.2f} -> {qps0 * 10:.2f} jobs/s",
                  file=sys.stderr)

            scaler = Autoscaler(
                router,
                config=AutoscaleConfig(
                    min_replicas=1, max_replicas=n_max,
                    # latency-biased posture: any sustained backlog
                    # beyond one job per replica scales up (the warm
                    # spare pool makes an up cheap); idle still drains
                    # fast enough to exercise scale-down under the
                    # live trickle below
                    interval_s=0.2, up_pressure=1.1, up_sustain_s=0.3,
                    down_idle_s=2.0, cooldown_s=1.0, socket_dir=tmp,
                    ready_timeout_s=30.0,
                    # hold_s > job wall: a burst arrival holds for the
                    # replica its own pressure spawns instead of
                    # serializing behind a committed sibling
                    hold_s=10.0),
                spawn=spawn, stop=stop).start()

            # replicas-over-time sampler: the artifact's scaling trace
            stop_sampling = threading.Event()
            t_wave0 = time.perf_counter()

            def sample():
                while not stop_sampling.is_set():
                    snap = scaler.snapshot()
                    samples.append(
                        {"t_s": round(time.perf_counter() - t_wave0, 2),
                         "replicas": 1 + snap["spawned"],
                         "pressure": round(snap["pressure"], 2)})
                    stop_sampling.wait(0.25)

            sampler = threading.Thread(target=sample, daemon=True)
            sampler.start()

            # the ramp wave: Poisson arrivals, rate climbing 1x -> 10x
            rng = random.Random(args.seed)
            lat: list = [None] * n_jobs
            lost: list[str] = []

            arrive: list = [None] * n_jobs
            shards: list = [None] * n_jobs

            def submit(i):
                t = time.perf_counter()
                arrive[i] = t - t_wave0
                try:
                    r = PolishClient(
                        socket_path=router.config.socket_path).submit(
                            *paths, tenant="gold", retries=8)
                except Exception as exc:  # noqa: BLE001
                    lost.append(f"ramp job {i}: "
                                f"{type(exc).__name__}: {exc}")
                    return
                lat[i] = time.perf_counter() - t
                rb = r.router or {}
                shards[i] = rb.get("shards")
                if r.fasta != solo.fasta:
                    fail.append(f"ramp job {i} FASTA diverged")

            threads = []
            for i in range(n_jobs):
                rate = qps0 * (1.0 + 9.0 * i / max(n_jobs - 1, 1))
                time.sleep(rng.expovariate(rate))
                th = threading.Thread(target=submit, args=(i,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            ramp_done = sorted(v for v in lat if v is not None)
            p99_ramp = (nearest_rank(ramp_done, 0.99) if ramp_done
                        else float("inf"))
            ups = scaler.snapshot()["scale_ups"]
            peak = max((s["replicas"] for s in samples), default=1)
            print(f"[servebench] ramp: {len(ramp_done)}/{n_jobs} jobs, "
                  f"gold p99 {p99_ramp:.2f}s, {ups} scale-up(s), "
                  f"peak {peak} replicas", file=sys.stderr)

            # scale-down under a live trickle: jobs keep arriving
            # slowly while the idle fleet drains back to the floor
            trickle_n = n_max + 1
            for i in range(trickle_n):
                time.sleep(3.0)
                try:
                    r = client.submit(*paths, tenant="gold", retries=8)
                    if r.fasta != solo.fasta:
                        fail.append(f"trickle job {i} FASTA diverged")
                except Exception as exc:  # noqa: BLE001
                    lost.append(f"trickle job {i}: "
                                f"{type(exc).__name__}: {exc}")
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline and scaler.spawned:
                time.sleep(0.25)
            snap = scaler.snapshot()
            drained = snap["spawned"] == 0
            stop_sampling.set()
            sampler.join(timeout=5)
        finally:
            if scaler is not None:
                scaler.close()
            router.drain(timeout=30)
            stop_replica(base)
            for proc in [*live.values(), *pool.values()]:
                stop_replica(proc)

    jobs_lost = len(lost)
    for msg in lost:
        fail.append(f"job lost: {msg}")
    if snap["scale_ups"] < 1:
        fail.append("the ramp never scaled up — the offered load "
                    "stayed inside one replica (raise --ramp-jobs or "
                    "lower --ramp-qps0)")
    if snap["scale_downs"] < 1 or not drained:
        fail.append(f"the fleet did not drain back to the floor "
                    f"({snap['spawned']} spawned replica(s) left, "
                    f"{snap['scale_downs']} scale-down(s))")
    flat = round(p99_ramp / max(p99_idle, 1e-9), 3)
    autoscale_block = {
        "replicas_min": 1,
        "replicas_max": n_max,
        "jobs": n_jobs,
        "completed": len(ramp_done),
        "jobs_lost": jobs_lost,
        "qps0": round(qps0, 3),
        "qps_peak": round(qps0 * 10.0, 3),
        "scale_ups": snap["scale_ups"],
        "scale_downs": snap["scale_downs"],
        "spawn_failures": snap["spawn_failures"],
        "drained_to_min": drained,
        "trickle_jobs": trickle_n,
        "gold_p99_idle_s": round(p99_idle, 3),
        "gold_p99_ramp_s": round(p99_ramp, 3),
        "gold_p99_flat": flat,
        "replicas_over_time": samples,
        # the per-job trace behind the p99: arrival offset into the
        # wave, end-to-end latency, shards the router planned
        "ramp_jobs": [
            {"i": i,
             "arrive_s": round(arrive[i], 2) if arrive[i] else None,
             "lat_s": round(lat[i], 2) if lat[i] else None,
             "shards": shards[i]}
            for i in range(n_jobs)],
        "device_latency_ms": args.device_latency_ms,
        "device_latency_x": args.device_latency_x,
        "host_poa_chunk": args.host_poa_chunk,
    }
    print(f"[servebench] autoscale: {snap['scale_ups']} up / "
          f"{snap['scale_downs']} down, {jobs_lost} jobs lost, gold "
          f"p99 idle {p99_idle:.2f}s vs ramp {p99_ramp:.2f}s "
          f"(x{flat:.2f} — perfgate gates autoscale.gold_p99_flat)",
          file=sys.stderr)
    if args.json:
        artifact = {"mode": "ramp", "jobs": n_jobs,
                    "autoscale": autoscale_block, "pass": not fail}
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)
    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


def run_openloop(client, paths, qps: float, n_jobs: int,
                 seed: int) -> dict:
    """One open-loop wave: Poisson arrivals at `qps`, every job
    streaming (progress + result parts), latency percentiles +
    time-to-first-byte + achieved throughput."""
    import random

    from racon_tpu.serve.queue import nearest_rank

    rng = random.Random(seed)
    lat: list = [None] * n_jobs
    ttfb: list = [None] * n_jobs
    threads = []

    def submit(i):
        t0 = time.perf_counter()

        def on_part(frame, _i=i, _t=t0):
            if ttfb[_i] is None:
                ttfb[_i] = time.perf_counter() - _t

        try:
            client.submit(*paths, retries=8, on_part=on_part)
        except Exception as exc:
            print(f"[servebench] openloop job {i} failed: {exc}",
                  file=sys.stderr)
            # keep lat and ttfb over the SAME population: a job that
            # streamed a part but then failed must not skew ttfb low
            ttfb[i] = None
            return
        lat[i] = time.perf_counter() - t0

    t_start = time.perf_counter()
    for i in range(n_jobs):
        time.sleep(rng.expovariate(qps))
        t = threading.Thread(target=submit, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    duration = time.perf_counter() - t_start
    done = sorted(v for v in lat if v is not None)
    tb = sorted(v for v in ttfb if v is not None)
    out = {"qps": qps, "jobs": n_jobs, "completed": len(done),
           "duration_s": round(duration, 3),
           "achieved_qps": round(len(done) / max(duration, 1e-9), 3)}
    if done:
        out.update(p50_s=round(nearest_rank(done, 0.50), 4),
                   p95_s=round(nearest_rank(done, 0.95), 4),
                   p99_s=round(nearest_rank(done, 0.99), 4))
    if tb:
        out["ttfb_p50_s"] = round(nearest_rank(tb, 0.50), 4)
    return out


def saturation_knee(curve: list[dict]) -> float | None:
    """The highest swept rate the server still absorbs: achieved
    throughput >= 90% of offered, STOPPING at the first rate that
    fails — a noisy high-rate point that spuriously passes must not
    report capacity above a rate the server demonstrably dropped.
    None when even the lowest rate saturates the server."""
    knee = None
    for pt in sorted(curve, key=lambda p: p["qps"]):
        if pt["achieved_qps"] < 0.9 * pt["qps"]:
            break
        knee = pt["qps"]
    return knee


def _baseline_view(doc: dict) -> dict:
    """Comparable numbers out of a --baseline artifact: either another
    servebench artifact (openloop.curve / warm keys) or a raw curve
    dump ({"curve": [...]})."""
    curve = (doc.get("openloop") or {}).get("curve") or \
        doc.get("curve") or []
    out = {"design": doc.get("design") or doc.get("mode"),
           "curve": curve}
    if curve:
        worst = max((p for p in curve if p.get("p99_s")),
                    key=lambda p: p["qps"], default=None)
        if worst:
            out["p99_s"] = worst.get("p99_s")
            out["ttfb_p50_s"] = worst.get("ttfb_p50_s")
    warm = doc.get("warm") or {}
    out.setdefault("p99_s", warm.get("p99_s"))
    out.setdefault("ttfb_p50_s", warm.get("ttfb_p50_s"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=4,
                    help="concurrent warm submissions")
    ap.add_argument("--cold-runs", type=int, default=None,
                    help="sequential cold CLI runs to time "
                         "(default min(jobs, 3))")
    ap.add_argument("--genome-kb", type=int, default=20)
    ap.add_argument("--contigs", type=int, default=4,
                    help="split the genome budget across this many "
                         "independent contigs (default 4) — "
                         "time-to-first-byte then measures the FIRST "
                         "contig streaming out, the shape the "
                         "continuous batcher optimizes")
    ap.add_argument("--coverage", type=int, default=20)
    ap.add_argument("--read-len", type=int, default=4000)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("-t", "--threads", type=int, default=2)
    ap.add_argument("-c", "--tpupoa-batches", type=int, default=0)
    ap.add_argument("--tpualigner-batches", type=int, default=0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--iteration-windows", type=int, default=None,
                    help="continuous feeder iteration bound passed to "
                         "the server (smaller = finer streaming "
                         "granularity and faster late-join turnaround)")
    ap.add_argument("--worker-lanes", type=int, default=None,
                    help="sub-mesh worker lanes passed to the server "
                         "(RACON_TPU_WORKER_LANES): device iterations "
                         "run concurrently across the lane partition; "
                         "with > 1 the bench additionally gates that "
                         "iterations really overlapped on distinct "
                         "lanes (batcher max_concurrent_iterations "
                         ">= 2)")
    ap.add_argument("--audit-rate", type=float, default=None,
                    help="arm the identity-audit sentinel at this "
                         "sampled fraction (RACON_TPU_AUDIT_RATE "
                         "semantics) and measure its overhead: the "
                         "bench runs an extra audit-OFF sequential "
                         "pass on the same warm server and reports the "
                         "wall delta plus the sentinel's sampled "
                         "fraction and shadow device seconds in an "
                         "`audit` artifact block, which "
                         "tools/perfgate.py gates at the <2% "
                         "observability budget (and at zero "
                         "mismatches)")
    ap.add_argument("--json", default=None,
                    help="write the bench-style JSON artifact here")
    ap.add_argument("--fleet", type=int, default=None,
                    help="fleet mode: run this many in-process server "
                         "replicas, round-robin the warm submissions "
                         "across them, and poll the fleet aggregator "
                         "(obs/fleet.py) mid-wave — the artifact gains "
                         "a `fleet` block with aggregator-lag and "
                         "scrape-overhead columns that "
                         "tools/perfgate.py gates at the <2% budget")
    ap.add_argument("--router", type=int, default=None,
                    help="router bench mode: start this many warm "
                         "replicas behind the shard-aware router "
                         "(serve/router.py) and sweep job throughput "
                         "at 1, 2, 4 ... replicas (capped here) — the "
                         "artifact gains a `router` block (jobs/s per "
                         "count, requeue count, merge overhead, "
                         "byte-identity vs a direct submit, scaling_x) "
                         "that tools/perfgate.py gates via "
                         "router.identical and --router-scaling-min")
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds bench mode: run a rounds=N iterative "
                         "polish on a cache-off and a cache-on warm "
                         "server (plus an identical resubmit) and "
                         "report per-round walls, cache hit rates and "
                         "the round-2+ speedup — the artifact gains "
                         "`rounds` / `cache` blocks that "
                         "tools/perfgate.py gates via cache.identical "
                         "and --round2-speedup-min")
    ap.add_argument("--fragment", type=int, default=None,
                    help="fragment bench mode: run this many "
                         "concurrent serve-native fragment-correction "
                         "jobs (mode: fragment — corrected reads out, "
                         "no contig assembly) on one warm server, "
                         "gated byte-identical to a solo kF run, plus "
                         "a contig comparison wave — the artifact "
                         "gains a `fragment` block (jobs_per_s, p99, "
                         "parts_per_job, vs_contig_x, identical) that "
                         "tools/perfgate.py gates via "
                         "fragment.identical and --fragment-jobs-min")
    ap.add_argument("--flood", type=int, default=None,
                    help="flood bench mode: this many free-tenant "
                         "submitter threads flood a 2-replica routed "
                         "fabric while gold-priority waves measure "
                         "p99 isolation (idle, flood preempt-off, "
                         "flood preempt-on), plus a doomed-abort "
                         "phase — the artifact gains a `qos` block "
                         "(gold_p99_flat, doomed_abort_saved_s) that "
                         "tools/perfgate.py gates via qos.gold_p99_flat "
                         "and --doomed-abort-min")
    ap.add_argument("--ramp", type=int, default=None,
                    help="ramp bench mode: Poisson offered load "
                         "ramping 1x->10x through a routed fabric "
                         "that starts at ONE replica with the elastic "
                         "autoscaler (serve/autoscale.py) armed, "
                         "ceiling at this many replicas — the "
                         "artifact gains an `autoscale` block "
                         "(replicas over time, scale up/down counts, "
                         "gold p99 idle vs ramp, jobs_lost) that "
                         "tools/perfgate.py gates via "
                         "autoscale.jobs_lost == 0 and "
                         "autoscale.gold_p99_flat")
    ap.add_argument("--ramp-jobs", type=int, default=24,
                    help="ramp mode: jobs across the ramp (default 24)")
    ap.add_argument("--device-latency-ms", type=float, default=0.0,
                    help="fleet modes (--router / --ramp): arm "
                         "RACON_TPU_DEVICE_LATENCY_S in every replica "
                         "subprocess — a simulated per-chunk accelerator "
                         "round-trip of this many ms, slept off-CPU, so "
                         "the bench measures device-dominated scaling "
                         "(the production posture) instead of being "
                         "bound by this host's core count; recorded in "
                         "the artifact as device_latency_ms")
    ap.add_argument("--device-latency-x", type=float, default=0.0,
                    help="fleet modes: arm RACON_TPU_DEVICE_LATENCY_X "
                         "in every replica subprocess — each pipeline "
                         "chunk's dispatch is followed by an off-CPU "
                         "sleep of this many times its measured "
                         "duration (a simulated device whose round-trip "
                         "scales with batch size); recorded in the "
                         "artifact as device_latency_x")
    ap.add_argument("--host-poa-chunk", type=int, default=0,
                    help="fleet modes: arm RACON_TPU_HOST_POA_CHUNK in "
                         "every replica subprocess — windows per host "
                         "POA batch call (default 4096), shrunk so "
                         "--device-latency-ms paces proportionally to "
                         "each job's window count")
    ap.add_argument("--ramp-qps0", type=float, default=None,
                    help="ramp mode: the 1x starting arrival rate in "
                         "jobs/s (default: 0.35x the measured "
                         "single-replica capacity)")
    ap.add_argument("--fleet-poll-s", type=float, default=0.25,
                    help="fleet mode: aggregator poll interval during "
                         "the wave (default 0.25s)")
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop arrival mode: Poisson arrivals at "
                         "this rate (jobs/s) instead of an all-at-once "
                         "wave; reports latency percentiles, "
                         "time-to-first-byte and achieved throughput")
    ap.add_argument("--qps-jobs", type=int, default=8,
                    help="jobs per open-loop wave (default 8)")
    ap.add_argument("--qps-curve", default=None,
                    help="comma-separated extra rates to sweep (e.g. "
                         "'0.5,1,2,4') — the saturation-knee curve in "
                         "the artifact")
    ap.add_argument("--baseline", default=None,
                    help="embed a prior measurement (servebench "
                         "artifact or raw curve JSON) in the artifact "
                         "and print the p99/ttfb comparison")
    ap.add_argument("--check-slo", action="store_true",
                    help="SLO gate mode: run a small concurrent wave "
                         "with per-job deadlines and assert p99 latency "
                         "/ deadline-miss-rate / scrape validity "
                         "(faultcheck-style pass/fail row, exit status "
                         "is the gate)")
    ap.add_argument("--slo-p99", type=float, default=60.0,
                    help="--check-slo: p99 end-to-end latency bound in "
                         "seconds (default 60)")
    ap.add_argument("--slo-miss-rate", type=float, default=0.0,
                    help="--check-slo: allowed deadline-miss rate "
                         "(default 0 — no misses)")
    ap.add_argument("--deadline", type=float, default=120.0,
                    help="--check-slo: per-job deadline_s attached to "
                         "every wave job (default 120)")
    args = ap.parse_args(argv)

    if args.worker_lanes is not None and args.worker_lanes > 1:
        # worker lanes partition the DEVICE LIST: on the CPU bench
        # backend expose enough virtual devices for a real partition
        # (must be set before jax initializes — the same trick the
        # test conftest and synthbench --scale-curve use)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    from racon_tpu.serve import PolishClient, PolishServer

    if args.check_slo:
        return check_slo(args, PolishClient, PolishServer)

    if args.router is not None:
        return run_router_bench(args, PolishClient, PolishServer)

    if args.rounds is not None:
        return run_rounds_bench(args, PolishClient, PolishServer)

    if args.fragment is not None:
        return run_fragment_bench(args, PolishClient, PolishServer)

    if args.flood is not None:
        return run_flood_bench(args, PolishClient, PolishServer)

    if args.ramp is not None:
        return run_ramp_bench(args, PolishClient, PolishServer)

    cold_n = args.cold_runs if args.cold_runs is not None \
        else min(args.jobs, 3)

    with tempfile.TemporaryDirectory(prefix="racon_servebench_") as tmp:
        print(f"[servebench] simulating {args.genome_kb} kb at "
              f"{args.coverage}x ...", file=sys.stderr)
        paths = build_dataset(tmp, args.genome_kb, args.coverage,
                              args.read_len, args.seed,
                              contigs=args.contigs)

        # ---- cold: N sequential fresh-process CLI runs
        cold_s: list[float] = []
        cold_out = None
        for i in range(cold_n):
            dt, out = cold_cli_run(paths, args)
            cold_s.append(dt)
            cold_out = out
            print(f"[servebench] cold run {i + 1}/{cold_n}: {dt:.2f}s",
                  file=sys.stderr)

        # ---- warm: one server, N concurrent submissions. The event
        # journal rides the measured run (its <2% overhead is part of
        # the warm numbers, not hidden from them) and is consistency-
        # checked after drain as part of the gate
        n_replicas = max(1, args.fleet or 1)
        server_kw = {}
        if args.iteration_windows is not None:
            server_kw["iteration_windows"] = args.iteration_windows
        if args.worker_lanes is not None:
            server_kw["worker_lanes"] = args.worker_lanes
        if args.audit_rate is not None:
            server_kw["audit_rate"] = args.audit_rate
        servers, clients, journal_paths = [], [], []
        t0 = time.perf_counter()
        for k in range(n_replicas):
            sock = os.path.join(tmp, f"serve{k}.sock")
            journal_path = os.path.join(tmp, f"journal{k}.jsonl")
            journal_paths.append(journal_path)
            srv = PolishServer(
                socket_path=sock, workers=args.workers, warmup=False,
                job_threads=args.threads, journal=journal_path,
                tpu_poa_batches=args.tpupoa_batches,
                tpu_aligner_batches=args.tpualigner_batches,
                **server_kw)
            srv.warmup(paths=paths)  # warm on the SAME shapes jobs use
            srv.start()
            servers.append(srv)
            clients.append(PolishClient(socket_path=sock))
        server, client = servers[0], clients[0]
        warm_ready_s = time.perf_counter() - t0
        print(f"[servebench] {n_replicas} server(s) warm in "
              f"{warm_ready_s:.2f}s "
              f"({server._warm['compiles']} compiles "
              f"{server._warm['compile_s']:.2f}s)", file=sys.stderr)

        # ---- warm sequential: like-for-like vs the cold runs (with
        # --audit-rate the sentinel is armed here — its overhead is part
        # of the measured warm numbers, not hidden from them)
        seq_s: list[float] = []
        seq_results: list = []
        for i in range(cold_n):
            t0 = time.perf_counter()
            seq_results.append(client.submit(*paths))
            seq_s.append(time.perf_counter() - t0)
            print(f"[servebench] warm seq run {i + 1}/{cold_n}: "
                  f"{seq_s[-1]:.2f}s", file=sys.stderr)

        # ---- audit overhead A/B (--audit-rate): the same sequential
        # workload on the same warm server with the sentinel armed vs
        # muted, INTERLEAVED (on, off, on, off, ...) so drift in the
        # host's background load cancels instead of biasing one arm —
        # the wall delta IS the audit cost (sampling + shadow
        # re-execution + compare), measured not modeled
        audit_on_s: list[float] = []
        audit_off_s: list[float] = []
        # rate 0 means the server built NO auditor (the flagless
        # byte-identity posture) — there is nothing to A/B
        if args.audit_rate and servers[0].auditor is not None:
            ab_pairs = max(cold_n, 5)
            for _ in range(ab_pairs):
                for rate, sink in ((args.audit_rate, audit_on_s),
                                   (0.0, audit_off_s)):
                    for srv in servers:
                        srv.auditor.set_rate(rate)
                    t0 = time.perf_counter()
                    r = client.submit(*paths)
                    sink.append(time.perf_counter() - t0)
                    if r.fasta != seq_results[0].fasta:
                        raise SystemExit("[servebench] audit A/B run "
                                         "diverged from the audited "
                                         "run")
            for srv in servers:
                srv.auditor.set_rate(args.audit_rate)
            print(f"[servebench] audit A/B ({ab_pairs} interleaved "
                  f"pairs): on {statistics.mean(audit_on_s):.2f}s vs "
                  f"off {statistics.mean(audit_off_s):.2f}s mean",
                  file=sys.stderr)

        # ---- warm concurrent wave: the multiplexing story, fully
        # streamed — every wave job asks for live progress AND streamed
        # result parts, so both time-to-first-progress and
        # time-to-first-BYTE (first polished contig on the wire) are
        # measured under contention, not just on an idle server
        results: list = [None] * args.jobs
        latencies: list = [0.0] * args.jobs
        first_progress: list = [None] * args.jobs
        first_byte: list = [None] * args.jobs

        def submit(i):
            t = time.perf_counter()

            def on_progress(ev, _i=i, _t=t):
                if first_progress[_i] is None:
                    first_progress[_i] = time.perf_counter() - _t

            def on_part(frame, _i=i, _t=t):
                if first_byte[_i] is None:
                    first_byte[_i] = time.perf_counter() - _t

            results[i] = clients[i % n_replicas].submit(
                *paths, retries=5, on_progress=on_progress,
                on_part=on_part)
            latencies[i] = time.perf_counter() - t

        # ---- fleet mode: the aggregator polls every replica's scrape
        # + healthz MID-WAVE (the overhead must be measured under
        # load, not on an idle server); each poll records its own
        # wall (aggregator lag) and the per-replica scrape times
        fleet_polls: list[dict] = []
        agg = None
        stop_polling = threading.Event()

        def poll_fleet():
            while not stop_polling.is_set():
                try:
                    snap = agg.poll()
                    fleet_polls.append(
                        {"poll_s": snap.poll_s,
                         "healthy": snap.healthy,
                         "scrape_s": sum(r.scrape_s
                                         for r in snap.replicas)})
                except Exception as exc:  # noqa: BLE001
                    fleet_polls.append({"error": str(exc)})
                stop_polling.wait(args.fleet_poll_s)

        poller = None
        if args.fleet:
            from racon_tpu.obs.fleet import FleetAggregator

            agg = FleetAggregator([s.config.socket_path
                                   for s in servers])
            poller = threading.Thread(target=poll_fleet, daemon=True)

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(args.jobs)]
        # replica-side scrape cost baseline: the servers self-meter
        # their exposition-render seconds (wire and aggregator-side
        # parse time are the aggregator's cost, not the replicas')
        scrape_render_pre = sum(s._scrape_render_s for s in servers)
        t_wave = time.perf_counter()
        if poller is not None:
            poller.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wave_s = time.perf_counter() - t_wave
        if poller is not None:
            stop_polling.set()
            poller.join(timeout=5)
        scrape_render_s = (sum(s._scrape_render_s for s in servers)
                           - scrape_render_pre)

        # ---- open-loop arrival sweep (--qps): Poisson arrivals on the
        # SAME warm server — the saturation-knee curve
        openloop: list[dict] = []
        if args.qps is not None or args.qps_curve:
            rates = []
            if args.qps_curve:
                rates += [float(r) for r in args.qps_curve.split(",")
                          if r.strip()]
            if args.qps is not None and args.qps not in rates:
                rates.append(args.qps)
            for k, rate in enumerate(sorted(set(rates))):
                pt = run_openloop(client, paths, rate, args.qps_jobs,
                                  seed=args.seed + k)
                openloop.append(pt)
                print(f"[servebench] openloop qps={rate:g}: "
                      f"p50 {pt.get('p50_s', float('nan')):.2f}s "
                      f"p99 {pt.get('p99_s', float('nan')):.2f}s "
                      f"ttfb_p50 {pt.get('ttfb_p50_s', float('nan')):.2f}s "
                      f"achieved {pt['achieved_qps']:g}/{rate:g}",
                      file=sys.stderr)

        # every replica's numbers reach the artifact: the gated SLO
        # counters and batcher activity aggregate across the fleet
        snap = merge_fleet_snaps([s.stats_snapshot() for s in servers])
        audit_snaps = [s.auditor.snapshot() for s in servers
                       if s.auditor is not None]
        for srv in servers:
            srv.drain(timeout=30)

        # ---- journal consistency: every journaled job reaches exactly
        # one terminal state, started/terminal pairs balance — per
        # replica journal (job ids restart per server, so the files
        # must be checked separately, not concatenated)
        from obsreport import check_parts_streamed
        from racon_tpu.obs.journal import check_consistency, read_journal

        journal_entries = []
        journal_problems = []
        for jp in journal_paths:
            entries = read_journal(jp)
            journal_entries += entries
            # lifecycle invariants PLUS the streamed-results receipt
            # (one part-streamed line per output contig) — the same
            # pair obsreport --check enforces
            journal_problems += (check_consistency(entries)
                                 + check_parts_streamed(entries))

    # ---- analysis
    from racon_tpu.serve.queue import nearest_rank

    fail: list[str] = []
    all_results = seq_results + results
    warm_sorted = sorted(latencies)
    p50 = nearest_rank(warm_sorted, 0.50)
    p95 = nearest_rank(warm_sorted, 0.95)
    p99 = nearest_rank(warm_sorted, 0.99)
    seq_p50 = nearest_rank(sorted(seq_s), 0.50)
    cold_p50 = nearest_rank(sorted(cold_s), 0.50)
    compiles_per_job = [
        (r.serve.get("batch") or {}).get("compiles", 0)
        for r in all_results]
    queue_waits = [r.serve["queue_wait_s"] for r in results]
    exec_s = [r.serve["exec_s"] for r in results]

    if cold_out is not None and any(r.fasta != cold_out
                                    for r in all_results):
        fail.append("warm output diverged from cold CLI bytes")
    if any(compiles_per_job):
        fail.append(f"warm jobs compiled: {compiles_per_job}")
    if seq_p50 >= cold_p50:
        fail.append(f"warm p50 {seq_p50:.2f}s did not beat cold p50 "
                    f"{cold_p50:.2f}s")
    ttfp = [v for v in first_progress if v is not None]
    if len(ttfp) < args.jobs:
        fail.append(f"only {len(ttfp)}/{args.jobs} wave jobs received "
                    "a progress frame before their result")
    ttfp_p50 = nearest_rank(sorted(ttfp), 0.50) if ttfp else None
    ttfb = [v for v in first_byte if v is not None]
    if len(ttfb) < args.jobs:
        fail.append(f"only {len(ttfb)}/{args.jobs} wave jobs received "
                    "a result_part frame before their result")
    ttfb_p50 = nearest_rank(sorted(ttfb), 0.50) if ttfb else None
    for p in journal_problems:
        fail.append(f"journal inconsistency: {p}")
    # ---- fleet columns: aggregator lag (one poll's scrape+parse+merge
    # wall) and scrape overhead (replica time spent answering the
    # aggregator as a fraction of the wave — the <2% budget perfgate
    # holds the observability plane to)
    fleet_block = None
    if args.fleet:
        good = [p for p in fleet_polls if "poll_s" in p]
        poll_errors = [p["error"] for p in fleet_polls if "error" in p]
        if not good:
            fail.append("fleet aggregator never completed a poll "
                        f"mid-wave ({poll_errors[:3]})")
        else:
            lags = sorted(p["poll_s"] for p in good)
            # overhead = the replicas' OWN exposition-render seconds
            # (self-metered) over the replica-seconds of wave wall —
            # what answering the aggregator actually cost the fleet
            overhead_pct = (scrape_render_s / max(wave_s, 1e-9)
                            / n_replicas * 100.0)
            unhealthy = sum(1 for p in good if not p["healthy"])
            fleet_block = {
                "replicas": n_replicas,
                "polls": len(good),
                "poll_errors": len(poll_errors),
                "agg_lag_p50_s": round(nearest_rank(lags, 0.50), 5),
                "agg_lag_max_s": round(lags[-1], 5),
                "scrape_render_s": round(scrape_render_s, 4),
                "scrape_overhead_pct": round(overhead_pct, 3),
                "unhealthy_polls": unhealthy,
            }
            if unhealthy or poll_errors:
                fail.append(
                    f"fleet aggregator saw {unhealthy} unhealthy and "
                    f"{len(poll_errors)} failed polls mid-wave — every "
                    "replica must answer scrape+healthz under load")
    # ---- audit overhead columns (--audit-rate): sampled fraction,
    # shadow device seconds, and the measured A/B wall delta — the
    # number perfgate holds to the <2% observability budget
    audit_block = None
    if args.audit_rate is not None and audit_snaps:
        def _tot(key):
            return sum(a[key] for a in audit_snaps)

        on_mean = statistics.mean(audit_on_s or seq_s)
        off_mean = statistics.mean(audit_off_s) if audit_off_s else 0.0
        overhead_pct = ((on_mean / off_mean - 1.0) * 100.0
                        if off_mean > 0 else 0.0)
        audit_block = {
            "rate": args.audit_rate,
            "windows": _tot("windows"),
            "sampled": _tot("sampled"),
            "sampled_frac": round(_tot("sampled")
                                  / max(1, _tot("windows")), 4),
            "audited": _tot("audited"),
            "mismatches": _tot("mismatches"),
            "demotions": _tot("demotions"),
            "repaired": _tot("repaired"),
            "shadow_s": round(_tot("shadow_s"), 4),
            "overhead_pct": round(overhead_pct, 3),
            "ab_runs": len(audit_on_s),
            "seq_mean_on_s": round(on_mean, 4),
            "seq_mean_off_s": round(off_mean, 4),
        }
        if audit_block["mismatches"]:
            # a mismatch on this clean synthetic workload is a REAL
            # silent-corruption (or oracle) bug, never acceptable noise
            fail.append(f"audit sentinel caught "
                        f"{audit_block['mismatches']} mismatches on a "
                        "clean bench workload")
    baseline = None
    if args.baseline:
        try:
            with open(args.baseline) as fh:
                baseline = _baseline_view(json.load(fh))
        except (OSError, ValueError) as exc:
            fail.append(f"unreadable --baseline {args.baseline}: {exc}")

    b = snap["batcher"]
    print(f"[servebench] warm sequential: p50 {seq_p50:.2f}s vs cold "
          f"p50 {cold_p50:.2f}s (speedup "
          f"x{cold_p50 / max(seq_p50, 1e-9):.1f}) "
          f"[{'OK' if seq_p50 < cold_p50 else 'FAIL'}]", file=sys.stderr)
    print(f"[servebench] warm concurrent: {args.jobs} jobs in "
          f"{wave_s:.2f}s ({wave_s / args.jobs:.2f}s/job) — latency "
          f"p50 {p50:.2f}s p95 {p95:.2f}s p99 {p99:.2f}s mean "
          f"{statistics.mean(latencies):.2f}s", file=sys.stderr)
    print(f"[servebench] cold: {len(cold_s)} runs — p50 {cold_p50:.2f}s "
          f"mean {statistics.mean(cold_s):.2f}s", file=sys.stderr)
    print(f"[servebench] compiles/job after warmup: {compiles_per_job} "
          f"[{'OK' if not any(compiles_per_job) else 'FAIL'} target 0]",
          file=sys.stderr)
    print(f"[servebench] queue wait mean {statistics.mean(queue_waits):.3f}s "
          f"max {max(queue_waits):.3f}s; exec mean "
          f"{statistics.mean(exec_s):.3f}s", file=sys.stderr)
    if ttfp:
        print(f"[servebench] time-to-first-progress: p50 "
              f"{ttfp_p50:.3f}s max {max(ttfp):.3f}s "
              f"({len(ttfp)}/{args.jobs} jobs) "
              f"[{'OK' if len(ttfp) == args.jobs else 'FAIL'}]",
              file=sys.stderr)
    if ttfb:
        print(f"[servebench] time-to-first-byte (streamed part): p50 "
              f"{ttfb_p50:.3f}s max {max(ttfb):.3f}s vs job p50 "
              f"{p50:.3f}s ({len(ttfb)}/{args.jobs} jobs) "
              f"[{'OK' if len(ttfb) == args.jobs else 'FAIL'}]",
              file=sys.stderr)
    if baseline and baseline.get("p99_s"):
        worst = (max((pt for pt in openloop if pt.get("p99_s")),
                     key=lambda pt: pt["qps"], default=None)
                 if openloop else None)
        cand_p99 = worst["p99_s"] if worst else p99
        cand_ttfb = (worst.get("ttfb_p50_s")
                     if worst else ttfb_p50)
        delta = (1 - cand_p99 / baseline["p99_s"]) * 100
        print(f"[servebench] vs baseline "
              f"({baseline.get('design') or 'prior'}): p99 "
              f"{cand_p99:.2f}s vs {baseline['p99_s']:.2f}s "
              f"({abs(delta):.0f}% {'better' if delta >= 0 else 'WORSE'})"
              + (f", ttfb_p50 {cand_ttfb:.2f}s vs "
                 f"{baseline['ttfb_p50_s']:.2f}s"
                 if cand_ttfb and baseline.get("ttfb_p50_s")
                 else ""), file=sys.stderr)
    if audit_block:
        print(f"[servebench] audit: rate {audit_block['rate']:g} — "
              f"{audit_block['sampled']}/{audit_block['windows']} "
              f"windows sampled "
              f"({audit_block['sampled_frac'] * 100:.1f}%), shadow "
              f"{audit_block['shadow_s']:.3f}s, "
              f"{audit_block['mismatches']} mismatches, overhead "
              f"{audit_block['overhead_pct']:+.2f}% "
              f"[{'OK' if audit_block['overhead_pct'] <= 2.0 else 'FAIL'} "
              "budget 2%]", file=sys.stderr)
    if fleet_block:
        print(f"[servebench] fleet: {n_replicas} replicas, "
              f"{fleet_block['polls']} aggregator polls mid-wave — "
              f"lag p50 {fleet_block['agg_lag_p50_s'] * 1e3:.1f}ms "
              f"max {fleet_block['agg_lag_max_s'] * 1e3:.1f}ms, "
              f"scrape overhead "
              f"{fleet_block['scrape_overhead_pct']:.2f}% "
              f"[{'OK' if fleet_block['scrape_overhead_pct'] < 2.0 else 'FAIL'} "
              "budget 2%]", file=sys.stderr)
    n_journal_jobs = len({e.get('job') for e in journal_entries
                          if e.get('job')})
    print(f"[servebench] journal: {len(journal_entries)} events / "
          f"{n_journal_jobs} jobs, "
          f"{len(journal_problems)} consistency problems "
          f"[{'OK' if not journal_problems else 'FAIL'}]",
          file=sys.stderr)
    print(f"[servebench] device iterations: {b['iterations']} "
          f"({b['shared_iterations']} cross-job, max "
          f"{b['max_jobs_in_iteration']} jobs / "
          f"{b['max_windows_in_iteration']} windows per iteration)",
          file=sys.stderr)
    # measured per-iteration host overhead (iteration wall - the
    # pipeline's device-stage seconds) — the dispatch-loop number
    shared_its = b["iterations"] - b.get("solo_iterations", 0)
    if shared_its > 0 and "host_s" in b:
        print(f"[servebench] dispatch host overhead: "
              f"{b['host_s']:.3f}s total, "
              f"{b['host_s'] / shared_its * 1e3:.1f}ms per feeder "
              "iteration", file=sys.stderr)
    lanes = b.get("lanes") or []
    # fleet mode concatenates per-replica lane rows: the multi-lane
    # overlap gate applies only when some single replica actually
    # partitioned its mesh (N single-lane replicas are not "2 lanes")
    lanes_per_replica: dict = {}
    for ln in lanes:
        rep = ln.get("replica", 0)
        lanes_per_replica[rep] = lanes_per_replica.get(rep, 0) + 1
    if max(lanes_per_replica.values(), default=0) > 1:
        per_lane = ", ".join(
            f"lane {ln['lane']} ({ln['n_devices']} dev): "
            f"{ln['iterations']} its / {ln['busy_s']:.2f}s busy"
            for ln in lanes)
        concurrent = b.get("max_concurrent_iterations", 0)
        print(f"[servebench] worker lanes: {per_lane}; max "
              f"{concurrent} iterations concurrent "
              f"[{'OK' if concurrent >= 2 else 'FAIL'} overlap]",
              file=sys.stderr)
        if concurrent < 2:
            fail.append("worker lanes never ran iterations "
                        "concurrently (max_concurrent_iterations "
                        f"{concurrent})")
    elif args.worker_lanes is not None and args.worker_lanes > 1:
        # the lane partition clamped away (e.g. an inherited XLA_FLAGS
        # pinning a 1-device mesh): the promised overlap gate cannot
        # run — that must FAIL loudly, not silently pass
        fail.append(f"--worker-lanes {args.worker_lanes} requested but "
                    f"the server ran "
                    f"{max(lanes_per_replica.values(), default=1)} "
                    "lane(s) — the device mesh was too small to "
                    "partition")
    for engine, e in (b.get("occupancy") or {}).items():
        if e.get("buckets"):
            print(f"[servebench] {engine} occupancy "
                  f"{e['occupancy_pct']:.1f}% across "
                  f"{len(e['buckets'])} shapes", file=sys.stderr)

    if args.json:
        artifact = {
            "mode": "serve",
            "jobs": args.jobs,
            "warm": {"seq_p50_s": round(seq_p50, 3),
                     "p50_s": round(p50, 3), "p95_s": round(p95, 3),
                     "p99_s": round(p99, 3),
                     "mean_s": round(statistics.mean(latencies), 3),
                     "wave_s": round(wave_s, 3),
                     "warmup_s": round(warm_ready_s, 3),
                     "queue_wait_mean_s": round(
                         statistics.mean(queue_waits), 4),
                     "ttfp_p50_s": (round(ttfp_p50, 4)
                                    if ttfp_p50 is not None else None),
                     "ttfp_max_s": (round(max(ttfp), 4)
                                    if ttfp else None),
                     "ttfb_p50_s": (round(ttfb_p50, 4)
                                    if ttfb_p50 is not None else None),
                     "ttfb_max_s": (round(max(ttfb), 4)
                                    if ttfb else None),
                     "compiles_per_job": compiles_per_job},
            "slo": {k: (snap.get("slo") or {}).get(k) for k in
                    ("deadline_hit", "deadline_miss", "expired",
                     "miss_rate")},
            "journal": {"events": len(journal_entries),
                        "jobs": n_journal_jobs,
                        "consistent": not journal_problems},
            "cold": {"runs": len(cold_s),
                     "p50_s": round(cold_p50, 3),
                     "mean_s": round(statistics.mean(cold_s), 3)},
            "speedup_p50": round(cold_p50 / max(seq_p50, 1e-9), 2),
            "iterations": {k: b[k] for k in
                           ("iterations", "shared_iterations", "jobs",
                            "windows", "max_jobs_in_iteration",
                            "max_windows_in_iteration",
                            "max_concurrent_iterations", "host_s")},
            "lanes": b.get("lanes") or [],
            "mesh": _mesh_block(b),
            "occupancy": b.get("occupancy", {}),
            "metrics": {"queue": snap["queue"],
                        "batcher": {k: v for k, v in b.items()
                                    if k != "occupancy"}},
            "pass": not fail,
        }
        if audit_block:
            artifact["audit"] = audit_block
        if fleet_block:
            artifact["fleet"] = fleet_block
        if openloop:
            artifact["openloop"] = {"curve": openloop,
                                    "jobs_per_rate": args.qps_jobs,
                                    "knee_qps": saturation_knee(
                                        openloop)}
        if baseline is not None:
            artifact["baseline"] = baseline
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=True)
        print(f"[servebench] wrote {args.json}", file=sys.stderr)

    if fail:
        for f in fail:
            print(f"[servebench] FAIL: {f}", file=sys.stderr)
        return 1
    print("[servebench] PASS", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
