"""Fault-matrix checker: the resilience layer's pass/fail grid.

Runs a small synthetic polishing job (mixed read lengths, so the device
aligner has both device chunks and host-fallback work) through every
fault-injection point — pack raise, device raise, device hang, unpack
corrupt, fallback raise — in both the alignment phase (device aligner
armed) and the consensus phase (host engine loop), at pipeline depths 0
and 2 AND with the occupancy-aware batch scheduler armed (depth 2 +
adaptive buckets + sorted packing: a repacked chunk must route through
the same fault hooks), plus a persistent-failure case that must degrade
to the per-window pass. Each cell passes when the injected run

  - exits cleanly (no exception reaches the driver),
  - fired its armed fault (`faults` counter >= 1),
  - and either reproduces the clean run's bytes (the watchdog/retry/
    fallback ladder absorbed the fault) or reports quarantined windows,
  - within a wall-clock bound (hang cases: the watchdog deadline, not
    the injected stall, must set the pace),
  - leaving no orphaned racon-tpu worker thread behind.

A depth2+fused column runs device consensus through the FUSED
single-launch align→window-slice→POA program (RACON_TPU_FUSED=1, fused
engine): faults injected inside the fused dispatch must fall back to
the SPLIT chained path byte-identically — the program's declared
fallback, gated against a split-vs-fused clean-identity check up front.

A 5th SERVE column runs each row's fault as a per-job fault plan against
a live PolishServer (racon_tpu/serve/): the poisoned job must fail with
a TYPED error response (DeviceError / DeviceTimeout / ChunkCorrupt — the
job is submitted strict, so nothing degrades it away), the server must
survive, and the NEXT clean job on the same warm server must reproduce
the clean run's bytes exactly.

An AUDIT section (two gated cells) exercises the identity-audit
sentinel (racon_tpu/obs/audit.py) against the one failure class nothing
above can represent: SILENT data corruption (`device:chunk=N:sdc`, a
wrong-bytes-no-exception flip). A sampled-corruption run (audit rate
1.0) MUST be caught within the iteration — typed `audit-mismatch`
journal event, labeled mismatch counter, online winner-table demotion
on disk, and the job's FASTA repaired back to the clean bytes — while
an unsampled-corruption run (rate 0) documents the miss: the corrupted
bytes ship and no audit event fires. Both cells are gated; together
they pin that detection is real AND that it comes from the sampling,
not from some hidden always-on check.

A RANGE section (one gated cell) exercises window-range sharding: a
single-contig job split by target-coordinate range across two real
replica subprocesses, one killed -9 mid-job — the requeued window range
must complete on the survivor with the reassembled contig
byte-identical to a solo run, the `range-plan`/`requeued` lines on the
ledger, and obsreport's segment-receipt check tiling clean.

A FRAGMENT section (two gated cells) exercises the serve-native
fragment-correction mode (`mode: "fragment"`) and its admit-time ingest
plane: a fragment submit pointing at a poisoned (non-FASTA) reads file
with `ingest` validation armed must fail TYPED (`bad-request`,
`rejected-ingest` on the ledger) while a CONCURRENT contig job on the
same server completes byte-identically — and the warm server's next
clean fragment job reproduces the solo kF bytes; then a fragment job
read-range-sharded across two real replica subprocesses with one
killed -9 mid-job must complete via the requeue byte-identically, the
`frag-plan`/`requeued` lines on the ledger and obsreport's
fragment-receipt check tiling the read axis clean.

A TRACE section (one gated cell) exercises the distributed-trace plane
under the same fault: a TRACED routed job (`submit_traced`) with one
replica killed -9 mid-job must complete byte-identically AND leave a
merged Chrome trace that tells the story straight — the
`router.requeue` instant present, `tools/tracereport.py --check` green
(the per-stage attribution still partitions the wall, the requeue
count still matches the router block), the journal still consistent.

A PREEMPT section (two gated cells) exercises the preemptive-QoS layer:
a gold-priority job preempting a running free job on a one-worker
server (both outputs byte-identical to an undisturbed run, balanced
`preempted`/`resumed` journal pair), and a cancel RPC landing during an
injected `device:hang` (watchdog absorbs the hang, the job fails with
the typed `cancelled` error, the warm server's next clean job is
byte-identical).

Usage: python tools/faultcheck.py [--quick]
  --quick drops the hang cases (the slow rows; the pytest suite tags the
  same cases with the `slow`/`faults` markers so tier-1 skips them too).

Prints the grid and exits 0 only when every cell passed — the CI gate
for the resilience acceptance criteria.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from racon_tpu.sched import default_cache_dir  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = default_cache_dir()

ACGT = b"ACGT"

#: (name, aligner_batches, fault spec, watchdog timeout, slow)
MATRIX = [
    ("align pack raise", 1, "pack:chunk=0:raise", 0.0, False),
    ("align device raise", 1, "device:chunk=0:raise", 0.0, False),
    ("align device hang", 1, "device:chunk=0:hang=5", 0.5, True),
    ("align unpack corrupt", 1, "unpack:chunk=0:corrupt", 0.0, False),
    ("align fallback raise", 1, "fallback:chunk=0:raise", 0.0, False),
    ("consensus pack raise", 0, "pack:chunk=0:raise", 0.0, False),
    ("consensus device raise", 0, "device:chunk=0:raise", 0.0, False),
    ("consensus device hang", 0, "device:chunk=0:hang=5", 0.5, True),
    ("consensus unpack corrupt", 0, "unpack:chunk=0:corrupt", 0.0, False),
    ("consensus device persistent", 0,
     "device:chunk=0:raise,device:chunk=0:raise", 0.0, False),
]

WALL_CAP = 120.0  # hard per-cell budget; a wedged run fails, not hangs CI


def make_dataset(dirname: str, rng: random.Random):
    truth = bytes(rng.choice(ACGT) for _ in range(2000))

    def mutate(s, rate):
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

    draft = mutate(truth, 0.04)
    jobs = [(start, 400) for start in range(0, len(truth) - 400, 100)]
    jobs += [(0, 1300), (600, 1300)]  # overlength: host-fallback pairs
    reads, paf = [], []
    for k, (start, read_len) in enumerate(jobs):
        read = mutate(truth[start:start + read_len], 0.05)
        reads.append((f"r{k}", read))
        t_end = min(start + read_len, len(draft))
        paf.append(f"r{k}\t{len(read)}\t0\t{len(read)}\t+\tdraft\t"
                   f"{len(draft)}\t{start}\t{t_end}\t{read_len}\t"
                   f"{read_len}\t60")
    paths = (os.path.join(dirname, "reads.fasta.gz"),
             os.path.join(dirname, "ovl.paf.gz"),
             os.path.join(dirname, "draft.fasta.gz"))
    with gzip.open(paths[0], "wb") as f:
        for name, read in reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    with gzip.open(paths[1], "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    with gzip.open(paths[2], "wb") as f:
        f.write(b">draft\n" + draft + b"\n")
    return paths


def polish(paths, depth: int, aligner: int, timeout: float,
           adaptive: bool = False, poa: int = 0,
           engine: str | None = None):
    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = create_polisher(*paths, PolisherType.kC, 500, -1.0, 0.3,
                        num_threads=2, tpu_aligner_batches=aligner,
                        tpu_poa_batches=poa, tpu_engine=engine,
                        tpu_pipeline_depth=depth,
                        tpu_device_timeout=timeout,
                        tpu_adaptive_buckets=adaptive)
    p.initialize()
    out = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                   for s in p.polish())
    return out, p.stage_stats


def orphans(grace: float = 3.0) -> list[str]:
    # racon-tpu-serve-* threads are the live job server's own pool
    # (the serve column keeps one server up across the whole grid) —
    # deliberately long-lived, not orphans of an injected run
    deadline = time.perf_counter() + grace
    while time.perf_counter() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith("racon-tpu")
                 and not t.name.startswith("racon-tpu-serve")]
        if not alive:
            return []
        time.sleep(0.05)
    return alive


def validate_trace(trace_path, stats):
    """The trace-validation gate: the injected run's trace must be valid
    Chrome trace-event JSON whose resilience instant events match the
    run's degradation counters. Returns None when OK, else a failure
    string."""
    import json

    try:
        with open(trace_path) as fh:
            doc = json.load(fh)
        events = doc["traceEvents"]
    except Exception as exc:
        return f"FAIL trace unparseable ({type(exc).__name__}: {exc})"
    for ev in events:
        for field in ("name", "ph", "pid", "tid"):
            if field not in ev:
                return f"FAIL trace event missing {field!r}"
        if ev["ph"] == "X" and (ev["dur"] < 0 or ev["ts"] < 0):
            return "FAIL trace span with negative ts/dur"
    for key in ("faults", "quarantined"):
        seen = sum(ev.get("args", {}).get("n", 1) for ev in events
                   if ev["name"] == f"resilience.{key}")
        if seen != stats[key]:
            return (f"FAIL trace {key} events {seen} != "
                    f"counter {stats[key]}")
    return None


def run_cell(paths, clean, depth, aligner, spec, timeout,
             adaptive=False, trace=False, pallas=False, fused=False):
    trace_path = None
    if trace:
        fd, trace_path = tempfile.mkstemp(suffix=".json",
                                          prefix="racon_trace_")
        os.close(fd)
    try:
        return _run_cell(paths, clean, depth, aligner, spec, timeout,
                         adaptive, trace_path, pallas, fused)
    finally:
        if trace_path is not None:
            try:
                os.unlink(trace_path)
            except OSError:
                pass


def _run_cell(paths, clean, depth, aligner, spec, timeout,
              adaptive, trace_path, pallas=False, fused=False):
    from racon_tpu.obs import trace as obs_trace
    from racon_tpu.resilience.faults import reset_fault_plan

    trace = trace_path is not None
    os.environ["RACON_TPU_FAULT_PLAN"] = spec
    os.environ["RACON_TPU_DEVICE_RETRIES"] = "1"
    os.environ["RACON_TPU_RETRY_BACKOFF"] = "0.01"
    if pallas:
        # the Pallas kernel plane (interpret mode on this CPU backend):
        # injected faults must quarantine / fall back exactly like the
        # XLA chunks — the fault hooks live at the pipeline layer, so a
        # Pallas-dispatched chunk routes through the identical ladder
        os.environ["RACON_TPU_PALLAS"] = "1"
    if fused:
        # the fused single-launch program (device consensus armed with
        # the fused engine): a fault inside the fused dispatch must
        # fall back to the SPLIT chained path byte-identically — the
        # declared fallback — before anything reaches the host tail
        os.environ["RACON_TPU_FUSED"] = "1"
    reset_fault_plan()
    if trace:
        obs_trace.configure(trace_path)
    t0 = time.perf_counter()
    try:
        out, stats = polish(paths, depth, aligner, timeout, adaptive,
                            poa=1 if fused else 0,
                            engine="fused" if fused else None)
    except Exception as exc:
        return f"FAIL crashed ({type(exc).__name__}: {exc})"
    finally:
        wall = time.perf_counter() - t0
        os.environ.pop("RACON_TPU_FAULT_PLAN", None)
        os.environ.pop("RACON_TPU_PALLAS", None)
        os.environ.pop("RACON_TPU_FUSED", None)
        reset_fault_plan()
        if trace:
            try:
                obs_trace.save(trace_path)
            finally:
                obs_trace.reset()
    if wall > WALL_CAP:
        return f"FAIL over budget ({wall:.0f}s)"
    if stats["faults"] < 1:
        return "FAIL fault never fired"
    left = orphans()
    if left:
        return f"FAIL orphaned threads {left}"
    traced = ""
    if trace:
        bad = validate_trace(trace_path, stats)
        if bad is not None:
            return bad
        traced = " traced"
    expect = clean["fused", aligner] if fused else clean[depth, aligner]
    if out == expect:
        how = "identical"
    elif stats["quarantined"] > 0:
        how = f"quarantined {stats['quarantined']}"
    else:
        return "FAIL output diverged without quarantine"
    extras = [f"{k} {stats[k]}" for k in ("retries", "timeouts")
              if stats[k]]
    return (f"pass  {how}{traced}"
            + (f" ({', '.join(extras)})" if extras else ""))


def run_serve_lanes_cell(client, paths, clean, aligner, spec, timeout):
    """One serve-lanes2 cell: the row's fault as a per-job strict plan
    against the shared --worker-lanes 2 server, CONCURRENT with a clean
    job. Isolation jobs run solo on one lane, so the injected fault may
    fail only the poisoned job (typed) while the clean job on the other
    lane(s) returns bytes identical to the clean run."""
    from racon_tpu.serve.client import JobFailed, ServeError

    os.environ["RACON_TPU_DEVICE_RETRIES"] = "0"
    opts = {"tpu_aligner_batches": aligner}
    if timeout:
        opts["tpu_device_timeout"] = timeout
    clean_result: dict = {}

    def clean_job():
        try:
            clean_result["resp"] = client.submit(
                *paths, options={"tpu_aligner_batches": aligner},
                retries=3)
        except Exception as exc:  # noqa: BLE001 — checked below
            clean_result["exc"] = exc

    t = threading.Thread(target=clean_job)
    t.start()
    t0 = time.perf_counter()
    try:
        client.submit(*paths, fault_plan=spec, strict=True, options=opts)
        t.join(WALL_CAP)
        return "FAIL poisoned job succeeded"
    except JobFailed as exc:
        etype = exc.error_type
        if etype not in ("DeviceError", "DeviceTimeout", "ChunkCorrupt"):
            t.join(WALL_CAP)
            return f"FAIL untyped failure ({etype})"
    except ServeError as exc:
        t.join(WALL_CAP)
        return f"FAIL {exc.code}: {exc}"
    except Exception as exc:
        t.join(WALL_CAP)
        return f"FAIL {type(exc).__name__}: {exc}"
    if time.perf_counter() - t0 > WALL_CAP:
        return f"FAIL over budget ({time.perf_counter() - t0:.0f}s)"
    t.join(WALL_CAP)
    if "exc" in clean_result:
        return (f"FAIL concurrent clean job died "
                f"({type(clean_result['exc']).__name__}: "
                f"{clean_result['exc']})")
    if "resp" not in clean_result:
        return "FAIL concurrent clean job never finished"
    if clean_result["resp"].fasta != clean[2, aligner]:
        return "FAIL concurrent clean job diverged"
    return f"pass  {etype}, clean lane identical"


def run_serve_cell(client, paths, clean, aligner, spec, timeout):
    """One serve-column cell: the row's fault as a per-job plan, strict,
    against the shared live server (see module docstring)."""
    from racon_tpu.serve.client import JobFailed, ServeError

    # the poisoned job must actually FAIL: no watchdog retry may absorb
    # its one-shot fault (other columns set RETRIES=1; per-job faults
    # are parsed fresh per submit, so only the retry knob leaks)
    os.environ["RACON_TPU_DEVICE_RETRIES"] = "0"
    opts = {"tpu_aligner_batches": aligner}
    if timeout:
        opts["tpu_device_timeout"] = timeout
    t0 = time.perf_counter()
    try:
        client.submit(*paths, fault_plan=spec, strict=True, options=opts)
        return "FAIL poisoned job succeeded"
    except JobFailed as exc:
        if exc.error_type not in ("DeviceError", "DeviceTimeout",
                                  "ChunkCorrupt"):
            return f"FAIL untyped failure ({exc.error_type})"
        etype = exc.error_type
    except ServeError as exc:
        return f"FAIL {exc.code}: {exc}"
    except Exception as exc:
        return f"FAIL {type(exc).__name__}: {exc}"
    if time.perf_counter() - t0 > WALL_CAP:
        return f"FAIL over budget ({time.perf_counter() - t0:.0f}s)"
    try:
        after = client.submit(*paths,
                              options={"tpu_aligner_batches": aligner})
    except Exception as exc:
        return f"FAIL server did not survive ({type(exc).__name__}: {exc})"
    if after.fasta != clean[2, aligner]:
        return "FAIL clean job after fault diverged"
    return f"pass  {etype}, next clean"


def run_audit_cells(tmp: str, paths) -> list[tuple[str, str]]:
    """The identity-audit sentinel section (module docstring): one
    server with audit rate 1.0, a planted autotuner winner table, a
    journal and a flight dir; a silent `sdc` corruption must be caught
    (and repaired, and demoted) when sampled, and must ship (with no
    audit events) when unsampled."""
    from racon_tpu.obs.journal import read_journal
    from racon_tpu.sched.autotune import Autotuner, reset_autotuner_cache
    from racon_tpu.serve import PolishClient, PolishServer

    cells: list[tuple[str, str]] = []
    at_path = os.path.join(tmp, "audit_autotune.json")
    prev_cache = os.environ.get("RACON_TPU_AUTOTUNE_CACHE")
    os.environ["RACON_TPU_AUTOTUNE_CACHE"] = at_path
    reset_autotuner_cache()
    try:
        # plant an aggressive session winner so the online demotion has
        # a concrete persisted entry to veto
        at = Autotuner(at_path)
        at.record("session", (64, 128), (3, -5, -4, 8),
                  {"kernel": "pallas", "dtype": "int16", "ms": {},
                   "identical": True})
        at.save()
        reset_autotuner_cache()
        sock = os.path.join(tmp, "audit.sock")
        journal = os.path.join(tmp, "audit_journal.jsonl")
        server = PolishServer(socket_path=sock, workers=1,
                              warmup=False, quality_threshold=-1.0,
                              audit_rate=1.0, journal=journal,
                              flight_dir=os.path.join(tmp, "audit_fl"))
        server.start()
        client = PolishClient(socket_path=sock)
        # small windows keep the device-session oracle compiles cheap
        opts = {"tpu_poa_batches": 1, "window_length": 100}
        try:
            clean = client.submit(*paths, options=opts).fasta
            bad = client.submit(*paths, options=opts,
                                fault_plan="device:chunk=1:sdc").fasta
            snap = server.auditor.snapshot()
            events = [e for e in read_journal(journal)
                      if e.get("event") == "audit-mismatch"]
            table = Autotuner(at_path).table
            demoted_on_disk = any(e.get("demoted") for e in
                                  table.values()
                                  if isinstance(e, dict))
            checks = [("repaired", bad == clean),
                      ("journal", len(events) >= 1),
                      ("counter", snap["mismatches"] >= 1),
                      ("demoted", snap["demotions"] >= 1
                       and demoted_on_disk)]
            failed = [n for n, ok in checks if not ok]
            cells.append((
                "audit sdc sampled",
                f"pass  caught ({snap['mismatches']} mismatches, "
                f"{snap['demotions']} demotions, FASTA identical)"
                if not failed else f"FAIL {' '.join(failed)}"))
            # unsampled half: the SAME corruption at rate 0 must ship —
            # the miss is the sampling tradeoff, documented and gated
            pre = snap["mismatches"]
            server.auditor.set_rate(0.0)
            missed = client.submit(*paths, options=opts,
                                   fault_plan="device:chunk=1:sdc").fasta
            snap2 = server.auditor.snapshot()
            checks = [("shipped-corrupt", missed != clean),
                      ("no-audit-event", snap2["mismatches"] == pre)]
            failed = [n for n, ok in checks if not ok]
            cells.append((
                "audit sdc unsampled",
                "pass  missed (corruption shipped, no audit event — "
                "the documented sampling tradeoff)"
                if not failed else f"FAIL {' '.join(failed)}"))
        finally:
            server.drain(timeout=30)
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red pair of cells, not a crashed grid
        detail = f"FAIL crashed ({type(exc).__name__}: {exc})"
        while len(cells) < 2:
            cells.append((("audit sdc sampled", "audit sdc unsampled")
                          [len(cells)], detail))
    finally:
        if prev_cache is None:
            os.environ.pop("RACON_TPU_AUTOTUNE_CACHE", None)
        else:
            os.environ["RACON_TPU_AUTOTUNE_CACHE"] = prev_cache
        reset_autotuner_cache()
    return cells


def run_router_cells(tmp: str) -> list[tuple[str, str]]:
    """The replicated-fabric section (serve/router.py): two REAL
    `racon_tpu serve` replica subprocesses behind one in-process
    router, then kill -9 one replica mid-job. The job must complete via
    the journal-backed requeue with FASTA byte-identical to a solo run
    (each contig exactly once), the `requeued` event must be on the
    router's ledger, and a CONCURRENT job sharing the fabric must come
    back undisturbed on the surviving replica."""
    import signal
    import subprocess

    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.obs.journal import read_journal
    from racon_tpu.serve import (PolishClient, PolishRouter,
                                 make_synth_dataset)

    names = ("router kill -9 mid-job", "router survivor concurrent job")
    cells: list[tuple[str, str]] = []
    data_dir = os.path.join(tmp, "router_data")
    os.makedirs(data_dir, exist_ok=True)
    rpaths = make_synth_dataset(data_dir, contigs=4)
    p = create_polisher(*rpaths, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    p.initialize()
    clean = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                     for s in p.polish())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RACON_TPU_DEVICE_RETRIES="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [q for q in env.get("PYTHONPATH", "").split(os.pathsep)
           if q])
    socks = [os.path.join(tmp, f"router_rep{i}.sock") for i in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve", "--socket", s,
         "--workers", "2", "--no-warmup"],
        env=env, stderr=subprocess.DEVNULL) for s in socks]
    router = None
    journal = os.path.join(tmp, "router_journal.jsonl")
    try:
        for s in socks:
            probe = PolishClient(socket_path=s, timeout=30)
            deadline = time.perf_counter() + 90
            while time.perf_counter() < deadline:
                try:
                    probe.request({"type": "ping"})
                    break
                except Exception:  # noqa: BLE001 — still starting
                    time.sleep(0.2)
            else:
                raise RuntimeError(f"replica {s} never came up")
        router = PolishRouter(replicas=",".join(socks),
                              socket_path=os.path.join(
                                  tmp, "router.sock"),
                              journal=journal,
                              health_interval_s=0.5).start()
        client = PolishClient(socket_path=router.config.socket_path)
        # a watchdog-absorbed hang plan (bytes unchanged — the MATRIX
        # hang rows pin that) keeps every shard busy long enough for
        # the kill to land genuinely mid-job
        slow = {"fault_plan": "device:chunk=0:hang=8",
                "options": {"tpu_device_timeout": 2.0}}
        main_res: dict = {}
        side_res: dict = {}

        def run_job(out: dict):
            mine = PolishClient(socket_path=router.config.socket_path)
            try:
                out["fasta"] = mine.submit(*rpaths, stream=True,
                                           **slow).fasta
            except Exception as exc:  # noqa: BLE001 — checked below
                out["exc"] = exc

        t_main = threading.Thread(target=run_job, args=(main_res,))
        t_side = threading.Thread(target=run_job, args=(side_res,))
        t_main.start()
        t_side.start()
        time.sleep(1.0)  # shards dispatched and stalled on chunk 0
        procs[0].send_signal(signal.SIGKILL)  # the real kill -9
        t_main.join(WALL_CAP)
        t_side.join(WALL_CAP)
        events = [e["event"] for e in read_journal(journal)]
        for name, res, wants_requeue in ((names[0], main_res, True),
                                         (names[1], side_res, False)):
            checks = [("completed", "fasta" in res),
                      ("identical", res.get("fasta") == clean)]
            if wants_requeue:
                checks.append(("requeued-journaled",
                               "requeued" in events
                               and "replica-down" in events))
            failed = [n for n, ok in checks if not ok]
            if "exc" in res:
                failed.append(f"({type(res['exc']).__name__}: "
                              f"{res['exc']})")
            cells.append((name,
                          "pass  " + ("requeued, identical"
                                      if wants_requeue
                                      else "undisturbed, identical")
                          if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red pair of cells, not a crashed grid
        detail = f"FAIL crashed ({type(exc).__name__}: {exc})"
        while len(cells) < 2:
            cells.append((names[len(cells)], detail))
    finally:
        if router is not None:
            router.drain()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
    return cells


def run_range_cells(tmp: str) -> list[tuple[str, str]]:
    """The window-range sharding section (serve/router.py sub-contig
    fan-out): a SINGLE-contig job range-sharded across two REAL
    `racon_tpu serve` replica subprocesses, with one replica killed -9
    mid-job. The requeue must re-run the dead replica's window range on
    the survivor and the reassembled contig must be byte-identical to a
    solo run; the ledger must carry the `range-plan` and `requeued`
    lines, stay lifecycle-consistent, AND pass obsreport's
    segment-receipt tiling check (each accepted segment journaled
    exactly once, covering the window axis with no gap or overlap)."""
    import signal
    import subprocess

    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.obs.journal import check_consistency, read_journal
    from racon_tpu.serve import (PolishClient, PolishRouter,
                                 make_synth_dataset)

    name = "range-shard kill -9 mid-job"
    cells: list[tuple[str, str]] = []
    data_dir = os.path.join(tmp, "range_data")
    os.makedirs(data_dir, exist_ok=True)
    rpaths = make_synth_dataset(data_dir)  # ONE contig: the mega-contig
    p = create_polisher(*rpaths, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    p.initialize()
    clean = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                     for s in p.polish())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RACON_TPU_DEVICE_RETRIES="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [q for q in env.get("PYTHONPATH", "").split(os.pathsep)
           if q])
    socks = [os.path.join(tmp, f"range_rep{i}.sock") for i in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve", "--socket", s,
         "--workers", "2", "--no-warmup"],
        env=env, stderr=subprocess.DEVNULL) for s in socks]
    router = None
    journal = os.path.join(tmp, "range_journal.jsonl")
    try:
        for s in socks:
            probe = PolishClient(socket_path=s, timeout=30)
            deadline = time.perf_counter() + 90
            while time.perf_counter() < deadline:
                try:
                    probe.request({"type": "ping"})
                    break
                except Exception:  # noqa: BLE001 — still starting
                    time.sleep(0.2)
            else:
                raise RuntimeError(f"replica {s} never came up")
        router = PolishRouter(replicas=",".join(socks),
                              socket_path=os.path.join(tmp,
                                                       "range_rt.sock"),
                              journal=journal,
                              health_interval_s=0.5).start()
        # same pacing trick as the contig-shard section: a
        # watchdog-absorbed hang keeps both range shards busy long
        # enough for the kill to land genuinely mid-job
        slow = {"fault_plan": "device:chunk=0:hang=8",
                "options": {"tpu_device_timeout": 2.0}}
        res: dict = {}

        def run_job(out: dict):
            mine = PolishClient(socket_path=router.config.socket_path)
            try:
                out["resp"] = mine.submit(*rpaths, stream=True, **slow)
            except Exception as exc:  # noqa: BLE001 — checked below
                out["exc"] = exc

        t = threading.Thread(target=run_job, args=(res,))
        t.start()
        time.sleep(1.0)  # both range shards dispatched and stalled
        procs[0].send_signal(signal.SIGKILL)  # the real kill -9
        t.join(WALL_CAP)
        entries = read_journal(journal)
        events = [e["event"] for e in entries]
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import obsreport
        resp = res.get("resp")
        checks = [("completed", resp is not None),
                  ("identical",
                   resp is not None and resp.fasta == clean),
                  ("range-sharded",
                   resp is not None
                   and resp.router.get("range") is True),
                  ("range-plan-journaled", "range-plan" in events),
                  ("requeued-journaled", "requeued" in events
                   and "replica-down" in events),
                  ("journal-consistent",
                   check_consistency(entries) == []),
                  ("segments-tile",
                   obsreport.check_parts_routed(entries) == [])]
        failed = [n for n, ok in checks if not ok]
        if "exc" in res:
            failed.append(f"({type(res['exc']).__name__}: "
                          f"{res['exc']})")
        cells.append((name,
                      "pass  requeued, segments tiled, identical"
                      if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red cell, not a crashed grid
        cells.append((name,
                      f"FAIL crashed ({type(exc).__name__}: {exc})"))
    finally:
        if router is not None:
            router.drain()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
    return cells


def run_fragment_cells(tmp: str) -> list[tuple[str, str]]:
    """The fragment-correction section (serve mode: "fragment" + the
    admit-time ingest plane). Two gated cells:

      1. poisoned ingest: a fragment submit pointing at a non-FASTA
         reads file with `ingest` validation armed must fail TYPED
         (`bad-request`, `rejected-ingest` journaled, no started/failed
         pair) while a CONCURRENT contig job on the same server
         completes byte-identically — and the warm server then serves
         a clean fragment job byte-identical to the solo kF run;
      2. kill -9 mid-fragment-job: a fragment job read-range-sharded
         across two REAL `racon_tpu serve` replica subprocesses, one
         killed -9 mid-job. The requeue must re-run the dead replica's
         [frag_lo, frag_hi) slice on the survivor, the merged
         corrected reads must be byte-identical to a solo kF run, the
         ledger must carry `frag-plan` and `requeued`, stay
         lifecycle-consistent, and pass obsreport's fragment-receipt
         tiling check (each read group journaled exactly once,
         covering the read axis with no gap or overlap)."""
    import signal
    import subprocess

    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.obs.journal import check_consistency, read_journal
    from racon_tpu.serve import (PolishClient, PolishRouter,
                                 PolishServer, ServeError,
                                 make_synth_dataset)
    from racon_tpu.serve.server import make_fragment_dataset

    names = ("fragment poisoned ingest, contig alongside",
             "fragment kill -9 mid-job")
    cells: list[tuple[str, str]] = []
    frag_dir = os.path.join(tmp, "frag_data")
    os.makedirs(frag_dir, exist_ok=True)
    fpaths = make_fragment_dataset(frag_dir)
    pf = create_polisher(*fpaths, PolisherType.kF, 500, 10.0, 0.3,
                         num_threads=2)
    pf.initialize()
    clean_frag = b"".join(b">" + s.name.encode() + b"\n" + s.data
                          + b"\n" for s in pf.polish(True))
    contig_dir = os.path.join(tmp, "frag_contig_data")
    os.makedirs(contig_dir, exist_ok=True)
    cpaths = make_synth_dataset(contig_dir)
    pc = create_polisher(*cpaths, PolisherType.kC, 500, 10.0, 0.3,
                         num_threads=2)
    pc.initialize()
    clean_contig = b"".join(b">" + s.name.encode() + b"\n" + s.data
                            + b"\n" for s in pc.polish())

    # ---- cell 1: poisoned fragment ingest, contig riding alongside
    journal1 = os.path.join(tmp, "frag_journal1.jsonl")
    try:
        bad = os.path.join(tmp, "frag_bad.fasta")
        with open(bad, "w") as fh:
            fh.write("this is not fasta\n")
        srv = PolishServer(socket_path=os.path.join(tmp, "frag.sock"),
                           workers=2, warmup=False,
                           journal=journal1).start()
        try:
            res: dict = {}

            def contig_job(out: dict):
                mine = PolishClient(
                    socket_path=srv.config.socket_path)
                try:
                    out["resp"] = mine.submit(*cpaths)
                except Exception as exc:  # noqa: BLE001 — checked
                    out["exc"] = exc

            t = threading.Thread(target=contig_job, args=(res,))
            t.start()
            client = PolishClient(socket_path=srv.config.socket_path)
            typed = None
            try:
                client.submit(bad, fpaths[1], fpaths[2],
                              fragment=True, ingest=True)
            except ServeError as exc:
                typed = exc
            # the warm server still serves fragment work afterwards
            after = client.submit(*fpaths, fragment=True)
            t.join(WALL_CAP)
        finally:
            srv.drain(timeout=30)
        entries = read_journal(journal1)
        events = [e["event"] for e in entries]
        checks = [("typed-reject", typed is not None
                   and typed.code == "bad-request"),
                  ("rejected-ingest-journaled",
                   "rejected-ingest" in events),
                  ("contig-survived", res.get("resp") is not None
                   and res["resp"].fasta == clean_contig),
                  ("fragment-after-reject-identical",
                   after.fasta == clean_frag),
                  ("journal-consistent",
                   check_consistency(entries) == [])]
        failed = [n for n, ok in checks if not ok]
        if "exc" in res:
            failed.append(f"({type(res['exc']).__name__}: "
                          f"{res['exc']})")
        cells.append((names[0],
                      "pass  typed bad-request, contig unharmed"
                      if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed cell is a red
        # cell, not a crashed grid
        cells.append((names[0],
                      f"FAIL crashed ({type(exc).__name__}: {exc})"))

    # ---- cell 2: kill -9 one of two replicas mid-fragment-job
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RACON_TPU_DEVICE_RETRIES="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [q for q in env.get("PYTHONPATH", "").split(os.pathsep)
           if q])
    socks = [os.path.join(tmp, f"frag_rep{i}.sock") for i in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve", "--socket", s,
         "--workers", "2", "--no-warmup"],
        env=env, stderr=subprocess.DEVNULL) for s in socks]
    router = None
    journal2 = os.path.join(tmp, "frag_journal2.jsonl")
    try:
        for s in socks:
            probe = PolishClient(socket_path=s, timeout=30)
            deadline = time.perf_counter() + 90
            while time.perf_counter() < deadline:
                try:
                    probe.request({"type": "ping"})
                    break
                except Exception:  # noqa: BLE001 — still starting
                    time.sleep(0.2)
            else:
                raise RuntimeError(f"replica {s} never came up")
        router = PolishRouter(replicas=",".join(socks),
                              socket_path=os.path.join(tmp,
                                                       "frag_rt.sock"),
                              journal=journal2,
                              health_interval_s=0.5).start()
        # the same pacing trick as the range section: a
        # watchdog-absorbed hang keeps both fragment shards busy long
        # enough for the kill to land genuinely mid-job
        slow = {"fault_plan": "device:chunk=0:hang=8",
                "options": {"tpu_device_timeout": 2.0}}
        res2: dict = {}

        def run_job(out: dict):
            mine = PolishClient(socket_path=router.config.socket_path)
            try:
                out["resp"] = mine.submit(*fpaths, fragment=True,
                                          stream=True, **slow)
            except Exception as exc:  # noqa: BLE001 — checked below
                out["exc"] = exc

        t = threading.Thread(target=run_job, args=(res2,))
        t.start()
        time.sleep(1.0)  # both fragment shards dispatched and stalled
        procs[0].send_signal(signal.SIGKILL)  # the real kill -9
        t.join(WALL_CAP)
        entries = read_journal(journal2)
        events = [e["event"] for e in entries]
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import obsreport
        resp = res2.get("resp")
        checks = [("completed", resp is not None),
                  ("identical",
                   resp is not None and resp.fasta == clean_frag),
                  ("fragment-sharded",
                   resp is not None
                   and resp.router.get("fragment") is True),
                  ("frag-plan-journaled", "frag-plan" in events),
                  ("requeued-journaled", "requeued" in events
                   and "replica-down" in events),
                  ("journal-consistent",
                   check_consistency(entries) == []),
                  ("read-groups-tile",
                   obsreport.check_parts_routed(entries) == [])]
        failed = [n for n, ok in checks if not ok]
        if "exc" in res2:
            failed.append(f"({type(res2['exc']).__name__}: "
                          f"{res2['exc']})")
        cells.append((names[1],
                      "pass  requeued, read groups tiled, identical"
                      if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red cell, not a crashed grid
        cells.append((names[1],
                      f"FAIL crashed ({type(exc).__name__}: {exc})"))
    finally:
        if router is not None:
            router.drain()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
    return cells


def run_trace_cells(tmp: str) -> list[tuple[str, str]]:
    """The distributed-trace section (serve/router.py trace collection
    + tools/tracereport.py): a TRACED routed job over two real replica
    subprocesses, one killed -9 mid-job. The job must complete via the
    journal-backed requeue byte-identically AND the merged Chrome
    trace must tell that story honestly: the `router.requeue` instant
    present for the re-dispatched shard, the dead replica simply
    absent as a track (trace_pull is best-effort), `tracereport
    --check` green — the per-stage attribution still partitions the
    job wall and the requeue-instant count still matches the router
    block's `requeues` — and the router journal still
    lifecycle-consistent. A crash that corrupts the trace artifact or
    double-counts the requeued shard's spans is a red cell here, not a
    plausible-looking report."""
    import signal
    import subprocess

    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.obs.journal import check_consistency, read_journal
    from racon_tpu.serve import (PolishClient, PolishRouter,
                                 make_synth_dataset)

    name = "traced requeue kill -9 mid-job"
    cells: list[tuple[str, str]] = []
    data_dir = os.path.join(tmp, "trace_data")
    os.makedirs(data_dir, exist_ok=True)
    rpaths = make_synth_dataset(data_dir, contigs=4)
    p = create_polisher(*rpaths, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    p.initialize()
    clean = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                     for s in p.polish())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RACON_TPU_DEVICE_RETRIES="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [q for q in env.get("PYTHONPATH", "").split(os.pathsep)
           if q])
    socks = [os.path.join(tmp, f"trace_rep{i}.sock") for i in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve", "--socket", s,
         "--workers", "2", "--no-warmup"],
        env=env, stderr=subprocess.DEVNULL) for s in socks]
    router = None
    journal = os.path.join(tmp, "trace_journal.jsonl")
    trace_out = os.path.join(tmp, "trace_merged.json")
    try:
        for s in socks:
            probe = PolishClient(socket_path=s, timeout=30)
            deadline = time.perf_counter() + 90
            while time.perf_counter() < deadline:
                try:
                    probe.request({"type": "ping"})
                    break
                except Exception:  # noqa: BLE001 — still starting
                    time.sleep(0.2)
            else:
                raise RuntimeError(f"replica {s} never came up")
        router = PolishRouter(replicas=",".join(socks),
                              socket_path=os.path.join(
                                  tmp, "trace_router.sock"),
                              journal=journal,
                              health_interval_s=0.5).start()
        # the same watchdog-absorbed hang plan the router cell uses:
        # bytes unchanged, every shard busy long enough for the kill
        # to land genuinely mid-job
        slow = {"fault_plan": "device:chunk=0:hang=8",
                "options": {"tpu_device_timeout": 2.0}}
        res: dict = {}

        def run_job(out: dict):
            mine = PolishClient(socket_path=router.config.socket_path)
            try:
                r, _doc = mine.submit_traced(*rpaths,
                                             trace_out=trace_out,
                                             **slow)
                out["fasta"] = r.fasta
            except Exception as exc:  # noqa: BLE001 — checked below
                out["exc"] = exc

        t = threading.Thread(target=run_job, args=(res,))
        t.start()
        time.sleep(1.0)  # shards dispatched and stalled on chunk 0
        procs[0].send_signal(signal.SIGKILL)  # the real kill -9
        t.join(WALL_CAP)
        entries = read_journal(journal)
        events = [e["event"] for e in entries]
        requeue_spans = 0
        if os.path.exists(trace_out):
            with open(trace_out) as fh:
                doc = json.load(fh)
            requeue_spans = sum(
                1 for ev in doc.get("traceEvents") or []
                if ev.get("ph") == "i"
                and ev.get("name") == "router.requeue")
        report = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tracereport.py"),
             trace_out, "--check"],
            env=env, capture_output=True, text=True)
        checks = [("completed", "fasta" in res),
                  ("identical", res.get("fasta") == clean),
                  ("requeued-journaled", "requeued" in events
                   and "replica-down" in events),
                  ("journal-consistent",
                   not check_consistency(entries)),
                  ("requeue-span", requeue_spans >= 1),
                  ("tracereport-check",
                   report.returncode == 0)]
        failed = [n for n, ok in checks if not ok]
        if "exc" in res:
            failed.append(f"({type(res['exc']).__name__}: "
                          f"{res['exc']})")
        if report.returncode != 0:
            failed.append(
                "(" + (report.stderr.strip().splitlines() or ["?"])[-1]
                + ")")
        cells.append((name,
                      "pass  requeue span present, report consistent"
                      if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red cell, not a crashed grid
        cells.append((name,
                      f"FAIL crashed ({type(exc).__name__}: {exc})"))
    finally:
        if router is not None:
            router.drain()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
    return cells


def run_preempt_cells(tmp: str) -> list[tuple[str, str]]:
    """The preemptive-QoS section (serve QoS: --preempt + cancel RPC):
    a gold-priority job preempts a running free job on a one-worker
    server — the free job's pooled windows are withdrawn and parked,
    gold runs, the free job resumes — and BOTH outputs must be
    byte-identical to an undisturbed run, with the balanced
    `preempted`/`resumed` pair on the journal. Then a cancel RPC lands
    during an injected `device:hang`: the watchdog absorbs the hang,
    the cancelled job fails with the typed `cancelled` error instead of
    shipping unwanted bytes, and the same warm server's next clean job
    reproduces the clean bytes exactly."""
    from racon_tpu.obs.journal import read_journal
    from racon_tpu.serve import (JobCancelled, PolishClient,
                                 PolishServer, make_synth_dataset)

    names = ("preempt gold over free", "cancel during device hang")
    cells: list[tuple[str, str]] = []
    data_dir = os.path.join(tmp, "preempt_data")
    os.makedirs(data_dir, exist_ok=True)
    ppaths = make_synth_dataset(data_dir, contigs=3)
    sock = os.path.join(tmp, "preempt.sock")
    journal = os.path.join(tmp, "preempt_journal.jsonl")
    server = None
    try:
        server = PolishServer(socket_path=sock, workers=1, warmup=False,
                              quality_threshold=-1.0, preempt=True,
                              journal=journal).start()
        client = PolishClient(socket_path=sock)
        clean = client.submit(*ppaths).fasta  # the undisturbed bytes

        def run_job(out: dict, **kw):
            mine = PolishClient(socket_path=sock)
            try:
                out["fasta"] = mine.submit(*ppaths, **kw).fasta
            except Exception as exc:  # noqa: BLE001 — checked below
                out["exc"] = exc

        free_res: dict = {}
        gold_res: dict = {}
        # hold the device feeder so the free job is deterministically
        # mid-flight (windows pooled, not yet dispatched) when gold
        # arrives — the admission-time preemption path, not a race
        server.batcher.hold()
        try:
            t_free = threading.Thread(target=run_job, args=(free_res,),
                                      kwargs={"tenant": "free"})
            t_free.start()
            deadline = time.perf_counter() + 60
            while (time.perf_counter() < deadline
                   and not server._running_jobs):
                time.sleep(0.02)
            t_gold = threading.Thread(target=run_job, args=(gold_res,),
                                      kwargs={"tenant": "gold",
                                              "priority": 5})
            t_gold.start()
            while (time.perf_counter() < deadline
                   and server.qos["preemptions"] < 1):
                time.sleep(0.02)
        finally:
            server.batcher.release()
        t_free.join(WALL_CAP)
        t_gold.join(WALL_CAP)
        events = [e["event"] for e in read_journal(journal)]
        checks = [("preempted", server.qos["preemptions"] >= 1),
                  ("preempted-journaled", "preempted" in events),
                  ("resumed-journaled", "resumed" in events),
                  ("free-identical", free_res.get("fasta") == clean),
                  ("gold-identical", gold_res.get("fasta") == clean)]
        failed = [n for n, ok in checks if not ok]
        for res in (free_res, gold_res):
            if "exc" in res:
                failed.append(f"({type(res['exc']).__name__}: "
                              f"{res['exc']})")
        cells.append((names[0],
                      "pass  preempted+resumed, both identical"
                      if not failed else f"FAIL {' '.join(failed)}"))

        # cell 2: cancel landing mid-hang on the SAME warm server —
        # the hang plan parks the job inside the device dispatch for
        # ~2s (watchdog timeout), a window no scheduler trick is
        # needed to hit
        poison_res: dict = {}
        t_poison = threading.Thread(
            target=run_job, args=(poison_res,),
            kwargs={"fault_plan": "device:chunk=0:hang=8",
                    "options": {"tpu_device_timeout": 2.0},
                    "trace_id": "faultcheck-cancel"})
        t_poison.start()
        time.sleep(1.0)  # job admitted and stalled inside the hang
        try:
            cres = client.cancel(trace_id="faultcheck-cancel")
        except Exception as exc:  # noqa: BLE001 — checked below
            cres = {"error": f"{type(exc).__name__}: {exc}"}
        t_poison.join(WALL_CAP)
        try:
            after = client.submit(*ppaths).fasta
        except Exception:  # noqa: BLE001 — dead server is the failure
            after = None
        checks = [("cancel-acked", cres.get("type") == "ok"),
                  ("typed-cancelled",
                   isinstance(poison_res.get("exc"), JobCancelled)),
                  ("server-survived-identical", after == clean)]
        failed = [n for n, ok in checks if not ok]
        cells.append((names[1],
                      "pass  cancelled typed, watchdog absorbed, "
                      "server clean"
                      if not failed else f"FAIL {' '.join(failed)}"))
    except Exception as exc:  # noqa: BLE001 — a crashed section is a
        # red pair of cells, not a crashed grid
        detail = f"FAIL crashed ({type(exc).__name__}: {exc})"
        while len(cells) < 2:
            cells.append((names[len(cells)], detail))
    finally:
        if server is not None:
            server.drain(timeout=30)
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow hang-injection rows")
    args = ap.parse_args()

    os.environ["RACON_TPU_ALIGNER_MAXLEN"] = "1024"
    os.environ.pop("RACON_TPU_STRICT", None)
    rows = [m for m in MATRIX if not (args.quick and m[4])]

    failures = 0
    with tempfile.TemporaryDirectory(prefix="racon_faultcheck_") as tmp:
        paths = make_dataset(tmp, random.Random(11))
        clean = {}
        for depth in (0, 2):
            for aligner in (0, 1):
                clean[depth, aligner] = polish(paths, depth, aligner,
                                               0.0)[0]
        # scheduler-on column: the clean adaptive run must be
        # byte-identical to the static one (the scheduler contract) —
        # checked once here, so every adaptive cell compares against the
        # same bytes the static cells do
        for aligner in (0, 1):
            sched_clean = polish(paths, 2, aligner, 0.0, adaptive=True)[0]
            if sched_clean != clean[2, aligner]:
                print("[faultcheck] FAIL: adaptive-bucket clean run "
                      "diverged from static", file=sys.stderr)
                return 1
        # pallas-column clean gate: the kernel-plane contract is that a
        # clean RACON_TPU_PALLAS=1 run is byte-identical to the XLA one
        # — checked once, so every pallas cell compares against the
        # same bytes the other columns do
        os.environ["RACON_TPU_PALLAS"] = "1"
        try:
            for aligner in (0, 1):
                pallas_clean = polish(paths, 2, aligner, 0.0)[0]
                if pallas_clean != clean[2, aligner]:
                    print("[faultcheck] FAIL: pallas clean run diverged "
                          "from XLA", file=sys.stderr)
                    return 1
        finally:
            os.environ.pop("RACON_TPU_PALLAS", None)
        # fused-column clean gate: the fused single-launch program
        # (device consensus, fused engine, RACON_TPU_FUSED=1) must be
        # byte-identical to the SPLIT chained path on a clean run —
        # the identity that makes split the fused program's declared
        # fault fallback; every fused cell compares against this
        for aligner in (0, 1):
            try:
                os.environ["RACON_TPU_FUSED"] = "0"
                split_clean = polish(paths, 2, aligner, 0.0, poa=1,
                                     engine="fused")[0]
                os.environ["RACON_TPU_FUSED"] = "1"
                fused_clean = polish(paths, 2, aligner, 0.0, poa=1,
                                     engine="fused")[0]
            finally:
                os.environ.pop("RACON_TPU_FUSED", None)
            if fused_clean != split_clean:
                print("[faultcheck] FAIL: fused single-launch clean "
                      "run diverged from the split path",
                      file=sys.stderr)
                return 1
            clean["fused", aligner] = fused_clean
        width = max(len(m[0]) for m in rows)
        print(f"{'injection point':<{width}}  depth0"
              f"{'':<30}depth2{'':<30}depth2+sched"
              f"{'':<24}depth2+trace{'':<24}depth2+pallas"
              f"{'':<23}depth2+fused{'':<24}serve{'':<31}serve-lanes2",
              file=sys.stderr)
        # the 4th column runs with span tracing armed: the injected run
        # must additionally produce a valid Chrome trace whose
        # fault/quarantine instant events match the degradation
        # counters; the 5th runs the Pallas kernel plane (aligner rows
        # dispatch the resident wavefront kernel in interpret mode);
        # the 6th runs device consensus through the FUSED single-launch
        # program — injected faults must fall back to the split chained
        # path byte-identically
        columns = ((0, False, False, False, False),
                   (2, False, False, False, False),
                   (2, True, False, False, False),
                   (2, False, True, False, False),
                   (2, False, False, True, False),
                   (2, False, False, False, True))
        # the final (serve) column submits the fault as a per-job plan
        # against ONE live warm server shared by every row — surviving
        # the whole poisoned sequence is itself part of the gate
        from racon_tpu.serve import PolishClient, PolishServer

        serve_sock = os.path.join(tmp, "faultcheck.sock")
        server = PolishServer(socket_path=serve_sock, workers=2,
                              quality_threshold=-1.0,
                              warmup=False).start()
        client = PolishClient(socket_path=serve_sock)
        # the 7th column shares a SECOND live server running two
        # sub-mesh worker lanes: the poisoned strict job (solo on one
        # lane) must fail typed while a CONCURRENT clean job on the
        # other lane stays byte-identical — lane-level fault isolation
        lanes_sock = os.path.join(tmp, "faultcheck_lanes.sock")
        lanes_server = PolishServer(socket_path=lanes_sock, workers=2,
                                    worker_lanes=2,
                                    quality_threshold=-1.0,
                                    warmup=False).start()
        lanes_client = PolishClient(socket_path=lanes_sock)
        try:
            for name, aligner, spec, timeout, _slow in rows:
                cells = []
                for depth, adaptive, traced, pallas, fused in columns:
                    cell = run_cell(paths, clean, depth, aligner, spec,
                                    timeout, adaptive, trace=traced,
                                    pallas=pallas, fused=fused)
                    failures += cell.startswith("FAIL")
                    cells.append(f"{cell:<36}")
                cell = run_serve_cell(client, paths, clean, aligner,
                                      spec, timeout)
                failures += cell.startswith("FAIL")
                cells.append(f"{cell:<36}")
                cell = run_serve_lanes_cell(lanes_client, paths, clean,
                                            aligner, spec, timeout)
                failures += cell.startswith("FAIL")
                cells.append(f"{cell:<36}")
                print(f"{name:<{width}}  {''.join(cells)}",
                      file=sys.stderr)
        finally:
            os.environ.pop("RACON_TPU_DEVICE_RETRIES", None)
            try:
                server.drain(timeout=30)
            finally:
                # a failed drain of the first server must not leak the
                # lanes server's threads/socket
                lanes_server.drain(timeout=30)
        # the identity-audit section: silent corruption vs the sentinel
        audit_cells = run_audit_cells(tmp, paths)
        for name, cell in audit_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
        # the replicated-fabric section: kill -9 a replica behind the
        # router mid-job — requeue must finish the job byte-identically
        router_cells = run_router_cells(tmp)
        for name, cell in router_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
        # the window-range sharding section: kill -9 one of two
        # replicas mid-range-sharded SINGLE-contig job — the requeued
        # window range must complete byte-identically with the
        # segment receipts tiling the contig exactly once
        range_cells = run_range_cells(tmp)
        for name, cell in range_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
        # the fragment-correction section: a poisoned fragment ingest
        # fails typed while a concurrent contig job survives; kill -9
        # one of two replicas mid-fragment-job — the requeued read
        # range must complete byte-identically with the read-group
        # receipts tiling the read axis exactly once
        fragment_cells = run_fragment_cells(tmp)
        for name, cell in fragment_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
        # the distributed-trace section: kill -9 under a TRACED routed
        # job — the merged trace must show the requeue and survive
        # tracereport --check with the journal still consistent
        trace_cells = run_trace_cells(tmp)
        for name, cell in trace_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
        # the preemptive-QoS section: gold preempts free byte-
        # identically; a cancel RPC lands during a watchdog-absorbed
        # hang and the server survives
        preempt_cells = run_preempt_cells(tmp)
        for name, cell in preempt_cells:
            failures += cell.startswith("FAIL")
            print(f"{name:<{width}}  {cell}", file=sys.stderr)
    n_cells = ((len(columns) + 2) * len(rows) + len(audit_cells)
               + len(router_cells) + len(range_cells)
               + len(fragment_cells) + len(trace_cells)
               + len(preempt_cells))
    print(f"[faultcheck] {'FAIL' if failures else 'PASS'}: "
          f"{n_cells - failures}/{n_cells} cells green",
          file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
