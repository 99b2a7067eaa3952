"""Benchmark harness.

Measures the polishing hot loop (per-window POA consensus — the cudapoa
role, BASELINE.md north star "windows/sec/chip") on the reference's own
sample data (lambda phage, ~48.5 kb, 181 overlaps, PAF + FASTQ path), then
prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "windows/sec", "vs_baseline": N}

Failure discipline (round-3 lesson: a pathological device path must not
eat the whole budget and lose the host number too): every measurement runs
in a SUBPROCESS with a hard wall-clock cap. The device phase (evolving-
graph engine, ops/poa_graph.py, RACON_TPU_STRICT so a device failure
raises instead of silently reporting the host fallback as "device") gets
_DEVICE_CAP seconds including its kernel precompile; the host phase gets
_HOST_CAP. The final JSON line is the device number when that phase
succeeded, else the host number, else an explicit zero — the line is
emitted under every failure mode.

Device warm-up is `DeviceGraphPOA.precompile()` — all four pinned
(bucket, batch) programs compiled before the timed loop — instead of a
second full pipeline run.

An optional device-aligner smoke (the cudaaligner role, ops/align.py;
enabled with the device phase) reports wall time and skipped-pair counts
on stderr, mirroring the reference's "[CUDAPolisher] Aligned overlaps ...
on GPU" accounting (cudapolisher.cpp:204-206). It never affects the JSON.

vs_baseline compares against the reference CPU implementation's
throughput on the same data: racon 1.4.x with 4 threads polishes this
sample's ~100 windows in about 2 s of consensus time on a modern x86 core
(the reference's CI runs all ten sample fixtures in well under a minute),
i.e. ~50 windows/sec. The reference publishes no official throughput
numbers (BASELINE.md), so this locally-grounded estimate is the
comparison point until a like-for-like A100 cudapoa run is available.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REFERENCE_CPU_WINDOWS_PER_SEC = 50.0

DATA = "/root/reference/test/data/"

_DEVICE_CAP = 780.0   # seconds, includes XLA precompile of 4 programs
_FUSED_CAP = 600.0    # fused engine phase (precompile of 4 depth buckets)
_HOST_CAP = 300.0     # host run is ~20 s; generous margin
_ALIGNER_CAP = 300.0


def probe_device(timeout: float | None = None, retries: int = 1) -> bool:
    """True when jax can reach an accelerator (TPU) without hanging.

    A first device claim can take minutes; the default timeout is 420 s
    and one retry is attempted, because a probe that gives up early
    silently downgrades the whole bench to host-only. Env-tunable via
    RACON_TPU_PROBE_TIMEOUT."""
    if timeout is None:
        timeout = float(os.environ.get("RACON_TPU_PROBE_TIMEOUT", "420"))
    for attempt in range(1 + max(0, retries)):
        # retry gets a shorter slice: its job is catching a device that
        # came up between attempts, not doubling the cost of a dead one
        t = timeout if attempt == 0 else min(timeout, 240.0)
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "import jax; ds = jax.devices(); "
                 "print('OK' if ds and ds[0].platform != 'cpu' else 'CPU')"],
                capture_output=True, text=True, timeout=t)
            if proc.returncode == 0 and "OK" in proc.stdout:
                return True
            if proc.returncode == 0 and "CPU" in proc.stdout:
                return False  # backend answered: no accelerator — final
            why = (f"rc={proc.returncode}, stderr tail: "
                   f"{proc.stderr[-300:]!r}")
        except subprocess.TimeoutExpired:
            why = f"timeout after {t:.0f}s"
        print(f"[bench] device probe attempt {attempt + 1} failed ({why})",
              file=sys.stderr)
    return False


def build_polisher(device_batches: int, aligner_batches: int = 0):
    from racon_tpu.core.polisher import create_polisher, PolisherType

    polisher = create_polisher(
        DATA + "sample_reads.fastq.gz", DATA + "sample_overlaps.paf.gz",
        DATA + "sample_layout.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
        True, 5, -4, -8, num_threads=os.cpu_count() or 1,
        tpu_poa_batches=device_batches,
        tpu_aligner_batches=aligner_batches,
        # the async dispatch pipeline depth (0 = synchronous, for A/B
        # bisection of the overlap win on the same data)
        tpu_pipeline_depth=int(
            os.environ.get("RACON_TPU_PIPELINE_DEPTH", "2")))
    return polisher


def _stage_fields(polisher) -> dict:
    """The polisher's per-stage pipeline counters, rounded for the JSON
    artifact. Overlap evidence: pack+device+unpack stage seconds exceeding
    the phase wall time means the stages really ran concurrently; device
    seconds ~ 0 means the pipeline is silently dead.

    The snapshot also carries the resilience degradation report (faults /
    retries / timeouts / backoff_s / breaker_trips / quarantined /
    cancelled — racon_tpu/resilience/): all zero on a clean run, and a
    nonzero `quarantined` or `breaker_trips` on a STRICT-less phase means
    the throughput number was earned on a degraded path — CI should read
    these next to the stage counters before trusting a comparison."""
    return {k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in polisher.stage_stats.items()}


def _identity(polished) -> tuple[int, float]:
    from racon_tpu.io.parsers import create_sequence_parser
    from racon_tpu.native import edit_distance

    ref: list = []
    create_sequence_parser(DATA + "sample_reference.fasta.gz",
                           "bench").parse(ref, -1)
    dist = edit_distance(polished[0].reverse_complement, ref[0].data)
    return dist, 1.0 - dist / len(ref[0].data)


def phase_consensus(mode: str) -> int:
    """Child process: measure one engine end-to-end; last stdout line is
    the phase's JSON result. Modes: "host" (C++ engine), "device" (the
    per-layer session engine), "fused" (the single-launch whole-window
    engine, failed/ineligible windows host-polished — the reference's own
    per-window GPU->CPU fallback discipline, cudapolisher.cpp:354-383)."""
    device = 0 if mode == "host" else 1
    if device and _cpu_backend_refused():
        return 3
    if mode == "fused":
        os.environ["RACON_TPU_ENGINE"] = "fused"
        os.environ.setdefault("RACON_TPU_FUSED_FALLBACK", "host")
    else:
        # pin: an inherited RACON_TPU_ENGINE=fused must not make the
        # session-engine phase silently measure the fused engine
        os.environ["RACON_TPU_ENGINE"] = "session"
    # warm-vs-cold compile-cache evidence: a non-empty persistent cache
    # at phase start means this phase's XLA compiles (inside initialize
    # for the aligner, inside precompile for the consensus engines)
    # should mostly be disk hits — the JSON records which run this was
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    cache_warm = bool(cache_dir) and bool(
        os.path.isdir(cache_dir) and os.listdir(cache_dir))
    polisher = build_polisher(device)
    t0 = time.perf_counter()
    polisher.initialize()
    init_time = time.perf_counter() - t0

    precompile_time = 0.0
    if device:
        t = time.perf_counter()
        from racon_tpu.ops.poa import _pack

        # with adaptive buckets armed, precompile the DERIVED shapes —
        # each engine's ladder is a pure (idempotent) function of the
        # window set, so the polish run's own engine instance re-derives
        # the same shapes and hits these programs in the jit cache
        wins = ([_pack(w) for w in polisher.windows]
                if polisher.scheduler.adaptive else None)
        if mode == "fused":
            from racon_tpu.ops.poa_fused import FusedPOA

            depth = max((len(w.sequences) - 1 for w in polisher.windows),
                        default=0)
            # banded_only must match what the timed polish constructs
            # (create_polisher's tpu_banded_alignment flows into
            # FusedPOA(banded_only=...) and keys its compiled programs);
            # a mismatch would recompile every depth bucket inside the
            # timed loop and waste the precompile entirely
            FusedPOA(5, -4, -8,
                     banded_only=polisher.tpu_banded_alignment,
                     scheduler=polisher.scheduler).precompile(
                max_depth=depth, windows=wins)
        else:
            from racon_tpu.ops.poa_graph import DeviceGraphPOA

            DeviceGraphPOA(5, -4, -8,
                           scheduler=polisher.scheduler).precompile(
                windows=wins)
        precompile_time = time.perf_counter() - t
        print(f"[bench] device precompile: {precompile_time:.2f}s "
              f"(compile cache {'warm' if cache_warm else 'cold'})",
              file=sys.stderr)

    n_windows = len(polisher.windows)
    t1 = time.perf_counter()
    polished = polisher.polish()
    t2 = time.perf_counter()

    dist, identity = _identity(polished)
    polish_time = t2 - t1
    wps = n_windows / polish_time if polish_time > 0 else 0.0
    print(f"[bench] initialize: {init_time:.2f}s  polish: {polish_time:.2f}s "
          f"({n_windows} windows, {mode} engine)", file=sys.stderr)
    print(f"[bench] edit distance vs reference assembly: {dist} "
          f"(identity {identity * 100:.2f}%; reference CPU fixture: 1312)",
          file=sys.stderr)
    rec = {"mode": mode, "wps": wps, "windows": n_windows, "dist": dist,
           "init_s": round(init_time, 2),
           "precompile_s": round(precompile_time, 2),
           "cache_warm": cache_warm,
           "adaptive_buckets": polisher.scheduler.adaptive,
           "stages": _stage_fields(polisher),
           "occupancy": polisher.occupancy_stats,
           "mesh": _mesh_info(),
           # the unified observability snapshot (racon_tpu/obs): the
           # stage/occupancy fields above, re-published under one
           # namespaced schema (pipeline.* / sched.* / resilience.*)
           "metrics": polisher.metrics.snapshot()}
    if device:
        rec["platform"] = _jax_platform()
    print(json.dumps(rec))
    return 0


def _jax_platform() -> str:
    import jax

    return jax.devices()[0].platform


def _mesh_info() -> dict:
    """The shared mesh-block schema (parallel/mesh.py). Worker lanes
    are a serve-only concept — one-shot bench phases always run 1."""
    from racon_tpu.parallel.mesh import mesh_info

    return mesh_info()


def _cpu_backend_refused() -> bool:
    """Blind attempt (probe failed): a jax that silently fell back to the
    CPU backend must not mislabel a CPU number as a device number."""
    if not os.environ.get("RACON_TPU_REQUIRE_ACCELERATOR"):
        return False
    if _jax_platform() == "cpu":
        print("[bench] blind device phase: backend is CPU — refusing to "
              "report it as a device number", file=sys.stderr)
        return True
    return False


def phase_aligner() -> int:
    """Child process: device-aligner smoke — overlap alignment phase only
    (initialize), device kernel mandatory (STRICT). Long overlaps host-
    align (counted as device skips, the cudaaligner exceeded_max_length
    discipline) so the smoke stays inside its wall cap."""
    if _cpu_backend_refused():
        return 3
    os.environ.setdefault("RACON_TPU_ALIGNER_MAXLEN", "16384")
    polisher = build_polisher(0, aligner_batches=1)
    t0 = time.perf_counter()
    polisher.initialize()
    t1 = time.perf_counter()
    print(f"[bench] device aligner initialize: {t1 - t0:.2f}s "
          f"({polisher.n_aligner_device}/{polisher.n_aligner_pairs} pairs "
          f"on device, {polisher.n_aligner_host_fallback} host fallbacks)",
          file=sys.stderr)
    # initialize-only flow: polish() never runs, so emit any armed
    # trace/metrics artifacts explicitly
    polisher.emit_observability()
    print(json.dumps({"mode": "aligner", "seconds": round(t1 - t0, 2),
                      "platform": _jax_platform(),
                      "pairs": polisher.n_aligner_pairs,
                      "device_pairs": polisher.n_aligner_device,
                      "host_fallbacks": polisher.n_aligner_host_fallback,
                      "adaptive_buckets": polisher.scheduler.adaptive,
                      "stages": _stage_fields(polisher),
                      "occupancy": polisher.occupancy_stats,
                      "mesh": _mesh_info(),
                      "metrics": polisher.metrics.snapshot()}))
    return 0


def _run_phase(phase: str, cap: float, strict: bool, argv=None,
               env_extra=None, expect_json: bool = True):
    """Run one phase in a subprocess under a wall-clock cap. Returns the
    parsed JSON result dict (or {"rc": 0} when expect_json=False), or
    None on timeout/failure."""
    env = dict(os.environ, **(env_extra or {}))
    # a None value removes the variable
    env = {k: v for k, v in env.items() if v is not None}
    if strict:
        env["RACON_TPU_STRICT"] = "1"
    # phases are separate processes; a persistent compilation cache lets
    # later phases (and warm re-runs) reuse earlier phases' XLA compiles.
    # JAX_COMPILATION_CACHE_DIR places it when set, else the checkout's
    # own .jax_cache
    from racon_tpu.sched import default_cache_dir

    env["JAX_COMPILATION_CACHE_DIR"] = default_cache_dir()
    cmd = argv or [sys.executable, os.path.abspath(__file__),
                   "--phase", phase]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=cap, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired as e:
        if e.stderr:
            text = (e.stderr.decode(errors="replace")
                    if isinstance(e.stderr, bytes) else e.stderr)
            sys.stderr.write(text[-2000:])
        print(f"[bench] phase {phase}: TIMEOUT after {cap:.0f}s",
              file=sys.stderr)
        return None
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        print(f"[bench] phase {phase}: rc={proc.returncode}; stdout tail: "
              f"{proc.stdout[-500:]!r}", file=sys.stderr)
        return None
    if not expect_json:
        return {"rc": 0}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print(f"[bench] phase {phase}: unparseable stdout "
              f"{proc.stdout[-500:]!r}", file=sys.stderr)
        return None


def _run_scale(cap: float) -> None:
    """Synthetic 250 kb / 20x polish on the fused device engine
    (tools/synthbench.py) — a scale data point toward BASELINE.md's
    E.-coli north star, reported on stderr only. STRICT so a device
    failure cannot masquerade as a device scale number."""
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "synthbench.py")
    _run_phase("scale", cap, strict=True,
               argv=[sys.executable, tool, "--genome-kb", "250",
                     "--coverage", "20", "-c", "1", "--fast-sim"],
               env_extra={"RACON_TPU_ENGINE": "fused",
                          "RACON_TPU_FUSED_FALLBACK": "host"},
               expect_json=False)


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] == "--phase":
        if sys.argv[2] == "aligner":
            return phase_aligner()
        return phase_consensus(sys.argv[2])

    t_start = time.monotonic()
    budget = float(os.environ.get("RACON_TPU_BENCH_BUDGET", "1500"))

    def room(reserve: float) -> float:
        """Wall-clock left inside the overall budget after `reserve`."""
        return budget - (time.monotonic() - t_start) - reserve

    forced = os.environ.get("RACON_TPU_POA_BATCHES")
    try_blind = False
    if forced is not None:
        want_device = int(forced) > 0
    else:
        want_device = probe_device()
        if not want_device:
            # A failed probe must not silently downgrade the round to
            # host-only (round-4 failure mode): attempt ONE capped STRICT
            # fused phase anyway. On a dead device this costs exactly one
            # subprocess cap; on a slow-but-alive device it saves the
            # round's device number.
            try_blind = True
    print(f"[bench] device reachable: {want_device}"
          + (" (probe failed; will attempt fused phase blind)"
             if try_blind else ""), file=sys.stderr)

    # Two device engines, both measured when the chip is up: the fused
    # single-launch engine first (the cudapoa-shaped flagship; leftover
    # windows host-polished), then the per-layer session engine (device
    # consensus byte-identical to host). The headline metric is the
    # faster one; every phase runs under both its own cap and the global
    # budget (the host phase's slice is always reserved).
    fused_res = None
    device_res = None
    fused_attempted = False
    if want_device or try_blind:
        cap = min(_FUSED_CAP, room(_HOST_CAP + 60))
        if cap > 120:
            fused_attempted = True
            extra = ({"RACON_TPU_REQUIRE_ACCELERATOR": "1"}
                     if try_blind else None)
            fused_res = _run_phase("fused", cap, strict=True,
                                   env_extra=extra)
        if try_blind and fused_res is not None:
            # the blind attempt reached the chip after all — the device
            # was slow, not dead; run the remaining device phases too
            want_device = True
    if want_device:
        cap = min(_DEVICE_CAP, room(_HOST_CAP + 60))
        if cap > 120:
            device_res = _run_phase("device", cap, strict=True)
    # aligner phase: attempted whenever a device is KNOWN to exist (probe
    # success, forced, or the blind fused phase reached the chip — which
    # sets want_device), NOT gated on a consensus phase succeeding
    # (round-4 verdict: the gate meant this kernel never produced a
    # recorded number). A blind fused phase that RAN and failed means the
    # device is dead: skip the blind aligner attempt too, so a dead
    # device costs exactly one subprocess cap and the CPU-pinned fallback
    # below runs immediately (ADVICE round-5). A blind fused phase that
    # never ran (budget too tight) proves nothing, so the blind aligner
    # attempt is still made then.
    aligner_res = None
    aligner_backend = "device"
    if want_device or (try_blind and not fused_attempted):
        cap = min(_ALIGNER_CAP, room(_HOST_CAP + 60 + 180))
        if cap > 60:
            extra = ({"RACON_TPU_REQUIRE_ACCELERATOR": "1"}
                     if not want_device else None)
            aligner_res = _run_phase("aligner", cap, strict=True,
                                     env_extra=extra)
    if aligner_res is None and (forced is None or int(forced) > 0):
        # no device-aligner number — record a CPU-backend one instead so
        # the artifact always carries cudaaligner-role evidence (pinned to
        # the CPU backend and labeled as such). Skipped only when the
        # operator explicitly forced the device off (tests do this).
        aligner_backend = "cpu"
        cap = min(240.0, room(_HOST_CAP + 60))
        if cap > 60:
            aligner_res = _run_phase(
                "aligner", cap, strict=True,
                env_extra={"JAX_PLATFORMS": "cpu", "PYTHONPATH": None,
                           "RACON_TPU_REQUIRE_ACCELERATOR": None})
    if want_device:
        # scale phase (stderr only, never the JSON artifact): the
        # north-star workload shape at ~5x the sample's window count,
        # on the fused device engine — run only when THAT engine just
        # proved itself and the budget has room
        cap = min(480.0, room(_HOST_CAP + 60))
        if fused_res is not None and cap > 240:
            _run_scale(cap)

    # host engine measured in every run: the comparison point for the
    # device number (stderr only when a device phase succeeded); its cap
    # honors the global budget too, but never drops below the floor it
    # needs to emit a number
    host_res = _run_phase("host", min(_HOST_CAP, max(120.0, room(0.0))),
                          strict=False)
    if host_res is not None:
        print(f"[bench] host engine: {host_res['wps']:.2f} windows/sec",
              file=sys.stderr)
    for r in (fused_res, device_res):
        if r is not None:
            print(f"[bench] {r['mode']} engine: {r['wps']:.2f} windows/sec",
                  file=sys.stderr)

    # aligner evidence rides the artifact line as extra fields (round-4
    # verdict #6: the cudaaligner-role kernel must produce a recorded
    # number regardless of the consensus phases' outcome)
    aligner_fields = {}
    if aligner_res is not None:
        aligner_fields = {
            # the phase reports the platform jax actually ran on — a
            # forced run on a silently-CPU jax is labeled cpu, not device
            "aligner_backend": aligner_res.get("platform",
                                               aligner_backend),
            "aligner_seconds": aligner_res.get("seconds"),
            "aligner_pairs": aligner_res.get("pairs"),
            "aligner_device_pairs": aligner_res.get("device_pairs"),
            "aligner_host_fallbacks": aligner_res.get("host_fallbacks"),
        }

    on_device = [r for r in (fused_res, device_res) if r is not None]
    res = max(on_device, key=lambda r: r["wps"]) if on_device else host_res
    if res is None:
        print(json.dumps({
            "metric": "sample_polish_consensus_throughput_failed",
            "value": 0.0, "unit": "windows/sec", "vs_baseline": 0.0,
            **aligner_fields}))
        return 1
    wps = float(res["wps"])
    # per-stage pipeline counters of the headline phase: the overlap win
    # is measurable (pack+device+unpack > phase wall) and a silently-dead
    # pipeline is visible (device seconds ~ 0)
    stage_fields = ({"stages": res["stages"]} if "stages" in res else {})
    # per-bucket occupancy of the headline phase (sched/ telemetry): how
    # much of each dispatched device shape was real work, plus warm-vs-
    # cold compile-cache evidence for the initialize-time comparison
    for key in ("occupancy", "init_s", "precompile_s", "cache_warm",
                "adaptive_buckets", "metrics", "mesh"):
        if key in res:
            stage_fields[key] = res[key]
    label = {"fused": "device_fused", "device": "device",
             "host": "host"}[res["mode"]]
    # honesty clause: a device-engine phase that actually ran on the CPU
    # backend (forced rehearsal, or jax silently falling back) must not
    # be labeled as a device number
    if res["mode"] != "host" and res.get("platform") == "cpu":
        label += "_cpubackend"
    print(json.dumps({
        "metric": f"sample_polish_consensus_throughput_{label}",
        "value": round(wps, 2),
        "unit": "windows/sec",
        "vs_baseline": round(wps / REFERENCE_CPU_WINDOWS_PER_SEC, 3),
        **stage_fields,
        **aligner_fields,
    }))
    # optional perf regression gate (tools/perfgate.py): stderr verdict
    # only — the JSON-line contract above is the artifact either way,
    # and a gate bug must never cost the round its number
    if os.environ.get("RACON_TPU_PERFGATE"):
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import perfgate

            ok, delta = perfgate.gate(
                wps, REFERENCE_CPU_WINDOWS_PER_SEC,
                float(os.environ.get("RACON_TPU_PERFGATE_TOL", "10")),
                higher_better=True)
            print(f"[bench] perfgate {'PASS' if ok else 'FAIL'}: "
                  f"{wps:.2f} windows/sec vs reference-CPU baseline "
                  f"{REFERENCE_CPU_WINDOWS_PER_SEC:g} ({delta:+.1f}%)",
                  file=sys.stderr)
        except Exception as exc:
            print(f"[bench] perfgate unavailable ({exc})",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
