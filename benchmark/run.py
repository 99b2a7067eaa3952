"""Benchmark harness: one cell of BENCHMARK.json on the chips of this
machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to a cell is found by name: the cell in
BENCHMARK.json names its configuration (`benchmark/configs/<config>.json`,
the deployment's shapes and the limits of its comparison) and its
traffic (`benchmark/traffic/<traffic>.json`, how jobs are cut and how
many a run generates); the configuration names its
job maker (`gen.py`); each metric is a reader in
`benchmark/metrics/<metric>.py`.

One process holds the chip. Set-up generates the cell's jobs from the
seed (the traffic's `jobs`) and polishes each once: the program
compiles per shape of its inputs (the aligner one program per length
bucket, band and batch width, and a bucket's last batch is as wide as
the job leaves it), so only the window's own jobs warm every program
the window runs. So the window compiles nothing; the compiles or
persistent-cache loads it does make are counted and printed all the
same. The window then runs the jobs in turn, from the first again once
all have run, one in flight, each an in-process call of the normal
entry `racon_tpu.cli.main`, until `--seconds` have passed; the job in
flight finishes. With `--trace 1` the window's first job runs under the
JAX profiler and the per-layer metrics are reported instead of the
end-to-end ones; that run's device trace holds one event per program
execution. Once the window is closed and the device's peak memory
read, every completed job's polished FASTA is compared with the truth
it was simulated from (`reference.py`).

The persistent compile cache is `.jax_cache` at the checkout's root,
given to the program as JAX_COMPILATION_CACHE_DIR.

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or no racon_tpu beside this directory.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import io
import json
import os
import sys
import tempfile
import time

_T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEGRADATION_KEYS = ("faults", "retries", "timeouts", "breaker_trips",
                    "quarantined")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Compiles:
    """Counts the XLA compiles of this process through jax.monitoring:
    every backend compile request with its seconds, and how many
    programs the persistent compile cache answered."""

    def __init__(self):
        import jax

        self.n = 0
        self.s = 0.0
        self.hits = 0
        #: program name of every compile, in order
        self.names: list[str] = []

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.s += duration
                self.names.append(kw.get("fun_name", "?"))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[int, float, int]:
        return self.n, self.s, self.hits

    def since(self, mark) -> tuple[int, float, int]:
        return self.n - mark[0], self.s - mark[1], self.hits - mark[2]


@dataclasses.dataclass
class JobResult:
    """One job of the window, as the harness saw it."""
    name: str
    ok: bool
    windows: int = 0
    init_s: float = 0.0
    polish_s: float = 0.0
    wall_s: float = 0.0
    occupancy: dict = dataclasses.field(default_factory=dict)
    fasta: bytes = b""
    why: str = ""


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    setup_s: float
    window_s: float
    jobs: list[JobResult]
    trace: dict | None = None
    traced_windows: int = 0

    @property
    def done(self) -> list[JobResult]:
        return [j for j in self.jobs if j.ok]


def cache_dir() -> str:
    """The persistent compile cache's directory, made if missing: JAX
    writes no entry into a directory that does not exist."""
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    return path


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    """The metric's reader, benchmark/metrics/<name>.py: `read(run)`
    returns a number, or None when there is nothing to read."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_spec(bench: dict, workload: str):
    """(cell, config, traffic, metrics of this cell for trace 0 and 1)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{', '.join(sorted(cells))}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return cell, cfg, traffic, mine(bench["end_to_end"]), \
        mine(bench["per_layer"])


def job_argv(paths: list[str], cfg: dict, threads: int) -> list[str]:
    """The cell's racon_tpu argv: reads, overlaps, targets, the
    configuration's window and scores, and one device batch for both
    phases with device failures raised."""
    argv = list(paths) + [
        "-w", str(cfg["window_length"]), "-m", str(cfg["match"]),
        "-x", str(cfg["mismatch"]), "-g", str(cfg["gap"]),
        "-t", str(threads), "-c", "1", "--tpualigner-batches", "1",
        "--tpu-strict"]
    if cfg["mode"] == "fragment":
        argv.append("-f")
    return argv


def _spanned(fn, span: str, times: dict):
    """`fn` inside the profiler annotation `bench.<span>`, its seconds
    added to times[span]."""
    import jax

    def wrapper(*a, **kw):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{span}"):
                return fn(*a, **kw)
        finally:
            times[span] = times.get(span, 0.0) + time.perf_counter() - t0
    return wrapper


def run_job(name: str, argv: list[str]) -> JobResult:
    """`racon_tpu.cli.main(argv)` in this process, with the polisher it
    builds captured and its phases timed from here."""
    import jax
    from racon_tpu import cli
    from racon_tpu.core import polisher as polisher_mod

    built, times = [], {}
    real = polisher_mod.create_polisher

    def capture(*a, **kw):
        p = real(*a, **kw)
        for method, span in (("initialize", "initialize"),
                             ("find_overlap_breaking_points", "align"),
                             ("polish", "polish"),
                             ("_consensus_pass", "consensus"),
                             ("_stitch", "stitch")):
            setattr(p, method, _spanned(getattr(p, method), span, times))
        built.append(p)
        return p

    out = io.TextIOWrapper(io.BytesIO(), encoding="latin-1")
    polisher_mod.create_polisher = capture
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.job"), \
                contextlib.redirect_stdout(out):
            rc = cli.main(argv)
        out.flush()
    except Exception as exc:  # noqa: BLE001 — a failed job is counted
        return JobResult(name, False, wall_s=time.perf_counter() - t0,
                         why=f"{type(exc).__name__}: {exc}")
    finally:
        polisher_mod.create_polisher = real
    wall = time.perf_counter() - t0
    if rc != 0 or not built:
        return JobResult(name, False, wall_s=wall, why=f"exit {rc}")
    p = built[0]
    res = JobResult(name, True, windows=sum(p.window_counts.values()),
                    init_s=times.get("initialize", 0.0),
                    polish_s=times.get("polish", 0.0), wall_s=wall,
                    occupancy=p.occupancy_stats,
                    fasta=out.buffer.getvalue())
    ss = p.stage_stats
    bad = {k: ss.get(k, 0) for k in DEGRADATION_KEYS if ss.get(k, 0)}
    if bad:
        res.ok, res.why = False, f"degradation counters {bad}"
    elif p.window_counts.get("host", 0):
        res.ok, res.why = False, (f"{p.window_counts['host']} windows "
                                  "inside the device envelope polished "
                                  "on the host")
    return res


def check(jobs, results: list[JobResult], cfg: dict) -> dict:
    """The comparison that decides `correct`: each number that the
    configuration's `limits` names, beside its limit. `err_ppm`, the
    worst job's edits per million bases against its truth;
    `worst_piece_pct`, the worst anchored piece's share of edits;
    `missing`, targets with no record and records that are no target. Every started job is compared, also one that failed: a job
    that raised gave no answer, so all its targets count as missing. A
    window that started no job reads as all wrong."""
    import reference

    fragment = cfg["mode"] == "fragment"
    by_name = {j.name: j for j in jobs}
    got = ({"err_ppm": 0.0, "worst_piece_pct": 0.0, "missing": 0}
           if results else
           {"err_ppm": 1e6, "worst_piece_pct": 100.0, "missing": 0})
    for r in results:
        c = reference.compare(by_name[r.name], r.fasta, fragment, 0)
        got["err_ppm"] = max(got["err_ppm"],
                             1e6 * c["edits"] / max(1, c["bases"]))
        got["worst_piece_pct"] = max(got["worst_piece_pct"],
                                     c["worst_piece_pct"])
        got["missing"] += c["missing"] + c["extra"]
    return {k: {"value": got[k], "limit": v}
            for k, v in cfg["limits"].items()}


def is_correct(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def _quiet():
    """Profiler options that keep device events and the harness's own
    annotations, and leave out the Python call tracer and HLO protos,
    which would make the trace of one job too large to read back."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def window(jobs, argv_of, seconds: float, trace_dir: str | None,
           compiles) -> tuple[list[JobResult], float, tuple]:
    """The jobs in turn, back to back, until `seconds` have passed; the
    job in flight finishes. The first job runs under the profiler when
    `trace_dir`."""
    import jax

    results: list[JobResult] = []
    mark = compiles.mark()
    t0 = time.perf_counter()
    while not results or time.perf_counter() - t0 < seconds:
        job = jobs[len(results) % len(jobs)]
        prof = (jax.profiler.trace(trace_dir, profiler_options=_quiet())
                if trace_dir and not results else contextlib.nullcontext())
        with prof:
            results.append(run_job(job.name, argv_of(job)))
        r = results[-1]
        log(f"job {r.name}: ok={r.ok} windows={r.windows} "
            f"wall_s={r.wall_s} init_s={r.init_s} polish_s={r.polish_s}"
            + (f" why={r.why}" if r.why else ""))
    return results, time.perf_counter() - t0, compiles.since(mark)


def traced(trace_dir: str, windows: int):
    """Reduce the first job's trace; None when it holds no device op."""
    import glob

    import trace_reduce

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    tr = trace_reduce.load(files[0])
    jobs = [s for s in tr.spans if s[2] == "job"]
    if not jobs or not windows:
        return None
    return trace_reduce.reduce(tr, jobs[0][0], jobs[0][1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, e2e, per_layer = cell_spec(bench, args.workload)
    if (traffic.get("loop", "closed"), traffic.get("in_flight", 1)) != \
            ("closed", 1):
        log("FAIL: the harness drives a closed loop with one job in "
            "flight, and no other traffic")
        return 1
    if args.trace:
        # one trace event per program execution rather than per
        # operation: per-operation events of one job overflow the
        # profiler's 2 GB trace within seconds. The traced run's
        # programs compile apart from the untraced runs' for it.
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
            os.environ.get("LIBTPU_INIT_ARGS"),
            "--xla_enable_hlo_trace=false")))
    # the compile cache the program keeps: a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    sys.path.insert(0, ROOT)
    try:
        import racon_tpu
    except ImportError as exc:
        log(f"FAIL: no racon_tpu beside the benchmark ({exc})")
        return 1
    if not os.path.abspath(racon_tpu.__file__).startswith(ROOT + os.sep):
        log(f"FAIL: racon_tpu comes from {racon_tpu.__file__}")
        return 1
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"FAIL: JAX found no TPU (platform {devices[0].platform!r})")
        return 1
    if len(devices) < cell["chips"]:
        log(f"FAIL: {cell['chips']} chips asked, {len(devices)} found")
        return 1
    return measure(args, cfg, traffic, e2e, per_layer, devices)


def measure(args, cfg, traffic, e2e, per_layer, devices) -> int:
    import gen
    from racon_tpu.sched import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles()
    threads = len(os.sched_getaffinity(0))
    with tempfile.TemporaryDirectory(prefix="racon_bench_") as d:
        jobs = gen.make_jobs(args.seed, cfg, traffic, int(traffic["jobs"]))
        paths = {j.name: j.write(d) for j in jobs}

        def argv_of(job):
            return job_argv(paths[job.name], cfg, threads)

        m = compiles.mark()
        for job in jobs:
            w = run_job(job.name, argv_of(job))
            log(f"warm-up {job.name}: ok={w.ok} wall_s={w.wall_s}")
            if not w.ok:
                log(f"FAIL: the warm-up of {job.name} failed: {w.why}")
                return 1
        n, s, hits = compiles.since(m)
        setup_s = time.perf_counter() - _T0
        log(f"setup: setup_s={setup_s} compiles={n} compile_s={s} "
            f"cache_hits={hits} threads={threads}")

        trace_dir = os.path.join(d, "trace") if args.trace else None
        results, window_s, (n, s, hits) = window(
            jobs, argv_of, args.seconds, trace_dir, compiles)
        log(f"window: window_s={window_s} jobs={len(results)} "
            f"compiles_in_window={n} compile_s_in_window={s} "
            f"cache_hits_in_window={hits} "
            f"compiled={compiles.names[len(compiles.names) - n:]}")
        peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for dv in devices)
        log(f"memory: peak_hbm_bytes={peak}")
        run = Run(setup_s, window_s, results)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        if trace_dir:
            first = results[0]
            run.traced_windows = first.windows if first.ok else 0
            run.trace = traced(trace_dir, run.traced_windows)
        jobs_by = {j.name: j for j in jobs}
        checks = check([jobs_by[r.name] for r in results], results, cfg)

    metrics = {}
    for spec in (per_layer if args.trace else e2e):
        v = load_reader(spec["name"])(run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    out = {"correct": is_correct(checks), "attempted": len(results),
           "failed": sum(not r.ok for r in results), "metrics": metrics,
           "device": device,
           "window_compiles": {"compiles": n, "compile_s": s,
                               "cache_loads": hits}}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
