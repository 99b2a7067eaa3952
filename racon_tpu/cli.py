"""Command-line interface.

TPU-native equivalent of the reference CLI (src/main.cpp:47-169): same 12
common options plus TPU device knobs paralleling the reference's CUDA flags
(src/main.cpp:36-41, --cudapoa-batches/--cuda-banded-alignment/
--cudaaligner-batches/--cudaaligner-band-width), polished FASTA on stdout,
errors as `[racon_tpu::...] error: ...` on stderr with exit status 1.
"""

from __future__ import annotations

import sys

from . import __version__
from .errors import RaconError

HELP = """\
usage: racon_tpu [options ...] <sequences> <overlaps> <target sequences>
       racon_tpu serve [serve options ...]
       racon_tpu submit [submit options ...] <sequences> <overlaps> <target>
       racon_tpu cancel --socket SOCK (--job-id ID | --trace-id ID)
       racon_tpu router [router options ...]
       racon_tpu fleet [fleet options ...]

    subcommands (see `racon_tpu serve --help` / `racon_tpu submit --help`
    and the README "Serving" section):
        serve   run the warm polishing job server (one process keeps the
                engines compiled; jobs from many clients share device
                batches; live Prometheus metrics via the `scrape` RPC
                or `--metrics-port`, post-mortems via the always-on
                flight recorder and the `debug` RPC, an auditable
                lifecycle journal via `--journal`)
        submit  send one polishing job to a running server; polished
                FASTA on stdout, byte-identical to the one-shot run;
                `--progress` streams live phase/window progress (incl.
                queue position), `--stream` writes each polished
                contig the moment it finishes on the server,
                `--tenant` names the fair-scheduling bucket, and
                `--trace-out t.json` writes one merged Chrome trace of
                the request — through the router, a DISTRIBUTED trace:
                client, router and every participating replica as
                clock-synced process tracks in one artifact
                (`tools/tracereport.py` prints its critical path and
                per-stage cost attribution)
        cancel  cancel a queued or running job by --job-id or
                --trace-id (name jobs via `submit --trace-id`): queued
                jobs dequeue with a typed `cancelled` error to their
                submitter, running jobs withdraw at the next
                iteration/round boundary; through the router the
                cancel fans out to the job's shards
        router  shard-aware front-end over N warm serve replicas: one
                submit is split by contig across routable replicas
                (wrapper partition math, output byte-identical to a
                solo server), merged back in contig order; a durable
                journal ledger requeues a dead replica's shards onto
                healthy ones with streamed contigs deduped (each
                contig exactly once), and rolling restarts — drain,
                restart, rejoin on clean healthz — lose no jobs; when
                replicas outnumber contigs, contigs split further by
                window-range so a one-contig job scales past a single
                replica, and --autoscale arms the elastic-fleet loop
                that spawns/drains replicas with backlog pressure
                (README "Serving"; RACON_TPU_ROUTER_* env knobs,
                RACON_TPU_ROUTER_AUTOSCALE_* for the loop); the router
                keeps its own flight ring of plan/dispatch/merge spans
                — `--trace` (or RACON_TPU_ROUTER_TRACE) dumps it at
                drain, and a traced submit pulls every replica's spans
                into ONE merged trace (README "Distributed tracing &
                cost accounting")
        fleet   federate N replicas' metrics and health into one view:
                polls every endpoint in --endpoints /
                RACON_TPU_FLEET_ENDPOINTS, merges counters and latency
                histograms (exact bucket pooling, exemplars preserved),
                and serves the merged /metrics + /healthz on --port —
                healthy only while EVERY replica is reachable and not
                draining; `--json` prints one machine-readable fleet
                snapshot instead (README "Fleet view"; the live
                console is tools/servetop.py)

    #default output is stdout
    <sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences used for correction
    <overlaps>
        input file in MHAP/PAF/SAM format (can be compressed with gzip)
        containing overlaps between sequences and target sequences
    <target sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences which will be corrected

    options:
        -u, --include-unpolished
            output unpolished target sequences
        -f, --fragment-correction
            perform fragment correction instead of contig polishing
            (overlaps file should contain dual/self overlaps!)
        -w, --window-length <int>
            default: 500
            size of window on which POA is performed
        -q, --quality-threshold <float>
            default: 10.0
            threshold for average base quality of windows used in POA
        -e, --error-threshold <float>
            default: 0.3
            maximum allowed error rate used for filtering overlaps
        --no-trimming
            disables consensus trimming at window ends
        -m, --match <int>
            default: 3
            score for matching bases
        -x, --mismatch <int>
            default: -5
            score for mismatching bases
        -g, --gap <int>
            default: -4
            gap penalty (must be negative)
        -t, --threads <int>
            default: 1
            number of threads
        --version
            prints the version number
        -h, --help
            prints the usage
        -c, --tpupoa-batches <int>
            default: 0
            number of device batches for TPU accelerated polishing
        -b, --tpu-banded-alignment
            use banding approximation for alignment on TPU: banded POA
            results are trusted as-is (the clipped-result full-DP retry is
            skipped), trading exact host-engine parity for speed
        --tpu-engine <session|fused>
            default: session
            device consensus engine: per-layer evolving-graph session
            (byte-identical to the host engine) or single-launch
            whole-window fused (equal aggregate quality; rare tie-order
            divergence possible on deep windows)
        --tpu-pipeline-depth <int>
            default: 2
            async dispatch pipeline depth: chunks packed/in flight ahead
            of the one being unpacked (host pack, device compute, host
            unpack and host-fallback work all overlap); 0 disables the
            overlap entirely (synchronous path, for bisection)
        --tpu-device-timeout <float>
            default: 0 (off)
            watchdog deadline in seconds for each device-stage call; a
            call past the deadline raises a timeout and the chunk is
            retried with exponential backoff (RACON_TPU_DEVICE_RETRIES,
            default 1) before routing to the host fallback
        --tpu-adaptive-buckets
            derive each device engine's shape ladder from the run's own
            job-shape histogram (occupancy-aware batch scheduler) and
            pack shape-sorted batches, instead of the static worst-case
            ladders; output is byte-identical either way (mirrors
            RACON_TPU_ADAPTIVE_BUCKETS)
        --tpu-compile-cache <dir>
            default: <checkout>/.jax_cache
            persistent XLA compilation cache directory: repeated runs
            skip recompiles, including adaptive-bucket runs whose shapes
            are data-derived (mirrors RACON_TPU_COMPILE_CACHE); yields
            to JAX_COMPILATION_CACHE_DIR when that is set
        --tpu-pallas <0|1|auto>
            default: 0
            hand-tiled Pallas device kernels for the banded aligner and
            the session POA sweep: 1 = whenever the VMEM envelope fits,
            auto = per-bucket from the persisted autotuner winner table
            (profile with sched.autotune.profile_production; buckets
            without an entry
            dispatch XLA), 0 = XLA programs only. Output is
            byte-identical in every mode (mirrors RACON_TPU_PALLAS)
        --tpu-dtype <auto|int32|int16>
            default: auto
            DP score dtype policy: auto shrinks each bucket to int16
            when its overflow envelope proof holds (half the DP bytes,
            bit-identical results), int32 forces the wide oracle
            everywhere (mirrors RACON_TPU_DTYPE)
        --tpu-fused <auto|0|1>
            default: auto
            fused-engine chunk dispatch: 1 = the single-launch fused
            align->window-slice->POA program (device-side slicing, one
            launch + one fetch per chunk), 0 = the split chained path,
            auto = per depth bucket from the persisted autotuner winner
            table. Output is byte-identical in every mode; a faulted
            fused chunk falls back to the split path (mirrors
            RACON_TPU_FUSED)
        --tpu-strict
            re-raise device failures instead of degrading to the host
            fallback / per-window quarantine (mirrors RACON_TPU_STRICT;
            the bench/CI discipline)
        --tpu-fault-plan <spec>
            default: none
            deterministic fault injection for resilience testing
            (mirrors RACON_TPU_FAULT_PLAN): comma-separated
            <stage>:chunk=<N>:<action> entries with stage one of
            pack|device|unpack|fallback and action raise | corrupt |
            hang=<seconds>, e.g. 'device:chunk=3:raise,unpack:chunk=2:corrupt'
        --tpu-trace <file>
            default: none
            record a span trace of the run (pipeline stages per chunk,
            engine dispatch loops, XLA compiles, fault/quarantine
            events) as Chrome trace-event JSON loadable in Perfetto /
            chrome://tracing (mirrors RACON_TPU_TRACE)
        --tpu-metrics <file>
            default: none
            dump the end-of-run metrics snapshot (pipeline.* / sched.* /
            resilience.* namespaces) as JSON, and render it as a stderr
            summary table (mirrors RACON_TPU_METRICS)
        --tpu-log-level <quiet|info|debug>
            default: info
            stderr verbosity: quiet silences progress/timing lines, info
            is the classic output, debug additionally shows every
            deduplicated per-chunk warning (mirrors RACON_TPU_LOG_LEVEL)
        --tpu-jax-profile <dir>
            default: none
            one jax.profiler capture of the whole run into <dir>: the
            device tracks and the racon.* host spans on one timeline
            (deep-dive XLA/TPU view; no-op when the backend cannot
            profile; mirrors RACON_TPU_PROFILE)
        --tpualigner-batches <int>
            default: 0
            number of device batches for TPU accelerated alignment
        --tpualigner-band-width <int>
            default: 0
            Band width for TPU alignment. Must be >= 0. Non-zero allows user
            defined band width, whereas 0 implies auto band width
            determination.
"""


def parse_args(argv: list[str]) -> dict | None:
    """getopt-style parser mirroring src/main.cpp:75-155.

    Returns the option dict, or None when --help/--version already handled.
    Mimics getopt_long behaviors the reference relies on: intermixed options
    and positionals, `-c` with an optional argument (src/main.cpp:113-125).
    """
    opts = {
        "window_length": 500,
        "quality_threshold": 10.0,
        "error_threshold": 0.3,
        "trim": True,
        "match": 3,
        "mismatch": -5,
        "gap": -4,
        "fragment_correction": False,
        "drop_unpolished_sequences": True,
        "num_threads": 1,
        "tpu_poa_batches": 0,
        "tpu_aligner_batches": 0,
        "tpu_aligner_band_width": 0,
        "tpu_banded_alignment": False,
        "tpu_engine": None,
        "tpu_pipeline_depth": 2,
        "tpu_device_timeout": 0.0,
        "tpu_strict": False,
        "tpu_fault_plan": None,
        "tpu_adaptive_buckets": None,
        "tpu_compile_cache": None,
        "tpu_pallas": None,
        "tpu_dtype": None,
        "tpu_fused": None,
        "tpu_trace": None,
        "tpu_metrics": None,
        "tpu_log_level": None,
        "tpu_jax_profile": None,
        "paths": [],
    }

    def _engine_choice(v: str) -> str:
        if v not in ("session", "fused"):
            print("racon_tpu: --tpu-engine must be 'session' or 'fused'",
                  file=sys.stderr)
            sys.exit(1)
        return v

    def _pallas_choice(v: str) -> str:
        if v not in ("0", "1", "auto"):
            print("racon_tpu: --tpu-pallas must be '0', '1' or 'auto'",
                  file=sys.stderr)
            sys.exit(1)
        return v

    def _dtype_choice(v: str) -> str:
        if v not in ("auto", "int32", "int16"):
            print("racon_tpu: --tpu-dtype must be 'auto', 'int32' or "
                  "'int16'", file=sys.stderr)
            sys.exit(1)
        return v

    def _fused_choice(v: str) -> str:
        if v not in ("0", "1", "auto"):
            print("racon_tpu: --tpu-fused must be '0', '1' or 'auto'",
                  file=sys.stderr)
            sys.exit(1)
        return v

    def _level_choice(v: str) -> str:
        from .utils.logger import LEVEL_NAMES

        if v not in LEVEL_NAMES:
            names = ", ".join(f"'{n}'" for n in LEVEL_NAMES)
            print(f"racon_tpu: --tpu-log-level must be one of {names}",
                  file=sys.stderr)
            sys.exit(1)
        return v

    value_short = {"w": ("window_length", int),
                   "q": ("quality_threshold", float),
                   "e": ("error_threshold", float),
                   "m": ("match", int),
                   "x": ("mismatch", int),
                   "g": ("gap", int),
                   "t": ("num_threads", int)}
    value_long = {"window-length": ("window_length", int),
                  "quality-threshold": ("quality_threshold", float),
                  "error-threshold": ("error_threshold", float),
                  "match": ("match", int),
                  "mismatch": ("mismatch", int),
                  "gap": ("gap", int),
                  "threads": ("num_threads", int),
                  "tpualigner-batches": ("tpu_aligner_batches", int),
                  "tpualigner-band-width": ("tpu_aligner_band_width", int),
                  "tpu-engine": ("tpu_engine", _engine_choice),
                  "tpu-pipeline-depth": ("tpu_pipeline_depth", int),
                  "tpu-device-timeout": ("tpu_device_timeout", float),
                  "tpu-fault-plan": ("tpu_fault_plan", str),
                  "tpu-compile-cache": ("tpu_compile_cache", str),
                  "tpu-pallas": ("tpu_pallas", _pallas_choice),
                  "tpu-dtype": ("tpu_dtype", _dtype_choice),
                  "tpu-fused": ("tpu_fused", _fused_choice),
                  "tpu-trace": ("tpu_trace", str),
                  "tpu-metrics": ("tpu_metrics", str),
                  "tpu-log-level": ("tpu_log_level", _level_choice),
                  "tpu-jax-profile": ("tpu_jax_profile", str)}

    def flag(name: str) -> bool:
        if name in ("u", "include-unpolished"):
            opts["drop_unpolished_sequences"] = False
        elif name in ("f", "fragment-correction"):
            opts["fragment_correction"] = True
        elif name in ("T", "no-trimming"):
            opts["trim"] = False
        elif name in ("b", "tpu-banded-alignment"):
            opts["tpu_banded_alignment"] = True
        elif name == "tpu-strict":
            opts["tpu_strict"] = True
        elif name == "tpu-adaptive-buckets":
            opts["tpu_adaptive_buckets"] = True
        else:
            return False
        return True

    i = 0
    n = len(argv)

    def take_value(display: str) -> str:
        nonlocal i
        i += 1
        if i >= n:
            print(f"racon_tpu: option '{display}' requires an argument",
                  file=sys.stderr)
            sys.exit(1)
        return argv[i]

    while i < n:
        arg = argv[i]
        if arg == "--":
            opts["paths"].extend(argv[i + 1:])
            break
        if arg.startswith("--"):
            name, eq, inline = arg[2:].partition("=")
            if name in ("help",):
                print(HELP, end="")
                return None
            if name == "version":
                print(f"v{__version__}")
                return None
            if flag(name):
                pass
            elif name in value_long:
                key, conv = value_long[name]
                opts[key] = conv(inline if eq else take_value(arg))
            elif name == "tpupoa-batches":
                if eq:
                    opts["tpu_poa_batches"] = int(inline)
                elif i + 1 < n and argv[i + 1].isdigit():
                    i += 1
                    opts["tpu_poa_batches"] = int(argv[i])
                else:
                    opts["tpu_poa_batches"] = 1
            else:
                print(f"racon_tpu: unrecognized option '{arg}'",
                      file=sys.stderr)
                sys.exit(1)
        elif arg.startswith("-") and arg != "-":
            # short option cluster, getopt-style
            j = 1
            while j < len(arg):
                c = arg[j]
                if c == "h":
                    print(HELP, end="")
                    return None
                if c == "v":
                    print(f"v{__version__}")
                    return None
                if flag(c) and c != "b":
                    j += 1
                    continue
                if c == "b":
                    j += 1
                    continue
                if c in value_short:
                    key, conv = value_short[c]
                    rest = arg[j + 1:]
                    opts[key] = conv(rest) if rest else conv(take_value("-" + c))
                    break
                if c == "c":
                    # optional argument: attached, or next non-option argv
                    # (reference src/main.cpp:113-125)
                    rest = arg[j + 1:]
                    if rest:
                        opts["tpu_poa_batches"] = int(rest)
                    elif i + 1 < n and argv[i + 1].isdigit():
                        i += 1
                        opts["tpu_poa_batches"] = int(argv[i])
                    else:
                        opts["tpu_poa_batches"] = 1
                    break
                print(f"racon_tpu: invalid option -- '{c}'", file=sys.stderr)
                sys.exit(1)
        else:
            opts["paths"].append(arg)
        i += 1

    return opts


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    # serve-mode subcommands (README "Serving"): `serve` runs the warm
    # polishing job server, `submit` sends one job to it. Everything
    # else is the classic one-shot surface below, untouched.
    if argv and argv[0] == "serve":
        from .serve.server import serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "submit":
        from .serve.client import submit_main

        return submit_main(argv[1:])
    if argv and argv[0] == "cancel":
        from .serve.client import cancel_main

        return cancel_main(argv[1:])
    if argv and argv[0] == "router":
        from .serve.router import router_main

        return router_main(argv[1:])
    if argv and argv[0] == "fleet":
        from .obs.fleet import fleet_main

        return fleet_main(argv[1:])
    opts = parse_args(argv)
    if opts is None:
        return 0

    if len(opts["paths"]) < 3:
        print("[racon_tpu::] error: missing input file(s)!", file=sys.stderr)
        print(HELP, end="")
        return 1

    from .core.polisher import create_polisher, PolisherType
    from .obs import jax_profile, trace
    from .utils.logger import set_log_level

    import os

    saved_env: dict[str, str | None] = {}
    try:
        # posture flags mirror their env knobs (env-only knobs are
        # invisible in --help): set the env so every layer — pipelines
        # constructed anywhere, strict checks in the ops — sees them
        if opts["tpu_strict"]:
            os.environ["RACON_TPU_STRICT"] = "1"
        # kernel-plane posture: the engines resolve these env knobs at
        # construction, so setting them here threads the CLI choice
        # through every dispatcher (aligner, session, fused)
        if opts["tpu_pallas"] is not None:
            os.environ["RACON_TPU_PALLAS"] = opts["tpu_pallas"]
        if opts["tpu_dtype"] is not None:
            os.environ["RACON_TPU_DTYPE"] = opts["tpu_dtype"]
        if opts["tpu_fused"] is not None:
            os.environ["RACON_TPU_FUSED"] = opts["tpu_fused"]
        if opts["tpu_fault_plan"]:
            from .resilience import FaultPlan

            FaultPlan.parse(opts["tpu_fault_plan"])  # fail fast on typos
            os.environ["RACON_TPU_FAULT_PLAN"] = opts["tpu_fault_plan"]
        # observability knobs follow the same pattern, but restore on
        # exit (saved_env) — unlike the posture flags, a stale armed
        # tracer would make a later flagless in-process main() call
        # record (and overwrite) the earlier run's trace
        for key, env in (("tpu_trace", "RACON_TPU_TRACE"),
                         ("tpu_metrics", "RACON_TPU_METRICS"),
                         ("tpu_log_level", "RACON_TPU_LOG_LEVEL"),
                         ("tpu_jax_profile", "RACON_TPU_PROFILE")):
            if opts[key]:
                saved_env[env] = os.environ.get(env)
                os.environ[env] = opts[key]
        # the level and tracer resolve once per process: force a fresh
        # resolution from the environment just set, so this invocation's
        # flags win over any earlier resolution and every main() call
        # records into its own recorder
        set_log_level(opts["tpu_log_level"] or None)
        trace.reset()
        if opts["tpu_poa_batches"] > 0 or opts["tpu_aligner_batches"] > 0:
            from .sched import enable_compile_cache

            # device runs always keep their compiles (the placement
            # rule: JAX_COMPILATION_CACHE_DIR, else the option, else
            # the checkout's .jax_cache)
            opts["tpu_compile_cache"] = enable_compile_cache(
                opts["tpu_compile_cache"]
                or os.environ.get("RACON_TPU_COMPILE_CACHE"))
        polisher = create_polisher(
            opts["paths"][0], opts["paths"][1], opts["paths"][2],
            PolisherType.kF if opts["fragment_correction"]
            else PolisherType.kC,
            opts["window_length"], opts["quality_threshold"],
            opts["error_threshold"], opts["trim"], opts["match"],
            opts["mismatch"], opts["gap"], opts["num_threads"],
            opts["tpu_poa_batches"], opts["tpu_banded_alignment"],
            opts["tpu_aligner_batches"], opts["tpu_aligner_band_width"],
            opts["tpu_engine"], opts["tpu_pipeline_depth"],
            opts["tpu_device_timeout"], opts["tpu_adaptive_buckets"],
            opts["tpu_compile_cache"])
        with jax_profile():
            polisher.initialize()
            polished = polisher.polish(opts["drop_unpolished_sequences"])

        out = sys.stdout.buffer
        for seq in polished:
            out.write(b">" + seq.name.encode() + b"\n" + seq.data + b"\n")
        out.flush()
        return 0
    except RaconError as exc:
        print(str(exc), file=sys.stderr)
        return 1
    finally:
        if saved_env:
            for env, old in saved_env.items():
                if old is None:
                    os.environ.pop(env, None)
                else:
                    os.environ[env] = old
            set_log_level(None)
            trace.reset()


if __name__ == "__main__":
    sys.exit(main())
