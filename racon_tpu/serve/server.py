"""PolishServer: a long-lived, warm polishing job server.

The one-shot CLI pays engine construction, XLA compilation and ladder
warmup on EVERY run — the cost profile a high-traffic service cannot
afford (PR 3 measured warm-vs-cold precompile at 0.67 s vs 1.79 s, and
that is before interpreter + jax import). `PolishServer` keeps one
process alive and multiplexes many polish requests through it:

  - ONE warm engine set: the persistent compile cache and the adaptive-
    ladder posture are armed at startup, a synthetic warmup job runs the
    full path once, and every later job reuses the process-level jit
    caches — the warm submit path compiles nothing (asserted via the
    sched compile telemetry in tools/servebench.py).
  - requests flow through a bounded `JobQueue` (admission control with
    retry-after, FIFO-within-priority, per-job deadlines) to a small
    worker pool;
  - concurrent jobs' windows pool into the continuous `WindowBatcher`:
    a persistent device feeder packs bounded shape-homogeneous
    iterations, so late arrivals join the next dispatch instead of a
    round barrier (byte-identical per-job output), finished contigs
    stitch immediately and can stream to the client as `result_part`
    frames before the job completes;
  - per-tenant weighted fair scheduling on the queue (submit frames
    carry a `tenant` id; RACON_TPU_SERVE_TENANT_WEIGHTS) keeps one
    heavy client from monopolizing the feeder;
  - SIGTERM (or a `shutdown` request) triggers graceful drain: stop
    admitting, finish in-flight jobs, flush metrics/trace, exit;
  - per-job failure isolation: a job's `DeviceError` / quarantine storm
    (fault-injectable per job via its OWN fault plan) produces one typed
    error response; the server, its warm engines and concurrent jobs
    survive.

What is NOT isolated: jobs share one process, one device, one host
thread pool and one jit cache — a hard process crash (OOM, native
segfault) takes every in-flight job down. The serve layer trades that
blast radius for warmth; run several servers for fault domains.

Transport: a unix socket (default) or localhost TCP, length-prefixed
JSON frames (serve/protocol.py). `racon_tpu.cli serve` is the CLI
surface; `serve.client.PolishClient` the Python one.
"""

from __future__ import annotations

import contextlib
import gzip
import json
import os
import random
import socket
import sys
import tempfile
import threading
import time

from collections import deque

from ..errors import RaconError
from ..obs import fleet as obs_fleet
from ..obs import flight as obs_flight
from ..obs import prom as obs_prom
from ..obs import trace as obs_trace
from ..obs.hist import HistogramSet
from ..obs.journal import Journal
from ..resilience import strict_scope
from ..utils.logger import log_info
from .batcher import WindowBatcher
from .protocol import (ProtocolError, error_response, max_frame_bytes,
                       recv_frame, send_frame)
from .queue import (DeadlineDoomed, Draining, Job, JobCancelledError,
                    JobQueue, QueueFull, TenantQuotaExceeded)

#: request option keys a submit may carry; anything else is rejected
#: with `bad-request` (a typo'd knob must not silently polish with
#: defaults)
ALLOWED_OPTIONS = frozenset((
    "window_length", "quality_threshold", "error_threshold", "trim",
    "match", "mismatch", "gap", "fragment_correction",
    "include_unpolished", "tpu_poa_batches", "tpu_banded_alignment",
    "tpu_aligner_batches", "tpu_aligner_band_width", "tpu_engine",
    "tpu_pipeline_depth", "tpu_device_timeout"))

DEFAULT_SOCKET = "/tmp/racon_tpu_serve.sock"

#: hard cap on `rounds=N` per submit — polishing converges in 2-4
#: rounds in practice; a runaway N must not pin a worker forever
MAX_ROUNDS = 64


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _deprecated_knob(name: str, what: str) -> None:
    """Round-barrier-era knobs are deprecated loudly, never silently
    ignored: a Python warning for programmatic users plus a stderr line
    for operators."""
    import warnings

    warnings.warn(f"{name} is deprecated since the continuous-batching "
                  f"rework: {what}", DeprecationWarning, stacklevel=3)
    log_info(f"[racon_tpu::serve] warning: {name} is deprecated "
             f"({what})")


def _parse_tenant_weights(raw) -> dict:
    """Tenant weight table from a dict or a "a=4,b=1,default=1" string.
    Strict: malformed entries fail ServeConfig (startup), mirroring the
    --metrics-port discipline."""
    if not raw:
        return {}
    if isinstance(raw, dict):
        items = raw.items()
    else:
        items = []
        for part in str(raw).split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise RaconError(
                    "ServeConfig",
                    f"invalid tenant weight entry {part!r} "
                    "(expected tenant=weight)")
            items.append(part.split("=", 1))
    out: dict = {}
    for tenant, weight in items:
        try:
            w = float(weight)
        except (TypeError, ValueError):
            raise RaconError(
                "ServeConfig",
                f"invalid tenant weight {weight!r} for tenant "
                f"{tenant!r} (expected a number)") from None
        if w <= 0:
            raise RaconError(
                "ServeConfig",
                f"tenant weight for {tenant!r} must be positive, "
                f"got {w}")
        out[str(tenant)] = w
    return out


class ServeConfig:
    """Server posture: transport, capacity, and the polish defaults jobs
    inherit when their request omits an option. Every field defaults
    from its RACON_TPU_SERVE_* env knob so a bare `racon_tpu serve` is
    deployable; constructor kwargs win over the environment."""

    def __init__(self, **kw):
        env = os.environ.get
        self.socket_path = kw.pop(
            "socket_path", env("RACON_TPU_SERVE_SOCKET") or DEFAULT_SOCKET)
        # None = unix socket; an int (including 0 = ephemeral, the real
        # port is published back into the config) = localhost TCP
        self.port = kw.pop(
            "port", _env_int("RACON_TPU_SERVE_PORT", -1)
            if env("RACON_TPU_SERVE_PORT") else None)
        self.workers = max(1, kw.pop(
            "workers", _env_int("RACON_TPU_SERVE_WORKERS", 2)))
        self.queue_depth = max(1, kw.pop(
            "queue_depth", _env_int("RACON_TPU_SERVE_QUEUE_DEPTH", 16)))
        self.drain_timeout_s = kw.pop(
            "drain_timeout_s", _env_float("RACON_TPU_SERVE_DRAIN_S", 30.0))
        # continuous-batching feeder knobs (serve/batcher.py):
        # iteration_windows bounds one device iteration's batch,
        # max_wait_s optionally lets a sparse pool coalesce briefly
        # before a short iteration (0 = dispatch the moment work is
        # pending — the default; there is no round gather anymore)
        self.iteration_windows = max(1, kw.pop(
            "iteration_windows",
            _env_int("RACON_TPU_SERVE_ITERATION_WINDOWS", 256)))
        # sub-mesh worker lanes (serve/batcher.py): partition the device
        # list into K independent sub-meshes, each with its own feeder
        # thread + exec lock, so iterations run concurrently across the
        # slice; 1 (the default) keeps the single full-mesh feeder
        self.worker_lanes = max(1, kw.pop(
            "worker_lanes", _env_int("RACON_TPU_WORKER_LANES", 1)))
        # hard per-tenant admission quota (queue.py): cap on QUEUED jobs
        # per tenant, rejected typed with retry_after; 0 = off. Weights
        # shape service order; the quota is the only thing stopping one
        # tenant from filling the whole queue depth
        self.tenant_quota = max(0, kw.pop(
            "tenant_quota",
            _env_int("RACON_TPU_SERVE_TENANT_QUOTA", 0)))
        # QoS layer (queue.py + batcher.py + the cancel RPC), all off
        # by default — with none of the three configured, every serve
        # surface is byte-identical to the pre-QoS server (test-
        # pinned). Strict env parsing throughout, mirroring the
        # --metrics-port / RACON_TPU_WINCACHE discipline: a typo'd
        # operator value fails the start, never silently serves with
        # QoS half-armed.
        # preempt: a newly admitted higher-priority job may preempt a
        # running lower-priority one (its not-yet-dispatched windows
        # park between iterations; it resumes byte-identically when
        # capacity frees)
        if "preempt" in kw:
            self.preempt = bool(kw.pop("preempt"))
        else:
            raw = env("RACON_TPU_SERVE_PREEMPT")
            if raw:
                try:
                    self.preempt = bool(int(raw))
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        f"invalid RACON_TPU_SERVE_PREEMPT value "
                        f"{raw!r} (expected an integer)") from None
            else:
                self.preempt = False
        # abort_margin: speculative deadline-abort margin in seconds
        # (None = off) — both at admission (queue EMA) and mid-run
        # (batcher iteration-boundary estimate)
        if "abort_margin" in kw:
            raw_am = kw.pop("abort_margin")
            self.abort_margin = (None if raw_am is None
                                 else max(0.0, float(raw_am)))
        else:
            raw = env("RACON_TPU_SERVE_ABORT_MARGIN")
            if raw:
                try:
                    self.abort_margin = max(0.0, float(raw))
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        "invalid RACON_TPU_SERVE_ABORT_MARGIN value "
                        f"{raw!r} (expected a number of seconds)") \
                        from None
            else:
                self.abort_margin = None
        # tenant_burst: token-bucket capacity letting a tenant briefly
        # exceed its hard quota, refilled at its DRR weight per second
        if "tenant_burst" in kw:
            self.tenant_burst = max(0, int(kw.pop("tenant_burst")))
        else:
            raw = env("RACON_TPU_SERVE_TENANT_BURST")
            if raw:
                try:
                    self.tenant_burst = max(0, int(raw))
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        "invalid RACON_TPU_SERVE_TENANT_BURST value "
                        f"{raw!r} (expected an integer)") from None
            else:
                self.tenant_burst = 0
        explicit_max_wait = "max_wait_s" in kw
        self.max_wait_s = max(0.0, kw.pop(
            "max_wait_s",
            _env_float("RACON_TPU_SERVE_MAX_WAIT_MS", 0.0) / 1000.0))
        # deprecated round-barrier knobs: the gather window aliases to
        # the feeder's coalescing wait, min_gather has no continuous
        # equivalent — both warn, neither is silently ignored
        explicit_gather = "gather_window_s" in kw
        if explicit_gather:
            _deprecated_knob(
                "gather_window_s",
                "aliased to max_wait_s (the feeder's coalescing wait); "
                "use max_wait_s / --max-wait-ms")
            val = float(kw.pop("gather_window_s"))
            # the deprecated alias must never beat the explicit NEW knob
            if not explicit_max_wait:
                self.max_wait_s = max(0.0, val)
        if "min_gather" in kw:
            _deprecated_knob(
                "min_gather",
                "the continuous feeder has no round to fill — the knob "
                "is ignored")
            kw.pop("min_gather")
        # env fallback only when NO explicit knob (new or deprecated)
        # was passed — an explicit argument must never lose to the
        # environment
        if env("RACON_TPU_SERVE_GATHER_MS") \
                and not env("RACON_TPU_SERVE_MAX_WAIT_MS") \
                and not explicit_max_wait and not explicit_gather:
            _deprecated_knob(
                "RACON_TPU_SERVE_GATHER_MS",
                "aliased to the feeder's max wait; set "
                "RACON_TPU_SERVE_MAX_WAIT_MS")
            self.max_wait_s = max(
                0.0, _env_float("RACON_TPU_SERVE_GATHER_MS", 0.0)
                / 1000.0)
        # per-tenant fair-scheduling weights: "gold=4,free=1,default=1"
        # (queue.py weighted deficit round-robin); strict parse — a
        # typo'd weights string fails the start, not the fairness
        self.tenant_weights = _parse_tenant_weights(kw.pop(
            "tenant_weights",
            env("RACON_TPU_SERVE_TENANT_WEIGHTS") or None))
        # identity-audit sentinel (obs/audit.py): the fraction of
        # production windows deterministically sampled for shadow
        # re-execution through the oracle path. 0 (the default) keeps
        # every serve surface byte-identical to the pre-audit code;
        # the companion knobs gate the mismatch consequences (online
        # winner-table demotion, lane quarantine/re-probe)
        self.audit_rate = min(1.0, max(0.0, kw.pop(
            "audit_rate", _env_float("RACON_TPU_AUDIT_RATE", 0.0))))
        self.audit_demote = bool(kw.pop(
            "audit_demote",
            (env("RACON_TPU_AUDIT_DEMOTE") or "1") != "0"))
        self.lane_quarantine = bool(kw.pop(
            "lane_quarantine",
            (env("RACON_TPU_LANE_QUARANTINE") or "1") != "0"))
        # content-addressed window consensus cache (serve/wincache.py):
        # off by default; armed, the batcher consults it before a
        # window enters the pooled stream (a hit skips device dispatch)
        # and populates it on iteration completion. Strict env parsing,
        # mirroring the --metrics-port discipline: a typo'd value fails
        # the start, never silently serves uncached
        if "wincache" in kw:
            self.wincache = bool(kw.pop("wincache"))
        else:
            raw = env("RACON_TPU_WINCACHE")
            if raw:
                try:
                    self.wincache = bool(int(raw))
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        f"invalid RACON_TPU_WINCACHE value {raw!r} "
                        "(expected an integer)") from None
            else:
                self.wincache = False
        from .wincache import DEFAULT_MAX_BYTES as _WINCACHE_DEFAULT

        if "wincache_max_bytes" in kw:
            self.wincache_max_bytes = int(kw.pop("wincache_max_bytes"))
        else:
            raw = env("RACON_TPU_WINCACHE_MAX_BYTES")
            if raw:
                try:
                    self.wincache_max_bytes = int(raw)
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        "invalid RACON_TPU_WINCACHE_MAX_BYTES value "
                        f"{raw!r} (expected an integer)") from None
            else:
                self.wincache_max_bytes = _WINCACHE_DEFAULT
        if self.wincache_max_bytes <= 0:
            raise RaconError(
                "ServeConfig",
                f"invalid wincache_max_bytes "
                f"{self.wincache_max_bytes} (expected a positive "
                "integer)")
        # fragment streaming group size (core/polisher.FragmentStreamer):
        # corrected reads of a fragment job ship in bounded groups of
        # this many targets per result_part frame — one frame per read
        # would mean millions of tiny frames on a real read set. Strict
        # env parsing like every other serve knob.
        if "frag_group" in kw:
            self.frag_group = int(kw.pop("frag_group"))
        else:
            raw = env("RACON_TPU_FRAG_GROUP")
            if raw:
                try:
                    self.frag_group = int(raw)
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        f"invalid RACON_TPU_FRAG_GROUP value {raw!r} "
                        "(expected an integer)") from None
            else:
                self.frag_group = 64
        if self.frag_group <= 0:
            raise RaconError(
                "ServeConfig",
                f"invalid frag_group {self.frag_group} (expected a "
                "positive integer)")
        self.warmup = kw.pop("warmup", True)
        self.max_frame = kw.pop("max_frame", max_frame_bytes())
        # telemetry exposition: None = no HTTP endpoint (the scrape RPC
        # is always available); an int (0 = ephemeral, published back)
        # serves Prometheus text on localhost HTTP. The env value is
        # parsed STRICTLY: a typo'd port must fail at startup, not
        # silently bind an ephemeral one Prometheus will never find
        if "metrics_port" in kw:
            self.metrics_port = kw.pop("metrics_port")
        else:
            raw = env("RACON_TPU_SERVE_METRICS_PORT")
            if raw:
                try:
                    self.metrics_port = int(raw)
                except ValueError:
                    raise RaconError(
                        "ServeConfig",
                        f"invalid RACON_TPU_SERVE_METRICS_PORT {raw!r} "
                        "(expected an integer)") from None
            else:
                self.metrics_port = None
        if self.metrics_port is not None and self.metrics_port < 0:
            raise RaconError(
                "ServeConfig",
                f"invalid metrics port {self.metrics_port} "
                "(expected >= 0; 0 = ephemeral)")
        # flight recorder: directory for automatic per-job dumps when a
        # job fails / times out / misses its deadline; empty string or
        # None disables dumping (the ring itself stays on). Resolution:
        # kwarg > RACON_TPU_SERVE_FLIGHT_DIR > the process-wide
        # RACON_TPU_FLIGHT_DIR (obs/flight.py) > the /tmp default.
        # start() validates the resolved directory STRICTLY — a bad
        # path fails the start, mirroring the --metrics-port discipline
        #: whether the operator CHOSE the flight dir (kwarg or either
        #: env knob): only then is startup validation strict — the
        #: built-in /tmp default keeps PR-6's best-effort-per-dump
        #: posture, so a plain `racon_tpu serve` on a host where
        #: another user owns /tmp/racon_tpu_flight still starts
        self.flight_dir_explicit = (
            "flight_dir" in kw
            or env("RACON_TPU_SERVE_FLIGHT_DIR") is not None
            or obs_flight.default_dump_dir() is not None)
        self.flight_dir = kw.pop(
            "flight_dir", env("RACON_TPU_SERVE_FLIGHT_DIR",
                              obs_flight.default_dump_dir()
                              or "/tmp/racon_tpu_flight"))
        # durable event journal (obs/journal.py): JSONL lifecycle log of
        # every job transition, keyed by job + trace id; None (the
        # default) disables it. Also validated strictly at start()
        self.journal_path = kw.pop(
            "journal", env("RACON_TPU_SERVE_JOURNAL") or None)
        # polish defaults (jobs may override per request, except
        # num_threads: host threads are a server resource)
        self.window_length = kw.pop("window_length", 500)
        self.quality_threshold = kw.pop("quality_threshold", 10.0)
        self.error_threshold = kw.pop("error_threshold", 0.3)
        self.trim = kw.pop("trim", True)
        self.match = kw.pop("match", 3)
        self.mismatch = kw.pop("mismatch", -5)
        self.gap = kw.pop("gap", -4)
        self.job_threads = max(1, kw.pop("job_threads", 2))
        self.tpu_poa_batches = kw.pop("tpu_poa_batches", 0)
        self.tpu_aligner_batches = kw.pop("tpu_aligner_batches", 0)
        self.tpu_aligner_band_width = kw.pop("tpu_aligner_band_width", 0)
        self.tpu_banded_alignment = kw.pop("tpu_banded_alignment", False)
        self.tpu_engine = kw.pop("tpu_engine", None)
        self.tpu_pipeline_depth = kw.pop("tpu_pipeline_depth", 2)
        self.tpu_device_timeout = kw.pop("tpu_device_timeout", 0.0)
        self.tpu_adaptive_buckets = kw.pop("tpu_adaptive_buckets", None)
        self.tpu_compile_cache = kw.pop("tpu_compile_cache", None)
        if kw:
            raise RaconError("ServeConfig",
                             f"unknown option(s): {', '.join(sorted(kw))}")

    @property
    def address(self) -> str:
        return (f"127.0.0.1:{self.port}" if self.port is not None
                else self.socket_path)


def make_synth_dataset(dirname: str, seed: int = 11,
                       genome_len: int = 2000, read_len: int = 400,
                       step: int = 100,
                       contigs: int = 1) -> tuple[str, str, str]:
    """Tiny deterministic ONT-shaped dataset (reads/PAF/draft gz
    triple) — the warmup job's input, also reused by servebench and the
    serve tests. Overlength pairs are included so the device-aligner
    fallback path warms too. `contigs` > 1 emits that many independent
    draft contigs (each with its own reads and PAF rows) for the
    multi-contig streaming / router-sharding tests; `contigs` == 1 is
    byte-identical to what this function always produced (same rng call
    order, same `draft` / `r{k}` names)."""
    rng = random.Random(seed)
    acgt = b"ACGT"

    def mutate(s, rate):
        out = bytearray()
        for c in s:
            r = rng.random()
            if r < rate / 3:
                continue
            if r < 2 * rate / 3:
                out.append(rng.choice(acgt))
                out.append(c)
                continue
            if r < rate:
                out.append(rng.choice(acgt))
                continue
            out.append(c)
        return bytes(out)

    reads, paf, drafts = [], [], []
    for c in range(max(1, contigs)):
        cname = "draft" if contigs <= 1 else f"ctg{c:02d}"
        truth = bytes(rng.choice(acgt) for _ in range(genome_len))
        draft = mutate(truth, 0.04)
        jobs = [(start, read_len)
                for start in range(0, genome_len - read_len, step)]
        jobs += [(0, genome_len - 700), (600, genome_len - 700)]
        for k, (start, length) in enumerate(jobs):
            read = mutate(truth[start:start + length], 0.05)
            rname = f"r{k}" if contigs <= 1 else f"r{c:02d}_{k}"
            reads.append((rname, read))
            t_end = min(start + length, len(draft))
            paf.append(f"{rname}\t{len(read)}\t0\t{len(read)}\t+\t"
                       f"{cname}\t{len(draft)}\t{start}\t{t_end}\t"
                       f"{length}\t{length}\t60")
        drafts.append((cname, draft))
    paths = (os.path.join(dirname, "reads.fasta.gz"),
             os.path.join(dirname, "ovl.paf.gz"),
             os.path.join(dirname, "draft.fasta.gz"))
    with gzip.open(paths[0], "wb") as f:
        for name, read in reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    with gzip.open(paths[1], "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    with gzip.open(paths[2], "wb") as f:
        for cname, draft in drafts:
            f.write(b">" + cname.encode() + b"\n" + draft + b"\n")
    return paths


def make_fragment_dataset(dirname: str, seed: int = 13,
                          genome_len: int = 2000, read_len: int = 400,
                          step: int = 100) -> tuple[str, str, str]:
    """Tiny deterministic reads-correcting-reads dataset for the
    fragment traffic class: staggered noisy reads off one truth genome
    plus their all-vs-all overlaps (PAF rows between position-adjacent
    read pairs). Returns (sequences, overlaps, target) where sequences
    and target are the SAME reads file — the one-shot CLI's
    `racon_tpu -f reads.fasta.gz ava.paf.gz reads.fasta.gz` shape —
    used by the serve fragment tests, servebench --fragment and
    faultcheck."""
    rng = random.Random(seed)
    acgt = b"ACGT"

    def mutate(s, rate):
        out = bytearray()
        for c in s:
            r = rng.random()
            if r < rate / 3:
                continue
            if r < 2 * rate / 3:
                out.append(rng.choice(acgt))
                out.append(c)
                continue
            if r < rate:
                out.append(rng.choice(acgt))
                continue
            out.append(c)
        return bytes(out)

    truth = bytes(rng.choice(acgt) for _ in range(genome_len))
    reads: list[tuple[str, bytes, int, int]] = []
    for k, start in enumerate(range(0, genome_len - read_len + 1,
                                    step)):
        end = min(start + read_len, genome_len)
        reads.append((f"f{k}", mutate(truth[start:end], 0.05),
                      start, end))
    paf = []
    for qn, qd, qs0, qe0 in reads:
        for tn, td, ts0, te0 in reads:
            if qn == tn:
                continue
            ov0, ov1 = max(qs0, ts0), min(qe0, te0)
            if ov1 - ov0 < read_len // 4:
                continue  # only meaningfully overlapping pairs
            # truth-coordinate overlap mapped onto each noisy read,
            # clamped to its (indel-shifted) actual length
            qlo = min(max(0, ov0 - qs0), len(qd))
            qhi = min(ov1 - qs0, len(qd))
            tlo = min(max(0, ov0 - ts0), len(td))
            thi = min(ov1 - ts0, len(td))
            if qhi <= qlo or thi <= tlo:
                continue
            paf.append(f"{qn}\t{len(qd)}\t{qlo}\t{qhi}\t+\t"
                       f"{tn}\t{len(td)}\t{tlo}\t{thi}\t"
                       f"{qhi - qlo}\t{qhi - qlo}\t60")
    reads_path = os.path.join(dirname, "frags.fasta.gz")
    ovl_path = os.path.join(dirname, "frags_ava.paf.gz")
    with gzip.open(reads_path, "wb") as f:
        for name, data, _s, _e in reads:
            f.write(b">" + name.encode() + b"\n" + data + b"\n")
    with gzip.open(ovl_path, "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    return reads_path, ovl_path, reads_path


class PolishServer:
    def __init__(self, config: ServeConfig | None = None, **overrides):
        self.config = config if config is not None \
            else ServeConfig(**overrides)
        cfg = self.config
        if cfg.tpu_compile_cache:
            from ..sched import enable_compile_cache

            enable_compile_cache(cfg.tpu_compile_cache)
        #: server-lifetime latency histograms (obs/hist.py): job
        #: end-to-end / queue wait / device iterations / pipeline
        #: stages / compiles — the scrape RPC's distribution view
        self.hists = HistogramSet()
        self.queue = JobQueue(cfg.queue_depth, workers=cfg.workers,
                              hists=self.hists,
                              tenant_weights=cfg.tenant_weights,
                              tenant_quota=cfg.tenant_quota,
                              tenant_burst=cfg.tenant_burst,
                              abort_margin=cfg.abort_margin)
        self.batcher = WindowBatcher(
            iteration_windows=cfg.iteration_windows,
            max_wait_s=cfg.max_wait_s,
            worker_lanes=cfg.worker_lanes)
        #: iteration-boundary speculative abort rides the batcher's
        #: consume loop (None keeps that check compiled out entirely)
        self.batcher.abort_margin = cfg.abort_margin
        #: QoS runtime state (all under `_qos_lock`): every RUNNING
        #: job by id (the cancel RPC's running-job lookup), the jobs
        #: currently parked by preemption, and the lifetime QoS
        #: counters. Counters live here (not in queue.counters) so the
        #: scrape can render them armed-only — queue counters render
        #: unconditionally and would break byte-identity when off.
        self._qos_lock = threading.Lock()
        self._running_jobs: dict[str, Job] = {}
        self._preempted: dict[str, Job] = {}
        self.qos = {"preemptions": 0, "aborted_doomed": 0,
                    "cancelled": 0}
        self.batcher.hists = self.hists
        self.batcher.pipeline_stats.hists = self.hists
        self.batcher.scheduler.stats.hists = self.hists
        #: identity-audit sentinel (obs/audit.py): armed only when the
        #: sampled fraction is nonzero — with it off, the scrape, the
        #: journal and the FASTA are byte-identical to the pre-audit
        #: server (test-pinned)
        self.auditor = None
        if cfg.audit_rate > 0.0:
            from ..obs.audit import WindowAuditor

            self.auditor = WindowAuditor(
                rate=cfg.audit_rate, demote=cfg.audit_demote,
                quarantine=cfg.lane_quarantine, hists=self.hists,
                flight_dir=cfg.flight_dir or None,
                on_alert=self._on_audit_alert)
            self.batcher.auditor = self.auditor
        #: content-addressed window consensus cache (serve/wincache.py)
        #: — armed only when configured; with it off the batcher path,
        #: the snapshot and the scrape are byte-identical to the
        #: pre-cache server (test-pinned)
        if cfg.wincache:
            from .wincache import WindowCache

            self.batcher.wincache = WindowCache(
                max_bytes=cfg.wincache_max_bytes)
        #: serve-native polishing rounds telemetry: jobs that requested
        #: rounds, rounds completed, live in-flight gauge. The scrape
        #: renders the families only once a rounds job has been seen
        self._rounds_lock = threading.Lock()
        self._rounds = {"jobs": 0, "completed": 0, "inflight": 0}
        #: flight recorder (obs/flight.py): installed at start() unless
        #: a full trace is already armed (then that recorder serves as
        #: the flight source too)
        self._flight: obs_trace.TraceRecorder | None = None
        self._flight_installed = False
        self._dumps: deque = deque(maxlen=8)
        self._http = None
        self._listener: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._conns: set[socket.socket] = set()
        self._conn_lock = threading.Lock()
        self._job_seq = 0
        self._job_seq_lock = threading.Lock()
        self._inflight = 0
        self._idle = threading.Condition()
        self._stop_workers = threading.Event()
        self._draining = threading.Event()
        self._stopped = threading.Event()
        self._t_start = time.perf_counter()
        #: wall-clock start time: exposed as the
        #: racon_tpu_serve_start_time_seconds gauge so a dashboard can
        #: tell a restarted server from a quiet one
        self._t_wall_start = time.time()
        self.journal: Journal | None = None
        self._warm: dict | None = None
        #: SLO burn-rate tracker (obs/fleet.py): sampled on every
        #: deadline-carrying job via the queue's on_slo hook; state
        #: transitions journal typed `alert` events and flip the
        #: racon_tpu_slo_burn_alert gauge. seed_zero: this process's
        #: counters were born with the tracker, so the very first miss
        #: counts against a zero baseline.
        self.burn = obs_fleet.BurnRateTracker(seed_zero=True)
        self.queue.on_slo = self._on_slo
        #: latency exemplars (obs/hist.py): on by default, disabled by
        #: RACON_TPU_SERVE_EXEMPLARS=0 — the byte-identity A/B knob
        self.exemplars_enabled = (
            os.environ.get("RACON_TPU_SERVE_EXEMPLARS", "1") != "0")
        #: self-metered exposition cost: seconds this process spent
        #: RENDERING scrape bodies (not wire or aggregator time) — the
        #: number servebench --fleet holds to the <2% budget
        self._scrape_count = 0
        self._scrape_render_s = 0.0
        self._scrape_lock = threading.Lock()
        #: admit-time ingest workdir (serve/ingest.py): lazily created
        #: server-lifetime scratch directory holding subsampled /
        #: pair-normalized inputs; removed on close()
        self._ingest_dir: str | None = None
        self._ingest_lock = threading.Lock()

    def _ingest_workdir(self) -> str:
        """Lazily created server-lifetime scratch directory for the
        ingest plane's rewritten inputs (subsample-on-admit, pair
        normalization). One directory per server so close() can remove
        every rewritten file in one sweep."""
        with self._ingest_lock:
            if self._ingest_dir is None:
                import tempfile

                self._ingest_dir = tempfile.mkdtemp(
                    prefix="racon-tpu-ingest-")
            return self._ingest_dir

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "PolishServer":
        """Warm up (unless disabled), bind the transport, spawn the
        worker pool and the accept loop. Returns self; the server is
        accepting when this returns."""
        cfg = self.config
        # strict startup validation (the --metrics-port discipline): an
        # operator who configured a flight-dump directory or an audit
        # journal must find out NOW that the path is unusable, not at
        # the first failed job / first lifecycle line
        if cfg.flight_dir and cfg.flight_dir_explicit:
            try:
                os.makedirs(cfg.flight_dir, exist_ok=True)
                probe = os.path.join(cfg.flight_dir,
                                     f".probe_{os.getpid()}")
                with open(probe, "w"):
                    pass
                os.unlink(probe)
            except OSError as exc:
                raise RaconError(
                    "PolishServer.start",
                    f"flight dump directory {cfg.flight_dir!r} is not "
                    f"writable ({exc}); point --flight-dir / "
                    "RACON_TPU_SERVE_FLIGHT_DIR / RACON_TPU_FLIGHT_DIR "
                    "at a writable directory, or '' to disable "
                    "dumping") from None
        if cfg.journal_path:
            try:
                self.journal = Journal(cfg.journal_path)
            except OSError as exc:
                raise RaconError(
                    "PolishServer.start",
                    f"cannot open serve journal {cfg.journal_path!r} "
                    f"({exc}); point --journal / "
                    "RACON_TPU_SERVE_JOURNAL at a writable path") \
                    from None
        # queue-side lifecycle transitions (started / expired) feed the
        # journal and the live progress relay
        self.queue.on_event = self._on_queue_event
        if self.auditor is not None:
            # the sentinel journals its annotation events (audit-
            # mismatch / audit-lane / alert) into the same lifecycle
            # journal, keyed by the owning job
            self.auditor.journal = self.journal
        # always-on flight recorder: when no full trace is armed,
        # install the bounded ring as the process tracer so every span
        # hook feeds it (<2% overhead, synthbench --flight A/Bs it);
        # an armed RACON_TPU_TRACE recorder doubles as the flight source
        tr = obs_trace.get_tracer()
        if tr is None:
            self._flight = obs_trace.install(obs_flight.FlightRecorder())
            self._flight_installed = True
        else:
            self._flight = tr
        if cfg.warmup:
            self.warmup()
        if cfg.metrics_port is not None:
            self._start_metrics_http()
        if cfg.port is not None:
            lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lst.bind(("127.0.0.1", max(0, cfg.port)))
            if cfg.port <= 0:  # ephemeral: publish the real port
                cfg.port = lst.getsockname()[1]
        else:
            with contextlib.suppress(OSError):
                os.unlink(cfg.socket_path)
            lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            lst.bind(cfg.socket_path)
        lst.listen(64)
        lst.settimeout(0.2)
        self._listener = lst
        for i in range(cfg.workers):
            t = threading.Thread(target=self._worker,
                                 name=f"racon-tpu-serve-worker-{i}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._accept_loop,
                             name="racon-tpu-serve-accept", daemon=True)
        t.start()
        self._threads.append(t)
        if self.journal is not None:
            self.journal.record("serve-start", address=cfg.address,
                                pid=os.getpid(), workers=cfg.workers,
                                queue_depth=cfg.queue_depth)
        log_info(f"[racon_tpu::serve] listening on {cfg.address} "
                 f"({cfg.workers} workers, queue depth "
                 f"{cfg.queue_depth}"
                 + (f", {cfg.worker_lanes} worker lanes"
                    if cfg.worker_lanes > 1 else "")
                 + (f", warm in {self._warm['warmup_s']:.2f}s"
                    if self._warm else "")
                 + (f", metrics on 127.0.0.1:{cfg.metrics_port}"
                    if self._http is not None else "")
                 + (f", journal {cfg.journal_path}"
                    if self.journal is not None else "") + ")")
        return self

    def _on_queue_event(self, event: str, job: Job, **fields) -> None:
        """JobQueue.on_event sink: journal the transition and, for a
        progress-streaming job, announce the queue->worker handoff.
        `admitted`/`expired` arrive UNDER the queue mutex, so they are
        STAGED (memory-only, order-preserving) rather than written — a
        stalled journal disk must not serialize every submit/pop/scrape
        behind it; the handler flushes once its job resolves."""
        if event == "started" and job.want_progress:
            job.notify_progress(
                {"phase": "start",
                 "queue_wait_s": fields.get("queue_wait_s")})
        if self.journal is not None:
            if event == "cancelled":
                # fired UNDER the queue mutex by queue.cancel: stage
                # the typed annotation AND the legal terminal (the job
                # never started, so it leaves as an expiry with the
                # reason pinned — `failed` would trip the journal's
                # ran-without-started check)
                self.journal.stage(event, job=job.id,
                                   trace=job.trace_id, **fields)
                self.journal.stage("expired", job=job.id,
                                   trace=job.trace_id,
                                   reason="cancelled")
            elif event in ("admitted", "expired"):
                self.journal.stage(event, job=job.id,
                                   trace=job.trace_id, **fields)
            else:
                self.journal.record(event, job=job.id,
                                    trace=job.trace_id, **fields)

    def _on_slo(self, job: Job, hit: int, miss: int) -> None:
        """JobQueue.on_slo sink: sample the burn-rate tracker with the
        cumulative deadline counters; a state transition journals a
        typed `alert` event carrying the job that tripped (or cleared)
        it, so obsreport's per-job timeline shows the alert next to
        the deadline-miss that caused it."""
        res = self.burn.sample(hit, miss)
        if res["changed"] and self.journal is not None:
            self.journal.record(
                "alert", job=job.id, trace=job.trace_id,
                kind="slo-burn",
                state="firing" if res["firing"] else "clear",
                burn_fast=res["fast"], burn_slow=res["slow"],
                threshold=res["threshold"],
                deadline_hit=hit, deadline_miss=miss)
        if res["changed"]:
            log_info(
                f"[racon_tpu::serve] SLO burn alert "
                f"{'FIRING' if res['firing'] else 'clear'}: "
                f"fast {res['fast']:g}x / slow {res['slow']:g}x of "
                f"budget (threshold {res['threshold']:g}x, "
                f"{miss} deadline misses)")

    def _on_audit_alert(self, state: str, detail: dict) -> None:
        """WindowAuditor.on_alert sink: a nonzero mismatch count flips
        the racon_tpu_audit_alert gauge (rendered from the auditor's
        live state) and journals a typed alert; the operator clears it
        with the debug RPC's `audit_ack`."""
        if self.journal is not None:
            self.journal.record(
                "alert", kind="audit-mismatch", state=state,
                mismatches=detail.get("mismatches"),
                acked=detail.get("acked"))
        log_info(f"[racon_tpu::serve] audit alert "
                 f"{'FIRING' if state == 'firing' else 'clear'}: "
                 f"{detail.get('mismatches', 0)} identity mismatches "
                 f"({detail.get('acked', 0)} acknowledged)")

    def healthz_snapshot(self) -> dict:
        """The health body both transports serve (`/healthz` HTTP —
        503 while draining — and the `healthz` RPC): ok + draining +
        enough context for a fleet view's per-replica detail."""
        draining = self._draining.is_set()
        return {"ok": not draining,
                "draining": draining,
                "warm": self._warm is not None,
                "uptime_s": round(
                    time.perf_counter() - self._t_start, 3),
                "queue_depth": len(self.queue),
                "inflight": self._inflight_count()}

    def _start_metrics_http(self) -> None:
        """Serve Prometheus text on localhost HTTP (stdlib only). Bind
        failure raises at start() — an operator asked for a port they
        cannot have — but once up, NO handler error ever propagates:
        a scrape bug answers 500 and the polish server keeps serving."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        polish_server = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                try:
                    path = self.path.split("?", 1)[0]
                    if path in ("/metrics", "/"):
                        body = polish_server.prometheus_text().encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         obs_prom.CONTENT_TYPE)
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    elif path == "/healthz":
                        # a draining replica answers 503 so a load
                        # balancer stops routing to it — the JSON body
                        # says WHY, for the operator behind the LB
                        doc = polish_server.healthz_snapshot()
                        body = (json.dumps(doc, sort_keys=True)
                                + "\n").encode()
                        self.send_response(200 if doc["ok"] else 503)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    else:
                        self.send_error(404)
                except Exception as exc:  # noqa: BLE001 — see docstring
                    with contextlib.suppress(Exception):
                        self.send_error(
                            500, f"{type(exc).__name__}: {exc}")

            def log_message(self, *args):  # scrapes must not spam stderr
                pass

        httpd = ThreadingHTTPServer(
            ("127.0.0.1", self.config.metrics_port), _Handler)
        httpd.daemon_threads = True
        self.config.metrics_port = httpd.server_address[1]
        self._http = httpd
        t = threading.Thread(target=httpd.serve_forever,
                             name="racon-tpu-serve-metrics-http",
                             daemon=True)
        t.start()

    def warmup(self, paths: tuple[str, str, str] | None = None) -> dict:
        """Run one job end to end (synthetic by default, or the caller's
        input triple — servebench passes its own so warmup shapes equal
        job shapes) so every engine the configured posture uses is jit-
        built before the first real request."""
        from ..core.polisher import PolisherType, create_polisher

        cfg = self.config
        t0 = time.perf_counter()
        with contextlib.ExitStack() as stack:
            if paths is None:
                tmp = stack.enter_context(
                    tempfile.TemporaryDirectory(prefix="racon_serve_warm_"))
                paths = make_synth_dataset(tmp)
            polisher = create_polisher(
                *paths, PolisherType.kC, cfg.window_length,
                cfg.quality_threshold, cfg.error_threshold, cfg.trim,
                cfg.match, cfg.mismatch, cfg.gap,
                num_threads=cfg.job_threads,
                tpu_poa_batches=cfg.tpu_poa_batches,
                tpu_banded_alignment=cfg.tpu_banded_alignment,
                tpu_aligner_batches=cfg.tpu_aligner_batches,
                tpu_aligner_band_width=cfg.tpu_aligner_band_width,
                tpu_engine=cfg.tpu_engine,
                tpu_pipeline_depth=cfg.tpu_pipeline_depth,
                tpu_device_timeout=cfg.tpu_device_timeout,
                tpu_adaptive_buckets=cfg.tpu_adaptive_buckets)
            polisher.initialize()
            polisher.polish(True, batcher=self.batcher)
        compiles, compile_s = self.batcher._compile_totals()
        self._warm = {"warmup_s": round(time.perf_counter() - t0, 3),
                      "compiles": compiles,
                      "compile_s": round(compile_s, 3)}
        return self._warm

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful shutdown: stop admitting, finish queued + in-flight
        jobs (bounded by `timeout`, default config.drain_timeout_s),
        flush observability, close the transport. True when everything
        finished inside the budget."""
        if self._draining.is_set():
            self._stopped.wait()
            return True
        self._draining.set()
        budget = (timeout if timeout is not None
                  else self.config.drain_timeout_s)
        if self.journal is not None:
            self.journal.record("drain", queued=len(self.queue),
                                inflight=self._inflight,
                                budget_s=round(budget, 1))
        log_info(f"[racon_tpu::serve] draining: {len(self.queue)} queued, "
                 f"{self._inflight} in flight (budget {budget:.0f}s)")
        self.queue.drain()
        if self._listener is not None:
            with contextlib.suppress(OSError):
                self._listener.close()
        deadline = time.monotonic() + budget
        clean = True
        with self._idle:
            while len(self.queue) or self._inflight:
                left = deadline - time.monotonic()
                if left <= 0:
                    clean = False
                    break
                self._idle.wait(min(left, 0.2))
        self._stop_workers.set()
        for t in self._threads:
            if t is not threading.current_thread():
                t.join(timeout=2.0)
        # in-flight jobs are done (or over budget): stop the device
        # feeder so the process can exit without a straggler iteration
        self.batcher.close()
        if self.auditor is not None:
            self.auditor.close()
        # flush observability BEFORE dropping connections: an armed
        # trace/metrics artifact must survive the shutdown
        self._flush_observability()
        if self._http is not None:
            with contextlib.suppress(Exception):
                self._http.shutdown()
                self._http.server_close()
            self._http = None
        # uninstall the flight ring (only if still ours): later runs in
        # this process must re-resolve tracing from their own environment
        if self._flight_installed \
                and obs_trace.get_tracer() is self._flight:
            obs_trace.reset()
        with self._conn_lock:
            conns = list(self._conns)
        for c in conns:
            with contextlib.suppress(OSError):
                c.shutdown(socket.SHUT_RDWR)
            with contextlib.suppress(OSError):
                c.close()
        if self.config.port is None:
            with contextlib.suppress(OSError):
                os.unlink(self.config.socket_path)
        if self._ingest_dir is not None:
            import shutil

            with contextlib.suppress(OSError):
                shutil.rmtree(self._ingest_dir)
            self._ingest_dir = None
        if self.journal is not None:
            self.journal.record(
                "serve-stop", clean=clean,
                completed=self.queue.counters["completed"],
                failed=self.queue.counters["failed"])
            self.journal.close()
        log_info(f"[racon_tpu::serve] drained "
                 f"{'cleanly' if clean else 'OVER BUDGET'}: "
                 f"{self.queue.counters['completed']} jobs completed, "
                 f"{self.queue.counters['failed']} failed")
        self._stopped.set()
        return clean

    def _flush_observability(self) -> None:
        snap = self.stats_snapshot()
        q, b = snap["queue"], snap["batcher"]
        log_info(f"[racon_tpu::serve] lifetime: {q['admitted']} admitted "
                 f"({q['rejected_full']} full-queue rejects, "
                 f"{q['expired']} expired), {b['iterations']} device "
                 f"iterations ({b['shared_iterations']} cross-job), "
                 f"{b['compiles']} compiles {b['compile_s']:.2f}s")
        metrics_path = os.environ.get("RACON_TPU_METRICS")
        if metrics_path:
            try:
                with open(metrics_path, "w") as fh:
                    json.dump(snap, fh, indent=2, sort_keys=True)
                log_info(f"[racon_tpu::serve] metrics written to "
                         f"{metrics_path}")
            except OSError as exc:
                log_info(f"[racon_tpu::serve] warning: could not write "
                         f"metrics ({exc})")
        try:
            saved = obs_trace.save()
        except OSError as exc:
            saved = None
            log_info(f"[racon_tpu::serve] warning: could not write trace "
                     f"({exc})")
        if saved:
            log_info(f"[racon_tpu::serve] trace written to {saved}")

    # ----------------------------------------------------------- serving
    def _accept_loop(self) -> None:
        while not self._draining.is_set():
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.settimeout(None)
            with self._conn_lock:
                self._conns.add(conn)
            t = threading.Thread(target=self._handle, args=(conn,),
                                 name="racon-tpu-serve-conn", daemon=True)
            t.start()

    def _handle(self, conn: socket.socket) -> None:
        try:
            while True:
                try:
                    req = recv_frame(conn, self.config.max_frame)
                except ProtocolError as exc:
                    with contextlib.suppress(OSError):
                        send_frame(conn,
                                   error_response(exc.code, str(exc)))
                    if not exc.resync:
                        return
                    continue
                except OSError:
                    return
                if req is None:
                    return
                try:
                    resp = self._dispatch(req, conn)
                except Exception as exc:
                    # a handler bug answers typed and keeps serving;
                    # it never takes the process down
                    resp = error_response(
                        "internal", f"{type(exc).__name__}: {exc}")
                try:
                    send_frame(conn, resp)
                except ProtocolError as exc:
                    # response too big for the wire: answer typed
                    # rather than dying mid-send with a desynced peer
                    with contextlib.suppress(OSError):
                        send_frame(conn,
                                   error_response(exc.code, str(exc)))
                except OSError:
                    return
        finally:
            with self._conn_lock:
                self._conns.discard(conn)
            with contextlib.suppress(OSError):
                conn.close()

    def _dispatch(self, req: dict, conn: socket.socket) -> dict:
        rtype = req.get("type")
        if rtype == "submit":
            return self._submit(req, conn)
        if rtype == "ping":
            # mono_s is the clock-handshake sample: a tracing client
            # RTT-brackets it to estimate this process's perf_counter
            # offset, so merged client+server traces share one timeline
            return {"type": "pong", "warm": self._warm is not None,
                    "uptime_s": round(
                        time.perf_counter() - self._t_start, 3),
                    "mono_s": time.perf_counter()}
        if rtype == "stats":
            return dict(self.stats_snapshot(), type="stats")
        if rtype == "healthz":
            # the RPC twin of the HTTP /healthz: same body, same
            # draining semantics, for unix/TCP-only deployments and
            # the fleet aggregator's replica probe
            return dict(self.healthz_snapshot(), type="healthz")
        if rtype == "scrape":
            return {"type": "metrics",
                    "content_type": obs_prom.CONTENT_TYPE,
                    "text": self.prometheus_text()}
        if rtype == "debug":
            resp = self.debug_snapshot(
                max_events=int(req.get("max_events", 5000)))
            if self.auditor is not None:
                # operator acknowledgement: clears the audit alert
                # (gauge + journal) until the next mismatch
                if req.get("audit_ack"):
                    resp["audit_ack"] = self.auditor.ack()
                resp["audit"] = self.auditor.snapshot()
            return resp
        if rtype == "trace_pull":
            return self._trace_pull(req)
        if rtype == "cancel":
            return self._cancel(req)
        if rtype == "shutdown":
            threading.Thread(target=self.drain,
                             name="racon-tpu-serve-drain",
                             daemon=True).start()
            return {"type": "ok", "message": "draining"}
        return error_response("bad-request",
                              f"unknown request type {rtype!r}")

    #: trace ids come from untrusted clients and ride journal lines,
    #: file-adjacent artifacts and Prometheus-adjacent text — constrain
    #: them to a boring charset instead of sanitizing at every sink
    _TRACE_ID_OK = frozenset(
        "abcdefghijklmnopqrstuvwxyz"
        "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_.")

    def _submit(self, req: dict, conn: socket.socket) -> dict:
        for key in ("sequences", "overlaps", "target"):
            path = req.get(key)
            if not isinstance(path, str) or not path:
                return error_response("bad-request",
                                      f"missing input path {key!r}")
            if not os.path.isfile(path):
                return error_response(
                    "bad-request", f"{key} file not found: {path}")
        options = req.get("options") or {}
        if not isinstance(options, dict):
            return error_response("bad-request", "options must be an object")
        unknown = set(options) - ALLOWED_OPTIONS
        if unknown:
            return error_response(
                "bad-request",
                f"unknown option(s): {', '.join(sorted(unknown))}")
        trace_id = req.get("trace_id")
        if trace_id is not None and (
                not isinstance(trace_id, str)
                or not 0 < len(trace_id) <= 64
                or not set(trace_id) <= self._TRACE_ID_OK):
            return error_response(
                "bad-request",
                "trace_id must be 1-64 chars of [A-Za-z0-9._-]")
        # tenant ids ride journal lines and Prometheus-adjacent metric
        # names — same boring charset as trace ids
        tenant = req.get("tenant")
        if tenant is not None and (
                not isinstance(tenant, str)
                or not 0 < len(tenant) <= 64
                or not set(tenant) <= self._TRACE_ID_OK):
            return error_response(
                "bad-request",
                "tenant must be 1-64 chars of [A-Za-z0-9._-]")
        fault_plan = req.get("fault_plan")
        if fault_plan:
            from ..resilience import FaultPlan

            try:
                FaultPlan.parse(fault_plan)
            except RaconError as exc:
                return error_response("bad-request", str(exc))
        # serve-native polishing rounds: validated here so a typo'd
        # request fails typed instead of silently polishing once
        rounds = req.get("rounds")
        if rounds is not None and (
                isinstance(rounds, bool) or not isinstance(rounds, int)
                or not 1 <= rounds <= MAX_ROUNDS):
            return error_response(
                "bad-request",
                f"rounds must be an integer in [1, {MAX_ROUNDS}]")
        # sub-contig window-range shard slice (router fan-out,
        # protocol.py "Child-job fields"): validated here so a typo'd
        # range fails typed instead of silently polishing the whole
        # target — the one unknown-key family a range-aware replica
        # must NOT ignore
        range_lo = req.get("range_lo")
        range_hi = req.get("range_hi")
        if range_lo is not None or range_hi is not None:
            if (isinstance(range_lo, bool) or isinstance(range_hi, bool)
                    or not isinstance(range_lo, int)
                    or not isinstance(range_hi, int)
                    or range_lo < 0 or range_hi <= range_lo):
                return error_response(
                    "bad-request",
                    "range_lo/range_hi must be integers with "
                    "0 <= range_lo < range_hi")
            if rounds is not None:
                # round 2 would re-map reads onto a SEGMENT, which is
                # not what solo rounds on the full contig compute —
                # the router falls back to contig sharding instead
                return error_response(
                    "bad-request",
                    "rounds cannot be combined with range_lo/range_hi")
        # fragment traffic class (reference `-f`, PolisherType.kF): an
        # explicit `mode` field rather than a bare option so the
        # router, journal, and streaming shape can tell the traffic
        # classes apart. Absent mode keeps every surface byte-identical
        # — including legacy `options.fragment_correction` jobs, which
        # keep their per-contig streaming shape.
        mode = req.get("mode")
        if mode is not None and mode not in ("contig", "fragment"):
            return error_response(
                "bad-request", 'mode must be "contig" or "fragment"')
        fragment = mode == "fragment"
        if fragment:
            if range_lo is not None or range_hi is not None:
                # the window-range planner slices ONE target's
                # coordinate axis; fragment jobs shard across the
                # target INDEX axis instead (frag_lo/frag_hi)
                return error_response(
                    "bad-request",
                    'mode "fragment" cannot be combined with '
                    "range_lo/range_hi")
            if rounds is not None and rounds > 1:
                # rounds re-polish a DRAFT assembly; corrected reads
                # are terminal outputs with nothing to re-map onto
                return error_response(
                    "bad-request",
                    'rounds > 1 cannot be combined with mode '
                    '"fragment"')
            # mode implies the kF polisher; normalize here so _run_job
            # and the audit config keep a single source of truth
            options = dict(options)
            options["fragment_correction"] = True
        # fragment child-job shard slice (router fan-out, protocol.py
        # "Fragment child jobs"): [frag_lo, frag_hi) target-INDEX
        # bounds, mirroring the range_lo/range_hi discipline
        frag_lo = req.get("frag_lo")
        frag_hi = req.get("frag_hi")
        if frag_lo is not None or frag_hi is not None:
            if (isinstance(frag_lo, bool) or isinstance(frag_hi, bool)
                    or not isinstance(frag_lo, int)
                    or not isinstance(frag_hi, int)
                    or frag_lo < 0 or frag_hi <= frag_lo):
                return error_response(
                    "bad-request",
                    "frag_lo/frag_hi must be integers with "
                    "0 <= frag_lo < frag_hi")
            if not fragment:
                return error_response(
                    "bad-request",
                    'frag_lo/frag_hi require mode "fragment"')
            if rounds is not None:
                return error_response(
                    "bad-request",
                    "rounds cannot be combined with frag_lo/frag_hi")
        # streaming ingest plane (serve/ingest.py): opt-in via any of
        # `ingest: true` (validate-only), `subsample: {...}`, or
        # `normalize: true`. Shapes are validated HERE so a typo'd
        # request fails typed before a job id is minted; the actual
        # (possibly slow) streaming parse runs after `received` below.
        ingest_spec = None
        if (req.get("ingest") is not None or req.get("subsample")
                is not None or req.get("normalize") is not None):
            from . import ingest as ingest_mod

            try:
                ingest_spec = ingest_mod.IngestSpec.from_request(req)
            except ingest_mod.IngestError as exc:
                return error_response("bad-request", str(exc))
            if not (req.get("ingest") or ingest_spec.subsample
                    or ingest_spec.normalize):
                # `ingest: false` with no other opt-in: shapes were
                # still validated above, but nothing to run
                ingest_spec = None
        with self._job_seq_lock:
            self._job_seq += 1
            job_id = f"j{self._job_seq}"
        job = Job(job_id, req["sequences"], req["overlaps"], req["target"],
                  options, priority=int(req.get("priority", 0)),
                  deadline_s=req.get("deadline_s"),
                  fault_plan=fault_plan, strict=req.get("strict"),
                  want_trace=bool(req.get("trace")),
                  trace_id=trace_id,
                  want_progress=bool(req.get("progress")),
                  want_stream=bool(req.get("stream")),
                  tenant=tenant or "", rounds=rounds,
                  range_lo=range_lo, range_hi=range_hi,
                  fragment=fragment, frag_lo=frag_lo, frag_hi=frag_hi)
        # child-job fields from a serve router (router.py): `parent` is
        # the router-side parent job id, `shard`/`shards` this child's
        # slot in the contig fan-out. Purely observational replica-side
        # — journaled so the replica's journal lines correlate with the
        # router's ledger — and ignored (like any unknown key) when
        # absent or malformed.
        parent = req.get("parent")
        if not isinstance(parent, str) or not parent \
                or not set(parent) <= self._TRACE_ID_OK:
            parent = None
        shard = req.get("shard") if isinstance(req.get("shard"), int) \
            else None
        shards = req.get("shards") if isinstance(req.get("shards"), int) \
            else None
        if self.journal is not None:
            self.journal.record("received", job=job.id, trace=trace_id,
                                priority=job.priority or None,
                                tenant=job.tenant or None,
                                deadline_s=req.get("deadline_s"),
                                rounds=job.rounds,
                                parent=parent, shard=shard,
                                shards=shards,
                                range_lo=job.range_lo,
                                range_hi=job.range_hi,
                                mode="fragment" if job.fragment
                                else None,
                                frag_lo=job.frag_lo,
                                frag_hi=job.frag_hi)
        if ingest_spec is not None:
            # admit-time ingest: streaming-validate (and optionally
            # subsample / pair-normalize) the raw inputs. A parse error
            # fails THIS job typed — `rejected-ingest` terminal, no
            # queue time, never the server — and rewritten paths land
            # on the job before it is queued.
            from . import ingest as ingest_mod

            try:
                done = ingest_mod.prepare(
                    job.sequences, job.overlaps, job.target,
                    ingest_spec, workdir=self._ingest_workdir(),
                    job_id=job.id, trace_id=trace_id,
                    journal=self.journal)
            except ingest_mod.IngestError as exc:
                if self.journal is not None:
                    self.journal.record("rejected-ingest", job=job.id,
                                        trace=trace_id,
                                        error=exc.stage,
                                        detail=str(exc))
                return error_response("bad-request", str(exc),
                                      job_id=job_id)
            job.sequences, job.overlaps, job.target = done
        try:
            self.queue.submit(job)
        except QueueFull as exc:
            if self.journal is not None:
                self.journal.record("rejected-full", job=job.id,
                                    trace=trace_id,
                                    retry_after=round(exc.retry_after, 3))
            return error_response("queue-full", str(exc),
                                  retry_after=round(exc.retry_after, 3),
                                  job_id=job_id)
        except TenantQuotaExceeded as exc:
            if self.journal is not None:
                self.journal.record("rejected-quota", job=job.id,
                                    trace=trace_id,
                                    tenant=job.tenant or None,
                                    retry_after=round(exc.retry_after, 3))
            return error_response("tenant-quota", str(exc),
                                  retry_after=round(exc.retry_after, 3),
                                  tenant=job.tenant, job_id=job_id)
        except DeadlineDoomed as exc:
            # speculative abort at the door: the EMA says this job
            # cannot finish inside its own deadline — fail fast, typed,
            # before it costs queue time or device time. Terminal is
            # `expired` (the job never ran), the typed annotation pins
            # the why.
            with self._qos_lock:
                self.qos["aborted_doomed"] += 1
            if self.journal is not None:
                self.journal.record(
                    "deadline-doomed", job=job.id, trace=trace_id,
                    phase="admission",
                    predicted_s=round(exc.predicted_s, 3),
                    remaining_s=round(exc.remaining_s, 3))
                self.journal.record("expired", job=job.id,
                                    trace=trace_id,
                                    reason="deadline-doomed")
            return error_response(
                "deadline-doomed", str(exc), job_id=job_id,
                predicted_s=round(exc.predicted_s, 3),
                remaining_s=round(exc.remaining_s, 3))
        except Draining as exc:
            if self.journal is not None:
                self.journal.record("rejected-draining", job=job.id,
                                    trace=trace_id)
            return error_response("draining", str(exc), job_id=job_id)
        self._maybe_preempt(job)
        # `admitted` is STAGED by the queue's on_event hook under the
        # submit lock (ordering vs `started` fixed at stage time, no
        # disk I/O behind the queue mutex); flushed below once the job
        # resolves, covering the expired-in-queue path too
        if not job.relaying:
            job.event.wait()
        else:
            self._stream_frames(job, conn)
        if self.journal is not None:
            self.journal.flush_staged()
        return job.response

    def _stream_frames(self, job: Job, conn: socket.socket) -> dict:
        """Forward the job's outbox — `progress` events and streamed
        `result_part` frames — as interleaved frames on the submitting
        connection while waiting for the result, including
        queue-position updates while the job is still pending. Returns
        the final response for the handler to send LAST, so the wire
        order is (progress|result_part)*, result. A client that stops
        reading only loses its interleaved frames (the first send error
        stops forwarding); the job itself runs to completion and is
        accounted normally either way — a mid-stream disconnect never
        touches the feeder or any other job."""
        seq = 0
        last_pos = None
        send_ok = True

        def push(ev: dict) -> None:
            nonlocal seq, send_ok
            if not send_ok:
                return
            if ev.get("type") == "result_part":
                # worker-built, ready to send (carries its own `part`
                # ordinal); only the trace context is stamped here
                frame = ev
            else:
                seq += 1
                frame = {"type": "progress", "job_id": job.id,
                         "seq": seq}
                frame.update(ev)
            if job.trace_id:
                frame.setdefault("trace_id", job.trace_id)
            try:
                send_frame(conn, frame)
            except (OSError, ProtocolError):
                send_ok = False

        last_version = None
        while True:
            ev = job.next_frame(timeout=0.05)
            if ev is not None:
                push(ev)
                continue
            if job.event.is_set():
                break
            # position recomputes (an O(depth) DRR simulation under the
            # queue mutex) only when the queue actually moved, and not
            # at all once the client stopped reading
            if job.started_t is None and send_ok and job.want_progress:
                version = self.queue.version
                if version != last_version:
                    last_version = version
                    pos = self.queue.position(job)
                    if pos is not None and pos != last_pos:
                        last_pos = pos
                        push({"phase": "queued", "position": pos,
                              "depth": len(self.queue)})
        # the worker set the event after its last notify: drain the tail
        while True:
            ev = job.next_frame()
            if ev is None:
                break
            push(ev)
        return job.response

    # ------------------------------------------------------------ workers
    def _worker(self) -> None:
        while True:
            job = self.queue.pop(timeout=0.2)
            if job is None:
                if self._stop_workers.is_set() and not len(self.queue):
                    return
                continue
            self._process_one(job)

    def _surge_worker(self) -> None:
        """One-shot worker spawned by a preemption: the victim's
        worker thread stays blocked in its consensus consume loop (its
        windows are parked, not failed), so the capacity the preemption
        freed needs a thread to spend it on the high-priority job —
        which the queue's priority-first pop hands over next."""
        job = self.queue.pop(timeout=1.0)
        if job is not None:
            self._process_one(job)

    def _process_one(self, job: Job) -> None:
        with self._idle:
            self._inflight += 1
        with self._qos_lock:
            self._running_jobs[job.id] = job
        t0 = time.perf_counter()
        try:
            resp = self._run_job(job)
            ok = True
        except JobCancelledError as exc:
            # typed terminal for the cancel RPC's running-job path: the
            # batcher's withdrawal seam (or the round-boundary flag)
            # raised this through the job's own thread
            if self.journal is not None:
                self.journal.record("cancelled", job=job.id,
                                    trace=job.trace_id,
                                    state="running")
            resp = error_response(
                "cancelled", str(exc), job_id=job.id,
                error_type=type(exc).__name__,
                queue_wait_s=round(job.queue_wait_s, 4))
            ok = False
        except DeadlineDoomed as exc:
            # mid-run speculative abort: the iteration-boundary
            # estimate said the deadline is provably lost — the job
            # fails typed within one iteration instead of at the end
            with self._qos_lock:
                self.qos["aborted_doomed"] += 1
            if self.journal is not None:
                self.journal.record(
                    "deadline-doomed", job=job.id, trace=job.trace_id,
                    phase=exc.phase,
                    predicted_s=round(exc.predicted_s, 3),
                    remaining_s=round(exc.remaining_s, 3))
            resp = error_response(
                "deadline-doomed", str(exc), job_id=job.id,
                error_type=type(exc).__name__,
                predicted_s=round(exc.predicted_s, 3),
                remaining_s=round(exc.remaining_s, 3),
                queue_wait_s=round(job.queue_wait_s, 4))
            ok = False
        except Exception as exc:
            # per-job failure isolation: the job answers typed, the
            # server and its warm engines survive
            resp = error_response(
                "job-failed", str(exc), job_id=job.id,
                error_type=type(exc).__name__,
                queue_wait_s=round(job.queue_wait_s, 4))
            ok = False
        job.response = resp
        try:
            # fold the job's own latency histograms (align phase,
            # solo rounds, polisher phases, compiles) into the
            # lifetime scrape view — on FAILURE too: the
            # pathological jobs are exactly the ones the p99s must
            # not exclude. (Shared batch rounds already observe
            # into the server set directly.)
            if job.stats_ref is not None \
                    and job.stats_ref.hists is not None:
                self.hists.merge(job.stats_ref.hists)
            service_s = time.perf_counter() - t0
            # latency exemplar: the job-latency bucket this job
            # lands in remembers WHO it was (trace id) and — for a
            # failed / deadline-missed job — the flight dump the
            # worker is about to write, so a fleet p99 bucket
            # clicks through to the exact job's Chrome trace. The
            # dump path is deterministic (_flight_dump names it
            # identically below).
            exemplar = None
            if self.exemplars_enabled:
                exemplar = {"trace_id": job.trace_id or job.id,
                            "job": job.id}
                will_miss = (job.deadline is not None
                             and time.perf_counter() > job.deadline)
                if (not ok or will_miss) and self.config.flight_dir:
                    reason = ("job-failed" if not ok
                              else "deadline-miss")
                    exemplar["flight"] = os.path.join(
                        self.config.flight_dir,
                        f"flight_{job.id}_{reason}.json")
            missed = self.queue.task_done(job, ok, service_s,
                                          exemplar=exemplar)
            if self.journal is not None:
                batch = ((resp.get("serve") or {}).get("batch")
                         if ok else None) or {}
                if batch:
                    self.journal.record(
                        "iterations", job=job.id,
                        trace=job.trace_id,
                        iterations=batch.get("iterations"),
                        shared=batch.get("shared_iterations"),
                        windows=batch.get("windows"))
                if missed:
                    self.journal.record("deadline-miss", job=job.id,
                                        trace=job.trace_id)
                self.journal.record(
                    "finished" if ok else "failed",
                    job=job.id, trace=job.trace_id,
                    service_s=round(service_s, 4),
                    sequences=resp.get("sequences"),
                    error_type=resp.get("error_type"))
            if not ok or missed:
                # post-mortem artifact: the flight ring windowed to
                # this job, with its stage stats riding along
                # (obs/flight.py). Written BEFORE the waiter is
                # unblocked, so a client reacting to its error
                # response finds the dump already listed by `debug`
                self._flight_dump(
                    job,
                    "job-failed" if not ok else "deadline-miss",
                    resp)
        except Exception as exc:  # noqa: BLE001
            # telemetry accounting must never kill the worker or
            # strand the waiter blocked on job.event
            log_info(f"[racon_tpu::serve] warning: post-job "
                     f"telemetry failed ({type(exc).__name__}: "
                     f"{exc})")
        finally:
            job.finish()
        self._qos_job_done(job)
        with self._idle:
            self._inflight -= 1
            self._idle.notify_all()

    # ---------------------------------------------------------------- qos
    def _qos_job_done(self, job: Job) -> None:
        """Post-terminal QoS bookkeeping: drop the job from the
        running set, clean any parked state it left in the batcher
        (a job can terminate WHILE preempted — cancelled, or finished
        because all of its windows were already in flight when the
        withdrawal landed), then hand freed capacity to the
        highest-priority parked job."""
        with self._qos_lock:
            self._running_jobs.pop(job.id, None)
            if not self.config.preempt:
                return
            was_parked = self._preempted.pop(job.id, None) is not None
        if was_parked:
            # releases the withdrawn mark and any still-parked entries
            # so the pools never leak a dead job's windows
            self.batcher.resume_job(job.id)
            if self.journal is not None:
                self.journal.record("resumed", job=job.id,
                                    trace=job.trace_id,
                                    reason="terminal")
        self._maybe_resume()

    def _maybe_preempt(self, job: Job) -> None:
        """A newly admitted job preempts the lowest-priority running
        job of a strictly lower class: the victim's not-yet-dispatched
        pooled windows are parked between iterations (completed
        windows stay — ContigStreamer tolerates the gap) and a surge
        worker thread spends the freed capacity on the new job.
        Fault-injected and strict jobs run the solo path and are
        never victims."""
        if not self.config.preempt:
            return
        with self._qos_lock:
            active = [j for jid, j in self._running_jobs.items()
                      if jid not in self._preempted]
            if len(active) < self.config.workers:
                return  # free capacity: no need to take any back
            victims = [j for j in active
                       if j.priority < job.priority
                       and j.fault_plan is None and not j.strict]
            if not victims:
                return
            victim = min(victims, key=lambda j: j.priority)
            self._preempted[victim.id] = victim
            self.qos["preemptions"] += 1
        parked = self.batcher.withdraw_job(victim.id)
        if self.journal is not None:
            self.journal.record(
                "preempted", job=victim.id, trace=victim.trace_id,
                by=job.id, priority=victim.priority,
                by_priority=job.priority, windows=parked)
        log_info(f"[racon_tpu::serve] preempted job {victim.id} "
                 f"(class {victim.priority}) for {job.id} "
                 f"(class {job.priority}): {parked} windows parked")
        threading.Thread(target=self._surge_worker,
                         name="racon-tpu-serve-surge",
                         daemon=True).start()

    def _maybe_resume(self) -> None:
        """Resume the highest-priority parked job once capacity frees
        — unless a strictly higher class is still waiting in the
        queue, which keeps its claim on the freed slot."""
        if not self.config.preempt:
            return
        top = self.queue.highest_queued_priority()
        with self._qos_lock:
            if not self._preempted:
                return
            active = len(self._running_jobs) - len(self._preempted)
            if active >= self.config.workers:
                return
            cand = max(self._preempted.values(),
                       key=lambda j: j.priority)
            if top is not None and top > cand.priority:
                return
            del self._preempted[cand.id]
        n = self.batcher.resume_job(cand.id)
        if self.journal is not None:
            self.journal.record("resumed", job=cand.id,
                                trace=cand.trace_id, windows=n)
        log_info(f"[racon_tpu::serve] resumed job {cand.id}: "
                 f"{n} windows back in pool")

    def _cancel(self, req: dict) -> dict:
        """Cancel RPC: dequeue a pending job (typed `cancelled`
        response delivered through its queue slot) or withdraw a
        running one (the batcher fails its tickets; solo/isolated
        jobs see the round-boundary flag instead)."""
        job_id = req.get("job_id")
        trace_id = req.get("trace_id")
        if not job_id and not trace_id:
            return error_response(
                "bad-request", "cancel needs job_id or trace_id")
        job = self.queue.cancel(job_id=job_id, trace_id=trace_id)
        if job is not None:
            with self._qos_lock:
                self.qos["cancelled"] += 1
            if self.journal is not None:
                self.journal.flush_staged()
            return {"type": "ok", "cancelled": "queued",
                    "job_id": job.id}
        with self._qos_lock:
            running = self._running_jobs.get(job_id or "")
            if running is None and trace_id:
                for j in self._running_jobs.values():
                    if j.trace_id == trace_id:
                        running = j
                        break
            if running is not None:
                self.qos["cancelled"] += 1
        if running is None:
            return error_response(
                "unknown-job", "no queued or running job matches",
                job_id=job_id, trace_id=trace_id)
        # round-boundary fallback for solo/isolated jobs the pools
        # never see; the pooled path fails the tickets directly
        running.cancelled = True
        pooled = self.batcher.cancel_job(running.id)
        return {"type": "ok", "cancelled": "running",
                "job_id": running.id, "pooled": pooled}

    def _run_job(self, job: Job) -> dict:
        from ..core.polisher import PolisherType, create_polisher

        opts, cfg = job.options, self.config
        t0 = time.perf_counter()
        trace_ctx = (obs_trace.scoped() if job.want_trace
                     else contextlib.nullcontext())
        with strict_scope(job.strict), trace_ctx as rec:
            if job.want_trace:
                # the job's timeline starts at ENQUEUE, not at this
                # worker pop: rebase the fresh per-job recorder so the
                # queue-wait span keeps its real offset, then record it
                # tagged with the client's trace context
                rec.rebase(job.enqueued_t)
                rec.complete("serve.queue_wait", job.enqueued_t,
                             job.started_t or t0,
                             {"job": job.id, "trace_id": job.trace_id})
            elif self._flight is not None:
                # untraced jobs (the router's child shards deliberately
                # run without a scoped trace — obs/trace.scoped
                # serializes on a module lock, which would serialize
                # same-replica shards) still leave their queue-wait in
                # the always-on flight ring, tagged with the trace id,
                # so a later `trace_pull` can window them out
                self._flight.complete(
                    "serve.queue_wait", job.enqueued_t,
                    job.started_t or t0,
                    {"job": job.id, "trace_id": job.trace_id})
            polisher = create_polisher(
                job.sequences, job.overlaps, job.target,
                PolisherType.kF if opts.get("fragment_correction")
                else PolisherType.kC,
                int(opts.get("window_length", cfg.window_length)),
                float(opts.get("quality_threshold",
                               cfg.quality_threshold)),
                float(opts.get("error_threshold", cfg.error_threshold)),
                bool(opts.get("trim", cfg.trim)),
                int(opts.get("match", cfg.match)),
                int(opts.get("mismatch", cfg.mismatch)),
                int(opts.get("gap", cfg.gap)),
                num_threads=cfg.job_threads,
                tpu_poa_batches=int(
                    opts.get("tpu_poa_batches", cfg.tpu_poa_batches)),
                tpu_banded_alignment=bool(
                    opts.get("tpu_banded_alignment",
                             cfg.tpu_banded_alignment)),
                tpu_aligner_batches=int(
                    opts.get("tpu_aligner_batches",
                             cfg.tpu_aligner_batches)),
                tpu_aligner_band_width=int(
                    opts.get("tpu_aligner_band_width",
                             cfg.tpu_aligner_band_width)),
                tpu_engine=opts.get("tpu_engine", cfg.tpu_engine),
                tpu_pipeline_depth=int(
                    opts.get("tpu_pipeline_depth",
                             cfg.tpu_pipeline_depth)),
                tpu_device_timeout=float(
                    opts.get("tpu_device_timeout",
                             cfg.tpu_device_timeout)),
                tpu_adaptive_buckets=cfg.tpu_adaptive_buckets,
                tpu_fault_plan=job.fault_plan)
            # live ref for the flight dump: a job that dies mid-phase
            # still gets its partial stage stats into the artifact
            job.stats_ref = polisher.pipeline_stats
            # trace context + live progress ride the polisher: the
            # batcher tags shared-round spans with serve_trace_id, and
            # progress events relay through the job to the handler;
            # the job id lets the audit sentinel journal a mismatch
            # into the OWNING job's timeline
            polisher.serve_trace_id = job.trace_id
            polisher.serve_job_id = job.id
            # tenant identity rides the polisher too: the batcher
            # prorates each lane iteration's device seconds onto the
            # tenants whose windows shared it (per-tenant device-cost
            # accounting, serve.tenant_device_seconds)
            polisher.serve_tenant = job.tenant
            # the absolute deadline rides the polisher so the batcher's
            # iteration-boundary doomed check can see it (mid-run
            # speculative abort, RACON_TPU_SERVE_ABORT_MARGIN)
            polisher.serve_deadline = job.deadline
            if job.cancelled:
                raise JobCancelledError("running")
            if job.want_progress:
                polisher.progress_hook = job.notify_progress
            if job.range_lo is not None:
                # sub-contig range shard: polish only the target
                # windows whose grid start falls in [lo, hi) — the
                # polisher emits bare-named segments and records the
                # stitch accounting in segment_meta (core/polisher.py)
                polisher.window_range = (job.range_lo, job.range_hi)
            if job.frag_lo is not None:
                # fragment child shard: correct only the reads whose
                # target-file index falls in [frag_lo, frag_hi) — the
                # read-axis twin of window_range (core/polisher.py
                # target_range)
                polisher.target_range = (job.frag_lo, job.frag_hi)
            polisher.initialize()
            # per-contig sink: every serve job stitches incrementally
            # through the continuous batcher, so each finished contig is
            # journaled (`part-streamed`, the obsreport --check receipt)
            # and — when the client asked to stream — shipped as a
            # `result_part` frame BEFORE the job completes. The
            # concatenation of parts is the job's full FASTA by
            # construction (ContigStreamer emits in contig order).
            parts: list[bytes] = []

            def on_part(seq) -> None:
                part = (b">" + seq.name.encode() + b"\n" + seq.data
                        + b"\n")
                parts.append(part)
                if self.journal is not None:
                    self.journal.record(
                        "part-streamed", job=job.id, trace=job.trace_id,
                        contig=seq.name.split(" ", 1)[0],
                        part=len(parts), bytes=len(part))
                frame = {"type": "result_part",
                         "job_id": job.id, "part": len(parts),
                         "name": seq.name,
                         "fasta": part.decode("latin-1")}
                if job.range_lo is not None:
                    # range shard: the frame carries the RAW segment
                    # body (no FASTA header/newline — Sequence.data has
                    # no newlines) plus the stitch accounting the
                    # router needs to re-derive the solo tags; the
                    # classic "parts' concatenation IS the body"
                    # contract deliberately does NOT apply here
                    # (protocol.py "Child-job fields")
                    frame["fasta"] = seq.data.decode("latin-1")
                    frame["seg"] = polisher.segment_meta.get(seq.name)
                job.notify_part(frame)

            def on_group(seqs, lo, hi) -> None:
                # fragment traffic class: targets are many small reads,
                # so corrected reads ship one result_part frame per
                # BOUNDED GROUP (cfg.frag_group consecutive targets,
                # core/polisher.FragmentStreamer), never one frame per
                # read. `lo`/`hi` are this polisher's local target
                # indices; the frame's `frag` receipt is rebased to the
                # GLOBAL read axis so a router's dedupe ledger can tile
                # [0, n_reads) across child shards. Dropped
                # (unpolished) reads still advance the receipt range,
                # so a group may carry fewer reads than indices — or
                # none at all.
                body = b"".join(b">" + s.name.encode() + b"\n" + s.data
                                + b"\n" for s in seqs)
                parts.append(body)
                if self.journal is not None:
                    self.journal.record(
                        "part-streamed", job=job.id, trace=job.trace_id,
                        part=len(parts), bytes=len(body),
                        reads=len(seqs))
                base = job.frag_lo or 0
                job.notify_part({"type": "result_part",
                                 "job_id": job.id, "part": len(parts),
                                 "reads": len(seqs),
                                 "frag": [base + lo, base + hi],
                                 "fasta": body.decode("latin-1")})

            drop = not opts.get("include_unpolished", False)
            per_round: list[dict] = []
            if job.rounds is None:
                # no rounds requested: the pre-rounds single-pass path,
                # byte-identical in output, journal and scrape
                if job.fragment:
                    polished = polisher.polish(
                        drop, batcher=self.batcher, on_group=on_group,
                        group_size=cfg.frag_group)
                else:
                    polished = polisher.polish(
                        drop, batcher=self.batcher, on_part=on_part)
            else:
                # serve-native polishing rounds: round k's stitched
                # contigs loop back as round k+1's draft WITHOUT
                # leaving the warm process — in-process re-overlap +
                # re-window (Polisher.redraft -> core/remap.py), warm
                # engines/jit caches/autotune posture carried across.
                # Only the FINAL round streams parts: the result_part
                # contract (and obsreport's parts-streamed receipt)
                # covers the job's authoritative output, not drafts.
                rounds = job.rounds
                with self._rounds_lock:
                    self._rounds["jobs"] += 1
                    self._rounds["inflight"] += 1
                try:
                    with tempfile.TemporaryDirectory(
                            prefix=f"racon_serve_rounds_{job.id}_") \
                            as workdir:
                        for rnd in range(1, rounds + 1):
                            final = rnd == rounds
                            if job.cancelled:
                                # round-boundary cancel fallback for
                                # solo/isolated jobs the pools miss
                                raise JobCancelledError("running")
                            if self.journal is not None:
                                self.journal.record(
                                    "round-started", job=job.id,
                                    trace=job.trace_id, round=rnd,
                                    of=rounds)
                            rt0 = time.perf_counter()
                            if job.fragment:
                                # only rounds == 1 reaches here (the
                                # submit validation rejects more), so
                                # `final` is always true — but keep the
                                # guard shape symmetric
                                polished = polisher.polish(
                                    drop, batcher=self.batcher,
                                    on_group=on_group if final
                                    else None,
                                    group_size=cfg.frag_group)
                            else:
                                polished = polisher.polish(
                                    drop, batcher=self.batcher,
                                    on_part=on_part if final else None)
                            wall = time.perf_counter() - rt0
                            batch = getattr(polisher, "serve_batch",
                                            None) or {}
                            info = {"round": rnd,
                                    "wall_s": round(wall, 4),
                                    "windows": batch.get("windows"),
                                    "iterations": batch.get(
                                        "iterations"),
                                    "sequences": len(polished)}
                            cache = getattr(polisher, "serve_cache",
                                            None)
                            if cache is not None:
                                info["cache"] = dict(cache)
                            per_round.append(info)
                            self.hists.observe(f"serve.round_{rnd}",
                                               wall)
                            if self.journal is not None:
                                self.journal.record(
                                    "round-finished", job=job.id,
                                    trace=job.trace_id, round=rnd,
                                    of=rounds, wall_s=round(wall, 4),
                                    sequences=len(polished),
                                    cache_hits=(cache or {}).get(
                                        "hits"))
                            with self._rounds_lock:
                                self._rounds["completed"] += 1
                            if not final:
                                polisher.redraft(polished, workdir,
                                                 tag=f"r{rnd}")
                                polisher.initialize()
                finally:
                    with self._rounds_lock:
                        self._rounds["inflight"] -= 1
        if job.cancelled:
            # a cancel that landed mid-run on a solo/isolated job has
            # no pooled tickets for the batcher to fail — honour it
            # here, before the completed work ships: cancel means the
            # bytes are unwanted, not that the run must have crashed
            raise JobCancelledError("running")
        # the response body comes from `polished`, NOT from the parts
        # collected in the callback: ContigStreamer swallows on_part
        # exceptions (streaming is decoration), so a callback bug may
        # lose a part — it must never truncate the authoritative body
        fasta = b"".join(b">" + s.name.encode() + b"\n" + s.data
                         + b"\n" for s in polished)
        resp = {"type": "result", "job_id": job.id,
                "sequences": len(polished),
                "metrics": polisher.metrics.snapshot(),
                "serve": {"queue_wait_s": round(job.queue_wait_s, 4),
                          "exec_s": round(time.perf_counter() - t0, 4),
                          "batch": getattr(polisher, "serve_batch",
                                           None)}}
        if job.rounds is not None:
            # rounds accounting block — present ONLY when the request
            # asked for rounds (a plain submit's response shape is
            # unchanged). Cache totals summed across rounds when the
            # window cache is armed.
            block = {"requested": job.rounds,
                     "completed": len(per_round),
                     "per_round": per_round}
            caches = [i["cache"] for i in per_round if i.get("cache")]
            if caches:
                block["cache"] = {
                    "hits": sum(c["hits"] for c in caches),
                    "misses": sum(c["misses"] for c in caches)}
            resp["rounds"] = block
        if job.want_stream:
            # the bytes already streamed as result_part frames; the
            # final frame carries the stats, not a second copy of the
            # assembly
            resp["streamed"] = True
            resp["parts"] = len(parts)
        else:
            resp["fasta"] = fasta.decode("latin-1")
        if job.want_trace:
            rec.complete("serve.job", t0, time.perf_counter(),
                         {"job": job.id, "trace_id": job.trace_id})
            resp["trace"] = rec.events()
            # the recorder's time zero in SERVER perf_counter terms:
            # with the ping handshake's clock offset, the client maps
            # every server span onto its own timeline (client.py)
            resp["trace_base_mono"] = rec._base
        elif self._flight is not None:
            # untraced twin of the span above, into the always-on ring,
            # for trace_pull (see the queue-wait comment)
            self._flight.complete(
                "serve.job", t0, time.perf_counter(),
                {"job": job.id, "trace_id": job.trace_id})
        return resp

    # -------------------------------------------------- flight recorder
    def _flight_dump(self, job: Job, reason: str,
                     resp: dict | None) -> None:
        """Write the flight ring, windowed to `job`, as a Chrome-trace
        artifact named for the job. Best-effort by design: a full disk
        or unwritable directory loses the artifact, never the server."""
        dirpath = self.config.flight_dir
        if not dirpath or self._flight is None:
            return
        try:
            os.makedirs(dirpath, exist_ok=True)
            path = os.path.join(dirpath,
                                f"flight_{job.id}_{reason}.json")
            info = {"job_id": job.id, "reason": reason,
                    "queue_wait_s": round(job.queue_wait_s, 4),
                    "error_type": (resp or {}).get("error_type"),
                    "message": (resp or {}).get("message"),
                    "stage_stats": (job.stats_ref.snapshot()
                                    if job.stats_ref is not None
                                    else None)}
            obs_flight.dump(self._flight, path,
                            since=job.started_t, flight=info)
            self._dumps.append(path)
            log_info(f"[racon_tpu::serve] flight recorder dumped to "
                     f"{path} ({reason})")
        except Exception as exc:  # noqa: BLE001 — full disk, an
            # unserializable span arg, anything: the artifact is lost,
            # never the job response or the server
            log_info(f"[racon_tpu::serve] warning: could not write "
                     f"flight dump ({type(exc).__name__}: {exc})")

    def debug_snapshot(self, max_events: int = 5000) -> dict:
        """The `debug` RPC body: the flight ring's most recent events
        (bounded so the response frame stays small) plus the automatic
        dump artifacts written so far."""
        events: list = []
        if self._flight is not None:
            events = obs_flight.window_events(self._flight)
            if max_events > 0 and len(events) > max_events:
                # keep thread metadata, trim the oldest spans
                meta = [e for e in events if e.get("ph") == "M"]
                rest = [e for e in events if e.get("ph") != "M"]
                events = meta + rest[-max_events:]
        return {"type": "debug", "events": events,
                "dumps": list(self._dumps),
                "flight_installed": self._flight_installed}

    def _trace_pull(self, req: dict) -> dict:
        """The `trace_pull` RPC body: flight-ring spans windowed to ONE
        distributed trace id (exact or dotted `<trace>.s<k>` child
        match — obs/flight.trace_events), with this process's recorder
        base and a fresh mono sample so the router can rebase the
        events onto its own timeline after a `clock_sync()`. An
        optional `trace_ids` list narrows the window to exactly those
        ids (union) — the router pulls each replica for only the child
        traces that completed there. Always-on: it reads the ring that
        is already recording, so pulling a trace costs the replica
        nothing beyond the reply frame."""
        trace_id = req.get("trace_id")
        if (not isinstance(trace_id, str) or not trace_id
                or len(trace_id) > 64
                or not set(trace_id) <= self._TRACE_ID_OK):
            return error_response(
                "bad-request", "trace_pull needs a trace_id of "
                "[A-Za-z0-9._-], at most 64 chars")
        want = trace_id
        tids = req.get("trace_ids")
        if tids is not None:
            if (not isinstance(tids, list) or not tids
                    or not all(isinstance(t, str) and t
                               and len(t) <= 64
                               and set(t) <= self._TRACE_ID_OK
                               for t in tids)):
                return error_response(
                    "bad-request", "trace_pull trace_ids must be a "
                    "non-empty list of [A-Za-z0-9._-] ids")
            want = tids
        events: list = []
        base = None
        if self._flight is not None:
            cap = req.get("max_events")
            events = obs_flight.trace_events(
                self._flight, want,
                max_events=int(cap) if cap is not None else None)
            base = self._flight._base
        return {"type": "trace", "trace_id": trace_id,
                "events": events, "base_mono": base,
                "mono_s": time.perf_counter()}

    # --------------------------------------------------------- exposition
    def prometheus_text(self) -> str:
        """One Prometheus scrape body (obs/prom.py): lifetime counters,
        live gauges and every latency histogram — refreshed at call
        time, safe to call at any lifecycle point including drain."""
        t_render = time.perf_counter()
        q = self.queue.snapshot()
        b = self.batcher.snapshot()
        counters = {f"serve.jobs.{k}": q[k] for k in (
            "submitted", "admitted", "rejected_full",
            "rejected_draining", "rejected_quota", "expired",
            "completed", "failed", "deadline_hit", "deadline_miss")}
        counters["serve.batch.iterations"] = b["iterations"]
        counters["serve.batch.shared_iterations"] = \
            b["shared_iterations"]
        counters["serve.batch.windows"] = b["windows"]
        # measured per-iteration host overhead (iteration wall minus
        # device-stage seconds), cumulative — the dispatch-loop number
        counters["serve.batch.host_seconds"] = round(
            b.get("host_s", 0.0), 4)
        counters["serve.compiles"] = b["compiles"]
        for lane in b.get("lanes") or ():
            counters[f"serve.lane.{lane['lane']}.iterations"] = \
                lane["iterations"]
        # per-tenant fairness receipts. Tenant ids embed in the metric
        # NAME, so only ids that survive Prometheus sanitization
        # unchanged ([A-Za-z0-9_]) are exported — 'team.a' and
        # 'team-a' would otherwise collide into one duplicated series
        # and invalidate the whole scrape. Skipped tenants (and the
        # anonymous "" tenant) remain fully visible in the `stats`
        # response's tenants view.
        for tenant, tc in (q.get("tenants") or {}).items():
            if tenant and all(c.isalnum() or c == "_" for c in tenant):
                counters[f"serve.tenant.{tenant}.admitted"] = \
                    tc["admitted"]
                counters[f"serve.tenant.{tenant}.completed"] = \
                    tc["completed"]
        if self.journal is not None:
            counters["serve.journal.events"] = self.journal.events
            counters["serve.journal.dropped"] = self.journal.dropped
        # autotuner decision receipts: which (engine, kernel, dtype)
        # decision the persisted winner tables handed each dispatcher —
        # the fleet view of which buckets run which kernel plane
        from ..sched.autotune import get_autotuner

        consults = get_autotuner().consult_counts()
        if consults:
            counters["sched.autotune.consults"] = obs_prom.Labeled(
                consults, "winner-table consults by decision "
                "(decision 'none' = cold bucket, XLA default)")
        gauges = {
            "serve.uptime_seconds": (
                round(time.perf_counter() - self._t_start, 3),
                "seconds since this server process started serving"),
            "serve.start_time_seconds": (
                round(self._t_wall_start, 3),
                "unix time the server started (restart detector: a "
                "counter reset with an unchanged start_time is a bug, "
                "with a changed one a restart)"),
            "serve.queue_depth": q["depth"],
            "serve.queue_capacity": q["maxsize"],
            "serve.queue_oldest_wait_seconds": q.get("oldest_wait_s",
                                                     0.0),
            "serve.inflight": self._inflight_count(),
            "serve.draining": self._draining.is_set(),
            "serve.service_time_ema_seconds": q["ema_service_s"],
            "serve.worker_lanes": b.get("worker_lanes", 1),
        }
        for lane in b.get("lanes") or ():
            gauges[f"serve.lane.{lane['lane']}.busy"] = (
                lane["busy"],
                "1 while this worker lane is executing a device "
                "iteration (sub-mesh occupancy view)")
        for engine, e in (b.get("occupancy") or {}).items():
            if "occupancy_pct" in e:
                gauges[f"sched.{engine}.occupancy_pct"] = \
                    e["occupancy_pct"]
        # per-tenant live view as PROPERLY LABELED series (tenant ids
        # are label VALUES here, escaped — any validated id survives,
        # unlike the name-embedded lifetime counters above): queue
        # depth per tenant is what makes the fleet per-tenant view
        # possible at all, credit is the live DRR fairness dial
        tenants = q.get("tenants") or {}
        if tenants:
            gauges["serve.tenant_queue_depth"] = obs_prom.Labeled(
                [({"tenant": t}, tc.get("queued", 0))
                 for t, tc in sorted(tenants.items())],
                "live queued jobs per tenant")
            gauges["serve.tenant_credit"] = obs_prom.Labeled(
                [({"tenant": t}, tc.get("credit", 0.0))
                 for t, tc in sorted(tenants.items())],
                "accrued DRR credit per tenant (spent one per pop)")
        # per-tenant device-cost accounting (batcher proration of lane
        # iteration wall by window share). Armed-only like the views
        # above: appears once a NAMED tenant has accrued device time;
        # the "" bucket then rides along so the series sum stays equal
        # to total lane device seconds (test-pinned)
        tdev = b.get("tenant_device_s")
        if tdev:
            counters["serve.tenant_device_seconds"] = obs_prom.Labeled(
                [({"tenant": t}, v) for t, v in sorted(tdev.items())],
                "device-seconds charged per tenant (lane iteration "
                "wall prorated by window share; empty tenant label = "
                "untenanted traffic)")
        # identity-audit families (obs/audit.py) — rendered ONLY when
        # the sentinel is armed, so an audit-off scrape stays
        # byte-identical to the pre-audit exposition (test-pinned)
        if self.auditor is not None:
            a = self.auditor.snapshot()
            counters["audit.windows"] = (
                a["windows"], "windows that passed through audited "
                "iterations (the sampling denominator)")
            counters["audit.sampled"] = (
                a["sampled"], "windows selected by the content-keyed "
                "sample at the armed rate")
            counters["audit.shadow_seconds"] = round(a["shadow_s"], 4)
            counters["audit.repaired"] = a["repaired"]
            counters["audit.demotions"] = (
                a["demotions"], "autotuner winner entries online-"
                "demoted to the oracle candidate after a mismatch")
            counters["audit.shadow_launches"] = a["shadow"]["launches"]
            counters["audit.shadow_compiles"] = a["shadow"]["compiles"]
            mism = self.auditor.mismatch_samples()
            if mism:
                counters["audit.mismatches"] = obs_prom.Labeled(
                    mism, "confirmed silent-data-corruption events by "
                    "(engine, kernel, dtype, bucket, lane)")
            gauges["audit.rate"] = (
                a["rate"], "deterministic content-keyed sample "
                "fraction the sentinel audits at")
            gauges["audit.alert"] = (
                a["alert_firing"],
                "1 while unacknowledged identity mismatches exist "
                "(clear via the debug RPC's audit_ack)")
            lane_rows = b.get("lanes") or ()
            if lane_rows:
                gauges["lane_health"] = obs_prom.Labeled(
                    [({"lane": str(l["lane"])}, l["health"])
                     for l in lane_rows],
                    "audit-sentinel lane health: 1 healthy, 0 "
                    "quarantined, 0.5 degraded (failed re-probe, "
                    "last serving lane)")
        # content-addressed window cache families (serve/wincache.py)
        # — rendered ONLY when the cache is armed, so a cache-off
        # scrape stays byte-identical to the pre-cache exposition
        # (test-pinned). The labeled ops family federates through
        # FleetAggregator like any labeled series.
        wc = self.batcher.wincache
        if wc is not None:
            c = wc.snapshot()
            counters["serve.wincache.ops"] = obs_prom.Labeled(
                [({"op": "eviction"}, c["evictions"]),
                 ({"op": "hit"}, c["hits"]),
                 ({"op": "invalidation"}, c["invalidations"]),
                 ({"op": "miss"}, c["misses"]),
                 ({"op": "put"}, c["puts"]),
                 ({"op": "quarantined"}, c["quarantined"])],
                "window consensus cache operations by outcome (a hit "
                "skips device dispatch entirely)")
            counters["serve.wincache.hit_bytes"] = (
                c["hit_bytes"], "consensus bytes served straight from "
                "the cache instead of a device iteration")
            gauges["serve.wincache.bytes"] = (
                c["bytes"], "resident cache payload bytes (LRU-bounded "
                "by max_bytes)")
            gauges["serve.wincache.entries"] = c["entries"]
            gauges["serve.wincache.max_bytes"] = c["max_bytes"]
        # serve-native rounds families — rendered only once a rounds
        # job has been seen (same armed-only discipline)
        with self._rounds_lock:
            r = dict(self._rounds)
        if r["jobs"]:
            counters["serve.rounds_jobs"] = (
                r["jobs"], "jobs that requested serve-native "
                "polishing rounds (rounds=N on the submit frame)")
            counters["serve.rounds_completed"] = (
                r["completed"], "polishing rounds completed across "
                "all rounds jobs")
            gauges["serve.rounds_inflight"] = (
                r["inflight"], "rounds jobs currently executing "
                "(each loops drafts in-process between rounds)")
        # QoS families (preemption / doomed-abort / cancel) — rendered
        # ONLY when a QoS knob is armed or an event has fired, so a
        # QoS-off scrape stays byte-identical to the pre-QoS
        # exposition (test-pinned)
        with self._qos_lock:
            qos = dict(self.qos)
            preempted_now = len(self._preempted)
        cfg = self.config
        if (cfg.preempt or cfg.abort_margin is not None
                or cfg.tenant_burst > 0 or any(qos.values())):
            counters["serve.preemptions"] = (
                qos["preemptions"], "running jobs preempted by a "
                "higher priority class (windows parked, resumed "
                "byte-identically when capacity frees)")
            counters["serve.aborted_doomed"] = (
                qos["aborted_doomed"], "jobs failed fast with "
                "deadline-doomed (predicted finish past the deadline "
                "by more than the abort margin)")
            counters["serve.cancelled"] = (
                qos["cancelled"], "jobs cancelled via the cancel RPC "
                "(queued or running)")
            gauges["serve.preempted_inflight"] = (
                preempted_now, "jobs currently parked by preemption "
                "(their completed windows are kept)")
            if cfg.tenant_burst > 0:
                counters["serve.burst_admits"] = (
                    q.get("burst_admits", 0), "admissions over the "
                    "hard tenant quota paid for by burst tokens")
        # SLO burn-rate view (obs/fleet.py tracker, fed by the queue's
        # on_slo hook)
        burn = self.burn.state()
        gauges["slo.burn_rate"] = (
            burn["fast"], "fast-window SLO burn rate: deadline-miss "
            "rate over the window as a multiple of the error budget")
        gauges["slo.burn_rate_slow"] = burn["slow"]
        gauges["slo.burn_alert"] = (
            burn["firing"],
            "1 while both burn windows exceed the threshold")
        # self-metered scrape cost (PRIOR renders — this body reports
        # the totals as they stood when it started rendering)
        with self._scrape_lock:
            counters["serve.scrapes"] = self._scrape_count
            counters["serve.scrape_seconds"] = round(
                self._scrape_render_s, 6)
        body = obs_prom.render(counters, gauges, self.hists)
        with self._scrape_lock:
            self._scrape_count += 1
            self._scrape_render_s += time.perf_counter() - t_render
        return body

    # -------------------------------------------------------------- misc
    def _inflight_count(self) -> int:
        with self._idle:
            return self._inflight

    def stats_snapshot(self) -> dict:
        with self._idle:
            inflight = self._inflight
        q = self.queue.snapshot()
        latency = self.hists.get("job.latency")
        deadlined = q["deadline_hit"] + q["deadline_miss"]
        # QoS view — present only when armed or an event fired (the
        # same discipline as the scrape families), so a QoS-off stats
        # body is byte-identical to pre-QoS output
        with self._qos_lock:
            qos = dict(self.qos)
            qos["preempted_inflight"] = len(self._preempted)
        cfg = self.config
        qos_armed = (cfg.preempt or cfg.abort_margin is not None
                     or cfg.tenant_burst > 0
                     or any(v for k, v in qos.items()))
        out = {"uptime_s": round(time.perf_counter() - self._t_start, 3),
                "warm": self._warm,
                "inflight": inflight,
                "draining": self._draining.is_set(),
                "queue": q,
                "batcher": self.batcher.snapshot(),
                # the SLO view: deadline hit/miss plus the rolling
                # latency window — the SAME service-time stream the
                # admission retry-after EMA is computed from
                "slo": {"deadline_hit": q["deadline_hit"],
                        "deadline_miss": q["deadline_miss"],
                        "expired": q["expired"],
                        "burn": self.burn.state(),
                        "miss_rate": round(
                            q["deadline_miss"] / deadlined, 4)
                        if deadlined else 0.0,
                        "recent": q.get("recent"),
                        "latency": (latency.snapshot()
                                    if latency is not None else None)},
                "audit": (self.auditor.snapshot()
                          if self.auditor is not None else None),
                "flight": {"dumps": list(self._dumps),
                           "installed": self._flight_installed},
                "journal": ({"path": self.config.journal_path,
                             "events": self.journal.events,
                             "dropped": self.journal.dropped}
                            if self.journal is not None else None)}
        if qos_armed:
            qos["preempt"] = cfg.preempt
            out["qos"] = qos
        return out

    @property
    def address(self) -> str:
        return self.config.address


# ------------------------------------------------------------------ CLI
def serve_main(argv: list[str]) -> int:
    """`racon_tpu serve` entry point: run a PolishServer until SIGTERM /
    SIGINT, then drain gracefully."""
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        prog="racon_tpu serve",
        description="warm polishing job server (unix socket or "
                    "localhost TCP; see README 'Serving')")
    ap.add_argument("--socket", default=None,
                    help=f"unix socket path (default "
                         f"RACON_TPU_SERVE_SOCKET or {DEFAULT_SOCKET})")
    ap.add_argument("--port", type=int, default=None,
                    help="listen on localhost TCP instead of the unix "
                         "socket (0 = ephemeral)")
    ap.add_argument("--workers", type=int, default=None,
                    help="job worker threads (RACON_TPU_SERVE_WORKERS, "
                         "default 2)")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="admission-control queue bound "
                         "(RACON_TPU_SERVE_QUEUE_DEPTH, default 16)")
    ap.add_argument("--drain-timeout", type=float, default=None,
                    help="graceful-drain budget in seconds "
                         "(RACON_TPU_SERVE_DRAIN_S, default 30)")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="continuous feeder: let a sparse window pool "
                         "coalesce up to this long before a short "
                         "device iteration (RACON_TPU_SERVE_MAX_WAIT_MS"
                         ", default 0 — dispatch immediately)")
    ap.add_argument("--iteration-windows", type=int, default=None,
                    help="continuous feeder: max windows per device "
                         "iteration — the latency quantum under load "
                         "(RACON_TPU_SERVE_ITERATION_WINDOWS, default "
                         "256)")
    ap.add_argument("--tenant-weights", default=None,
                    help="per-tenant fair-scheduling weights, e.g. "
                         "'gold=4,free=1,default=1' "
                         "(RACON_TPU_SERVE_TENANT_WEIGHTS)")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="hard per-tenant admission quota: max QUEUED "
                         "jobs per tenant, excess submits rejected "
                         "typed with retry_after "
                         "(RACON_TPU_SERVE_TENANT_QUOTA, default 0 = "
                         "off)")
    ap.add_argument("--worker-lanes", type=int, default=None,
                    help="partition the device mesh into this many "
                         "sub-mesh worker lanes, each with its own "
                         "feeder thread + engines, so device "
                         "iterations run concurrently across the "
                         "slice (RACON_TPU_WORKER_LANES, default 1; "
                         "clamps to the device count; output stays "
                         "byte-identical at any lane count)")
    ap.add_argument("--gather-ms", type=float, default=None,
                    help="DEPRECATED (round-barrier era): aliased to "
                         "--max-wait-ms with a deprecation warning")
    ap.add_argument("--audit-rate", type=float, default=None,
                    help="identity-audit sentinel: deterministically "
                         "sample this fraction of production windows "
                         "(content-keyed hash, no RNG) and shadow "
                         "re-execute them through the oracle path, "
                         "byte-comparing consensus output "
                         "(RACON_TPU_AUDIT_RATE, default 0 = off; "
                         "companions RACON_TPU_AUDIT_DEMOTE / "
                         "RACON_TPU_LANE_QUARANTINE gate the mismatch "
                         "consequences)")
    ap.add_argument("--wincache", action="store_true", default=None,
                    help="arm the content-addressed window cache: "
                         "windows whose (content, engine parameters, "
                         "kernel posture) key was already polished "
                         "skip device dispatch entirely and reuse the "
                         "stored consensus (RACON_TPU_WINCACHE; "
                         "biggest win with rounds=N where later "
                         "rounds converge; output stays "
                         "byte-identical, audit-compatible)")
    ap.add_argument("--wincache-max-bytes", type=int, default=None,
                    help="window-cache capacity bound in bytes, "
                         "LRU-evicted (RACON_TPU_WINCACHE_MAX_BYTES, "
                         "default 64 MiB)")
    ap.add_argument("--frag-group", type=int, default=None,
                    help="reads per streamed result_part frame on "
                         "fragment-correction jobs "
                         "(RACON_TPU_FRAG_GROUP, default 64; keep "
                         "homogeneous across a routed fleet — the "
                         "router's requeue dedupe assumes replicas "
                         "decompose a shard into the same read groups)")
    ap.add_argument("--preempt", action="store_true", default=None,
                    help="arm priority preemption: a newly admitted "
                         "higher-priority job parks the pooled windows "
                         "of a running lower-class job between device "
                         "iterations, resuming it byte-identically "
                         "when capacity frees (RACON_TPU_SERVE_PREEMPT"
                         ", default off)")
    ap.add_argument("--abort-margin", type=float, default=None,
                    help="speculative deadline-abort margin in "
                         "seconds: fail a job fast with "
                         "deadline-doomed when its predicted finish "
                         "exceeds the deadline by more than this, at "
                         "admission and at iteration boundaries "
                         "(RACON_TPU_SERVE_ABORT_MARGIN, default off)")
    ap.add_argument("--tenant-burst", type=int, default=None,
                    help="per-tenant burst tokens on top of the hard "
                         "quota: a tenant may exceed --tenant-quota by "
                         "up to this many queued jobs, tokens refilled "
                         "at its DRR weight per second "
                         "(RACON_TPU_SERVE_TENANT_BURST, default 0 = "
                         "off)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the synthetic warmup job (first real "
                         "request pays the compiles)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text metrics on this "
                         "localhost HTTP port (0 = ephemeral; "
                         "RACON_TPU_SERVE_METRICS_PORT; the `scrape` "
                         "RPC works regardless)")
    ap.add_argument("--flight-dir", default=None,
                    help="directory for automatic flight-recorder "
                         "dumps of failed / deadline-missed jobs "
                         "(RACON_TPU_SERVE_FLIGHT_DIR, falling back to "
                         "RACON_TPU_FLIGHT_DIR, default "
                         "/tmp/racon_tpu_flight; '' disables; an "
                         "unwritable path fails the start)")
    ap.add_argument("--journal", default=None,
                    help="durable JSONL event journal of every job "
                         "lifecycle transition, keyed by job and trace "
                         "id (RACON_TPU_SERVE_JOURNAL; size-bounded "
                         "via RACON_TPU_JOURNAL_MAX_BYTES; render with "
                         "tools/obsreport.py; an unwritable path fails "
                         "the start)")
    ap.add_argument("-w", "--window-length", type=int, default=500)
    ap.add_argument("-q", "--quality-threshold", type=float, default=10.0)
    ap.add_argument("-e", "--error-threshold", type=float, default=0.3)
    ap.add_argument("-m", "--match", type=int, default=3)
    ap.add_argument("-x", "--mismatch", type=int, default=-5)
    ap.add_argument("-g", "--gap", type=int, default=-4)
    ap.add_argument("-t", "--threads", type=int, default=2,
                    help="host threads per job")
    ap.add_argument("-c", "--tpupoa-batches", type=int, default=0)
    ap.add_argument("--tpualigner-batches", type=int, default=0)
    ap.add_argument("--tpualigner-band-width", type=int, default=0)
    ap.add_argument("--tpu-engine", choices=("session", "fused"),
                    default=None)
    ap.add_argument("--tpu-pipeline-depth", type=int, default=2)
    ap.add_argument("--tpu-adaptive-buckets", action="store_true")
    ap.add_argument("--tpu-compile-cache", default=None)
    args = ap.parse_args(argv)
    if args.tpupoa_batches > 0 or args.tpualigner_batches > 0:
        from ..sched import enable_compile_cache

        # a served process always keeps its compiles (the placement
        # rule: JAX_COMPILATION_CACHE_DIR, else the option, else the
        # checkout's .jax_cache)
        args.tpu_compile_cache = enable_compile_cache(args.tpu_compile_cache)

    kw: dict = {
        "warmup": not args.no_warmup,
        "window_length": args.window_length,
        "quality_threshold": args.quality_threshold,
        "error_threshold": args.error_threshold,
        "match": args.match, "mismatch": args.mismatch, "gap": args.gap,
        "job_threads": args.threads,
        "tpu_poa_batches": args.tpupoa_batches,
        "tpu_aligner_batches": args.tpualigner_batches,
        "tpu_aligner_band_width": args.tpualigner_band_width,
        "tpu_engine": args.tpu_engine,
        "tpu_pipeline_depth": args.tpu_pipeline_depth,
        "tpu_adaptive_buckets": args.tpu_adaptive_buckets or None,
        "tpu_compile_cache": args.tpu_compile_cache,
    }
    if args.socket is not None:
        kw["socket_path"] = args.socket
    if args.port is not None:
        kw["port"] = args.port
    if args.metrics_port is not None:
        kw["metrics_port"] = args.metrics_port
    if args.flight_dir is not None:
        kw["flight_dir"] = args.flight_dir
    if args.journal is not None:
        kw["journal"] = args.journal
    if args.workers is not None:
        kw["workers"] = args.workers
    if args.queue_depth is not None:
        kw["queue_depth"] = args.queue_depth
    if args.drain_timeout is not None:
        kw["drain_timeout_s"] = args.drain_timeout
    if args.max_wait_ms is not None:
        kw["max_wait_s"] = args.max_wait_ms / 1000.0
    if args.iteration_windows is not None:
        kw["iteration_windows"] = args.iteration_windows
    if args.tenant_weights is not None:
        kw["tenant_weights"] = args.tenant_weights
    if args.tenant_quota is not None:
        kw["tenant_quota"] = args.tenant_quota
    if args.worker_lanes is not None:
        kw["worker_lanes"] = args.worker_lanes
    if args.audit_rate is not None:
        kw["audit_rate"] = args.audit_rate
    if args.frag_group is not None:
        kw["frag_group"] = args.frag_group
    if args.wincache:
        kw["wincache"] = True
    if args.wincache_max_bytes is not None:
        kw["wincache_max_bytes"] = args.wincache_max_bytes
    if args.preempt:
        kw["preempt"] = True
    if args.abort_margin is not None:
        kw["abort_margin"] = args.abort_margin
    if args.tenant_burst is not None:
        kw["tenant_burst"] = args.tenant_burst
    if args.gather_ms is not None:
        # deprecated alias: ServeConfig warns and maps it to max_wait_s
        kw["gather_window_s"] = args.gather_ms / 1000.0

    try:
        server = PolishServer(**kw).start()
    except (RaconError, OSError) as exc:
        print(f"[racon_tpu::serve] error: {exc}", file=sys.stderr)
        return 1

    stop = threading.Event()

    def _on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    while not stop.is_set() and not server._stopped.is_set():
        stop.wait(0.2)
    server.drain()
    return 0
