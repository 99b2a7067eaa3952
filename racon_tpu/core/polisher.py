"""Polisher: whole-pipeline orchestration.

parse -> filter -> align (device) -> window -> POA consensus (device) -> stitch.

Mirrors the reference pipeline semantics (src/polisher.cpp:192-548) while
replacing both compute hot spots with batched XLA programs:

  - overlap CIGARs: ops/align.BatchAligner  (vs edlib / cudaaligner)
  - window consensus: ops/poa.BatchPOA      (vs spoa / cudapoa)

The reference's CPU/GPU split (Polisher vs CUDAPolisher,
src/cuda/cudapolisher.cpp) becomes a single Polisher whose device batches run
wherever JAX is pointed (TPU chip(s) or CPU), optionally sharded over a mesh
(parallel/mesh.py) — the TPU-native equivalent of its multi-GPU batch loop.
"""

from __future__ import annotations

import collections
import enum
import os
import time

import numpy as np

from ..errors import DeviceError, RaconError, as_device_error
from ..obs import trace
from ..obs.metrics import MetricsRegistry
from ..resilience import REPORT_KEYS, degradation_summary, strict_mode
from ..io.parsers import create_sequence_parser, create_overlap_parser
from ..utils.logger import (Logger, flush_dedup, log_info, log_level,
                            reset_dedup, DEBUG)
from ..utils.cigar import cigar_from_ops
from .sequence import Sequence, create_sequence
from .window import Window, WindowType, create_window

KCHUNK_SIZE = 1024 * 1024 * 1024  # reference polisher.cpp:26


class PolisherType(enum.Enum):
    kC = 0  # contig polishing
    kF = 1  # fragment (read) error correction


def create_polisher(sequences_path: str, overlaps_path: str, target_path: str,
                    type_: PolisherType, window_length: int,
                    quality_threshold: float, error_threshold: float,
                    trim: bool = True, match: int = 3, mismatch: int = -5,
                    gap: int = -4, num_threads: int = 1,
                    tpu_poa_batches: int = 0, tpu_banded_alignment: bool = True,
                    tpu_aligner_batches: int = 0,
                    tpu_aligner_band_width: int = 0,
                    tpu_engine: str | None = None,
                    tpu_pipeline_depth: int = 2,
                    tpu_device_timeout: float = 0.0,
                    tpu_adaptive_buckets: bool | None = None,
                    tpu_compile_cache: str | None = None,
                    tpu_fault_plan: str | None = None) -> "Polisher":
    """Factory mirroring reference createPolisher (polisher.cpp:55-160).

    The tpu_* knobs parallel the reference's CUDA flags (main.cpp:36-41); the
    device path is always available, so they tune batching rather than select
    a different subclass. `tpu_pipeline_depth` sizes the async dispatch
    pipeline (pipeline.DispatchPipeline) both hot phases run through;
    0 disables the overlap entirely (the synchronous path, for bisection).
    `tpu_device_timeout` (seconds, 0 = off) arms the resilience watchdog:
    device-stage calls run under that deadline with bounded retry +
    backoff before a chunk routes to host fallback.
    `tpu_adaptive_buckets` arms the occupancy-aware batch scheduler
    (racon_tpu/sched/): every device engine derives its shape ladder from
    the run's job-shape histogram and packs shape-sorted chunks (output
    stays byte-identical; None defers to RACON_TPU_ADAPTIVE_BUCKETS).
    `tpu_compile_cache` points jax's persistent compilation cache at a
    directory so repeated runs — including adaptive ones with
    data-derived shapes — skip recompiles (None defers to
    RACON_TPU_COMPILE_CACHE).
    `tpu_fault_plan` arms a fault-injection plan for THIS polisher only
    (the serve layer's per-job isolation; None defers to the process-wide
    RACON_TPU_FAULT_PLAN posture).
    """
    if not isinstance(type_, PolisherType):
        raise RaconError("createPolisher", "invalid polisher type!")
    if window_length == 0:
        raise RaconError("createPolisher", "invalid window length!")

    sparser = create_sequence_parser(sequences_path, "createPolisher")
    oparser = create_overlap_parser(overlaps_path, "createPolisher")
    tparser = create_sequence_parser(target_path, "createPolisher")

    return Polisher(sparser, oparser, tparser, type_, window_length,
                    quality_threshold, error_threshold, trim, match, mismatch,
                    gap, num_threads, tpu_poa_batches, tpu_banded_alignment,
                    tpu_aligner_batches, tpu_aligner_band_width, tpu_engine,
                    tpu_pipeline_depth, tpu_device_timeout,
                    tpu_adaptive_buckets, tpu_compile_cache, tpu_fault_plan)


class Polisher:
    def __init__(self, sparser, oparser, tparser, type_: PolisherType,
                 window_length: int, quality_threshold: float,
                 error_threshold: float, trim: bool, match: int, mismatch: int,
                 gap: int, num_threads: int = 1, tpu_poa_batches: int = 0,
                 tpu_banded_alignment: bool = True, tpu_aligner_batches: int = 0,
                 tpu_aligner_band_width: int = 0,
                 tpu_engine: str | None = None,
                 tpu_pipeline_depth: int = 2,
                 tpu_device_timeout: float = 0.0,
                 tpu_adaptive_buckets: bool | None = None,
                 tpu_compile_cache: str | None = None,
                 tpu_fault_plan: str | None = None):
        self.sparser = sparser
        self.oparser = oparser
        self.tparser = tparser
        self.type = type_
        self.window_length = window_length
        self.quality_threshold = quality_threshold
        self.error_threshold = error_threshold
        self.trim = trim
        self.match = match
        self.mismatch = mismatch
        self.gap = gap
        self.num_threads = num_threads
        self.tpu_poa_batches = tpu_poa_batches
        self.tpu_banded_alignment = tpu_banded_alignment
        self.tpu_aligner_batches = tpu_aligner_batches
        self.tpu_aligner_band_width = tpu_aligner_band_width
        self.tpu_engine = tpu_engine
        self.tpu_pipeline_depth = max(0, tpu_pipeline_depth)
        self.tpu_device_timeout = max(0.0, tpu_device_timeout)
        # per-polisher fault plan (serve mode: each job's injected faults
        # stay its own); None defers every pipeline to the process-wide
        # RACON_TPU_FAULT_PLAN posture, the one-shot CLI behavior
        from ..resilience import FaultPlan

        self.faults = (FaultPlan.parse(tpu_fault_plan)
                       if tpu_fault_plan else None)
        # per-stage wall-clock counters shared by both hot phases' dispatch
        # pipelines (pack / device / unpack / fallback seconds, launch and
        # chunk counts) — the observability half of the overlap design;
        # bench.py emits the snapshot in its JSON artifact
        from ..obs.hist import HistogramSet
        from ..pipeline import PipelineStats

        # per-run latency histograms (obs/hist.py): per-chunk pipeline
        # stage durations, per-engine compile stalls and the polisher
        # phase durations, snapshotted as the metrics registry's
        # `latency` namespace — the serve layer folds each job's set
        # into its lifetime scrape view
        self.hists = HistogramSet()
        self.pipeline_stats = PipelineStats(hists=self.hists)
        # the occupancy-aware batch scheduler (racon_tpu/sched/), shared
        # by the aligner and whichever consensus engine runs: adaptive
        # ladders + sorted packing when armed (CLI flag winning over
        # RACON_TPU_ADAPTIVE_BUCKETS), per-bucket occupancy telemetry
        # always; the compile-cache knob composes so adaptive shapes
        # survive process restarts
        from ..sched import BatchScheduler

        self.scheduler = BatchScheduler.from_env(
            adaptive=tpu_adaptive_buckets,
            compile_cache=tpu_compile_cache)
        self.scheduler.stats.hists = self.hists

        self.sequences: list[Sequence] = []
        self.windows: list[Window] = []
        self.targets_coverages: list[int] = []
        # window-range shard slice (serve/router.py sub-contig sharding):
        # (lo, hi) target coordinates — initialize() keeps only windows
        # whose grid start j satisfies lo <= j < hi (boundary windows
        # owned by exactly one shard since starts are exact), and
        # _stitch_contig emits bare-named SEGMENTS with their stitch
        # accounting in `segment_meta` instead of tagged contigs. None
        # (the default) is the classic whole-target run, byte-identical
        # to the pre-range code path.
        self.window_range: tuple[int, int] | None = None
        # fragment read-range shard slice (serve/router.py fragment
        # fan-out): (lo, hi) TARGET-INDEX bounds — initialize() keeps
        # only targets whose index in the target file falls in [lo, hi).
        # Overlaps onto dropped targets resolve to no target and are
        # skipped (Overlap.transmute marks them invalid), so a shard
        # corrects exactly its read slice. None (the default) is the
        # classic whole-set run. Orthogonal to window_range, which
        # slices one target's COORDINATE axis.
        self.target_range: tuple[int, int] | None = None
        #: per-contig segment accounting for range-shard runs —
        #: {name: {polished, windows, total_windows, coverage, lo, hi}};
        #: the router re-derives the solo LN/RC/XC tags from these when
        #: it stitches sibling segments back together
        self.segment_meta: dict[str, dict] = {}
        #: per-target rank of the first KEPT window (all zeros outside
        #: range mode) — the layer loop's window-id remap offset
        self._range_first_rank: list[int] = []
        self.dummy_quality = b"!" * window_length
        self.logger = Logger()
        # live progress hook (serve mode: the server forwards these as
        # interleaved progress frames; see README "End-to-end tracing &
        # progress"): callable(event_dict) or None — the zero-overhead
        # default. Events carry phase / done / total; emission is
        # best-effort and monotonic per phase (emit_progress).
        self.progress_hook = None
        # device-mesh pin (serve worker lanes): a parallel.mesh
        # BatchRunner the consensus engines dispatch through instead of
        # the full auto-discovered mesh. The serve batcher sets it so an
        # ISOLATION job (own fault plan / strict) runs solo on ONE
        # lane's sub-mesh while the other lanes keep serving; None (the
        # one-shot default) lets every engine build its own full-mesh
        # runner.
        self.device_runner = None
        self._progress_phase: str | None = None
        self._progress_hwm: tuple[str, int, int] = ("", 0, 0)
        import threading as _threading

        # built eagerly: a lazy check-then-set would race the first two
        # concurrent bar ticks (pipeline unpack worker vs fallback
        # pool) into two different locks, defeating the monotone HWM
        self._progress_lock = _threading.Lock()
        self._num_targets = 0
        #: completed initialize()+polish() cycles — a reused (warm)
        #: polisher resets its per-run counters at the next initialize()
        #: so every run's stats describe that run alone
        self._runs_completed = 0
        # alignment-phase accounting (reference cudapolisher.cpp:204-206)
        self.n_aligner_pairs = 0
        self.n_aligner_device = 0
        self.n_aligner_host_fallback = 0
        #: consensus-phase window placement (ops/poa.BatchPOA)
        self.window_counts: dict = {}
        # the unified metrics registry (obs/metrics.py): the pipeline
        # stage counters, the resilience degradation counters, the
        # scheduler's occupancy telemetry and the aligner accounting, one
        # namespaced snapshot — bench JSON "metrics" field, the
        # --tpu-metrics dump, and the end-of-run stderr table
        # resolve the env-armed tracer NOW so its time base predates
        # every phase span (a lazy first-hook resolution mid-initialize
        # would start the clock after t_init and clamp the ts to 0)
        trace.get_tracer()
        self.metrics = MetricsRegistry()
        self.metrics.register(
            "pipeline", lambda: {k: v
                                 for k, v in self.stage_stats.items()
                                 if k not in REPORT_KEYS})
        self.metrics.register(
            "resilience", lambda: {k: self.stage_stats.get(k, 0)
                                   for k in REPORT_KEYS})
        # late-bound lambda, not the bound method: a warm-reused polisher
        # swaps in a fresh OccupancyStats per run and the registry must
        # follow it
        self.metrics.register("sched",
                              lambda: self.scheduler.stats.snapshot())
        self.metrics.register("latency", lambda: self.hists.snapshot())
        self.metrics.register(
            "aligner", lambda: {
                "pairs": self.n_aligner_pairs,
                "device_pairs": self.n_aligner_device,
                "host_fallbacks": self.n_aligner_host_fallback,
                "band_width": self.tpu_aligner_band_width})

    def _make_pipeline(self):
        """One DispatchPipeline per hot phase, all feeding the shared
        stage counters. depth 0 = the synchronous path (bisection).
        The resilience posture rides along: the device watchdog (CLI
        --tpu-device-timeout winning over the env knobs) and the armed
        fault plan, both usually None — the zero-overhead clean path."""
        from ..pipeline import DispatchPipeline
        from ..resilience import Watchdog, get_fault_plan

        return DispatchPipeline(depth=self.tpu_pipeline_depth,
                                stats=self.pipeline_stats,
                                fallback_workers=max(
                                    1, min(4, self.num_threads)),
                                watchdog=Watchdog.from_env(
                                    timeout=self.tpu_device_timeout
                                    or None),
                                faults=(self.faults
                                        if self.faults is not None
                                        else get_fault_plan()))

    @property
    def stage_stats(self) -> dict:
        """Snapshot of the per-stage pipeline counters (both phases)."""
        return self.pipeline_stats.snapshot()

    @property
    def occupancy_stats(self) -> dict:
        """Snapshot of the scheduler's per-bucket occupancy counters
        (jobs / batches / lanes / useful vs padded cells / occupancy %
        per engine, plus compile count and seconds) — bench.py publishes
        this next to `stages` in its JSON artifact."""
        return self.scheduler.stats.snapshot()

    # ------------------------------------------------------- progress
    def emit_progress(self, done, total, phase: str | None = None,
                      **extra) -> None:
        """Push one live-progress event at the armed hook. Contract the
        serve layer's progress frames inherit: per phase, `done` and
        `total` are monotonically non-decreasing (a fallback engine
        re-arming a smaller bar inside the same phase cannot make the
        client's bar run backwards), and emission NEVER raises — live
        progress is decoration on a run, not a dependency of it."""
        hook = self.progress_hook
        if hook is None:
            return
        ph = phase or self._progress_phase or "run"
        # the hook is invoked INSIDE the lock: two concurrent bar ticks
        # that computed done=5 and done=6 under the lock could
        # otherwise deliver 6 then 5 and run the client's bar
        # backwards; hooks only enqueue (Job.notify_progress pushes onto
        # its DeliveryQueue), so holding the lock across them is safe and cheap
        with self._progress_lock:
            hwm_phase, hwm_done, hwm_total = self._progress_hwm
            if ph != hwm_phase:
                hwm_done = hwm_total = 0
            d = max(int(done), hwm_done)
            t = max(int(total), hwm_total)
            self._progress_hwm = (ph, d, t)
            ev = {"phase": ph, "done": min(d, t), "total": t}
            ev.update(extra)
            try:
                hook(ev)
            except Exception:  # noqa: BLE001 — see docstring
                pass

    def _progress_tick(self, count: int, total: int) -> None:
        """Logger.on_bar adapter: bar bin transitions become progress
        events attributed to the phase currently running."""
        self.emit_progress(min(count, total), total)

    def _arm_progress(self) -> None:
        """Wire the (per-run) logger's bar ticks into the progress hook;
        called at phase starts because _reset_run_state swaps loggers."""
        if self.progress_hook is not None:
            self.logger.on_bar = self._progress_tick

    # ------------------------------------------------------- warm reuse
    def _reset_run_state(self) -> None:
        """Fresh per-run counters for a warm-reused polisher: a second
        initialize()+polish() cycle must report ITS OWN stage seconds,
        occupancy, degradation and aligner counts — not a running total
        across jobs — and its FASTA must be byte-identical to a fresh-
        process run (tests/test_serve.py pins both). Engines, jit caches
        and the compile-cache posture are process-level and deliberately
        stay warm."""
        from ..obs.hist import HistogramSet
        from ..pipeline import PipelineStats
        from ..sched import OccupancyStats

        self.hists = HistogramSet()
        self.pipeline_stats = PipelineStats(hists=self.hists)
        self.scheduler.stats = OccupancyStats()
        self.scheduler.stats.hists = self.hists
        self.n_aligner_pairs = 0
        self.n_aligner_device = 0
        self.n_aligner_host_fallback = 0
        self.logger = Logger()
        self.targets_coverages = []
        self.segment_meta = {}
        self._range_first_rank = []
        self._num_targets = 0
        self._progress_phase = None
        self._progress_hwm = ("", 0, 0)

    def rebind(self, sequences_path: str, overlaps_path: str,
               target_path: str) -> "Polisher":
        """Warm-reuse entry point: point this polisher at a new input
        triple (parsers rebuilt, per-run state reset) while keeping the
        warm process-level state — jit caches, adaptive posture, compile
        cache, metrics registry. The serve layer uses this shape of
        reuse; the next initialize() parses the new inputs."""
        if self.windows:
            raise RaconError("Polisher.rebind",
                             "cannot rebind mid-run (windows pending)!")
        self.sparser = create_sequence_parser(sequences_path,
                                              "Polisher.rebind")
        self.oparser = create_overlap_parser(overlaps_path,
                                             "Polisher.rebind")
        self.tparser = create_sequence_parser(target_path,
                                              "Polisher.rebind")
        self._reset_run_state()
        return self

    def redraft(self, polished, workdir: str,
                tag: str = "round") -> tuple[str, str]:
        """Warm re-draft for serve-native polishing rounds: take round
        k's stitched contigs, write them as round k+1's draft, re-map
        the ORIGINAL reads against them in-process (core/remap.py — no
        external mapper, no process exit), and rebind this polisher to
        the new triple. The next initialize()+polish() cycle IS round
        k+1, on the same warm engines/jit caches/autotune posture.

        Both the serve rounds loop and the chained-solo test path call
        this one entry, so `rounds=N` output is byte-identical to N
        chained runs by construction (tests/test_rounds.py pins it).
        Returns the (draft_fasta, overlaps_paf) paths written under
        `workdir`. The reads are re-parsed from the ORIGINAL reads path
        (the polisher streams reads and never holds them whole — one
        extra parse per round is the cost of the bounded-memory
        contract)."""
        import os as _os

        from .remap import remap_overlaps, write_fasta, write_paf

        if not polished:
            raise RaconError("Polisher.redraft",
                             "no polished sequences to re-draft from!")
        reads_path = self.sparser.path
        fasta_path = write_fasta(
            polished, _os.path.join(workdir, f"{tag}_draft.fasta"))
        reads: list[Sequence] = []
        rparser = create_sequence_parser(reads_path, "Polisher.redraft")
        rparser.reset()
        rparser.parse(reads, -1)
        rows = remap_overlaps(reads, polished)
        if not rows:
            raise RaconError("Polisher.redraft",
                             "no reads re-mapped onto the new draft!")
        paf_path = write_paf(
            rows, _os.path.join(workdir, f"{tag}_ovl.paf"))
        self.rebind(reads_path, paf_path, fasta_path)
        return fasta_path, paf_path

    # ------------------------------------------------------------------ init
    def initialize(self) -> None:
        if self.windows:
            log_info("[racon_tpu::Polisher.initialize] warning: "
                     "object already initialized!")
            return
        if self._runs_completed:
            # warm reuse: this is run N+1 in the same process — counters
            # describe one run each (see _reset_run_state)
            self._reset_run_state()

        # a new run starts with clean dedup state: a previous in-process
        # run that crashed before its flush must not leave keys behind
        # that would silently swallow this run's first warnings
        reset_dedup()
        self._arm_progress()
        with trace.timed("polisher.initialize") as sp:
            self._initialize()
            sp.set(windows=len(self.windows), targets=self._num_targets)
        self.hists.observe("phase.initialize", sp.t1 - sp.t0)
        # per-phase flush: initialize-only flows (bench's aligner phase)
        # must still report suppressed duplicate-warning counts; a repeat
        # spanning both phases then reports once per phase
        flush_dedup()

    def _initialize(self) -> None:
        """initialize()'s work, one span per phase: targets, reads,
        overlaps (parse, filter, reverse complements), their alignment
        into breaking points, and the window grid with its layers."""
        log = self.logger
        log.log()

        with trace.span("polisher.load_targets"):
            # -- targets (loaded whole; reference polisher.cpp:202-217)
            self.tparser.reset()
            self.tparser.parse(self.sequences, -1)
            target_base = 0
            if self.target_range is not None:
                # fragment read-range shard: keep only the targets whose
                # FILE index falls in [lo, hi). The id_to_id keys below use
                # the original file index, so id-keyed overlap formats
                # (MHAP) resolve identically to name-keyed ones; overlaps
                # onto dropped targets simply fail to resolve and are
                # skipped as invalid.
                lo, hi = self.target_range
                total = len(self.sequences)
                lo, hi = max(0, int(lo)), min(int(hi), total)
                if hi <= lo:
                    raise RaconError(
                        "Polisher.initialize",
                        f"target_range [{self.target_range[0]}, "
                        f"{self.target_range[1]}) selects no targets out of "
                        f"{total}!")
                del self.sequences[hi:]
                del self.sequences[:lo]
                target_base = lo
            targets_size = len(self.sequences)
            self._num_targets = targets_size
            if targets_size == 0:
                raise RaconError("Polisher.initialize",
                                 "empty target sequences set!")

            name_to_id: dict[str, int] = {}
            id_to_id: dict[int, int] = {}
            for i in range(targets_size):
                name_to_id[self.sequences[i].name + "t"] = i
                id_to_id[(target_base + i) << 1 | 1] = i

            has_name = [True] * targets_size
            has_data = [True] * targets_size
            has_reverse_data = [False] * targets_size

            log.log("[racon_tpu::Polisher.initialize] loaded target "
                    "sequences")
            log.log()

        with trace.span("polisher.load_reads"):
            # -- reads streamed in chunks; duplicates of targets share storage
            #    (reference polisher.cpp:228-264)
            sequences_size = 0
            total_sequences_length = 0
            self.sparser.reset()
            more = True
            while more:
                start = len(self.sequences)
                more = self.sparser.parse(self.sequences, KCHUNK_SIZE)
                kept: list[Sequence] = []
                for seq in self.sequences[start:]:
                    total_sequences_length += len(seq.data)
                    tgt = name_to_id.get(seq.name + "t")
                    if tgt is not None:
                        dup = self.sequences[tgt]
                        if len(seq.data) != len(dup.data) or \
                           len(seq.quality) != len(dup.quality):
                            raise RaconError(
                                "Polisher.initialize",
                                f"duplicate sequence {seq.name} with "
                                "unequal data")
                        name_to_id[seq.name + "q"] = tgt
                        id_to_id[sequences_size << 1 | 0] = tgt
                    else:
                        gid = start + len(kept)
                        name_to_id[seq.name + "q"] = gid
                        id_to_id[sequences_size << 1 | 0] = gid
                        kept.append(seq)
                    sequences_size += 1
                del self.sequences[start:]
                self.sequences.extend(kept)

            if sequences_size == 0:
                raise RaconError("Polisher.initialize", "empty sequences set!")

            n_seqs = len(self.sequences)
            has_name += [False] * (n_seqs - targets_size)
            has_data += [False] * (n_seqs - targets_size)
            has_reverse_data += [False] * (n_seqs - targets_size)

            window_type = (WindowType.kNGS
                           if total_sequences_length / sequences_size <= 1000
                           else WindowType.kTGS)

            log.log("[racon_tpu::Polisher.initialize] loaded sequences")
            log.log()

        with trace.span("polisher.load_overlaps") as sp:
            # -- overlaps streamed; per-query filtering (polisher.cpp:284-355)
            overlaps, counts = self._load_overlaps(
                name_to_id, id_to_id, has_data, has_reverse_data)
            sp.set(kept=len(overlaps), **counts)
            if not overlaps and self.target_range is None:
                # a fragment read-range shard may legitimately hold only
                # targets without overlaps (they come back unpolished, and
                # drop the same way a solo run drops them) — the whole-set
                # run keeps the reference's hard error
                raise RaconError("Polisher.initialize", "empty overlap set!")

            log.log("[racon_tpu::Polisher.initialize] loaded overlaps")
            log.log()

            # -- free unneeded storage; build revcomps where needed
            for i, seq in enumerate(self.sequences):
                seq.transmute(has_name[i], has_data[i], has_reverse_data[i])

        self._progress_phase = "align"
        with trace.span("polisher.align_overlaps"):
            self.find_overlap_breaking_points(overlaps)

        log.log()

        with trace.span("polisher.build_windows") as sp:
            # -- windows (polisher.cpp:384-399); in range mode only the grid
            #    positions with lo <= start < hi materialize, but `rank`
            #    stays the GLOBAL grid rank so per-window identity (and
            #    output) is independent of which slice holds the window
            rng = self.window_range
            id_to_first_window_id = [0] * (targets_size + 1)
            self._range_first_rank = [0] * targets_size
            for i in range(targets_size):
                data = self.sequences[i].data
                quality = self.sequences[i].quality
                k = 0
                kept = 0
                for j in range(0, len(data), self.window_length):
                    if rng is None or rng[0] <= j < rng[1]:
                        length = min(j + self.window_length, len(data)) - j
                        q = quality[j:j + length] if quality \
                            else self.dummy_quality[:length]
                        self.windows.append(create_window(
                            i, k, window_type, data[j:j + length], q))
                        if kept == 0:
                            self._range_first_rank[i] = k
                        kept += 1
                    k += 1
                id_to_first_window_id[i + 1] = id_to_first_window_id[i] + kept

            self.targets_coverages = [0] * targets_size

            # -- layer assignment (polisher.cpp:403-457)
            wl = self.window_length
            layers = 0
            for o in overlaps:
                self.targets_coverages[o.t_id] += 1
                seq = self.sequences[o.q_id]
                bps = o.breaking_points
                if bps is None:
                    continue
                qual_fwd = seq.quality
                has_qual = bool(qual_fwd) or bool(seq._reverse_quality)
                if o.strand:
                    data_src = seq.reverse_complement
                    qual_src = seq.reverse_quality if has_qual else None
                else:
                    data_src = seq.data
                    qual_src = qual_fwd if has_qual else None
                qual_arr = (np.frombuffer(qual_src, dtype=np.uint8)
                            if qual_src else None)

                for t_first, q_first, t_last1, q_last1 in bps:
                    if q_last1 - q_first < 0.02 * wl:
                        continue
                    if qual_arr is not None:
                        avg = float(qual_arr[q_first:q_last1].mean()) - 33.0
                        if avg < self.quality_threshold:
                            continue
                    window_start = (t_first // wl) * wl
                    if rng is not None and \
                            not rng[0] <= window_start < rng[1]:
                        continue
                    window_id = (id_to_first_window_id[o.t_id]
                                 + t_first // wl
                                 - self._range_first_rank[o.t_id])
                    data = data_src[q_first:q_last1]
                    qual = (qual_src[q_first:q_last1] if qual_src else None)
                    self.windows[window_id].add_layer(
                        data, qual, int(t_first - window_start),
                        int(t_last1 - window_start - 1))
                    layers += 1
                o.breaking_points = None
            sp.set(targets=targets_size, windows=len(self.windows),
                   layers=layers)

            log.log("[racon_tpu::Polisher.initialize] transformed data "
                    "into windows")
        # announce the window total as consensus progress zero: the
        # client's bar knows its denominator before the first round
        self.emit_progress(0, len(self.windows), phase="consensus")

    def _load_overlaps(self, name_to_id, id_to_id, has_data, has_reverse_data):
        """The overlaps kept by the per-query filter, and the counts of
        rows parsed and of rows dropped for error and for self-overlap."""
        overlaps: list = []
        counts = {"rows": 0, "dropped_error": 0, "dropped_self": 0}
        error_threshold = self.error_threshold
        is_kc = self.type == PolisherType.kC

        def filter_group(group: list) -> list:
            """Drop high-error/self overlaps; for contig polishing keep only
            the longest overlap per query. Replicates the reference's exact
            pass structure (polisher.cpp:284-308): the error check runs when
            the outer scan reaches an overlap, so a high-error overlap can
            still knock out a longer-or-equal earlier one before being
            removed itself, and length ties keep the LATER overlap."""
            arr: list = list(group)
            for i in range(len(arr)):
                o = arr[i]
                if o is None:
                    continue
                if o.error > error_threshold or o.q_id == o.t_id:
                    counts["dropped_error" if o.error > error_threshold
                           else "dropped_self"] += 1
                    arr[i] = None
                    continue
                if is_kc:
                    for j in range(i + 1, len(arr)):
                        if arr[j] is None:
                            continue
                        if o.length > arr[j].length:
                            arr[j] = None
                        else:
                            arr[i] = None
                            break
            return [o for o in arr if o is not None]

        self.oparser.reset()
        pending: list = []   # current same-q_id run
        more = True
        while more:
            chunk: list = []
            more = self.oparser.parse(chunk, KCHUNK_SIZE)
            counts["rows"] += len(chunk)
            for o in chunk:
                o.transmute(self.sequences, name_to_id, id_to_id)
                if not o.is_valid:
                    continue
                if pending and pending[0].q_id != o.q_id:
                    for f in filter_group(pending):
                        overlaps.append(f)
                        if f.strand:
                            has_reverse_data[f.q_id] = True
                        else:
                            has_data[f.q_id] = True
                    pending = []
                pending.append(o)
        for f in filter_group(pending):
            overlaps.append(f)
            if f.strand:
                has_reverse_data[f.q_id] = True
            else:
                has_data[f.q_id] = True
        return overlaps, counts

    # ------------------------------------------------------- alignment phase
    def find_overlap_breaking_points(self, overlaps: list) -> None:
        """Align CIGAR-less overlaps, then walk all CIGARs into per-window
        breaking points (reference polisher.cpp:462-484 /
        cudapolisher.cpp:74-214).

        Default path is the host exact aligner (the edlib role). With
        tpu_aligner_batches > 0 the batched device kernel handles everything
        it can and the host aligns the rejects — the reference's GPU->CPU
        fallback (cudapolisher.cpp:203-213): no overlap is ever dropped.
        """
        from ..native import nw_cigar_batch

        need = [o for o in overlaps
                if not o.cigar and o.is_valid and self._range_keeps(o)]
        if need:
            with trace.span("polisher.extract_pairs", pairs=len(need)):
                pairs = []
                for o in need:
                    q_span = o.aligned_query_span(self.sequences)
                    t_span = self.sequences[o.t_id].data[o.t_begin:o.t_end]
                    pairs.append((q_span, t_span))

            self.logger.bar_total(len(pairs))
            bar_msg = "[racon_tpu::Polisher.initialize] aligning overlaps"

            def bar_n(n):
                for _ in range(n):
                    self.logger.bar(bar_msg)

            runs = [None] * len(pairs)
            self.n_aligner_pairs = len(pairs)
            handled: set[int] = set()
            if self.tpu_aligner_batches > 0:
                from ..ops.align import BatchAligner
                aligner = BatchAligner(band_width=self.tpu_aligner_band_width,
                                       scheduler=self.scheduler,
                                       runner=self.device_runner)
                pipeline = self._make_pipeline()
                fb: list[tuple[list[int], object]] = []
                # concurrent fallback jobs split the thread budget so the
                # pool never oversubscribes the host beyond num_threads;
                # at depth 0 jobs run inline (serial) and keep the full
                # budget — the synchronous bisection path must not be
                # slower than the pre-pipeline code
                fb_threads = (self.num_threads if pipeline.depth == 0
                              else max(1, self.num_threads
                                       // pipeline.fallback_workers))

                #: pair index -> why the aligner left it to the host; one
                #: dict.update per call, so the pipeline's threads may
                #: report at once
                host_reasons: dict[int, str] = {}

                def on_reject(idxs, reason):
                    # rejected pairs (too long for any bucket, or band-
                    # clipped) start host-aligning the moment they are
                    # known — the reference's GPU->CPU fallback
                    # (cudapolisher.cpp:203-213), overlapped with the
                    # device pass instead of serialized after it
                    host_reasons.update(dict.fromkeys(idxs, reason))
                    fb.extend(pipeline.map_fallback(
                        idxs,
                        lambda sub: nw_cigar_batch(
                            [pairs[i] for i in sub], n_threads=fb_threads,
                            progress=bar_n),
                        chunk=512))

                def degrade(exc: DeviceError):
                    # the cudautils-style device error check with graceful
                    # degradation instead of exit (cudautils.hpp:10-18).
                    # Before the host re-align pass restarts, the fallback
                    # pool must be emptied — cancel the queued jobs and
                    # drain the running ones — or orphaned fallback
                    # threads would keep aligning (and bumping the
                    # just-restarted progress bar) underneath it
                    cancelled, drained = pipeline.cancel_fallback()
                    log_info("[racon_tpu::Polisher.initialize] warning: "
                             f"device alignment failed ({exc}); falling "
                             f"back to host aligner ({cancelled} fallback "
                             f"jobs cancelled, {drained} drained)")
                    self.logger.bar_total(len(pairs))  # restart progress
                    host_reasons.update(dict.fromkeys(range(len(pairs)),
                                                      "device_failure"))
                    return [None] * len(pairs), set()

                try:
                    runs = aligner.align(pairs, progress=bar_n,
                                         pipeline=pipeline,
                                         on_reject=on_reject)
                    with trace.span("polisher.fallback_drain",
                                    jobs=len(fb)):
                        pipeline.drain_fallback()
                        for sub, fut in fb:
                            for i, c in zip(sub, fut.result()):
                                need[i].cigar = c
                            handled.update(sub)
                except DeviceError as exc:
                    if strict_mode():
                        raise
                    runs, handled = degrade(exc)
                except RaconError:
                    raise  # user-facing input error: never degraded away
                except Exception as exc:  # device init/OOM: host completes
                    if strict_mode():
                        raise
                    runs, handled = degrade(as_device_error(
                        exc, "Polisher.initialize"))
                finally:
                    pipeline.close()
                # every pair asked of the device, and why those that left
                # it did: the benchmark's guard against a cell timing the
                # host aligner unseen
                self.scheduler.stats.record_pairs(
                    "aligner", len(pairs),
                    collections.Counter(host_reasons.values()))

            # host exact aligner for everything the device didn't take and
            # the fallback pool didn't already finish
            rest = [i for i, r in enumerate(runs)
                    if r is None and i not in handled]
            if rest:
                with trace.span("polisher.host_align", pairs=len(rest)):
                    cigars = nw_cigar_batch([pairs[i] for i in rest],
                                            n_threads=self.num_threads,
                                            progress=bar_n)
                    for i, c in zip(rest, cigars):
                        need[i].cigar = c
            with trace.span("polisher.cigars"):
                for o, r in zip(need, runs):
                    if r is not None:
                        o.cigar = cigar_from_ops(r).encode()
            # skip accounting mirrors the reference's "Aligned overlaps ...
            # on GPU" line (cudapolisher.cpp:204-206); exposed as counters
            # so the bench can put them in its JSON artifact
            self.n_aligner_host_fallback = len(rest) + len(handled)
            self.n_aligner_device = len(pairs) - self.n_aligner_host_fallback
            if self.tpu_aligner_batches > 0 and self.n_aligner_host_fallback:
                log_info(f"[racon_tpu::Polisher.initialize] "
                         f"{self.n_aligner_host_fallback} overlaps "
                         "aligned on host (device capacity fallback)")

        with trace.span("polisher.breaking_points"):
            for o in overlaps:
                if o.is_valid and o.cigar and self._range_keeps(o):
                    o.find_breaking_points(self.sequences,
                                           self.window_length)

        self.logger.log("[racon_tpu::Polisher.initialize] aligned overlaps")

    def _range_keeps(self, o) -> bool:
        """Whether an overlap can contribute layers to this run's kept
        window slice (always True outside range mode — the classic path
        pays one attribute check). Coverage (RC) is counted for EVERY
        overlap regardless: the layer loop increments it before
        consulting breaking points, so skipping the aligner and the
        breaking-point walk here is pure saved work, never a semantic
        change — this is where range sharding's per-shard speedup
        comes from."""
        rng = self.window_range
        if rng is None:
            return True
        wl = self.window_length
        length = len(self.sequences[o.t_id].data)
        lo, hi = rng
        # the kept windows' covered coordinate region: window starts are
        # exact multiples of wl, so membership never depends on the
        # split points being wl-aligned
        first_start = -(-max(lo, 0) // wl) * wl
        cap = min(hi, length)
        if first_start >= cap:
            return False
        last_start = ((cap - 1) // wl) * wl
        region_hi = min(length, last_start + wl)
        return o.t_begin < region_hi and o.t_end > first_start

    # ---------------------------------------------------------------- polish
    def polish(self, drop_unpolished_sequences: bool = True,
               batcher=None, on_part=None, on_group=None,
               group_size: int = 64) -> list[Sequence]:
        """Per-window consensus + stitch (reference polisher.cpp:486-548).

        Per-phase windows/sec is reported on stderr; the CLI's
        `--tpu-jax-profile` (obs.jax_profile) captures the whole run.

        `batcher` (serve mode) replaces the in-process consensus pass:
        this job's windows join the shared continuous window batcher
        (serve/batcher.py), which merges them into bounded device
        iterations alongside concurrent jobs' windows and delivers them
        back incrementally as each iteration lands. Contigs whose
        windows are all complete are stitched IMMEDIATELY (in contig
        order) — `on_part` (callable(Sequence)) receives each finished
        contig before the job as a whole completes, which is what the
        server streams to clients as `result_part` frames. Per-window
        results are independent of batch composition, so both the
        streamed parts and the final list stay byte-identical to a solo
        run (test-pinned).

        `on_group` (fragment serve jobs, mutually exclusive with
        `on_part`) swaps the streamer for the read-order
        FragmentStreamer: callable(list[Sequence], lo, hi) receives
        corrected reads in bounded groups of `group_size` instead of
        one callback per read — see FragmentStreamer.
        """
        if batcher is not None:
            if on_group is not None:
                streamer = FragmentStreamer(self,
                                            drop_unpolished_sequences,
                                            on_group, group_size)
            else:
                streamer = ContigStreamer(self,
                                          drop_unpolished_sequences,
                                          on_part)
            # contigs stitch as their windows land, between the
            # batcher's iterations: their seconds are counted, and no
            # one span covers them
            batcher.consensus(self, on_windows=streamer.on_windows)
            dst = streamer.finish()
            stitch_s = streamer.stitch_s
        else:
            self._consensus_pass()
            with trace.timed("polisher.stitch") as sp:
                dst, targets = self._stitch(drop_unpolished_sequences)
                sp.set(sequences=len(dst), targets=targets,
                       dropped=targets - len(dst))
            stitch_s = sp.t1 - sp.t0
        self.emit_progress(len(self.windows), len(self.windows),
                           phase="stitch", sequences=len(dst))
        self.hists.observe("phase.stitch", stitch_s)
        self.logger.log("[racon_tpu::Polisher.polish] generated consensus")
        # cumulative wall-clock, mirroring ~Polisher (polisher.cpp:189)
        self.logger.total("[racon_tpu::Polisher.] total =")
        self.windows = []
        self.sequences = []
        self._runs_completed += 1
        self.emit_observability()
        return dst

    def _consensus_pass(self) -> None:
        """Run the consensus engine over this run's windows (every
        window ends up carrying `consensus`/`polished`) and emit the
        per-phase reports. polish() calls this for the one-shot path;
        serve mode substitutes the cross-job batcher."""
        from ..ops.poa import BatchPOA

        self.logger.log()
        self._progress_phase = "consensus"
        self._arm_progress()
        self.emit_progress(0, len(self.windows))

        pipeline = self._make_pipeline()
        # stage counters accumulate across phases (bench artifact wants
        # the run total); the diagnostic line below must describe THIS
        # phase only, so delta against the pre-phase snapshot
        stats_base = self.pipeline_stats.snapshot()
        engine = BatchPOA(self.match, self.mismatch, self.gap,
                          self.window_length, num_threads=self.num_threads,
                          device_batches=self.tpu_poa_batches,
                          banded=self.tpu_banded_alignment,
                          band_width=self.tpu_aligner_band_width,
                          logger=self.logger, engine=self.tpu_engine,
                          pipeline=pipeline, scheduler=self.scheduler,
                          runner=self.device_runner)
        with trace.timed("polisher.consensus", windows=len(self.windows),
                         engine=(engine.engine if self.tpu_poa_batches > 0
                                 else "host")) as sp, pipeline:
            engine.generate_consensus(self.windows, self.trim)
        dt = sp.t1 - sp.t0
        self.window_counts = dict(engine.window_counts)
        snap_occ = self.scheduler.stats.snapshot()
        self.emit_progress(
            len(self.windows), len(self.windows),
            occupancy={e: round(v["occupancy_pct"], 1)
                       for e, v in snap_occ.items()
                       if "occupancy_pct" in v} or None)
        self.hists.observe("phase.consensus", dt)
        if dt > 0 and self.windows:
            log_info(f"[racon_tpu::Polisher.polish] consensus throughput: "
                     f"{len(self.windows) / dt:.1f} windows/s")
        ss = {k: v - stats_base[k] for k, v in self.stage_stats.items()}
        # overlap evidence: with the pipeline live, pack+device+unpack
        # stage seconds exceed the phase wall time; additive means dead
        log_info(f"[racon_tpu::Polisher.polish] pipeline stages (depth "
                 f"{self.tpu_pipeline_depth}): pack {ss['pack_s']:.2f}s "
                 f"device {ss['device_s']:.2f}s unpack {ss['unpack_s']:.2f}s "
                 f"fallback {ss['fallback_s']:.2f}s, {ss['chunks']} chunks / "
                 f"{ss['launches']} launches")
        # degradation report: what the resilience layer absorbed across
        # the whole run (silent on a clean run); the same counters ride
        # stage_stats into bench.py's JSON artifact
        degraded = degradation_summary(self.stage_stats)
        if degraded:
            log_info(f"[racon_tpu::Polisher.polish] degradation report: "
                     f"{degraded}")
        # occupancy report: how much of the dispatched device shapes was
        # real work (silent on host-only runs); adaptive ladders move
        # this number, the bench JSON records it per bucket
        occ = self.scheduler.stats.summary()
        if occ:
            log_info(f"[racon_tpu::Polisher.polish] batch occupancy "
                     f"(adaptive={'on' if self.scheduler.adaptive else 'off'})"
                     f": {occ}")

    def _contig_slices(self) -> list[tuple[int, int]]:
        """[start, end) window-index ranges, one per target contig, in
        target order — a contig boundary is the next window belonging
        to a different target id (equivalent to the historical rank-0
        test on whole-target runs; range-shard slices start at a
        nonzero rank, where only the id transition is right). The unit
        the incremental stitcher completes on."""
        slices: list[tuple[int, int]] = []
        start = 0
        for i in range(len(self.windows)):
            if (i == len(self.windows) - 1
                    or self.windows[i + 1].id != self.windows[i].id):
                slices.append((start, i + 1))
                start = i + 1
        return slices

    def _stitch_contig(self, windows: list[Window],
                       drop_unpolished_sequences: bool) -> Sequence | None:
        """Stitch ONE contig's windows (rank-ascending) into a polished
        sequence with the reference's LN/RC/XC tagging
        (polisher.cpp:506-545); None when the contig is dropped as
        fully unpolished."""
        polished_data = bytearray()
        num_polished_windows = 0
        for window in windows:
            num_polished_windows += 1 if window.polished else 0
            polished_data += window.consensus
        last = windows[-1]
        if self.window_range is not None:
            # range-shard segment: bare name, never dropped — the
            # router stitches sibling segments back together and
            # re-derives the solo LN/RC/XC tags (and the drop rule)
            # from the accounting recorded here
            name = self.sequences[last.id].name
            data_len = len(self.sequences[last.id].data)
            wl = self.window_length
            self.segment_meta[name] = {
                "polished": num_polished_windows,
                "windows": len(windows),
                "total_windows": (data_len + wl - 1) // wl,
                "coverage": self.targets_coverages[last.id],
                "lo": self.window_range[0],
                "hi": self.window_range[1],
            }
            return create_sequence(name, bytes(polished_data))
        ratio = num_polished_windows / float(last.rank + 1)
        if drop_unpolished_sequences and ratio <= 0:
            return None
        tags = "r" if self.type == PolisherType.kF else ""
        tags += f" LN:i:{len(polished_data)}"
        tags += f" RC:i:{self.targets_coverages[last.id]}"
        tags += f" XC:f:{ratio:.6f}"
        return create_sequence(self.sequences[last.id].name + tags,
                               bytes(polished_data))

    def _stitch(self, drop_unpolished_sequences: bool
                ) -> tuple[list[Sequence], int]:
        """Stitch per-window consensus back into whole sequences, one
        contig at a time; returns them and the number of targets
        stitched (the dropped ones included)."""
        dst: list[Sequence] = []
        slices = self._contig_slices()
        for start, end in slices:
            seq = self._stitch_contig(self.windows[start:end],
                                      drop_unpolished_sequences)
            if seq is not None:
                dst.append(seq)
        return dst, len(slices)

    def emit_observability(self) -> None:
        """End-of-run observability emission — every part a no-op when
        its knob is off, so the default run's stderr stays byte-identical:
        report suppressed duplicate warnings, dump the metrics snapshot
        (RACON_TPU_METRICS / --tpu-metrics), render the stderr metrics
        table (when metrics are dumped or at debug level), and write the
        Chrome trace (RACON_TPU_TRACE / --tpu-trace). polish() calls
        this; initialize-only flows (bench's aligner phase) call it
        themselves so an armed trace/metrics artifact is never silently
        dropped."""
        flush_dedup()
        metrics_path = os.environ.get("RACON_TPU_METRICS")
        if metrics_path:
            # observability must never take a finished run down: an
            # unwritable path loses the artifact, not the polished FASTA
            try:
                self.metrics.dump(metrics_path)
                log_info(f"[racon_tpu::obs] metrics written to "
                         f"{metrics_path}")
            except OSError as exc:
                log_info(f"[racon_tpu::obs] warning: could not write "
                         f"metrics to {metrics_path} ({exc})")
        if metrics_path or log_level() >= DEBUG:
            log_info("[racon_tpu::obs] end-of-run metrics:\n"
                     + self.metrics.table())
        try:
            saved = trace.save()
        except OSError as exc:
            saved = None
            log_info(f"[racon_tpu::obs] warning: could not write trace "
                     f"({exc})")
        if saved:
            log_info(f"[racon_tpu::obs] trace written to {saved} "
                     "(open in https://ui.perfetto.dev)")


class ContigStreamer:
    """Incremental stitcher over the continuous batcher's iteration
    stream: feed completed windows in ANY order (`on_windows` is the
    batcher's per-iteration delivery hook), receive finished contigs in
    CONTIG order — a contig ships the moment its last window lands AND
    every earlier contig has shipped, so the concatenation of emitted
    parts is byte-identical to `Polisher._stitch`'s one-shot output
    (test-pinned, including with quarantined windows in the mix).

    `on_part` (callable(Sequence) or None) sees each stitched contig as
    it completes — the serve layer forwards these as `result_part`
    frames; exceptions from it are swallowed (streaming is decoration
    on the polish, never a dependency of it)."""

    def __init__(self, polisher: "Polisher", drop_unpolished: bool,
                 on_part=None):
        self._polisher = polisher
        self._drop = drop_unpolished
        self._on_part = on_part
        self._slices = polisher._contig_slices()
        self._remaining = [end - start for start, end in self._slices]
        self._contig_of: dict[int, int] = {}
        for ci, (start, end) in enumerate(self._slices):
            for w in polisher.windows[start:end]:
                self._contig_of[id(w)] = ci
        self._next = 0
        self._out: list[Sequence] = []
        #: cumulative stitch seconds, scattered across deliveries —
        #: polish() observes it as the phase.stitch latency
        self.stitch_s = 0.0

    def on_windows(self, windows: list[Window]) -> None:
        for w in windows:
            self._remaining[self._contig_of[id(w)]] -= 1
        while (self._next < len(self._slices)
               and self._remaining[self._next] == 0):
            start, end = self._slices[self._next]
            t0 = time.perf_counter()
            seq = self._polisher._stitch_contig(
                self._polisher.windows[start:end], self._drop)
            self.stitch_s += time.perf_counter() - t0
            self._next += 1
            if seq is None:
                continue
            self._out.append(seq)
            if self._on_part is not None:
                try:
                    self._on_part(seq)
                except Exception:  # noqa: BLE001 — see docstring
                    pass

    def finish(self) -> list[Sequence]:
        """The full stitched output, identical to `_stitch`'s list.
        Valid once the batcher's consensus() returned (every window
        delivered)."""
        return self._out


class FragmentStreamer(ContigStreamer):
    """Read-order analogue of ContigStreamer for fragment correction
    (PolisherType.kF): every target is a READ, so the per-contig
    delivery contract would mean one `result_part` frame per read —
    millions of tiny frames on a real read set. Corrected reads instead
    ship in bounded GROUPS: `on_group(seqs, lo, hi)` fires once per
    completed group of `group_size` consecutive targets, where
    [lo, hi) is the contiguous local target-INDEX range the group
    covers. Reads dropped as unpolished still advance the range (a
    group may even be empty), so sibling shards' group receipts tile
    the read axis exactly — the dedupe/requeue ledger and obsreport's
    receipt checks lean on that.

    finish() flushes the final partial group; the returned list is the
    authoritative output, byte-identical to `Polisher._stitch`'s
    one-shot result exactly like the contig streamer's. `on_group`
    exceptions are swallowed (streaming is decoration)."""

    def __init__(self, polisher: "Polisher", drop_unpolished: bool,
                 on_group=None, group_size: int = 64):
        super().__init__(polisher, drop_unpolished, on_part=None)
        self._on_group = on_group
        self._group_size = max(1, int(group_size))
        self._pend: list[Sequence] = []
        self._group_lo = 0

    def on_windows(self, windows: list[Window]) -> None:
        for w in windows:
            self._remaining[self._contig_of[id(w)]] -= 1
        while (self._next < len(self._slices)
               and self._remaining[self._next] == 0):
            start, end = self._slices[self._next]
            t0 = time.perf_counter()
            seq = self._polisher._stitch_contig(
                self._polisher.windows[start:end], self._drop)
            self.stitch_s += time.perf_counter() - t0
            self._next += 1
            if seq is not None:
                self._out.append(seq)
                self._pend.append(seq)
            if self._next - self._group_lo >= self._group_size:
                self._flush_group()

    def _flush_group(self) -> None:
        if self._next == self._group_lo:
            return
        group, lo, hi = self._pend, self._group_lo, self._next
        self._pend = []
        self._group_lo = self._next
        if self._on_group is not None:
            try:
                self._on_group(group, lo, hi)
            except Exception:  # noqa: BLE001 — see docstring
                pass

    def finish(self) -> list[Sequence]:
        self._flush_group()
        return self._out
