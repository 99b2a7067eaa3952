"""Batched POA consensus over windows.

The consensus role spoa (CPU) and GenomeWorks cudapoa (GPU) play in the
reference. Two engines:

  - host: the native C++ POA graph engine (racon_tpu/native), threaded over
    windows — the spoa-equivalent path (reference src/polisher.cpp:491-504).
  - device (`device_batches > 0`): the evolving-graph engine
    (ops/poa_graph.py + native/src/session.cpp). The graph-NW DP — the hot
    loop — runs on the TPU as batched fixed-shape XLA programs while the
    graph bookkeeping stays in the C++ session; every layer is aligned
    against the *evolving* graph with host-identical DP and tie-breaking,
    so device consensus is byte-identical to the host engine (unlike the
    reference, which pins diverging GPU numbers separately,
    racon_test.cpp:292-496). Windows outside the kernel's shape envelope
    fall back to the host engine per window, the reference's GPU->CPU
    fallback discipline (cudapolisher.cpp:354-383).

Windows with fewer than 3 sequences keep their backbone (reference
window.cpp:68-71); TGS windows are coverage-trimmed (window.cpp:118-139).

Failure ladder (racon_tpu/resilience/): device consensus falls back to
the host engine (whole-batch or, in the fused path, per chunk); a HOST
chunk that fails is retried window by window; a window that still fails
alone is QUARANTINED — it keeps its draft backbone as consensus, counts
as unpolished (so the XC ratio reflects it, mirroring the reference's
`ratio > 0` handling, polisher.cpp:515) and bumps the `quarantined`
degradation counter. Only strict mode turns any of these back into a
raise. The run never aborts on a single poisoned window.
"""

from __future__ import annotations

import os

from ..native import poa_batch
from ..obs import trace
from ..resilience import strict_mode
from ..utils.logger import Logger, log_info, warn_dedup


class BatchPOA:
    def __init__(self, match: int, mismatch: int, gap: int,
                 window_length: int, num_threads: int = 1,
                 device_batches: int = 0, banded: bool = False,
                 band_width: int = 0, logger: Logger | None = None,
                 engine: str | None = None, pipeline=None,
                 scheduler=None, runner=None):
        self.match = match
        # the occupancy-aware batch scheduler (sched/), threaded into
        # whichever device engine runs; None lets each engine default
        # from the environment posture
        self.scheduler = scheduler
        # an explicit parallel.mesh.BatchRunner pins the device engines
        # to a sub-mesh — the serve layer's worker lanes each dispatch
        # through their own device partition; None = the full mesh
        self.runner = runner
        self.mismatch = mismatch
        self.gap = gap
        self.window_length = window_length
        self.num_threads = num_threads
        self.device_batches = device_batches
        # the reference's -b / cuda-banded-alignment flag selects cudapoa's
        # static-band mode as a speed/accuracy trade (cudabatch.cpp:56-59)
        # that only affects the GPU path. Mirrored here: with -b the device
        # session trusts banded DP results (skips the clipped -> full-DP
        # retry), trading the byte-identity-with-host guarantee for fewer
        # device round trips — exactly the reference's GPU-only divergence
        # pattern (racon_test.cpp:292-496 pins GPU numbers separately).
        self.banded_only = banded
        self.logger = logger
        # the polisher's async dispatch pipeline (pipeline.DispatchPipeline
        # or None): overlaps host pack/unpack with compute in both the
        # fused device path and the host chunk loop; None keeps every
        # stage synchronous (direct callers, tests)
        self.pipeline = pipeline
        # device engine selection: explicit parameter (the CLI's
        # --tpu-engine) wins over the RACON_TPU_ENGINE env var; an empty
        # env value means unset (the `VAR= cmd` idiom), not a typo
        self.engine = (engine or os.environ.get("RACON_TPU_ENGINE")
                       or "session")
        # the CLI validates --tpu-engine; the env-var path must too, or a
        # typo like RACON_TPU_ENGINE=Fused silently measures the session
        # engine while the user believes they measured the fused one
        if self.engine not in ("session", "fused"):
            raise ValueError(
                f"[racon_tpu::BatchPOA] invalid TPU engine "
                f"{self.engine!r} (expected 'session' or 'fused'; set via "
                "--tpu-engine or RACON_TPU_ENGINE)")
        # device engines cached across generate_consensus calls: the
        # serve feeder's persistent dispatch loop reuses ONE BatchPOA
        # per lane+engine-key, so per-iteration engine construction
        # (kernel plans, batch-width pinning, runner lookups) drops out
        # of the iteration hot path. Everything in an engine's identity
        # is fixed at BatchPOA construction; only the logger is rebound
        # per call.
        self._device_engine = None
        self._session_net = None
        #: where the last generate_consensus call built each window:
        #: on the device, on the host because the window is outside the
        #: device envelope (the reference's per-window GPU->CPU rule),
        #: on the host for any other reason (host-only run, device
        #: failure), or backbone-only (too few layers)
        self.window_counts = dict.fromkeys(
            ("device", "host_envelope", "host", "backbone"), 0)

    #: windows per host batch call (bounds peak packed-buffer memory);
    #: RACON_TPU_HOST_POA_CHUNK overrides it — chunk granularity never
    #: changes output (windows are independent), only pipeline batching,
    #: so the fleet benches shrink it to pace per-chunk device latency
    #: proportionally to a job's window count
    HOST_CHUNK = 4096

    def _host_chunk(self) -> int:
        raw = os.environ.get("RACON_TPU_HOST_POA_CHUNK", "")
        if not raw:
            return self.HOST_CHUNK
        try:
            n = int(raw)
        except ValueError:
            n = 0
        if n <= 0:
            from ..errors import RaconError
            raise RaconError(
                "BatchPOA",
                f"invalid RACON_TPU_HOST_POA_CHUNK {raw!r} (expected a "
                "positive integer)!")
        return n

    def generate_consensus(self, windows, trim: bool) -> None:
        """Fill `window.consensus` / `window.polished` for every window.

        After the pass, any armed `sdc` fault (resilience/faults.py) is
        consumed against the finished consensus — the silent-corruption
        injection the audit sentinel (obs/audit.py) exists to catch. A
        plan-less run (the universal default) pays one None check."""
        from ..resilience import get_fault_plan

        self._generate_consensus(windows, trim)
        plan = (self.pipeline.faults if self.pipeline is not None
                else get_fault_plan())
        if plan is not None:
            plan.corrupt_consensus(
                windows, stats=(self.pipeline.stats
                                if self.pipeline is not None else None))

    def _generate_consensus(self, windows, trim: bool) -> None:
        counts = self.window_counts = dict.fromkeys(self.window_counts, 0)
        todo = []
        for w in windows:
            if len(w.sequences) < 3:
                w.backbone_fallback()
            else:
                todo.append(w)
        counts["backbone"] = len(windows) - len(todo)
        if not todo:
            return

        host = todo
        if self.device_batches > 0:
            from ..errors import DeviceError, RaconError

            def degrade(msg):
                # the device pass died mid-flight: before the host pass
                # reruns the unpolished windows, empty the shared
                # fallback pool — a queued/running prefall job would
                # keep polishing those same windows underneath it
                if self.pipeline is not None:
                    self.pipeline.cancel_fallback()
                log_info(f"[racon_tpu::BatchPOA] warning: device consensus "
                         f"failed ({msg}); falling back to host engine")
                return [w for w in todo if not w.polished]

            try:
                host = self._device_consensus(todo, trim)
            except RaconError as exc:
                # device failures degrade; genuine user-facing errors
                # (bad input discovered late) propagate regardless
                if not isinstance(exc, DeviceError) or strict_mode():
                    raise
                host = degrade(str(exc))
            except Exception as exc:  # device init/OOM: host completes all
                if strict_mode():
                    raise
                host = degrade(f"{type(exc).__name__}: {exc}")

        counts["host"] = len(host)
        if not host:
            return
        bar = self.logger.bar if self.logger is not None else None
        if self.logger is not None:
            self.logger.bar_total(len(host))

        # the host engine runs through the same staged pipeline: the
        # native POA call (GIL released inside the C++ batch entry point)
        # computes chunk k on the dispatch thread while a pack worker
        # builds chunk k+1's window lists and the unpack worker trims
        # chunk k-1
        from ..pipeline import DispatchPipeline

        pl = (self.pipeline if self.pipeline is not None
              else DispatchPipeline(depth=0))
        host_chunk = self._host_chunk()
        chunks = [host[s:s + host_chunk]
                  for s in range(0, len(host), host_chunk)]

        def pack(chunk):
            return [_pack(w) for w in chunk]

        def dispatch(chunk, packed):
            results = poa_batch(packed, self.match, self.mismatch,
                                self.gap, n_threads=self.num_threads)
            pl.stats.bump("launches")
            return results

        def wait(results):
            return results

        def unpack(chunk, results):
            for w, (cons, cov) in zip(chunk, results):
                w.apply_trim(cons, cov, trim)
            if bar is not None:
                for _ in chunk:
                    bar("[racon_tpu::Polisher.polish] generating consensus")

        def chunk_error(chunk, exc):
            # host-chunk failure: retry each window on its own; a window
            # that fails alone is poisoned — quarantine it (draft
            # backbone as consensus, counted) and keep the run alive
            warn_dedup(
                "BatchPOA.host_chunk_failed",
                f"[racon_tpu::BatchPOA] warning: host consensus chunk "
                f"failed ({type(exc).__name__}: {exc}); retrying "
                f"{len(chunk)} windows individually")
            for w in chunk:
                try:
                    (cons, cov), = poa_batch([_pack(w)], self.match,
                                             self.mismatch, self.gap,
                                             n_threads=1)
                    w.apply_trim(cons, cov, trim)
                except Exception as wexc:
                    w.backbone_fallback()
                    pl.stats.bump("quarantined")
                    warn_dedup(
                        "BatchPOA.window_quarantined",
                        "[racon_tpu::BatchPOA] warning: window "
                        f"quarantined (kept draft backbone; "
                        f"{type(wexc).__name__}: {wexc})")
                if bar is not None:
                    bar("[racon_tpu::Polisher.polish] generating consensus")

        pl.run(chunks, pack, dispatch, wait, unpack,
               on_error=None if strict_mode() else chunk_error,
               label="host_poa",
               describe=lambda c: {"engine": "host", "jobs": len(c)})

    def _device_consensus(self, todo, trim) -> list:
        """Device consensus over `todo`; unfit/failed windows are
        host-polished internally when possible. Returns the windows left
        unbuilt (normally none) — the caller routes them through the
        host chunk loop, whose per-window quarantine is the last rung of
        the failure ladder.

        `self.engine` selects the device engine — the explicit
        constructor/CLI choice, falling back to RACON_TPU_ENGINE:
        "session" (default, the per-layer evolving-graph engine —
        byte-identical to the host engine) or "fused" (whole-window
        single-launch engine, ops/poa_fused.py — the cudapoa-shaped
        design; equal aggregate quality, rare topo-order tie divergence
        possible on deep windows — see its module docstring)."""
        from .poa_graph import DeviceGraphPOA

        with trace.span("poa.pack", windows=len(todo)):
            packed = [_pack(w) for w in todo]
        if self.engine == "fused":
            from .poa_fused import FusedPOA

            if self._device_engine is None:
                self._device_engine = FusedPOA(
                    self.match, self.mismatch, self.gap,
                    num_threads=self.num_threads,
                    banded_only=self.banded_only,
                    scheduler=self.scheduler,
                    runner=self.runner)
            fused = self._device_engine
            fused.logger = self.logger
            # RACON_TPU_FUSED_FALLBACK picks who polishes the windows the
            # fused engine cannot take (graph overflowed its envelope):
            # "session" (default) keeps the whole batch on device via the
            # per-layer session engine; "host" uses the C++ engine — the
            # reference's per-window GPU->CPU fallback discipline
            # (cudapolisher.cpp:354-383), no second device engine compile
            to_host = (os.environ.get("RACON_TPU_FUSED_FALLBACK",
                                      "session") == "host")
            results, statuses = fused.consensus(packed, fallback=to_host,
                                                pipeline=self.pipeline)
            rest = [i for i, r in enumerate(results) if r is None]
            fs = fused.last_stats
            log_info(f"[racon_tpu::BatchPOA] fused engine built "
                     f"{int((statuses == 0).sum())} windows "
                     f"({fs['chunks']} chunks, {fs['launches']} device "
                     f"launches, pack {fs['pack_s']:.2f}s, device "
                     f"{fs['device_s']:.2f}s, finalize "
                     f"{fs['unpack_s']:.2f}s); {fused.n_fallback} to "
                     f"{'host' if to_host else 'session'} engine")
            if rest:
                # leftover windows are a handful of envelope-tail cases:
                # adapting a grid to THEM would compile throwaway
                # programs mid-run (the stall precompile exists to
                # prevent), so this engine pins the static grid —
                # telemetry still flows into the shared counters
                from ..sched import BatchScheduler

                if self._session_net is None:
                    static_sched = BatchScheduler(
                        adaptive=False,
                        stats=(self.scheduler.stats
                               if self.scheduler is not None else None))
                    self._session_net = DeviceGraphPOA(
                        self.match, self.mismatch, self.gap,
                        num_threads=self.num_threads,
                        banded_only=self.banded_only,
                        scheduler=static_sched,
                        runner=self.runner)
                engine = self._session_net
                engine.logger = self.logger
                sub_res, sub_st = engine.consensus(
                    [packed[i] for i in rest])
                for i, r, st in zip(rest, sub_res, sub_st):
                    results[i] = r
                    statuses[i] = st
            else:
                engine = fused
        else:
            if self._device_engine is None:
                self._device_engine = DeviceGraphPOA(
                    self.match, self.mismatch, self.gap,
                    num_threads=self.num_threads,
                    banded_only=self.banded_only,
                    scheduler=self.scheduler,
                    runner=self.runner)
            engine = self._device_engine
            engine.logger = self.logger
            results, statuses = engine.consensus(packed)
        leftover = []
        with trace.span("poa.apply", windows=len(todo)):
            for w, r in zip(todo, results):
                if r is None:  # neither engine built it: host loop's turn
                    leftover.append(w)
                else:
                    w.apply_trim(r[0], r[1], trim)
        stats = getattr(engine, "last_stats", None) or {}
        if "committed" in stats:
            log_info(f"[racon_tpu::BatchPOA] device layer alignments: "
                     f"{stats['committed']} committed, {stats['redos']} "
                     "banded-clip full-DP retries")
        n_fallback = int((statuses == 1).sum())
        counts = self.window_counts
        counts["device"] = int((statuses == 0).sum())
        counts["host_envelope"] = n_fallback
        counts["backbone"] += int((statuses == 2).sum())
        if n_fallback:
            # the reference logs GPU-skipped work the same way
            # (cudapolisher.cpp:204-206)
            log_info(f"[racon_tpu::BatchPOA] {n_fallback} windows polished "
                     "on host (outside device kernel envelope)")
        return leftover


def _pack(w):
    return [(w.sequences[i], w.qualities[i], w.positions[i][0],
             w.positions[i][1]) for i in range(len(w.sequences))]
