"""Unified observability layer: tracing, metrics, leveled logging.

Three pillars, all off (or invisible) by default so the clean run's
output and stderr stay byte-identical:

  1. SPAN TRACING (`obs.trace`): one span idiom, `trace.span()`, for
     the polisher's phases and their parts, per-chunk pipeline stages,
     engine dispatch loops and watchdog backoff. Its sinks: a
     thread-safe `TraceRecorder` armed by RACON_TPU_TRACE=<out.json> /
     `--tpu-trace`, emitting Chrome trace-event JSON for Perfetto (with
     XLA compiles and instant events mirroring every resilience counter
     bump), and any running JAX profiler capture, as `racon.*`
     annotations on the device trace's clock.
  2. METRICS REGISTRY (`obs.metrics.MetricsRegistry`): the pipeline /
     sched / resilience telemetry islands consolidated into one
     namespaced snapshot — bench JSON `"metrics"` field, `--tpu-metrics
     out.json` dump, end-of-run stderr table.
  3. LEVELED LOGGING (`utils/logger.py`, re-exported here):
     RACON_TPU_LOG_LEVEL=quiet|info|debug structured stderr logging
     with once-per-run deduplication of repeated per-chunk warnings.

`jax_profile()` is the optional deep-dive hook: one `jax.profiler`
capture around a whole run when RACON_TPU_PROFILE / `--tpu-jax-profile
<dir>` names a directory, holding the device tracks and, on the same
clock, every `racon.*` span the run opens (`obs.trace`); a silent
no-op when the profiler is unavailable on the backend.

The serve-grade additions (PR 6) build on the same pillars:

  4. LATENCY HISTOGRAMS (`obs.hist`): log-bucketed, thread-safe
     `Histogram` / `HistogramSet` — p50/p95/p99/max for pipeline stage
     durations, job latency, queue wait, gather wait, compiles.
  5. PROMETHEUS EXPOSITION (`obs.prom`): stdlib-only text-format
     rendering behind the serve layer's `scrape` RPC and optional
     localhost HTTP endpoint.
  6. FLIGHT RECORDER (`obs.flight`): an always-on bounded ring of
     recent spans (a `TraceRecorder` with deque buffers) the serve
     layer dumps as a Chrome-trace artifact when a job fails, times
     out, or misses its deadline."""

from __future__ import annotations

import os

from . import trace
from .hist import Histogram, HistogramSet
from .metrics import MetricsRegistry
from ..utils.logger import (log_debug, log_info, log_level, warn_dedup,
                            flush_dedup)

__all__ = ["trace", "MetricsRegistry", "Histogram", "HistogramSet",
           "jax_profile", "log_debug", "log_info", "log_level",
           "warn_dedup", "flush_dedup"]


class _SafeJaxProfile:
    """`jax.profiler.trace` bracket. On a TPU a profiler that fails to
    start or stop fails the run (the capture was asked for); on other
    backends (the CPU tests) it degrades to a no-op."""

    def __init__(self, directory: str):
        self._dir = directory
        self._cm = None
        self._strict = False

    def __enter__(self) -> "_SafeJaxProfile":
        import jax

        self._strict = jax.default_backend() == "tpu"
        try:
            cm = jax.profiler.trace(self._dir)
            cm.__enter__()
            self._cm = cm
        except Exception as exc:
            if self._strict:
                raise
            log_debug(f"[racon_tpu::obs] jax profiler unavailable "
                      f"({type(exc).__name__}: {exc}); run goes "
                      "unprofiled")
            self._cm = None
        return self

    def __exit__(self, *exc_info) -> bool:
        if self._cm is not None:
            try:
                self._cm.__exit__(*exc_info)
            except Exception as exc:
                if self._strict:
                    raise
                log_debug(f"[racon_tpu::obs] jax profiler stop failed "
                          f"({type(exc).__name__}: {exc})")
        return False


def jax_profile():
    """Context manager bracketing a whole run with one jax.profiler
    capture into RACON_TPU_PROFILE: parsing, alignment, consensus and
    the stitch with their `racon.*` spans beside the device tracks. A
    no-op context when the knob is unset."""
    import contextlib

    base = os.environ.get("RACON_TPU_PROFILE")
    if not base:
        return contextlib.nullcontext()
    return _SafeJaxProfile(base)
