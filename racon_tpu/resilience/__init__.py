"""Resilience layer: fault injection, device watchdog/retry, quarantine.

The reference's only failure posture is a hard exit via `CU_CHECK_ERR`
(cudautils.hpp:10-18). The TPU pipeline instead degrades in bounded,
observable steps, and every failure mode is *injectable* so the whole
ladder is exercisable in CI without real hardware faults:

  1. `faults.FaultPlan` — a deterministic fault-injection harness armed
     from `RACON_TPU_FAULT_PLAN` / `--tpu-fault-plan`
     (`device:chunk=3:raise,device:chunk=7:hang=5,unpack:chunk=2:corrupt`);
     hooks sit at the dispatch pipeline's pack/device/unpack stages and
     its fallback pool (pipeline/__init__.py).
  2. `watchdog.Watchdog` — a configurable deadline on device-stage calls
     (`--tpu-device-timeout`; a timed-out call raises
     errors.DeviceTimeout instead of hanging the run) plus bounded retry
     with exponential backoff (`RACON_TPU_DEVICE_RETRIES`, default 1
     once the watchdog is on) before a chunk routes to host fallback.
  3. Per-window quarantine — a window whose consensus fails on both the
     device and the host keeps its draft backbone as consensus and is
     counted (ops/poa.py), mirroring the reference's `ratio > 0`
     unpolished handling (polisher.cpp:515) at failure time instead of
     output time.
  4. Degradation report — retries / backoff seconds / timeouts / breaker
     trips / quarantined windows / cancelled futures accumulate in the
     shared PipelineStats, surface in `polisher.stage_stats`, and ride
     bench.py's JSON artifact next to the PR-1 stage counters.

Strictness: `RACON_TPU_STRICT` / `--tpu-strict` (`strict_mode()`) turns
every degradation point back into a raise — the bench/CI discipline.
Decisions key on the error hierarchy in errors.py (DeviceError /
DeviceTimeout / ChunkCorrupt), never on exception message strings.

With no fault plan and no timeout/retry configuration, every hook in the
hot path collapses to a `None` check — the clean path stays byte- and
cost-identical to the pre-resilience code.
"""

from __future__ import annotations

import contextlib
import os
import threading

from .faults import FaultPlan, get_fault_plan, reset_fault_plan
from .watchdog import Watchdog

__all__ = ["FaultPlan", "Watchdog", "get_fault_plan", "reset_fault_plan",
           "strict_mode", "strict_scope", "degradation_summary"]

#: per-thread strictness override (serve mode: one job's strict posture
#: must not leak into concurrent jobs sharing the process, so the env
#: knob alone cannot carry it)
_strict_local = threading.local()


def strict_mode() -> bool:
    """True when device failures must re-raise instead of degrading
    (RACON_TPU_STRICT env, mirrored by the --tpu-strict CLI flag). A
    `strict_scope` override on the calling thread wins over the env —
    the serve layer's per-job posture. Every strict decision is made on
    the thread driving the failing phase (the polisher's catch sites and
    the engines' on_error selection), so a thread-local is sufficient."""
    override = getattr(_strict_local, "value", None)
    if override is not None:
        return override
    return bool(os.environ.get("RACON_TPU_STRICT"))


@contextlib.contextmanager
def strict_scope(value: bool | None):
    """Pin `strict_mode()` to `value` for the calling thread (None =
    no-op, defer to the environment). The serve worker wraps each job in
    this so a `strict: true` request degrades nothing — its failures
    surface as one typed error response — while concurrent jobs keep
    the default posture."""
    if value is None:
        yield
        return
    prev = getattr(_strict_local, "value", None)
    _strict_local.value = bool(value)
    try:
        yield
    finally:
        _strict_local.value = prev


#: stage_stats keys owned by the resilience layer (PipelineStats carries
#: them next to the PR-1 stage counters; bench.py publishes the snapshot)
REPORT_KEYS = ("faults", "retries", "timeouts", "backoff_s",
               "breaker_trips", "quarantined", "cancelled")


def degradation_summary(stats: dict) -> str | None:
    """One-line human degradation report from a PipelineStats snapshot,
    or None when the run degraded nowhere (the common case: silence)."""
    parts = []
    for key in REPORT_KEYS:
        v = stats.get(key, 0)
        if v:
            parts.append(f"{key} {v:.2f}s" if key == "backoff_s"
                         else f"{key} {v}")
    return ", ".join(parts) if parts else None
