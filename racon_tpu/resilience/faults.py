"""Deterministic fault-injection harness.

A fault plan is a comma-separated list of armed faults:

    <stage>:chunk=<N>:<action>
    stage  ::= pack | device | unpack | fallback
    action ::= raise | corrupt | hang=<seconds>

e.g. ``device:chunk=3:raise,device:chunk=7:hang=5,unpack:chunk=2:corrupt``
arms a DeviceError on the 4th device dispatch, a 5 s stall on the 8th,
and a ChunkCorrupt on the 3rd unpack. `chunk` counts per stage per
pipeline run, in submission order; the first stage to reach the armed
index fires the fault (with the device aligner enabled the alignment
phase's pipeline runs first, otherwise the consensus phase's). Every
fault is ONE-SHOT: a retry of the same call finds it already consumed
and succeeds — exactly the transient-fault shape the watchdog/retry
policy (resilience/watchdog.py) is meant to absorb. Persistent failures
are modelled by arming the same (stage, chunk) several times.

Actions map onto the error hierarchy (errors.py): `raise` -> DeviceError,
`corrupt` -> ChunkCorrupt (the detected-corruption model: bad data raises
at the unpack boundary rather than flowing downstream), `hang=<s>` ->
the call stalls for <s> seconds — under a watchdog deadline that becomes
a DeviceTimeout; without one the run just finishes late, never deadlocks
(hangs are finite by construction). A stalled sleep is cancellable
(`cancel_hangs`) so a watchdog-abandoned thread exits promptly instead
of lingering past the run.

`sdc` is the SILENT-data-corruption model, the one failure the whole
detected-error hierarchy above cannot represent: a device that computed
WRONG BYTES without tripping any check. A `device:chunk=<N>:sdc` fault
never raises — `fire()` skips it; instead the consensus engine consumes
it at the end of its pass (`corrupt_consensus`), flipping one base of
the N-th polished window's consensus. Nothing in the resilience ladder
can catch it by design: only the identity-audit sentinel
(racon_tpu/obs/audit.py), which shadow re-executes sampled windows
through the oracle path and byte-compares, detects it — faultcheck's
audit cells gate exactly that.

The plan armed from RACON_TPU_FAULT_PLAN is process-cached per spec
string (`get_fault_plan`) so the polisher's alignment- and consensus-
phase pipelines share ONE set of one-shot faults; tests re-arm with
`reset_fault_plan()`.
"""

from __future__ import annotations

import os
import threading
import time

from ..errors import ChunkCorrupt, DeviceError, RaconError

STAGES = ("pack", "device", "unpack", "fallback")
ACTIONS = ("raise", "corrupt", "hang", "sdc")

#: the base substituted in by an `sdc` flip: deterministic (same plan,
#: same bytes) and always a REAL base, so the corruption is plausible
#: biological output — invisible to any format-level check
_SDC_FLIP = {65: 67, 67: 71, 71: 84, 84: 65}  # A->C->G->T->A

#: granularity of the cancellable hang sleep
_HANG_SLICE = 0.05


class Fault:
    """One armed fault: fires at most once, then stays consumed."""

    __slots__ = ("stage", "chunk", "action", "seconds", "fired")

    def __init__(self, stage: str, chunk: int, action: str,
                 seconds: float = 0.0):
        self.stage = stage
        self.chunk = chunk
        self.action = action
        self.seconds = seconds
        self.fired = False

    def __repr__(self):  # diagnostics only
        arg = f"={self.seconds:g}" if self.action == "hang" else ""
        return (f"{self.stage}:chunk={self.chunk}:{self.action}{arg}"
                f"{' (fired)' if self.fired else ''}")


class FaultPlan:
    """Parsed fault plan with thread-safe one-shot firing."""

    def __init__(self, faults: list[Fault], spec: str = ""):
        self.spec = spec
        self._faults = faults
        self._lock = threading.Lock()
        self._hang_abort = threading.Event()

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        faults: list[Fault] = []
        for entry in spec.split(","):
            entry = entry.strip()
            if not entry:
                continue
            parts = entry.split(":")
            if len(parts) != 3:
                raise RaconError(
                    "resilience.FaultPlan",
                    f"invalid fault entry {entry!r} (expected "
                    "<stage>:chunk=<N>:<action>)!")
            stage, chunk_s, action_s = parts
            if stage not in STAGES:
                raise RaconError(
                    "resilience.FaultPlan",
                    f"unknown fault stage {stage!r} (expected one of "
                    f"{', '.join(STAGES)})!")
            if not chunk_s.startswith("chunk="):
                raise RaconError(
                    "resilience.FaultPlan",
                    f"invalid fault target {chunk_s!r} (expected "
                    "chunk=<N>)!")
            try:
                chunk = int(chunk_s[len("chunk="):])
            except ValueError:
                raise RaconError(
                    "resilience.FaultPlan",
                    f"invalid fault chunk index {chunk_s!r}!") from None
            action, _, arg = action_s.partition("=")
            if action not in ACTIONS:
                raise RaconError(
                    "resilience.FaultPlan",
                    f"unknown fault action {action!r} (expected one of "
                    f"{', '.join(ACTIONS)})!")
            seconds = 0.0
            if action == "hang":
                try:
                    seconds = float(arg)
                except ValueError:
                    raise RaconError(
                        "resilience.FaultPlan",
                        f"invalid hang duration {arg!r} (expected "
                        "hang=<seconds>)!") from None
                if seconds <= 0:
                    raise RaconError(
                        "resilience.FaultPlan",
                        "hang duration must be positive!")
            elif arg:
                raise RaconError(
                    "resilience.FaultPlan",
                    f"action {action!r} takes no argument!")
            faults.append(Fault(stage, chunk, action, seconds))
        if not faults:
            raise RaconError("resilience.FaultPlan", "empty fault plan!")
        return cls(faults, spec)

    # ------------------------------------------------------------- firing
    def fire(self, stage: str, chunk: int, stats=None) -> None:
        """Hook called by the pipeline as `stage` starts its `chunk`-th
        item: consumes and enacts the first matching unfired fault."""
        with self._lock:
            # sdc faults are NOT stage hooks: they model corruption the
            # stages never see, consumed by corrupt_consensus() instead
            fault = next((f for f in self._faults
                          if not f.fired and f.stage == stage
                          and f.chunk == chunk
                          and f.action != "sdc"), None)
            if fault is None:
                return
            fault.fired = True
        if stats is not None:
            stats.bump("faults")
        if fault.action == "hang":
            self._hang(fault.seconds)
            return
        exc_cls = ChunkCorrupt if fault.action == "corrupt" else DeviceError
        raise exc_cls("resilience.FaultPlan",
                      f"injected {fault.action} fault at {stage} "
                      f"chunk {chunk}")

    def _hang(self, seconds: float) -> None:
        # a cancel that fired with no sleeper (a REAL slow call tripped
        # the watchdog) must not instantly void this armed stall: the
        # flag belongs to the sleep in progress, so clear it on entry
        self._hang_abort.clear()
        deadline = time.perf_counter() + seconds
        while time.perf_counter() < deadline:
            if self._hang_abort.wait(_HANG_SLICE):
                self._hang_abort.clear()
                return

    def corrupt_consensus(self, windows, stats=None) -> int:
        """Consume armed `sdc` faults against a finished consensus pass:
        for each unfired `device:chunk=N:sdc`, flip one base in the N-th
        POLISHED window's consensus (submission order) — wrong bytes,
        no exception, exactly the silent-corruption shape a bad chip
        produces. Returns the number of windows corrupted. Called by
        BatchPOA at the end of every generate_consensus; a plan with no
        sdc faults costs one lock-free scan."""
        with self._lock:
            armed = [f for f in self._faults
                     if not f.fired and f.action == "sdc"]
            if not armed:
                return 0
            polished = [w for w in windows if w.polished and w.consensus]
            hit = 0
            for fault in armed:
                if fault.chunk >= len(polished):
                    continue  # stays armed for a later, larger pass
                fault.fired = True
                w = polished[fault.chunk]
                cons = bytearray(w.consensus)
                i = len(cons) // 2
                cons[i] = _SDC_FLIP.get(cons[i], 65)
                w.consensus = bytes(cons)
                hit += 1
        if stats is not None:
            for _ in range(hit):
                stats.bump("faults")
        return hit

    def cancel_hangs(self) -> None:
        """Wake any in-progress hang sleep — the watchdog calls this on a
        deadline trip so the abandoned thread exits promptly instead of
        outliving the run."""
        self._hang_abort.set()

    @property
    def unfired(self) -> list[Fault]:
        with self._lock:
            return [f for f in self._faults if not f.fired]


# process-level plan cache: one set of one-shot faults shared by every
# pipeline the run constructs (alignment + consensus phases)
_cache: dict[str, FaultPlan] = {}


def get_fault_plan() -> FaultPlan | None:
    """The armed plan from RACON_TPU_FAULT_PLAN, or None (the common
    case — callers skip every hook)."""
    spec = os.environ.get("RACON_TPU_FAULT_PLAN")
    if not spec:
        return None
    plan = _cache.get(spec)
    if plan is None:
        plan = _cache[spec] = FaultPlan.parse(spec)
    return plan


def reset_fault_plan() -> None:
    """Drop cached plans so the next get_fault_plan() re-arms (tests and
    tools running several injected runs in one process)."""
    _cache.clear()
