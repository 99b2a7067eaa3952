"""Shared device-program seam (ROADMAP item 5, first slice).

Every device engine has so far privately re-wired the same chunk-loop
plumbing: a consecutive-failure circuit breaker, per-shard occupancy
splits, and the declared-fallback discipline around a failed chunk.
This module hosts the pieces the engines can share TODAY without any
behavior change — the aligner and the fused POA engine bind to it
instead of keeping private copies, and the fused align→window→POA
program (ops/poa_fused.py's single-launch path) is wired through it
rather than growing a fifth private copy. The full
shapes → ladder → dtype-plan → pack → dispatch → unpack interface
extraction is the rest of item 5; this slice deliberately starts with
the parts whose unification cannot move a byte.
"""

from __future__ import annotations


class ChunkBreaker:
    """Consecutive-chunk-failure circuit breaker for a device chunk
    loop (one implementation of the FusedPOA/BatchAligner discipline):
    one flaky chunk degrades to the engine's declared fallback, but a
    device that fails every chunk (a lost device, OOM) must not burn a
    pack+dispatch attempt — or a watchdog deadline — per chunk for the
    whole phase. After `max_streak` consecutive failures the pass
    aborts with a DeviceError chained to the last cause, restoring the
    old first-exception whole-phase fallback.
    """

    def __init__(self, engine: str, stats, abort_what: str,
                 max_streak: int = 3):
        #: `engine` names the loop in warnings/errors (BatchAligner /
        #: FusedPOA); `stats` is the pipeline's PipelineStats (or None)
        #: for the breaker_trips counter; `abort_what` finishes the
        #: abort message ("the device alignment pass" / "the device
        #: pass")
        self.engine = engine
        self.stats = stats
        self.abort_what = abort_what
        self.max_streak = max_streak
        self.n = 0

    def ok(self) -> None:
        """A chunk came all the way back: the device is alive."""
        self.n = 0

    def failed(self, exc: BaseException, detail: str) -> None:
        """Count one failed chunk (warning deduplicated per engine —
        on a wedged device this fires once per chunk with
        near-identical text); raises DeviceError past the streak
        limit. `detail` says where the chunk's items went
        ("N pairs to host fallback")."""
        from ..errors import DeviceError
        from ..utils.logger import warn_dedup

        self.n += 1
        warn_dedup(
            f"{self.engine}.device_chunk_failed",
            f"[racon_tpu::{self.engine}] warning: device chunk failed "
            f"({type(exc).__name__}: {exc}); {detail}")
        if self.n >= self.max_streak:
            if self.stats is not None:
                self.stats.bump("breaker_trips")
            err = DeviceError(
                self.engine,
                f"{self.n} consecutive device chunk failures; aborting "
                f"{self.abort_what}")
            err.__cause__ = exc
            raise err


def shard_useful_split(row_cells, lanes: int, n_devices: int) -> list:
    """Per-shard useful-cell sums for a contiguously-sharded batch of
    `lanes` rows (rows s*per .. (s+1)*per land on device s) — the
    occupancy mesh view every engine records. `row_cells` is the
    per-row useful-cell list for the REAL rows only; the padding rows
    at the batch tail contribute zero wherever they land."""
    per = lanes // max(1, n_devices)
    return [sum(row_cells[s * per:(s + 1) * per])
            for s in range(n_devices)]
