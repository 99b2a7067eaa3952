"""Device watchdog: deadline + bounded retry with exponential backoff.

The reference's failure posture on a stuck CUDA launch is to block
forever inside the driver (CU_CHECK_ERR only sees *returned* errors,
cudautils.hpp:10-18). Here every device-stage call can run under a
deadline: the call is made on a disposable worker thread and the caller
waits at most `timeout` seconds — past that a DeviceTimeout (errors.py)
is raised and the worker is abandoned (daemon; an injected hang is also
cancelled via the fault plan's `cancel_hangs` so the thread exits
promptly). The chunk then follows the normal failure route: bounded
retry here, host fallback in the caller, per-window quarantine last.

Retry policy: `retries` extra attempts with exponential backoff
(`backoff * 2^attempt` seconds). Retries and backoff seconds are counted
into the shared PipelineStats so the degradation report can show them.

Configuration (all off by default — the clean path never pays a thread
hop): `--tpu-device-timeout` / RACON_TPU_DEVICE_TIMEOUT seconds (0 =
no deadline), RACON_TPU_DEVICE_RETRIES (default 1 once a timeout is
set, else 0), RACON_TPU_RETRY_BACKOFF base seconds (default 0.25).
`from_env()` returns None when nothing is configured, and callers treat
a None watchdog as "call directly".
"""

from __future__ import annotations

import os
import threading
import time

from ..errors import DeviceTimeout, RaconError


def _env_number(var: str, default: str, conv):
    """Posture knobs fail as RaconError (clean CLI diagnostic), never a
    ValueError traceback from deep inside pipeline construction."""
    raw = os.environ.get(var, default)
    try:
        return conv(raw or default)
    except ValueError:
        raise RaconError(
            "resilience.Watchdog",
            f"invalid {var} value {raw!r} (expected a number)!") from None


class Watchdog:
    def __init__(self, timeout: float = 0.0, retries: int = 0,
                 backoff: float = 0.25):
        self.timeout = max(0.0, float(timeout))
        self.retries = max(0, int(retries))
        self.backoff = max(0.0, float(backoff))

    @classmethod
    def from_env(cls, timeout: float | None = None) -> "Watchdog | None":
        """Watchdog per the env posture knobs (explicit `timeout`, e.g.
        the CLI flag, wins over RACON_TPU_DEVICE_TIMEOUT). None when
        neither a deadline nor retries are configured."""
        if timeout is None:
            timeout = _env_number("RACON_TPU_DEVICE_TIMEOUT", "0", float)
        if os.environ.get("RACON_TPU_DEVICE_RETRIES") is not None:
            retries = _env_number("RACON_TPU_DEVICE_RETRIES", "0", int)
        else:
            retries = 1 if timeout > 0 else 0
        if timeout <= 0 and retries <= 0:
            return None
        backoff = _env_number("RACON_TPU_RETRY_BACKOFF", "0.25", float)
        return cls(timeout=timeout, retries=retries, backoff=backoff)

    # -------------------------------------------------------------- calls
    def call(self, fn, stats=None, retry: bool = True,
             deadline: bool = True, on_timeout=None):
        """Run `fn()` under the deadline, retrying failed attempts with
        exponential backoff. `retry=False` limits to one attempt (the
        result-wait stage: re-waiting on a hung handle would just burn a
        second deadline — the chunk routes to fallback instead).
        `deadline=False` keeps the retry policy but calls inline (host
        pack/unpack stages: CPU-bound and finite, and abandoning them
        would leak the thread). `on_timeout` runs when a deadline trips,
        before the retry/raise (used to cancel injected hang sleeps)."""
        attempts = 1 + (self.retries if retry else 0)
        for attempt in range(attempts):
            try:
                if deadline:
                    return self._deadline(fn, stats, on_timeout)
                return fn()
            except Exception:
                if attempt + 1 >= attempts:
                    raise
                delay = self.backoff * (2 ** attempt)
                if stats is not None:
                    stats.bump("retries")
                    stats.bump("backoff_s", delay)
                if delay:
                    from ..obs import trace

                    with trace.span("watchdog.backoff",
                                    attempt=attempt + 1):
                        time.sleep(delay)

    def _deadline(self, fn, stats, on_timeout):
        if self.timeout <= 0:
            return fn()
        box: dict = {}
        done = threading.Event()

        def runner():
            try:
                box["result"] = fn()
            except BaseException as exc:
                box["error"] = exc
            finally:
                done.set()

        worker = threading.Thread(target=runner, daemon=True,
                                  name="racon-tpu-watchdog")
        worker.start()
        if not done.wait(self.timeout):
            if on_timeout is not None:
                on_timeout()
            if stats is not None:
                stats.bump("timeouts")
            raise DeviceTimeout(
                "resilience.Watchdog",
                f"device stage exceeded the {self.timeout:g}s deadline")
        if "error" in box:
            raise box["error"]
        return box["result"]
