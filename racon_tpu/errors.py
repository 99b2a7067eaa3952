"""Error types: user-facing failures and the device-failure hierarchy.

The reference hard-exits with a diagnostic prefix `[racon::Class::method] error: ...`
(e.g. src/polisher.cpp:206-209, src/overlap.cpp:148-153, src/window.cpp:19-23).
We raise RaconError with the same message shape; the CLI converts it to
stderr + exit(1) so the observable behavior matches.

The reference's only *device* failure posture is a hard exit via
`CU_CHECK_ERR` (cudautils.hpp:10-18). Here device-side failures get their
own hierarchy under `DeviceError` so degradation decisions (retry, host
fallback, per-window quarantine — racon_tpu/resilience/) and the strict
mode key on error CLASS, not string matching:

  - DeviceError:   a device launch/compute/fetch failed (the CU_CHECK_ERR
    role; also the class injected faults raise);
  - DeviceTimeout: a device-stage call exceeded the watchdog deadline
    (resilience.Watchdog) — the "stuck launch" failure mode CUDA surfaces
    as a hung stream;
  - ChunkCorrupt:  fetched results failed validation / could not be
    unpacked (detected-corruption model: bad data raises rather than
    flowing downstream).

All three are RaconErrors, so an un-degraded escape still exits the CLI
with the reference's diagnostic shape instead of a traceback.
"""

from __future__ import annotations


class RaconError(RuntimeError):
    """User-facing error carrying a `[racon_tpu::Scope] error: ...` message."""

    def __init__(self, scope: str, message: str):
        self.scope = scope
        super().__init__(f"[racon_tpu::{scope}] error: {message}")


class DeviceError(RaconError):
    """A device launch, compute or result fetch failed (CU_CHECK_ERR role)."""


class DeviceTimeout(DeviceError):
    """A device-stage call exceeded the watchdog deadline (stuck launch)."""


class ChunkCorrupt(DeviceError):
    """Fetched chunk results failed validation or could not be unpacked."""


def as_device_error(exc: BaseException, scope: str) -> DeviceError:
    """Classify an arbitrary device-path exception: DeviceErrors pass
    through unchanged (their class carries the failure mode), anything
    else — a raw XLA/jax/runtime error — is wrapped so callers can key
    degradation on `except DeviceError` instead of a bare `except
    Exception`."""
    if isinstance(exc, DeviceError):
        return exc
    wrapped = DeviceError(scope, f"device failure "
                                 f"({type(exc).__name__}: {exc})")
    wrapped.__cause__ = exc
    return wrapped
