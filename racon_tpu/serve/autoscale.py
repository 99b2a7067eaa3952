"""Elastic replica autoscaling for the serve router.

`Autoscaler` closes the loop the router left open: the fleet poll
already computes every signal an operator would scale on — per-replica
queue depth and inflight from healthz, the deadline-miss burn rate
(`obs.fleet.BurnRateTracker` fast/slow windows), the admission EMA
behind `queue.ema_service_s` — and the router already survives replicas
joining and leaving (`add_replica` / `remove_replica`, journal-backed
requeue). The autoscaler just connects signal to action:

  - **Scale-up.** When backlog pressure (queued + inflight jobs per
    routable replica) stays above ``up_pressure`` for ``up_sustain_s``
    seconds — or the deadline burn-rate alert is firing — and the fleet
    is below ``max_replicas``, spawn one warm replica subprocess
    (``racon_tpu serve --socket <dir>/autoscale_<n>.sock``) — on a chip
    host confined to a chip no other spawned replica holds, or refused
    (`NoFreeChip`, counted under ``spawn_failures``) — wait for
    its first clean healthz, and join it to the routing set: rejoin is
    instant because the router routes on healthz, not on config.
  - **Scale-down.** When the fleet has been fully idle (zero backlog,
    zero router in-flight jobs) for ``down_idle_s`` seconds and the
    autoscaler owns at least one replica above ``min_replicas``, drain
    the NEWEST spawned replica: SIGTERM triggers the server's graceful
    drain (stop admitting, finish in-flight), and if it dies mid-job
    anyway the router's journal-backed requeue re-dispatches the shard
    — scale-down loses zero jobs by construction, the same invariant
    the rolling-restart runbook pins.
  - Only replicas the autoscaler spawned are ever drained; the
    operator's configured replicas are the floor it never touches.
    Every action journals (``autoscale-up`` / ``autoscale-down``,
    outside LIFECYCLE_EVENTS) and counts into the router's armed-only
    ``router.autoscale.*`` metric families.
  - **Scale-up hold.** While the autoscaler is armed and below
    ``max_replicas``, a shard whose only routable replicas are already
    busy (device in use) HOLDS in the router's dispatch loop for up to
    ``hold_s`` seconds instead of committing to a busy queue — and the
    held shard itself counts into the pressure signal, so the hold is
    what summons the capacity it is waiting for. The moment any
    replica goes idle (or the spawned one joins), the hold ends and
    the shard dispatches there. Without an armed autoscaler the hold
    path is never taken and dispatch behaves exactly as before.

Env knobs (strict-parsed at construction, the --metrics-port
discipline — a typo fails the start, never silently defaults):
RACON_TPU_ROUTER_AUTOSCALE_MIN / _MAX (fleet size bounds, default
1 / 4), _INTERVAL (loop seconds, default 1), _UP_PRESSURE (backlog per
routable replica that counts as pressure, default 2), _UP_SUSTAIN_S
(how long pressure must hold, default 2), _DOWN_IDLE_S (idle before a
drain, default 10), _COOLDOWN_S (minimum gap between actions, default
3), _DIR (socket directory for spawned replicas, default a tempdir),
_HOLD_S (how long a shard may hold out for an idle/new replica before
settling for a busy one, default 5; 0 disables the hold).

CLI: ``racon_tpu router --autoscale`` (router_main wires the loop and
tears it down on drain). Tests drive `step()` directly with injected
`spawn` / `stop` callables — no subprocesses, no clocks.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import subprocess
import sys
import tempfile
import threading
import time

from ..errors import RaconError
from ..utils.logger import log_info
from .protocol import ProtocolError


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise RaconError(
            "autoscale",
            f"{name} must be an integer, got {raw!r}") from None


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        raise RaconError(
            "autoscale",
            f"{name} must be a number, got {raw!r}") from None


class AutoscaleConfig:
    """Autoscaler knobs; every constructor override has an env twin
    (module docstring) and parse failures raise NOW."""

    def __init__(self, **kw):
        mn = kw.pop("min_replicas", None)
        self.min_replicas = (
            int(mn) if mn is not None
            else _env_int("RACON_TPU_ROUTER_AUTOSCALE_MIN", 1))
        mx = kw.pop("max_replicas", None)
        self.max_replicas = (
            int(mx) if mx is not None
            else _env_int("RACON_TPU_ROUTER_AUTOSCALE_MAX", 4))
        iv = kw.pop("interval_s", None)
        self.interval_s = (
            float(iv) if iv is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_INTERVAL", 1.0))
        up = kw.pop("up_pressure", None)
        self.up_pressure = (
            float(up) if up is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_UP_PRESSURE",
                            2.0))
        us = kw.pop("up_sustain_s", None)
        self.up_sustain_s = (
            float(us) if us is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_UP_SUSTAIN_S",
                            2.0))
        di = kw.pop("down_idle_s", None)
        self.down_idle_s = (
            float(di) if di is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_DOWN_IDLE_S",
                            10.0))
        cd = kw.pop("cooldown_s", None)
        self.cooldown_s = (
            float(cd) if cd is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_COOLDOWN_S",
                            3.0))
        self.socket_dir = (
            kw.pop("socket_dir", None)
            or os.environ.get("RACON_TPU_ROUTER_AUTOSCALE_DIR") or "")
        rt = kw.pop("ready_timeout_s", None)
        self.ready_timeout_s = (
            float(rt) if rt is not None
            else _env_float(
                "RACON_TPU_ROUTER_AUTOSCALE_READY_TIMEOUT", 20.0))
        hs = kw.pop("hold_s", None)
        self.hold_s = (
            float(hs) if hs is not None
            else _env_float("RACON_TPU_ROUTER_AUTOSCALE_HOLD_S", 5.0))
        if self.hold_s < 0:
            raise RaconError(
                "autoscale", f"hold_s must be >= 0, got {self.hold_s}")
        if self.min_replicas < 0 or \
                self.max_replicas < max(1, self.min_replicas):
            raise RaconError(
                "autoscale",
                f"bad fleet bounds min={self.min_replicas} "
                f"max={self.max_replicas}")
        if kw:
            raise RaconError(
                "autoscale",
                f"unknown autoscale option(s): {', '.join(sorted(kw))}")


class NoFreeChip(RaconError):
    """A scale-up found every chip on this host already serving a
    replica this loop spawned."""


def host_chips() -> list[int]:
    """Indices of this host's TPU chips, read from the accelerator
    device nodes a TPU VM exposes — never through JAX: a router that
    loaded the TPU runtime would hold every chip its replicas need.
    Empty on a host without chips."""
    found = (re.fullmatch(r"/dev/accel(\d+)", p)
             for p in glob.glob("/dev/accel*"))
    return sorted(int(m.group(1)) for m in found if m)


def chip_env(chip: int) -> dict:
    """Environment that confines one replica process to chip `chip`
    (one process per chip, each with its own runtime port)."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + chip)}


def _default_spawn(spec: str, chip: int | None = None):
    """Spawn one warm replica subprocess serving on `spec` (unix
    socket), confined to `chip` on a chip host. The child inherits the
    environment, so the operator's RACON_TPU_SERVE_* posture applies to
    scaled-up replicas too."""
    env = None if chip is None else dict(os.environ, **chip_env(chip))
    handle = subprocess.Popen(
        [sys.executable, "-m", "racon_tpu", "serve", "--socket", spec],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
    handle.chip = chip
    return handle


def _default_stop(handle) -> None:
    """SIGTERM -> the server's graceful drain; SIGKILL only if it
    ignores us (the requeue path covers even that)."""
    with contextlib.suppress(Exception):
        handle.terminate()
    try:
        handle.wait(timeout=15.0)
    except Exception:  # noqa: BLE001 — escalate, requeue covers it
        with contextlib.suppress(Exception):
            handle.kill()
            handle.wait(timeout=5.0)


class Autoscaler:
    """The elastic-fleet control loop (module docstring). `spawn(spec)
    -> handle` and `stop(handle)` are injectable so tests scale
    in-process PolishServers with no subprocesses; `step(now)` is the
    whole decision function, drivable without the thread."""

    def __init__(self, router, config: AutoscaleConfig | None = None,
                 spawn=None, stop=None, chips=None, **overrides):
        self.router = router
        self.config = config if config is not None \
            else AutoscaleConfig(**overrides)
        self._spawn = spawn or self._spawn_on_chip
        #: chips the default spawn hands out, one replica each (empty:
        #: no chips on this host, replicas are not pinned)
        self._chips = host_chips() if chips is None else list(chips)
        self._stop_replica = stop or _default_stop
        self._dir = self.config.socket_dir or tempfile.mkdtemp(
            prefix="racon_tpu_autoscale_")
        #: replicas this loop owns, oldest first:
        #: {"spec", "handle", "t"} — scale-down drains the newest
        self.spawned: list[dict] = []
        self._seq = 0
        self._lock = threading.Lock()
        self._pressure_since: float | None = None
        self._idle_since: float | None = None
        self._last_action_t = float("-inf")
        self._last_pressure = 0.0
        self.counters = {"scale_ups": 0, "scale_downs": 0,
                         "spawn_failures": 0}
        self._thread: threading.Thread | None = None
        self._halt = threading.Event()
        router.autoscaler = self

    # ------------------------------------------------------------ loop
    def start(self) -> "Autoscaler":
        t = threading.Thread(target=self._loop,
                             name="racon-tpu-router-autoscale",
                             daemon=True)
        t.start()
        self._thread = t
        log_info(f"[racon_tpu::autoscale] armed: "
                 f"{self.config.min_replicas}-"
                 f"{self.config.max_replicas} replicas, "
                 f"up at pressure {self.config.up_pressure:g} for "
                 f"{self.config.up_sustain_s:g}s, down after "
                 f"{self.config.down_idle_s:g}s idle")
        return self

    def _loop(self) -> None:
        while not self._halt.is_set():
            self._halt.wait(self.config.interval_s)
            if self._halt.is_set():
                return
            with contextlib.suppress(Exception):
                self.step()

    def close(self, stop_spawned: bool = True) -> None:
        """Stop the loop; by default also drain every replica this
        loop spawned (the router tear-down path)."""
        self._halt.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if stop_spawned:
            with self._lock:
                owned, self.spawned = self.spawned, []
            for entry in owned:
                self.router.remove_replica(entry["spec"])
                with contextlib.suppress(Exception):
                    self._stop_replica(entry["handle"])

    # -------------------------------------------------------- decision
    def _signals(self) -> tuple[float, bool, int, int]:
        """(pressure, burn_firing, backlog, router_inflight) from the
        router's LAST fleet poll — the health loop already paid for the
        probe; the autoscaler never double-polls replicas."""
        snap = self.router.fleet.last()
        backlog = 0
        if snap is not None:
            for rs in snap.replicas:
                if not rs.ok or not isinstance(rs.health, dict):
                    continue
                backlog += int(rs.health.get("queue_depth", 0) or 0)
                backlog += int(rs.health.get("inflight", 0) or 0)
        burn = getattr(snap, "burn", None) or {}
        firing = bool(burn.get("firing"))
        with self.router._state_lock:
            routable = sum(1 for r in self.router.replicas
                           if r.routable)
            inflight = self.router._inflight_jobs
            outstanding = self.router._requeued_outstanding
            waiting = getattr(self.router, "_dispatch_waiting", 0)
        # shards holding in the dispatch loop for an idle replica ARE
        # backlog — counting them is what lets the hold summon the
        # scale-up it is waiting for
        backlog += outstanding + waiting
        pressure = backlog / max(1, routable)
        return pressure, firing, backlog, inflight

    def step(self, now: float | None = None) -> str | None:
        """One control decision; returns "up" / "down" / None (what it
        did). `now` is injectable for clockless tests."""
        now = time.monotonic() if now is None else now
        cfg = self.config
        pressure, firing, backlog, inflight = self._signals()
        self._last_pressure = pressure

        if pressure >= cfg.up_pressure or firing:
            if self._pressure_since is None:
                self._pressure_since = now
        else:
            self._pressure_since = None
        if backlog == 0 and inflight == 0:
            if self._idle_since is None:
                self._idle_since = now
        else:
            self._idle_since = None

        if now - self._last_action_t < cfg.cooldown_s:
            return None
        total = len(self.router.replicas)
        if (self._pressure_since is not None
                and now - self._pressure_since >= cfg.up_sustain_s
                and total < cfg.max_replicas):
            if self._scale_up(reason="burn" if firing else "pressure",
                              pressure=pressure):
                self._last_action_t = now
                self._pressure_since = None
                return "up"
            return None
        if (self._idle_since is not None
                and now - self._idle_since >= cfg.down_idle_s
                and self.spawned
                and total > max(1, cfg.min_replicas)):
            self._scale_down()
            self._last_action_t = now
            self._idle_since = None
            return "down"
        return None

    # --------------------------------------------------------- actions
    def _spawn_on_chip(self, spec: str):
        """The default spawn: on a chip host, a replica gets a chip no
        replica of this loop holds, or the spawn is refused."""
        if not self._chips:
            return _default_spawn(spec)
        with self._lock:
            held = {getattr(e["handle"], "chip", None)
                    for e in self.spawned}
        free = [c for c in self._chips if c not in held]
        if not free:
            raise NoFreeChip("autoscale",
                             f"all {len(self._chips)} chips on this host "
                             "already serve a replica")
        return _default_spawn(spec, chip=free[0])

    def _scale_up(self, reason: str, pressure: float) -> bool:
        with self._lock:
            self._seq += 1
            spec = os.path.join(self._dir,
                                f"autoscale_{self._seq}.sock")
        try:
            handle = self._spawn(spec)
        except Exception as exc:  # noqa: BLE001 — never kill the loop
            self.counters["spawn_failures"] += 1
            log_info(f"[racon_tpu::autoscale] spawn failed: {exc}")
            return False
        if not self._wait_ready(spec):
            self.counters["spawn_failures"] += 1
            log_info(f"[racon_tpu::autoscale] replica {spec} never "
                     "answered healthz; giving up on it")
            with contextlib.suppress(Exception):
                self._stop_replica(handle)
            return False
        with self._lock:
            self.spawned.append({"spec": spec, "handle": handle,
                                 "t": time.monotonic()})
        self.router.add_replica(spec)
        self.counters["scale_ups"] += 1
        if self.router.journal is not None:
            self.router.journal.record(
                "autoscale-up", replica=spec, reason=reason,
                pressure=round(pressure, 3),
                replicas=len(self.router.replicas))
        log_info(f"[racon_tpu::autoscale] scaled up to "
                 f"{len(self.router.replicas)} replicas "
                 f"({reason}, pressure {pressure:.2f})")
        return True

    def _wait_ready(self, spec: str) -> bool:
        """Poll the new replica's healthz RPC until its first clean
        answer (ok, not draining) — routable from its first poll."""
        from .client import PolishClient, ServeError

        deadline = time.monotonic() + self.config.ready_timeout_s
        while time.monotonic() < deadline:
            if self._halt.is_set():
                return False
            try:
                doc = PolishClient(socket_path=spec,
                                   timeout=2.0).healthz()
                if doc.get("ok") and not doc.get("draining"):
                    return True
            except (ServeError, ProtocolError, OSError):
                pass
            time.sleep(0.1)
        return False

    def _scale_down(self) -> None:
        with self._lock:
            entry = self.spawned.pop()
        # unroute FIRST, then drain: nothing new lands on the replica
        # while it finishes; a mid-job death is the normal requeue path
        self.router.remove_replica(entry["spec"])
        with contextlib.suppress(Exception):
            self._stop_replica(entry["handle"])
        self.counters["scale_downs"] += 1
        if self.router.journal is not None:
            self.router.journal.record(
                "autoscale-down", replica=entry["spec"],
                replicas=len(self.router.replicas))
        log_info(f"[racon_tpu::autoscale] scaled down to "
                 f"{len(self.router.replicas)} replicas")

    # -------------------------------------------------------- exposure
    def snapshot(self) -> dict:
        return {"min": self.config.min_replicas,
                "max": self.config.max_replicas,
                "spawned": len(self.spawned),
                "pressure": round(self._last_pressure, 3),
                "scale_ups": self.counters["scale_ups"],
                "scale_downs": self.counters["scale_downs"],
                "spawn_failures": self.counters["spawn_failures"]}
