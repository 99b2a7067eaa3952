"""Elastic replica autoscaling units (serve/autoscale.py) — the
decision function driven clocklessly with injected spawn/stop, plus the
router's armed-only exposure surfaces:

  - config: every env twin strict-parses (a typo fails the start),
    unknown kwargs and inverted fleet bounds raise;
  - scale-up only on SUSTAINED pressure (a one-poll burst never
    scales), bounded by the ceiling and the cooldown;
  - scale-down only after sustained full idle, only replicas the loop
    itself spawned, newest first, UNROUTED before stopped (the
    zero-job-loss ordering), never below the floor;
  - spawn failures count, never throw, and never join the routing set;
  - journal `autoscale-up` / `autoscale-down` records; snapshot keys;
  - healthz carries an `autoscale` block and /metrics the
    `racon_tpu_router_autoscale_*` families ONLY once armed — the
    off-knob exposition stays byte-identical.
"""

from __future__ import annotations

import threading
import types
import urllib.request

import pytest

from racon_tpu.errors import RaconError
from racon_tpu.serve import PolishClient, PolishRouter, PolishServer
from racon_tpu.serve.autoscale import Autoscaler, AutoscaleConfig


# ---------------------------------------------------------------- fakes
class _Replica:
    def __init__(self):
        self.routable = True


class _Fleet:
    def __init__(self):
        self.snap = None

    def last(self):
        return self.snap


class _Journal:
    def __init__(self):
        self.events: list[tuple] = []

    def record(self, event, **kw):
        self.events.append((event, kw))


class _Router:
    """The autoscaler-facing sliver of PolishRouter."""

    def __init__(self, n: int = 1):
        self.fleet = _Fleet()
        self._state_lock = threading.Lock()
        self.replicas = [_Replica() for _ in range(n)]
        self._inflight_jobs = 0
        self._requeued_outstanding = 0
        self.journal = None
        self.autoscaler = None
        self.added: list[str] = []
        self.removed: list[str] = []

    def add_replica(self, spec):
        self.added.append(spec)
        self.replicas.append(_Replica())

    def remove_replica(self, spec):
        self.removed.append(spec)
        self.replicas.pop()


def _snap(queue_depths):
    reps = [types.SimpleNamespace(ok=True,
                                  health={"queue_depth": q, "inflight": 0})
            for q in queue_depths]
    return types.SimpleNamespace(replicas=reps, burn=None)


def _scaler(router, tmp_path, monkeypatch, ready=True, spawn=None,
            stop=None, **kw):
    monkeypatch.setattr(Autoscaler, "_wait_ready",
                        lambda self, spec: ready)
    base = dict(min_replicas=1, max_replicas=3, up_pressure=2.0,
                up_sustain_s=1.0, down_idle_s=2.0, cooldown_s=0.0,
                interval_s=999.0, socket_dir=str(tmp_path))
    base.update(kw)
    cfg = AutoscaleConfig(**base)
    spawned: list[str] = []
    stopped: list[str] = []
    sc = Autoscaler(
        router, cfg,
        spawn=spawn or (lambda spec: spawned.append(spec) or spec),
        stop=stop or (lambda h: stopped.append(h)))
    return sc, spawned, stopped


# --------------------------------------------------------------- config
def test_autoscale_config_env_strict_parse(monkeypatch):
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_MIN", "two")
    with pytest.raises(RaconError, match="AUTOSCALE_MIN"):
        AutoscaleConfig()
    monkeypatch.delenv("RACON_TPU_ROUTER_AUTOSCALE_MIN")
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_UP_PRESSURE", "hot")
    with pytest.raises(RaconError, match="UP_PRESSURE"):
        AutoscaleConfig()
    monkeypatch.delenv("RACON_TPU_ROUTER_AUTOSCALE_UP_PRESSURE")
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_MAX", "8")
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_DOWN_IDLE_S", "5.5")
    cfg = AutoscaleConfig()
    assert cfg.max_replicas == 8 and cfg.down_idle_s == 5.5
    assert cfg.min_replicas == 1  # defaults survive alongside
    with pytest.raises(RaconError, match="unknown autoscale option"):
        AutoscaleConfig(bogus=1)
    with pytest.raises(RaconError, match="bad fleet bounds"):
        AutoscaleConfig(min_replicas=5, max_replicas=2)


# ------------------------------------------------------------- scale up
def test_scale_up_requires_sustained_pressure(tmp_path, monkeypatch):
    router = _Router(n=1)
    router.journal = _Journal()
    router.fleet.snap = _snap([5])  # pressure 5/1
    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch)
    assert sc.step(now=0.0) is None  # pressure noted, not sustained
    assert sc.step(now=0.5) is None
    assert sc.step(now=1.1) == "up"
    assert spawned and spawned[0].endswith("autoscale_1.sock")
    assert router.added == spawned
    assert sc.counters["scale_ups"] == 1
    assert [e for e, _ in router.journal.events] == ["autoscale-up"]


def test_pressure_burst_that_subsides_never_scales(tmp_path,
                                                   monkeypatch):
    router = _Router(n=1)
    router.fleet.snap = _snap([5])
    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch)
    assert sc.step(now=0.0) is None
    router.fleet.snap = _snap([0])  # burst over: sustain clock resets
    assert sc.step(now=0.9) is None
    router.fleet.snap = _snap([5])
    assert sc.step(now=1.5) is None  # restarted sustain, not elapsed
    assert spawned == [] and sc.counters["scale_ups"] == 0


def test_scale_up_respects_ceiling_and_cooldown(tmp_path, monkeypatch):
    router = _Router(n=3)  # already at max_replicas
    router.fleet.snap = _snap([9, 9, 9])
    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch)
    assert sc.step(now=0.0) is None
    assert sc.step(now=5.0) is None
    assert spawned == []

    router = _Router(n=1)
    router.fleet.snap = _snap([9])
    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch,
                             cooldown_s=5.0)
    sc.step(now=0.0)
    assert sc.step(now=1.1) == "up"
    assert sc.step(now=1.2) is None  # sustain restarts
    assert sc.step(now=2.5) is None  # sustained again, but cooling down
    assert sc.step(now=7.0) == "up"  # cooldown elapsed
    assert len(spawned) == 2


def test_spawn_failure_counts_and_never_routes(tmp_path, monkeypatch):
    router = _Router(n=1)
    router.fleet.snap = _snap([9])

    def boom(_spec):
        raise OSError("fork failed")

    sc, _, _ = _scaler(router, tmp_path, monkeypatch, spawn=boom)
    sc.step(now=0.0)
    assert sc.step(now=1.5) is None
    assert sc.counters["spawn_failures"] == 1
    assert router.added == [] and sc.spawned == []

    # spawned but never answered healthz: stopped, counted, not routed
    router = _Router(n=1)
    router.fleet.snap = _snap([9])
    sc, spawned, stopped = _scaler(router, tmp_path, monkeypatch,
                                   ready=False)
    sc.step(now=0.0)
    assert sc.step(now=1.5) is None
    assert sc.counters["spawn_failures"] == 1
    assert spawned and stopped == spawned and router.added == []


def test_default_spawn_gives_each_replica_its_own_chip(tmp_path,
                                                      monkeypatch):
    """On a chip host every spawned replica is confined to a chip no
    other replica of the loop holds; with none free the spawn is refused
    with a typed error and counted, never started."""
    from racon_tpu.serve import autoscale

    started: list[tuple[str, int | None]] = []

    def fake_spawn(spec, chip=None):
        started.append((spec, chip))
        return types.SimpleNamespace(chip=chip)

    monkeypatch.setattr(autoscale, "_default_spawn", fake_spawn)
    monkeypatch.setattr(Autoscaler, "_wait_ready", lambda self, spec: True)
    router = _Router(n=1)
    sc = Autoscaler(router, AutoscaleConfig(
        min_replicas=1, max_replicas=4, cooldown_s=0.0, interval_s=999.0,
        socket_dir=str(tmp_path)), stop=lambda h: None, chips=[0, 1])
    assert sc._scale_up("pressure", 9.0) and sc._scale_up("pressure", 9.0)
    assert [c for _, c in started] == [0, 1]
    with pytest.raises(autoscale.NoFreeChip):
        sc._spawn_on_chip(str(tmp_path / "x.sock"))
    assert not sc._scale_up("pressure", 9.0)
    assert sc.counters["spawn_failures"] == 1 and len(started) == 2
    assert autoscale.chip_env(1)["TPU_VISIBLE_CHIPS"] == "1"
    # a host without chips spawns unpinned
    sc2 = Autoscaler(_Router(n=1), AutoscaleConfig(
        min_replicas=1, max_replicas=4, interval_s=999.0,
        socket_dir=str(tmp_path)), stop=lambda h: None, chips=[])
    assert sc2._scale_up("pressure", 9.0) and started[-1][1] is None


# ----------------------------------------------------------- scale down
def test_scale_down_unroutes_before_stopping(tmp_path, monkeypatch):
    router = _Router(n=1)
    router.journal = _Journal()
    router.fleet.snap = _snap([5])
    order: list[str] = []

    def stop(handle):  # the zero-job-loss ordering: unroute FIRST
        assert handle in router.removed
        order.append(handle)

    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch, stop=stop)
    sc.step(now=0.0)
    assert sc.step(now=1.1) == "up"
    router.fleet.snap = _snap([0, 0])  # fleet fully idle
    assert sc.step(now=2.0) is None  # idle noted, not sustained
    assert sc.step(now=4.1) == "down"
    assert order == spawned and router.removed == spawned
    assert sc.counters["scale_downs"] == 1 and sc.spawned == []
    assert [e for e, _ in router.journal.events] \
        == ["autoscale-up", "autoscale-down"]


def test_never_drains_operator_replicas(tmp_path, monkeypatch):
    router = _Router(n=2)  # both operator-provisioned
    router.fleet.snap = _snap([0, 0])
    sc, _, stopped = _scaler(router, tmp_path, monkeypatch)
    assert sc.step(now=0.0) is None
    assert sc.step(now=100.0) is None  # idle forever: owns nothing
    assert stopped == [] and router.removed == []


def test_inflight_jobs_block_scale_down(tmp_path, monkeypatch):
    router = _Router(n=1)
    router.fleet.snap = _snap([5])
    sc, _, stopped = _scaler(router, tmp_path, monkeypatch)
    sc.step(now=0.0)
    assert sc.step(now=1.1) == "up"
    router.fleet.snap = _snap([0, 0])
    router._inflight_jobs = 1  # router still owes a client a merge
    assert sc.step(now=2.0) is None
    assert sc.step(now=10.0) is None
    router._inflight_jobs = 0
    sc.step(now=11.0)
    assert sc.step(now=13.1) == "down"
    assert len(stopped) == 1


def test_held_shards_count_as_pressure(tmp_path, monkeypatch):
    """A shard holding in the dispatch loop for an idle replica IS
    backlog: router._dispatch_waiting drives the pressure signal, so
    the hold summons the scale-up it waits for."""
    router = _Router(n=1)
    router.fleet.snap = _snap([0])
    sc, spawned, _ = _scaler(router, tmp_path, monkeypatch)
    assert sc.step(now=0.0) is None  # truly idle: no pressure
    router._dispatch_waiting = 3  # three shards holding for capacity
    sc.step(now=1.0)
    assert sc._last_pressure == 3.0
    assert sc.step(now=2.1) == "up"
    assert len(spawned) == 1
    # holding shards also block scale-down (they are not idle)
    router._dispatch_waiting = 1
    router.fleet.snap = _snap([0, 0])
    assert sc.step(now=20.0) is None


def test_dispatch_hold_insists_on_idle_replica(tmp_path):
    """The autoscale hold machinery in PolishRouter: with
    max_inflight=1 only an idle replica qualifies, and headroom is
    True only while an armed autoscaler is below its ceiling."""
    router = PolishRouter(replicas=str(tmp_path / "rep.sock"),
                          socket_path=str(tmp_path / "r.sock"))
    # no autoscaler armed: never hold
    assert router._scaleup_headroom() is False
    # capped pick refuses the busy replica, uncapped takes it
    r = router._pick_replica(set(), max_inflight=1)
    assert r is not None and r.inflight == 1
    assert router._pick_replica(set(), max_inflight=1) is None
    assert router._pick_replica(set()) is not None
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2,
                          socket_dir=str(tmp_path))
    assert cfg.hold_s == 5.0  # default on; 0 disables
    Autoscaler(router, cfg, spawn=lambda spec: spec,
               stop=lambda h: None)
    assert router._scaleup_headroom() is True  # 1 replica < max 2
    router.add_replica(str(tmp_path / "rep2.sock"))
    assert router._scaleup_headroom() is False  # at the ceiling


def test_hold_s_config_strict_parse(monkeypatch, tmp_path):
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_HOLD_S", "forever")
    with pytest.raises(RaconError, match="AUTOSCALE_HOLD_S"):
        AutoscaleConfig()
    monkeypatch.setenv("RACON_TPU_ROUTER_AUTOSCALE_HOLD_S", "2.5")
    assert AutoscaleConfig().hold_s == 2.5
    monkeypatch.delenv("RACON_TPU_ROUTER_AUTOSCALE_HOLD_S")
    with pytest.raises(RaconError, match="hold_s"):
        AutoscaleConfig(hold_s=-1.0)


def test_snapshot_shape(tmp_path, monkeypatch):
    router = _Router(n=1)
    router.fleet.snap = _snap([4])
    sc, _, _ = _scaler(router, tmp_path, monkeypatch)
    sc.step(now=0.0)
    snap = sc.snapshot()
    assert snap == {"min": 1, "max": 3, "spawned": 0, "pressure": 4.0,
                    "scale_ups": 0, "scale_downs": 0,
                    "spawn_failures": 0}


# ------------------------------------------------- armed-only exposure
def test_router_surfaces_autoscale_only_when_armed(tmp_path,
                                                   monkeypatch):
    srv = PolishServer(socket_path=str(tmp_path / "rep.sock"),
                       workers=1).start()
    router = PolishRouter(replicas=srv.config.socket_path,
                          socket_path=str(tmp_path / "r.sock"),
                          metrics_port=0,
                          health_interval_s=0.2).start()
    try:
        cli = PolishClient(socket_path=router.config.socket_path)
        base = f"http://127.0.0.1:{router.config.metrics_port}"
        hz = cli.request({"type": "healthz"})
        assert "autoscale" not in hz  # off-knob surface unchanged
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        assert "racon_tpu_router_autoscale" not in body
        # arming (constructor attaches; no loop needed) flips both on
        monkeypatch.setattr(Autoscaler, "_wait_ready",
                            lambda self, spec: True)
        Autoscaler(router,
                   AutoscaleConfig(socket_dir=str(tmp_path)),
                   spawn=lambda spec: spec, stop=lambda h: None)
        hz = cli.request({"type": "healthz"})
        assert hz["autoscale"]["min"] == 1
        assert hz["autoscale"]["spawned"] == 0
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        assert "racon_tpu_router_autoscale_spawned 0" in body
        assert "racon_tpu_router_autoscale_scale_ups" in body
        assert "racon_tpu_router_autoscale_pressure" in body
    finally:
        router.drain()
        srv.drain(timeout=10)
