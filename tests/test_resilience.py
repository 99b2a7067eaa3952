"""Resilience layer tests (racon_tpu/resilience + pipeline wiring).

The contracts:

  - the fault plan grammar parses/rejects deterministically and every
    armed fault is one-shot;
  - the watchdog bounds device-stage calls in time (DeviceTimeout, never
    a hang) and retries with exponential backoff;
  - FAULT MATRIX: for each injection point (pack raise, device raise,
    device hang, unpack corrupt, fallback raise) at pipeline depth 0 and
    2, a full polisher run either produces byte-identical output to the
    clean run (the watchdog/retry/fallback ladder absorbed the fault) or
    reports quarantined windows — and never crashes, never exceeds the
    watchdog budget, never leaves orphaned worker threads;
  - a window whose consensus fails on both device and host is
    QUARANTINED: draft backbone kept as consensus, counted in the
    degradation report, reflected in the XC ratio;
  - truncated/corrupt gzip inputs surface as RaconError naming the file,
    not a traceback;
  - the CLI exposes the posture knobs (--tpu-strict, --tpu-fault-plan,
    --tpu-device-timeout).

tools/faultcheck.py runs the full matrix (including the slow hang cases
excluded from tier-1 via the `slow` marker) as a pass/fail grid.
"""

import gzip
import random
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from racon_tpu.errors import (ChunkCorrupt, DeviceError, DeviceTimeout,  # noqa: E402
                              RaconError)
from racon_tpu.pipeline import DispatchPipeline  # noqa: E402
from racon_tpu.resilience import (FaultPlan, Watchdog,  # noqa: E402
                                  degradation_summary)
from racon_tpu.resilience.faults import reset_fault_plan  # noqa: E402

ACGT = b"ACGT"

RESILIENCE_ENV = ("RACON_TPU_FAULT_PLAN", "RACON_TPU_DEVICE_TIMEOUT",
                  "RACON_TPU_DEVICE_RETRIES", "RACON_TPU_RETRY_BACKOFF",
                  "RACON_TPU_STRICT")


@pytest.fixture(autouse=True)
def _clean_resilience_env(monkeypatch):
    for var in RESILIENCE_ENV:
        monkeypatch.delenv(var, raising=False)
    reset_fault_plan()
    yield
    reset_fault_plan()


def _no_orphan_threads(grace: float = 3.0):
    """No racon-tpu worker thread may outlive the run (abandoned watchdog
    workers get a short grace to notice their cancelled hang)."""
    deadline = time.perf_counter() + grace
    while time.perf_counter() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith("racon-tpu")]
        if not alive:
            return
        time.sleep(0.05)
    raise AssertionError(f"orphaned worker threads: {alive}")


# ----------------------------------------------------------- fault plan

def test_fault_plan_parses_the_documented_spec():
    plan = FaultPlan.parse(
        "device:chunk=3:raise,device:chunk=7:hang=5,unpack:chunk=2:corrupt")
    assert len(plan.unfired) == 3
    stages = sorted(f.stage for f in plan.unfired)
    assert stages == ["device", "device", "unpack"]


@pytest.mark.parametrize("bad", [
    "device:3:raise",            # missing chunk=
    "gpu:chunk=1:raise",         # unknown stage
    "device:chunk=x:raise",      # non-integer chunk
    "device:chunk=1:explode",    # unknown action
    "device:chunk=1:hang",       # hang without duration
    "device:chunk=1:hang=-2",    # non-positive duration
    "device:chunk=1:raise=3",    # raise takes no argument
    "",                          # empty plan
])
def test_fault_plan_rejects_bad_specs(bad):
    with pytest.raises(RaconError, match="FaultPlan"):
        FaultPlan.parse(bad)


def test_faults_are_one_shot_and_typed():
    plan = FaultPlan.parse("device:chunk=1:raise,unpack:chunk=0:corrupt")
    plan.fire("device", 0)  # no fault armed there
    with pytest.raises(DeviceError):
        plan.fire("device", 1)
    plan.fire("device", 1)  # consumed: the retry succeeds
    with pytest.raises(ChunkCorrupt):
        plan.fire("unpack", 0)
    assert plan.unfired == []


def test_injected_hang_is_cancellable():
    plan = FaultPlan.parse("device:chunk=0:hang=30")
    t = threading.Thread(target=lambda: plan.fire("device", 0))
    t0 = time.perf_counter()
    t.start()
    time.sleep(0.15)
    plan.cancel_hangs()
    t.join(timeout=5)
    assert not t.is_alive()
    assert time.perf_counter() - t0 < 5


# ------------------------------------------------------------- watchdog

def test_watchdog_deadline_raises_device_timeout():
    wd = Watchdog(timeout=0.2, retries=0)
    release = threading.Event()  # lets the abandoned worker exit promptly
    t0 = time.perf_counter()
    try:
        with pytest.raises(DeviceTimeout):
            wd.call(lambda: release.wait(30))
        assert time.perf_counter() - t0 < 2
    finally:
        release.set()


def test_watchdog_retries_with_exponential_backoff():
    from racon_tpu.pipeline import PipelineStats

    stats = PipelineStats()
    wd = Watchdog(timeout=0.0, retries=2, backoff=0.01)
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert wd.call(flaky, stats=stats) == "ok"
    s = stats.snapshot()
    assert len(attempts) == 3
    assert s["retries"] == 2
    assert s["backoff_s"] == pytest.approx(0.01 + 0.02)

    # exhausted retries re-raise the final error
    with pytest.raises(RuntimeError, match="persistent"):
        wd.call(lambda: (_ for _ in ()).throw(RuntimeError("persistent")))


def test_stale_cancel_does_not_void_next_hang():
    """A cancel with no sleeper (a real slow call tripped the watchdog)
    must not make a later armed hang return instantly."""
    plan = FaultPlan.parse("device:chunk=0:hang=0.4")
    plan.cancel_hangs()  # stale: nothing is sleeping
    t0 = time.perf_counter()
    plan.fire("device", 0)
    assert time.perf_counter() - t0 >= 0.3  # the stall still happened


def test_watchdog_from_env_rejects_garbage(monkeypatch):
    monkeypatch.setenv("RACON_TPU_DEVICE_TIMEOUT", "5s")
    with pytest.raises(RaconError, match="RACON_TPU_DEVICE_TIMEOUT"):
        Watchdog.from_env()
    monkeypatch.delenv("RACON_TPU_DEVICE_TIMEOUT")
    monkeypatch.setenv("RACON_TPU_DEVICE_RETRIES", "two")
    with pytest.raises(RaconError, match="RACON_TPU_DEVICE_RETRIES"):
        Watchdog.from_env()


def test_watchdog_from_env(monkeypatch):
    assert Watchdog.from_env() is None  # nothing configured: no overhead
    monkeypatch.setenv("RACON_TPU_DEVICE_TIMEOUT", "1.5")
    wd = Watchdog.from_env()
    assert wd is not None and wd.timeout == 1.5
    assert wd.retries == 1  # default once the watchdog is on
    monkeypatch.setenv("RACON_TPU_DEVICE_RETRIES", "3")
    assert Watchdog.from_env().retries == 3
    # explicit (CLI) timeout wins over the env
    assert Watchdog.from_env(timeout=0.7).timeout == 0.7


# ----------------------------------------------- pipeline-level injection

@pytest.mark.parametrize("depth", [0, 2])
def test_injected_device_raise_absorbed_by_retry(monkeypatch, depth):
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", "device:chunk=1:raise")
    monkeypatch.setenv("RACON_TPU_DEVICE_RETRIES", "1")
    monkeypatch.setenv("RACON_TPU_RETRY_BACKOFF", "0.01")
    reset_fault_plan()
    pl = DispatchPipeline(depth=depth)
    seen = []
    pl.run(range(4), lambda i: i * 10, lambda i, o: o + 1, lambda h: h + 1,
           lambda i, r: seen.append((i, r)))
    pl.close()
    assert seen == [(i, i * 10 + 2) for i in range(4)]  # nothing lost
    s = pl.stats.snapshot()
    assert s["faults"] == 1 and s["retries"] == 1 and s["errors"] == 0


@pytest.mark.parametrize("depth", [0, 2])
def test_injected_corrupt_routes_chunk_to_on_error(monkeypatch, depth):
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", "unpack:chunk=1:corrupt")
    reset_fault_plan()
    pl = DispatchPipeline(depth=depth)
    failed = []
    pl.run(range(3), lambda i: i, lambda i, o: o, lambda h: h,
           lambda i, r: None,
           on_error=lambda i, exc: failed.append((i, exc)))
    pl.close()
    assert [i for i, _ in failed] == [1]
    assert isinstance(failed[0][1], ChunkCorrupt)  # typed, not stringly


def test_cancel_fallback_cancels_pending_and_drains_running():
    pl = DispatchPipeline(depth=2, fallback_workers=1)
    started = threading.Event()

    def slow():
        started.set()
        time.sleep(0.3)
        return "done"

    futs = [pl.submit_fallback(slow) for _ in range(4)]
    started.wait(2)
    cancelled, drained = pl.cancel_fallback()
    assert cancelled >= 2 and drained >= 1
    assert cancelled + drained == 4
    assert pl.stats.snapshot()["cancelled"] == cancelled
    assert all(f.cancelled() or f.done() for f in futs)
    assert pl._futures == []  # drain_fallback later is a no-op
    pl.close()


def test_aligner_circuit_breaker_trips(monkeypatch):
    """A device failing every aligner chunk must not burn a fault/retry
    per chunk forever: after 3 consecutive chunk failures the pass
    aborts with a DeviceError (the polisher then host-aligns the whole
    phase), and the trip is counted."""
    from racon_tpu.ops.align import BatchAligner

    monkeypatch.setenv(
        "RACON_TPU_FAULT_PLAN",
        ",".join(f"device:chunk={i}:raise" for i in range(4)))
    reset_fault_plan()
    rng = random.Random(5)
    # three length buckets -> three device chunks
    pairs = []
    for length in (300, 800, 1500, 300, 800, 1500):
        s = bytes(rng.choice(ACGT) for _ in range(length))
        pairs.append((s, s))
    rejected = []
    al = BatchAligner(band_width=64)
    with DispatchPipeline(depth=0) as pl:
        with pytest.raises(DeviceError, match="consecutive"):
            al.align(pairs, pipeline=pl, on_reject=lambda idxs, reason: rejected.extend(idxs))
        assert pl.stats.snapshot()["breaker_trips"] == 1


def test_consensus_degrade_cancels_prefall_futures(monkeypatch):
    """When the device consensus pass dies mid-flight, queued fallback
    futures on the shared pipeline are cancelled/drained before the host
    pass reruns those windows (no duplicated work, no stale futures)."""
    from test_device_poa import _make_windows

    from racon_tpu.ops import poa as poa_mod

    rng = random.Random(3)
    windows, _ = _make_windows(rng, 5, length=160, depth=5, rate=0.1)
    pl = DispatchPipeline(depth=2, fallback_workers=1)

    def dead_device(self, todo, trim):
        # a prefall-shaped job is in flight when the device pass dies
        pl.submit_fallback(time.sleep, 0.2)
        pl.submit_fallback(time.sleep, 0.2)
        raise DeviceError("FusedPOA", "3 consecutive device chunk "
                          "failures; aborting the device pass")

    monkeypatch.setattr(poa_mod.BatchPOA, "_device_consensus", dead_device)
    with pl:
        eng = poa_mod.BatchPOA(3, -5, -4, 160, num_threads=2,
                               device_batches=1, pipeline=pl)
        eng.generate_consensus(windows, trim=False)
        stats = pl.stats.snapshot()
    assert pl._futures == []  # nothing stale left on the pipeline
    assert stats["cancelled"] >= 1
    for w in windows:
        assert w.polished and w.consensus  # host pass completed everything


# ----------------------------------------------------- polisher matrix

def _dataset(tmp_path, rng):
    """Small synthetic polishing job with MIXED read lengths so the
    device aligner path has both bucketable pairs (device chunks) and
    overlength pairs (host-fallback jobs) once ALIGNER_MAXLEN=1024."""
    truth = bytes(rng.choice(ACGT) for _ in range(2000))

    def mutate(s, rate):
        out = bytearray()
        for c in s:
            r = rng.random()
            if r < rate / 3:
                continue
            if r < 2 * rate / 3:
                out.append(rng.choice(ACGT))
                out.append(c)
                continue
            if r < rate:
                out.append(rng.choice(ACGT))
                continue
            out.append(c)
        return bytes(out)

    draft = mutate(truth, 0.04)
    reads, paf = [], []
    jobs = [(start, 400) for start in range(0, len(truth) - 400, 100)]
    jobs += [(0, 1300), (600, 1300)]  # overlength: reject -> fallback pool
    for k, (start, read_len) in enumerate(jobs):
        read = mutate(truth[start:start + read_len], 0.05)
        name = f"r{k}"
        reads.append((name, read))
        t_begin = min(start, len(draft) - 1)
        t_end = min(start + read_len, len(draft))
        paf.append(f"{name}\t{len(read)}\t0\t{len(read)}\t+\tdraft\t"
                   f"{len(draft)}\t{t_begin}\t{t_end}\t{read_len}\t"
                   f"{read_len}\t60")
    reads_path = tmp_path / "reads.fasta.gz"
    with gzip.open(reads_path, "wb") as f:
        for name, read in reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    paf_path = tmp_path / "ovl.paf.gz"
    with gzip.open(paf_path, "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    draft_path = tmp_path / "draft.fasta.gz"
    with gzip.open(draft_path, "wb") as f:
        f.write(b">draft\n" + draft + b"\n")
    return reads_path, paf_path, draft_path


@pytest.fixture(scope="module")
def matrix_paths(tmp_path_factory):
    return _dataset(tmp_path_factory.mktemp("faultmx"),
                    random.Random(11))


def _polish(paths, depth, aligner, timeout=0.0):
    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = create_polisher(*(str(x) for x in paths), PolisherType.kC,
                        500, -1.0, 0.3, num_threads=2,
                        tpu_aligner_batches=aligner,
                        tpu_pipeline_depth=depth,
                        tpu_device_timeout=timeout)
    p.initialize()
    out = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                   for s in p.polish())
    return out, p.stage_stats


_CLEAN: dict = {}


def _clean_run(matrix_paths, depth, aligner, monkeypatch):
    key = (depth, aligner)
    if key not in _CLEAN:
        monkeypatch.setenv("RACON_TPU_ALIGNER_MAXLEN", "1024")
        out, stats = _polish(matrix_paths, depth, aligner)
        assert stats["faults"] == 0 and stats["quarantined"] == 0
        _CLEAN[key] = out
    return _CLEAN[key]


# the matrix: every injection point, absorbed by the retry/fallback
# ladder. aligner=1 arms the alignment phase's pipeline (it runs first);
# aligner=0 arms the consensus phase's host loop. Hang cases (below,
# marked slow) exercise the watchdog deadline the same way.
MATRIX = [
    ("align-pack-raise", 1, "pack:chunk=0:raise"),
    ("align-device-raise", 1, "device:chunk=0:raise"),
    ("align-unpack-corrupt", 1, "unpack:chunk=0:corrupt"),
    ("align-fallback-raise", 1, "fallback:chunk=0:raise"),
    ("consensus-pack-raise", 0, "pack:chunk=0:raise"),
    ("consensus-device-raise", 0, "device:chunk=0:raise"),
    ("consensus-unpack-corrupt", 0, "unpack:chunk=0:corrupt"),
    # persistent device failure: retry cannot absorb it (two armed
    # faults vs one retry); the chunk degrades to the per-window host
    # pass, which still reproduces the clean bytes
    ("consensus-device-persistent", 0,
     "device:chunk=0:raise,device:chunk=0:raise"),
]


@pytest.mark.faults
@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("name,aligner,spec",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_fault_matrix_absorbed(matrix_paths, monkeypatch, depth, name,
                               aligner, spec):
    clean = _clean_run(matrix_paths, depth, aligner, monkeypatch)
    monkeypatch.setenv("RACON_TPU_ALIGNER_MAXLEN", "1024")
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", spec)
    monkeypatch.setenv("RACON_TPU_DEVICE_RETRIES", "1")
    monkeypatch.setenv("RACON_TPU_RETRY_BACKOFF", "0.01")
    reset_fault_plan()
    out, stats = _polish(matrix_paths, depth, aligner)
    assert stats["faults"] >= 1, "armed fault never fired"
    assert out == clean or stats["quarantined"] > 0
    _no_orphan_threads()


HANGS = [
    ("align-device-hang", 1, "device:chunk=0:hang=5"),
    ("consensus-device-hang", 0, "device:chunk=0:hang=5"),
]


@pytest.mark.faults
@pytest.mark.slow
@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("name,aligner,spec",
                         HANGS, ids=[h[0] for h in HANGS])
def test_fault_matrix_hang_bounded_by_watchdog(matrix_paths, monkeypatch,
                                               depth, name, aligner, spec):
    """A 5 s injected stall under a 0.5 s deadline: the run must finish
    well inside the hang duration (DeviceTimeout -> retry absorbed it),
    byte-identical, with no abandoned worker left behind."""
    clean = _clean_run(matrix_paths, depth, aligner, monkeypatch)
    monkeypatch.setenv("RACON_TPU_ALIGNER_MAXLEN", "1024")
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", spec)
    monkeypatch.setenv("RACON_TPU_RETRY_BACKOFF", "0.01")
    reset_fault_plan()
    t0 = time.perf_counter()
    out, stats = _polish(matrix_paths, depth, aligner, timeout=0.5)
    wall = time.perf_counter() - t0
    assert stats["faults"] >= 1 and stats["timeouts"] >= 1
    assert out == clean or stats["quarantined"] > 0
    assert wall < 60  # bounded: nowhere near a wedged run
    _no_orphan_threads()


def test_clean_run_reports_nothing(matrix_paths, monkeypatch):
    """No fault plan, no timeout: the degradation report is empty and
    the resilience counters all zero — the hooks cost nothing."""
    clean = _clean_run(matrix_paths, 2, 1, monkeypatch)
    assert clean  # produced output
    out, stats = _polish(matrix_paths, 2, 1)
    assert out == clean
    for key in ("faults", "retries", "timeouts", "breaker_trips",
                "quarantined", "cancelled"):
        assert stats[key] == 0
    assert stats["backoff_s"] == 0.0
    assert degradation_summary(stats) is None


# ---------------------------------------------------------- quarantine

def test_quarantined_window_keeps_backbone(monkeypatch):
    """A window whose consensus fails on the whole-chunk pass AND on its
    individual retry keeps the draft backbone, counts as unpolished and
    bumps the quarantine counter; its neighbours still polish."""
    from test_device_poa import _make_windows

    from racon_tpu.ops import poa as poa_mod

    rng = random.Random(3)
    windows, _ = _make_windows(rng, 6, length=160, depth=5, rate=0.1)
    poison = windows[2].sequences[0]
    real_poa_batch = poa_mod.poa_batch

    def sabotaged(packed, *args, **kwargs):
        if any(win[0][0] == poison for win in packed):
            raise RuntimeError("poisoned window")
        return real_poa_batch(packed, *args, **kwargs)

    monkeypatch.setattr(poa_mod, "poa_batch", sabotaged)
    with DispatchPipeline(depth=2) as pl:
        eng = poa_mod.BatchPOA(3, -5, -4, 160, num_threads=2, pipeline=pl)
        eng.generate_consensus(windows, trim=False)
        stats = pl.stats.snapshot()
    assert stats["quarantined"] == 1
    assert windows[2].consensus == poison  # draft backbone kept
    assert not windows[2].polished
    for w in windows[:2] + windows[3:]:
        assert w.polished and w.consensus


def test_quarantine_strict_mode_raises(monkeypatch):
    from test_device_poa import _make_windows

    from racon_tpu.ops import poa as poa_mod

    rng = random.Random(3)
    windows, _ = _make_windows(rng, 4, length=160, depth=5, rate=0.1)
    monkeypatch.setenv("RACON_TPU_STRICT", "1")
    monkeypatch.setattr(
        poa_mod, "poa_batch",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("dead")))
    with DispatchPipeline(depth=0) as pl:
        eng = poa_mod.BatchPOA(3, -5, -4, 160, num_threads=1, pipeline=pl)
        with pytest.raises(RuntimeError, match="dead"):
            eng.generate_consensus(windows, trim=False)


def test_quarantine_xc_ratio_reflects_unpolished(matrix_paths, monkeypatch):
    """Every window quarantined -> the stitched sequence's XC ratio is 0,
    its data is the concatenated draft backbones, and with the default
    drop-unpolished policy the sequence is dropped entirely — the
    reference's `ratio > 0` discipline (polisher.cpp:515) applied to
    failure-time quarantine."""
    from racon_tpu.core.polisher import PolisherType, create_polisher
    from racon_tpu.ops import poa as poa_mod

    monkeypatch.setattr(
        poa_mod, "poa_batch",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("dead engine")))

    def run(drop):
        p = create_polisher(*(str(x) for x in matrix_paths),
                            PolisherType.kC, 500, -1.0, 0.3,
                            num_threads=1, tpu_pipeline_depth=0)
        p.initialize()
        draft = p.sequences[0].data
        # only windows deep enough for POA can fail into quarantine;
        # sub-3-sequence windows keep their backbone by design already
        n_q = sum(1 for w in p.windows if len(w.sequences) >= 3)
        return p.polish(drop), p.stage_stats, draft, n_q

    polished, stats, draft, n_q = run(drop=True)
    assert n_q > 0 and stats["quarantined"] == n_q
    assert polished == []  # ratio 0: dropped, not crashed

    polished, stats, draft, n_q = run(drop=False)
    assert len(polished) == 1
    assert "XC:f:0.000000" in polished[0].name
    assert polished[0].data == draft  # every window kept its backbone
    _no_orphan_threads()


# ------------------------------------------------------- corrupt inputs

def test_truncated_gzip_overlaps_is_racon_error(tmp_path, matrix_paths):
    reads, paf, draft = matrix_paths
    blob = paf.read_bytes()
    bad = tmp_path / "trunc.paf.gz"
    bad.write_bytes(blob[:len(blob) // 2])

    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = create_polisher(str(reads), str(bad), str(draft),
                        PolisherType.kC, 500, -1.0, 0.3)
    with pytest.raises(RaconError, match="trunc.paf.gz"):
        p.initialize()


def test_corrupt_gzip_fasta_is_racon_error(tmp_path):
    from racon_tpu.io.parsers import FastaParser

    blob = bytearray(gzip.compress(b">s\n" + b"ACGT" * 3000 + b"\n"))
    blob[len(blob) // 2] ^= 0xFF  # flip a byte mid-stream
    bad = tmp_path / "corrupt.fasta.gz"
    bad.write_bytes(bytes(blob))
    with pytest.raises(RaconError, match="corrupt.fasta.gz"):
        FastaParser(str(bad)).parse([], -1)


def test_truncated_gzip_cli_exits_cleanly(tmp_path, matrix_paths, capsys):
    """Through the CLI: stderr carries the [racon_tpu::...] error line
    and the exit status is 1 — no traceback."""
    from racon_tpu.cli import main

    reads, paf, draft = matrix_paths
    blob = paf.read_bytes()
    bad = tmp_path / "trunc.paf.gz"
    bad.write_bytes(blob[:len(blob) // 2])
    rc = main([str(reads), str(bad), str(draft)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "error:" in err and "trunc.paf.gz" in err
    assert "Traceback" not in err


# -------------------------------------------------------------- CLI

def test_cli_resilience_flags_parse():
    from racon_tpu.cli import parse_args

    opts = parse_args(["--tpu-strict", "--tpu-device-timeout", "2.5",
                       "--tpu-fault-plan", "device:chunk=0:raise",
                       "a.fasta", "b.paf", "c.fasta"])
    assert opts["tpu_strict"] is True
    assert opts["tpu_device_timeout"] == 2.5
    assert opts["tpu_fault_plan"] == "device:chunk=0:raise"


def test_cli_strict_flag_in_help(capsys):
    from racon_tpu.cli import parse_args

    assert parse_args(["--help"]) is None
    out = capsys.readouterr().out
    for flag in ("--tpu-strict", "--tpu-fault-plan",
                 "--tpu-device-timeout"):
        assert flag in out


def test_cli_bad_fault_plan_exits_cleanly(capsys):
    from racon_tpu.cli import main

    rc = main(["--tpu-fault-plan", "bogus-spec",
               "a.fasta", "b.paf", "c.fasta"])
    err = capsys.readouterr().err
    assert rc == 1
    assert "FaultPlan" in err and "error:" in err
