"""Observability layer: trace/metrics integrity, leveled logging.

Pins the PR-4 contracts:
  - every emitted span is well-formed (ph/pid/tid/name present, dur >= 0)
    and the trace file is valid Chrome trace-event JSON;
  - per-stage span-duration sums agree with the PipelineStats wall-clock
    counters (they share perf_counter endpoints, so within tolerance);
  - fault-plan runs produce resilience instant events matching the
    degradation counters exactly (both come from the same bump);
  - concurrent pipeline threads produce a parseable trace;
  - tracing off by default, and a traced run's FASTA is byte-identical;
  - spans are live: inside a JAX profiler capture they are
    `racon.<name>` annotations on the capture's clock, nested and in
    order, and a run's `--tpu-jax-profile` is one capture;
  - the metrics registry namespaces (pipeline/sched/resilience/aligner),
    the --tpu-metrics dump, and the bench-facing snapshot;
  - leveled logging (quiet/info/debug), warn_dedup suppression, and the
    Logger.total() open-section fix.
"""

import gzip
import json
import os
import random
import time

import pytest

from racon_tpu.obs import trace
from racon_tpu.obs.metrics import MetricsRegistry
from racon_tpu.utils import logger as ulog

ACGT = b"ACGT"


@pytest.fixture(autouse=True)
def _reset_obs(monkeypatch):
    """Every test starts with tracing unarmed, dedup empty and the log
    level re-resolving from a clean environment."""
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    monkeypatch.delenv("RACON_TPU_METRICS", raising=False)
    monkeypatch.delenv("RACON_TPU_LOG_LEVEL", raising=False)
    monkeypatch.delenv("RACON_TPU_FAULT_PLAN", raising=False)
    trace.reset()
    ulog.reset_dedup()
    ulog.set_log_level(None)
    yield
    trace.reset()
    ulog.reset_dedup()
    ulog.set_log_level(None)


# ------------------------------------------------------------------ fixture
def _mutate(rng, s, rate):
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


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """Small synthetic polishing job (the faultcheck shape): a 2 kb
    draft, windowed reads, PAF overlaps — enough windows and layers to
    drive both pipeline phases on the host backend in well under a
    second."""
    rng = random.Random(11)
    truth = bytes(rng.choice(ACGT) for _ in range(2000))
    draft = _mutate(rng, truth, 0.04)
    jobs = [(start, 400) for start in range(0, len(truth) - 400, 100)]
    reads, paf = [], []
    for k, (start, read_len) in enumerate(jobs):
        read = _mutate(rng, truth[start:start + read_len], 0.05)
        reads.append((f"r{k}", read))
        t_end = min(start + read_len, len(draft))
        paf.append(f"r{k}\t{len(read)}\t0\t{len(read)}\t+\tdraft\t"
                   f"{len(draft)}\t{start}\t{t_end}\t{read_len}\t"
                   f"{read_len}\t60")
    d = tmp_path_factory.mktemp("obsdata")
    paths = (str(d / "reads.fasta.gz"), str(d / "ovl.paf.gz"),
             str(d / "draft.fasta.gz"))
    with gzip.open(paths[0], "wb") as f:
        for name, read in reads:
            f.write(b">" + name.encode() + b"\n" + read + b"\n")
    with gzip.open(paths[1], "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    with gzip.open(paths[2], "wb") as f:
        f.write(b">draft\n" + draft + b"\n")
    return paths


def _polish(paths, depth=2):
    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = create_polisher(*paths, PolisherType.kC, 500, -1.0, 0.3,
                        num_threads=2, tpu_pipeline_depth=depth)
    p.initialize()
    out = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                   for s in p.polish())
    return out, p


def _load_trace(path):
    with open(path) as fh:
        doc = json.load(fh)
    return doc["traceEvents"]


# ------------------------------------------------------------- span tracing
def test_tracing_off_by_default():
    assert trace.get_tracer() is None
    # the disabled convenience span is a working no-op context
    with trace.span("noop", x=1):
        pass


def test_trace_events_well_formed(dataset, tmp_path):
    path = str(tmp_path / "trace.json")
    trace.configure(path)
    _polish(dataset, depth=2)
    events = _load_trace(path)  # polish() end saves automatically
    assert events, "traced polish emitted no events"
    names = {e["name"] for e in events}
    for expected in ("polisher.initialize", "polisher.consensus",
                     "pipeline.pack", "pipeline.device",
                     "pipeline.unpack"):
        assert expected in names, f"missing {expected} spans"
    for ev in events:
        for field in ("name", "ph", "pid", "tid"):
            assert field in ev, f"event missing {field}: {ev}"
        if ev["ph"] == "X":
            assert ev["dur"] >= 0  # end >= start
            assert ev["ts"] >= 0


def test_span_sums_match_stage_stats(dataset, tmp_path):
    path = str(tmp_path / "trace.json")
    trace.configure(path)
    _, polisher = _polish(dataset, depth=2)
    events = _load_trace(path)
    stats = polisher.stage_stats
    sums = {}
    for ev in events:
        if ev["ph"] == "X" and ev["name"].startswith("pipeline."):
            stage = ev["name"].split(".", 1)[1]
            sums[stage] = sums.get(stage, 0.0) + ev["dur"] / 1e6
    for stage, key in (("pack", "pack_s"), ("device", "device_s"),
                       ("unpack", "unpack_s"), ("fallback", "fallback_s")):
        want = stats[key]
        got = sums.get(stage, 0.0)
        # spans reuse the counters' perf_counter endpoints, so only
        # float/serialization rounding separates them; 5% is the
        # acceptance bound, 1 ms the small-value floor
        assert got == pytest.approx(want, rel=0.05, abs=1e-3), \
            f"{stage}: span sum {got} vs stage counter {want}"


def test_fault_instants_match_counters(dataset, tmp_path, monkeypatch):
    from racon_tpu.resilience.faults import reset_fault_plan

    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("RACON_TPU_FAULT_PLAN", "device:chunk=0:raise")
    reset_fault_plan()
    trace.configure(path)
    try:
        _, polisher = _polish(dataset, depth=2)
    finally:
        monkeypatch.delenv("RACON_TPU_FAULT_PLAN")
        reset_fault_plan()
    stats = polisher.stage_stats
    assert stats["faults"] >= 1
    events = _load_trace(path)
    fired = sum(e["args"]["n"] for e in events
                if e["name"] == "resilience.faults")
    assert fired == stats["faults"]
    for e in events:
        if e["name"].startswith("resilience."):
            assert e["ph"] == "i"


def test_quarantine_instants_match_counters(dataset, tmp_path,
                                            monkeypatch):
    # poison the host POA engine entirely: the chunk fails, the
    # per-window retries fail, every eligible window quarantines — the
    # trace's quarantine instants must equal the counter exactly
    import racon_tpu.ops.poa as poa_mod

    def boom(*a, **kw):
        raise RuntimeError("poisoned poa")

    monkeypatch.setattr(poa_mod, "poa_batch", boom)
    path = str(tmp_path / "trace.json")
    trace.configure(path)
    out, polisher = _polish(dataset, depth=2)
    stats = polisher.stage_stats
    assert stats["quarantined"] > 0
    # the run survived (every window on its draft backbone; with ratio 0
    # the target is dropped from the output, the reference's `ratio > 0`
    # rule — the point is no exception reached us)
    events = _load_trace(path)
    quarantined = sum(e["args"]["n"] for e in events
                      if e["name"] == "resilience.quarantined")
    assert quarantined == stats["quarantined"]


def test_concurrent_pipeline_trace_parseable(tmp_path):
    from racon_tpu.pipeline import DispatchPipeline

    path = str(tmp_path / "trace.json")
    rec = trace.configure(path)
    results = []
    with DispatchPipeline(depth=2, fallback_workers=3) as pl:
        for _ in range(40):
            pl.submit_fallback(lambda: time.sleep(0.0005))
        pl.run(range(60),
               pack=lambda i: i * 2,
               dispatch=lambda i, ops: ops + 1,
               wait=lambda h: h,
               unpack=lambda i, res: results.append(res),
               label="t", describe=lambda i: {"i": i})
        pl.drain_fallback()
    rec.save()
    events = _load_trace(path)  # parseable despite 5+ writer threads
    counts = {}
    for e in events:
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    assert counts["pipeline.pack"] == 60
    assert counts["pipeline.device"] == 120  # dispatch + wait segments
    assert counts["pipeline.unpack"] == 60
    assert counts["pipeline.fallback"] == 40
    assert len(results) == 60


def test_env_armed_trace_nonnegative_ts(dataset, tmp_path, monkeypatch):
    # arm via the env (the documented primary knob): the recorder is
    # created lazily at polisher construction, yet phase spans whose
    # start predates it must still clamp to ts >= 0
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("RACON_TPU_TRACE", path)
    _polish(dataset, depth=2)
    for ev in _load_trace(path):
        if ev["ph"] != "M":
            assert ev["ts"] >= 0, ev


def test_traced_output_byte_identical(dataset, tmp_path):
    out_plain, _ = _polish(dataset, depth=2)
    trace.configure(str(tmp_path / "trace.json"))
    out_traced, _ = _polish(dataset, depth=2)
    assert out_plain == out_traced


# ---------------------------------------------------------------- metrics
def test_metrics_registry_basics(tmp_path):
    reg = MetricsRegistry()
    reg.register("pipeline", lambda: {"pack_s": 1.5, "chunks": 3})
    reg.register("sched", lambda: {"aligner": {"occupancy_pct": 42.0}})
    snap = reg.snapshot()
    assert snap["pipeline"]["chunks"] == 3
    flat = reg.flat()
    assert flat["pipeline.pack_s"] == 1.5
    assert flat["sched.aligner.occupancy_pct"] == 42.0
    assert "pipeline.pack_s" in reg.table()
    p = str(tmp_path / "m.json")
    reg.dump(p)
    assert json.load(open(p))["pipeline"]["chunks"] == 3
    with pytest.raises(ValueError):
        reg.register("a.b", dict)


def test_polisher_metrics_namespaces(dataset):
    _, polisher = _polish(dataset, depth=2)
    snap = polisher.metrics.snapshot()
    for ns in ("pipeline", "resilience", "sched", "aligner"):
        assert ns in snap
    stats = polisher.stage_stats
    assert snap["pipeline"]["chunks"] == stats["chunks"]
    assert snap["resilience"]["quarantined"] == stats["quarantined"]
    # clean run: the whole resilience namespace is zero
    assert all(not v for v in snap["resilience"].values())
    flat = polisher.metrics.flat()
    assert flat["pipeline.pack_s"] == stats["pack_s"]


def test_metrics_env_dump(dataset, tmp_path, monkeypatch, capsys):
    path = str(tmp_path / "metrics.json")
    monkeypatch.setenv("RACON_TPU_METRICS", path)
    _polish(dataset, depth=2)
    snap = json.load(open(path))
    assert "pipeline" in snap and "resilience" in snap
    err = capsys.readouterr().err
    assert "end-of-run metrics" in err
    assert "pipeline.chunks" in err  # the stderr summary table


# ---------------------------------------------------------------- logging
def test_log_levels(capsys):
    ulog.set_log_level("quiet")
    ulog.log_info("INFO-LINE")
    ulog.log_debug("DEBUG-LINE")
    assert capsys.readouterr().err == ""
    ulog.set_log_level("info")
    ulog.log_info("INFO-LINE")
    ulog.log_debug("DEBUG-LINE")
    assert capsys.readouterr().err == "INFO-LINE\n"
    ulog.set_log_level("debug")
    ulog.log_info("INFO-LINE")
    ulog.log_debug("DEBUG-LINE")
    assert capsys.readouterr().err == "INFO-LINE\nDEBUG-LINE\n"


def test_log_level_env_resolution(monkeypatch):
    monkeypatch.setenv("RACON_TPU_LOG_LEVEL", "quiet")
    ulog.set_log_level(None)
    assert ulog.log_level() == ulog.QUIET
    monkeypatch.setenv("RACON_TPU_LOG_LEVEL", "bogus")
    ulog.set_log_level(None)
    assert ulog.log_level() == ulog.INFO  # typo falls back, never crashes


def test_warn_dedup_suppresses_repeats(capsys):
    ulog.set_log_level("info")
    for i in range(5):
        ulog.warn_dedup("site.key", f"warning text {i}")
    err = capsys.readouterr().err
    assert err == "warning text 0\n"  # first occurrence only
    ulog.flush_dedup()
    err = capsys.readouterr().err
    assert "repeated 4 more times" in err
    # flushed: state cleared, the next run warns afresh
    ulog.warn_dedup("site.key", "again")
    assert capsys.readouterr().err == "again\n"


def test_warn_dedup_debug_shows_all(capsys):
    ulog.set_log_level("debug")
    ulog.warn_dedup("k", "w1")
    ulog.warn_dedup("k", "w2")
    assert capsys.readouterr().err == "w1\nw2\n"
    ulog.flush_dedup()  # nothing suppressed at debug: no summary
    assert capsys.readouterr().err == ""


def test_logger_total_counts_open_section(capsys):
    ulog.set_log_level("info")
    lg = ulog.Logger()
    lg.log()  # open a section, no bar armed
    time.sleep(0.02)
    lg.total("total =")
    line = capsys.readouterr().err.strip()
    seconds = float(line.split()[-2])
    assert seconds >= 0.015  # used to report 0 with no active bar


def test_quiet_run_keeps_timing_totals(dataset, capsys):
    ulog.set_log_level("quiet")
    out, polisher = _polish(dataset, depth=2)
    assert capsys.readouterr().err == ""  # quiet really is silent
    assert out  # and the output is unaffected
    assert polisher.stage_stats["chunks"] >= 1


# -------------------------------------------------------------- CLI / misc
def test_cli_obs_flags_parse():
    from racon_tpu.cli import parse_args

    opts = parse_args(["--tpu-trace", "t.json", "--tpu-metrics=m.json",
                       "--tpu-log-level", "debug",
                       "--tpu-jax-profile", "prof", "a", "b", "c"])
    assert opts["tpu_trace"] == "t.json"
    assert opts["tpu_metrics"] == "m.json"
    assert opts["tpu_log_level"] == "debug"
    assert opts["tpu_jax_profile"] == "prof"
    assert opts["paths"] == ["a", "b", "c"]


def test_cli_obs_flags_in_help(capsys):
    from racon_tpu import cli

    assert cli.main(["--help"]) == 0
    out = capsys.readouterr().out
    for flag in ("--tpu-trace", "--tpu-metrics", "--tpu-log-level",
                 "--tpu-jax-profile"):
        assert flag in out


def test_jax_profile_noop_and_safe(monkeypatch, tmp_path):
    from racon_tpu.obs import jax_profile

    # unset: a null context
    monkeypatch.delenv("RACON_TPU_PROFILE", raising=False)
    with jax_profile():
        pass
    # set but profiler broken: still a silent no-op, never a crash
    monkeypatch.setenv("RACON_TPU_PROFILE", str(tmp_path / "prof"))
    import jax

    def broken(*a, **kw):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    with jax_profile():
        pass


# ------------------------------------------------ spans in a profiler capture
def _capture_spans(directory):
    """[(start_ns, end_ns, name without `racon.`, thread line, args)] of
    the one capture under `directory`, in start order."""
    import glob

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(trace.CAPTURE_PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name[len(trace.CAPTURE_PREFIX):], li,
                                dict(e.stats)))
    return sorted(out)


def _one(spans, name):
    got = [s for s in spans if s[2] == name]
    assert len(got) == 1, (name, got)
    return got[0]


def test_span_is_shared_null_when_nothing_records():
    assert not trace.capturing() and not trace.enabled()
    assert trace.span("x", k=1) is trace.span("y")
    with trace.span("x") as sp:
        sp.set(n=1)  # a no-op, never an error
    # timed() is live all the same: counters are charged from it
    with trace.timed("x", k=1) as sp:
        time.sleep(0.001)
    assert sp.t1 - sp.t0 >= 0.001


def test_live_span_records_its_own_endpoints(tmp_path):
    rec = trace.configure(str(tmp_path / "t.json"))
    with trace.span("outer", k=1) as sp:
        sp.set(n=2)
    ev, = [e for e in rec.events() if e["ph"] == "X"]
    assert ev["name"] == "outer" and ev["args"] == {"k": 1, "n": 2}
    assert ev["ts"] == pytest.approx((sp.t0 - rec._base) * 1e6, abs=0.01)
    assert ev["dur"] == pytest.approx((sp.t1 - sp.t0) * 1e6, abs=0.01)


def test_span_recorded_when_its_work_raises(tmp_path):
    import jax

    rec = trace.configure(str(tmp_path / "t.json"))
    with jax.profiler.trace(str(tmp_path / "prof")):
        with pytest.raises(ValueError):
            with trace.span("fails"):
                raise ValueError("boom")
        # the failed span left this thread's capture stack: a later
        # tag lands on nothing instead of on the dead span
        trace.tag(compile_s=1.0)
    assert [e["name"] for e in rec.events() if e["ph"] == "X"] == ["fails"]
    assert "compile_s" not in (rec.events()[-1].get("args") or {})
    assert _one(_capture_spans(tmp_path / "prof"), "fails")[4] == {}


def test_profiler_capture_holds_polisher_spans_nested_and_in_order(
        dataset, tmp_path):
    """No recorder armed: the capture alone switches the spans on, and
    the polisher's phases land in it nested and in order."""
    import jax

    assert trace.get_tracer() is None
    with jax.profiler.trace(str(tmp_path / "prof")):
        _polish(dataset, depth=2)
    spans = _capture_spans(tmp_path / "prof")
    init = _one(spans, "polisher.initialize")
    parts = [_one(spans, f"polisher.{n}") for n in (
        "load_targets", "load_reads", "load_overlaps", "align_overlaps",
        "build_windows")]
    for a, b in zip(parts, parts[1:]):
        assert a[1] <= b[0], (a[2], b[2])
    for p in parts:
        assert init[0] <= p[0] and p[1] <= init[1] and p[3] == init[3]
    align = parts[3]
    bp = _one(spans, "polisher.breaking_points")
    assert align[0] <= bp[0] and bp[1] <= align[1]
    consensus = _one(spans, "polisher.consensus")
    stitch = _one(spans, "polisher.stitch")
    assert init[1] <= consensus[0] and consensus[1] <= stitch[0]
    assert init[4]["windows"] > 0 and consensus[4]["engine"] == "host"
    # the pipeline's chunk spans, on their worker threads, fall inside
    # the consensus phase and carry their loop's arguments
    chunks = [s for s in spans if s[2].startswith("pipeline.")]
    assert {"pipeline.pack", "pipeline.device",
            "pipeline.unpack"} <= {s[2] for s in chunks}
    for s in chunks:
        assert consensus[0] <= s[0] and s[1] <= consensus[1]
        assert s[4]["loop"] == "host_poa"


def test_capture_and_recorder_see_the_same_spans(dataset, tmp_path):
    import jax

    rec = trace.configure(str(tmp_path / "t.json"))
    with jax.profiler.trace(str(tmp_path / "prof")):
        _polish(dataset, depth=2)
    chrome = sorted(e["name"] for e in rec.events() if e["ph"] == "X")
    captured = sorted(s[2] for s in _capture_spans(tmp_path / "prof"))
    assert chrome == captured and "polisher.stitch" in chrome


def test_span_sums_match_stage_stats_under_capture(dataset, tmp_path):
    """The counters and both sinks share each span's endpoints."""
    import jax

    with jax.profiler.trace(str(tmp_path / "prof")):
        _, polisher = _polish(dataset, depth=2)
    stats = polisher.stage_stats
    sums = {}
    for s0, s1, name, _, _ in _capture_spans(tmp_path / "prof"):
        if name.startswith("pipeline."):
            stage = name.split(".", 1)[1]
            sums[stage] = sums.get(stage, 0.0) + (s1 - s0) / 1e9
    for stage, key in (("pack", "pack_s"), ("device", "device_s"),
                       ("unpack", "unpack_s")):
        assert sums.get(stage, 0.0) == pytest.approx(
            stats[key], rel=0.05, abs=1e-3), stage


def test_compile_seconds_ride_the_open_dispatch_span(tmp_path):
    """A compile is charged after its dispatch returned: the Chrome
    recorder takes it as its own `xla.compile` span, a capture as an
    argument of the dispatch span still open."""
    import jax

    from racon_tpu.sched.telemetry import OccupancyStats

    rec = trace.configure(str(tmp_path / "t.json"))
    key = ("test_obs", time.perf_counter())
    with jax.profiler.trace(str(tmp_path / "prof")):
        with trace.span("pipeline.device", seg="dispatch"):
            assert OccupancyStats().record_compile_once("aligner", key,
                                                        1.25)
    disp = _one(_capture_spans(tmp_path / "prof"), "pipeline.device")
    assert disp[4] == {"seg": "dispatch", "compile_s": 1.25}
    names = [e["name"] for e in rec.events() if e["ph"] == "X"]
    assert sorted(names) == ["pipeline.device", "xla.compile"]


def test_concurrent_pipeline_spans_under_capture(tmp_path):
    """Five writer threads, each with its own capture stack: every
    stage span lands once, on the thread that ran it."""
    import jax

    from racon_tpu.pipeline import DispatchPipeline

    with jax.profiler.trace(str(tmp_path / "prof")):
        with DispatchPipeline(depth=2, fallback_workers=3) as pl:
            for _ in range(20):
                pl.submit_fallback(lambda: time.sleep(0.0005))
            pl.run(range(30), pack=lambda i: i, dispatch=lambda i, o: o,
                   wait=lambda h: h, unpack=lambda i, r: None,
                   label="t", describe=lambda i: {"i": i})
            pl.drain_fallback()
    counts, lines = {}, {}
    for _, _, name, line, args in _capture_spans(tmp_path / "prof"):
        counts[name] = counts.get(name, 0) + 1
        lines.setdefault(name, set()).add(line)
    assert counts == {"pipeline.pack": 30, "pipeline.device": 60,
                      "pipeline.unpack": 30, "pipeline.fallback": 20}
    assert len(lines["pipeline.pack"]) == 1
    assert lines["pipeline.pack"].isdisjoint(lines["pipeline.unpack"])


def test_aligner_chunk_spans_name_their_shape(tmp_path):
    from racon_tpu.ops.align import BatchAligner
    from racon_tpu.pipeline import DispatchPipeline

    rng = random.Random(5)
    pairs = []
    for n in (300, 320, 700):
        t = bytes(rng.choice(ACGT) for _ in range(n))
        pairs.append((_mutate(rng, t, 0.05), t))
    rec = trace.configure(str(tmp_path / "t.json"))
    with DispatchPipeline(depth=2) as pl:
        BatchAligner(band_width=64).align(pairs, pipeline=pl)
    events = [e for e in rec.events() if e["ph"] == "X"]
    plan = [e for e in events if e["name"] == "aligner.plan"]
    assert len(plan) == 1 and plan[0]["args"] == {"pairs": 3, "chunks": 2}
    chunk = [e["args"] for e in events if e["name"] == "pipeline.pack"]
    assert sorted((a["edge"], a["jobs"]) for a in chunk) == [(512, 2),
                                                            (1024, 1)]
    for a in chunk:
        assert a["band"] == 64 and a["kernel"] in ("xla", "pallas")
        assert a["lanes"] >= a["jobs"] and a["lane_cap"] >= a["lanes"]



@pytest.mark.parametrize("pack_bases", ["1", "0"])
def test_aligner_chunk_spans_say_whether_packed(tmp_path, monkeypatch,
                                                pack_bases):
    """`packed` on a chunk's spans: ACGT-only chunks ship 2-bit operands,
    a chunk with an N keeps int8 ones, and the knob turns packing off."""
    from racon_tpu.ops.align import BatchAligner
    from racon_tpu.pipeline import DispatchPipeline

    monkeypatch.setenv("RACON_TPU_PACK_BASES", pack_bases)
    rng = random.Random(6)
    pairs = []
    for n in (300, 700):
        t = bytes(rng.choice(ACGT) for _ in range(n))
        pairs.append((_mutate(rng, t, 0.05), t))
    q, t = pairs[1]
    pairs[1] = (q[:50] + b"N" + q[51:], t)
    rec = trace.configure(str(tmp_path / "t.json"))
    with DispatchPipeline(depth=2) as pl:
        BatchAligner(band_width=64).align(pairs, pipeline=pl)
    spans = [e["args"] for e in rec.events() if e["ph"] == "X"
             and e["name"] in ("pipeline.pack", "pipeline.device")]
    assert len(spans) == 6
    assert {(a["edge"], a["packed"]) for a in spans} == {
        (512, pack_bases == "1"), (1024, False)}

def test_jax_profile_one_capture_per_run(dataset, tmp_path, monkeypatch,
                                         capsys):
    """`--tpu-jax-profile` is one capture of the whole run: parsing,
    consensus and the stitch side by side, no per-phase directories."""
    from racon_tpu import cli

    prof = tmp_path / "prof"
    reads, paf, draft = dataset
    assert cli.main(["--tpu-jax-profile", str(prof), "-t", "2",
                     reads, paf, draft]) == 0
    assert capsys.readouterr().out.startswith(">")
    assert not (prof / "align").exists()
    assert not (prof / "consensus").exists()
    names = {s[2] for s in _capture_spans(prof)}
    assert {"polisher.load_targets", "polisher.consensus",
            "polisher.stitch"} <= names
    assert "RACON_TPU_PROFILE" not in os.environ  # restored after main
