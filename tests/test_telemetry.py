"""Serve-grade telemetry: histograms, Prometheus exposition, flight
recorder, SLO accounting, and the non-tty progress-bar pin.

The load-bearing contracts, in ISSUE order:

  - `obs.hist.Histogram` quantile estimates agree with exact numpy
    percentiles on known distributions (within the log-bucket bound),
    survive concurrent observers without losing counts, and merge
    exactly;
  - a live `scrape` during a running job returns Prometheus text a
    minimal parser accepts — cumulative buckets monotone, `+Inf` equals
    `_count` — with non-zero latency histogram buckets;
  - the metrics-flush error path (unwritable RACON_TPU_METRICS) and a
    scrape issued mid-drain never take the server down;
  - a fault-injected job produces a parseable flight-recorder dump whose
    pipeline span sums match the stage_stats snapshot embedded in it;
  - a job that finishes past its deadline counts as an SLO miss, dumps
    a flight artifact, and surfaces in `stats`' slo view;
  - the optional localhost HTTP endpoint serves the same scrape body;
  - a subprocess whose stderr is a pipe emits ONE progress line per
    phase (the BENCH_r05 per-tick bloat stays dead).
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from racon_tpu.obs import prom
from racon_tpu.obs.flight import FlightRecorder, dump, window_events
from racon_tpu.obs.hist import Histogram, HistogramSet
from racon_tpu.serve import PolishClient, PolishServer, make_synth_dataset
from racon_tpu.serve.client import JobFailed
from racon_tpu.serve.protocol import recv_frame, send_frame
from racon_tpu.serve.queue import Job, JobQueue


# ---------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    return make_synth_dataset(str(tmp_path_factory.mktemp("telem_data")))


@pytest.fixture(scope="module")
def server(dataset, tmp_path_factory):
    d = tmp_path_factory.mktemp("telem_srv")
    srv = PolishServer(socket_path=str(d / "s.sock"), workers=2,
                       flight_dir=str(d / "flight")).start()
    yield srv
    srv.drain(timeout=10)


@pytest.fixture(scope="module")
def client(server):
    return PolishClient(socket_path=server.config.socket_path)


# -------------------------------------------------------------- histograms
@pytest.mark.parametrize("sample", ["uniform", "lognormal"])
def test_histogram_quantiles_vs_numpy(sample):
    rng = np.random.default_rng(7)
    if sample == "uniform":
        values = rng.uniform(0.001, 10.0, 20000)
    else:
        values = rng.lognormal(mean=-2.0, sigma=1.5, size=20000)
    h = Histogram()
    for v in values:
        h.observe(float(v))
    assert h.count == len(values)
    assert h.sum == pytest.approx(values.sum(), rel=1e-9)
    assert h.min == pytest.approx(values.min())
    assert h.max == pytest.approx(values.max())  # max is EXACT
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(values, q * 100))
        est = h.quantile(q)
        # log buckets grow by 2**0.25 (~19%/bucket); the estimate is
        # inside the true value's bucket, so 20% relative is the bound
        assert est == pytest.approx(exact, rel=0.20), \
            f"{sample} p{int(q * 100)}: {est} vs exact {exact}"


def test_histogram_concurrent_observe():
    h = Histogram()
    n_threads, per_thread = 8, 5000

    def work(k):
        for i in range(per_thread):
            h.observe(0.001 * ((k * per_thread + i) % 100 + 1))

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * per_thread  # no lost increments
    le, cum = h.cumulative()[-1]
    assert le == float("inf") and cum == h.count
    assert sum(1 for _ in h.cumulative()) >= 10


def test_histogram_merge_exact():
    a, b, both = Histogram(), Histogram(), Histogram()
    rng = np.random.default_rng(3)
    for v in rng.uniform(0.01, 2.0, 500):
        a.observe(float(v))
        both.observe(float(v))
    for v in rng.lognormal(0.0, 1.0, 500):
        b.observe(float(v))
        both.observe(float(v))
    a.merge(b)
    assert a.count == both.count
    assert a.sum == pytest.approx(both.sum)
    assert a.max == both.max and a.min == both.min
    assert [c for _, c in a.cumulative()] == \
        [c for _, c in both.cumulative()]


def test_histogram_edge_cases():
    h = Histogram()
    assert h.snapshot() == {"count": 0}
    assert h.quantile(0.5) == 0.0
    h.observe(-1.0)   # clamped, not crashed
    h.observe(0.0)
    h.observe(1e9)    # overflow bucket
    assert h.count == 3
    assert h.max == 1e9
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["min"] == 0.0


# ---------------------------------------------------- prometheus rendering
def parse_prom(text: str) -> dict:
    """Minimal Prometheus text parser: {family: {"type": t, "samples":
    [(full_name, labels_dict, value)]}}. Asserts line-level syntax."""
    families: dict = {}
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)"
        r"(?:\s+#\s+\{[^}]*\}.*)?$")  # optional OpenMetrics exemplar
    cur = None
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split()
            cur = families.setdefault(name,
                                      {"type": typ, "samples": []})
            continue
        if line.startswith("#"):
            continue
        m = line_re.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = {}
        if labels_raw:
            for part in labels_raw[1:-1].split(","):
                k, v = part.split("=", 1)
                assert v.startswith('"') and v.endswith('"'), line
                labels[k] = v[1:-1]
        v = float("inf") if value == "+Inf" else float(value)
        base = re.sub(r"_(bucket|sum|count|total)$", "", name)
        fam = families.get(name) or families.get(base)
        assert fam is not None, f"sample before TYPE: {line!r}"
        fam["samples"].append((name, labels, v))
    return families


def check_histogram_family(fam: dict) -> int:
    """Cumulative-bucket invariants; returns the family's count."""
    assert fam["type"] == "histogram"
    buckets = [(lbl["le"], v) for n, lbl, v in fam["samples"]
               if n.endswith("_bucket")]
    count = [v for n, _, v in fam["samples"] if n.endswith("_count")]
    assert buckets and len(count) == 1
    cums = [v for _, v in buckets]
    assert cums == sorted(cums), "buckets not cumulative"
    assert buckets[-1][0] == "+Inf"
    assert cums[-1] == count[0], "+Inf bucket != count"
    return int(count[0])


def test_prom_render_parseable():
    hs = HistogramSet()
    for v in (0.01, 0.1, 0.1, 5.0):
        hs.observe("job.latency", v)
    text = prom.render(
        counters={"serve.jobs.completed": 4,
                  "serve.jobs.failed": (1, "jobs that failed")},
        gauges={"serve.inflight": 2, "serve.draining": False},
        hists=hs)
    fams = parse_prom(text)
    assert fams["racon_tpu_serve_jobs_completed_total"]["type"] == \
        "counter"
    assert fams["racon_tpu_serve_inflight"]["type"] == "gauge"
    n = check_histogram_family(fams["racon_tpu_job_latency_seconds"])
    assert n == 4
    sums = [v for name, _, v in
            fams["racon_tpu_job_latency_seconds"]["samples"]
            if name.endswith("_sum")]
    assert sums[0] == pytest.approx(5.21)


def test_prom_histogram_consistent_under_concurrent_observe():
    """The scrape body must satisfy bucket{le="+Inf"} == _count even
    while another thread keeps observing — one atomic export per
    histogram, not three racing reads."""
    hs = HistogramSet()
    hs.observe("x", 0.01)
    stop = threading.Event()

    def observer():
        i = 0
        while not stop.is_set():
            hs.observe("x", 0.001 * (i % 50 + 1))
            i += 1

    t = threading.Thread(target=observer)
    t.start()
    try:
        for _ in range(200):
            fams = parse_prom(prom.render(hists=hs))
            check_histogram_family(fams["racon_tpu_x_seconds"])
    finally:
        stop.set()
        t.join()


def test_nearest_rank_percentiles():
    from racon_tpu.serve.queue import nearest_rank

    vals = list(range(1, 101))  # ranks 1..100
    assert nearest_rank(vals, 0.99) == 99  # NOT the max
    assert nearest_rank(vals, 0.95) == 95
    assert nearest_rank(vals, 0.50) == 50
    assert nearest_rank(vals, 1.00) == 100
    assert nearest_rank([5.0], 0.99) == 5.0
    assert nearest_rank([1, 2], 0.50) == 1


# --------------------------------------------------------- flight recorder
def test_flight_ring_bounded():
    rec = FlightRecorder(capacity=16)
    for i in range(200):
        rec.complete(f"span{i}", 0.0, 0.001)
    events = [e for e in rec.events() if e["ph"] != "M"]
    assert len(events) == 16  # ring evicted the oldest 184
    names = [e["name"] for e in events]
    assert names[-1] == "span199" and names[0] == "span184"


def test_flight_constant_memory_across_thread_churn():
    """A long-lived server spawns fresh pipeline threads per job; the
    recorder must not retain one buffer (or one track id) per dead
    thread — rings and tracks both stay bounded."""
    rec = FlightRecorder(capacity=64)

    def job(k):
        for i in range(50):
            rec.complete("pipeline.pack", 0.0, 0.001, {"k": k})

    for wave in range(20):  # 100 short-lived threads, 5 repeating names
        threads = [threading.Thread(target=job, args=(wave,),
                                    name=f"racon-tpu-worker-{i}")
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(rec._buffers) == 1          # ONE shared ring, ever
    assert len(rec._threads) == 5          # tracks keyed by name
    events = rec.events()
    assert len([e for e in events if e["ph"] != "M"]) == 64
    assert len([e for e in events if e["ph"] == "M"]) == 5


def test_scoped_trace_tees_into_flight_ring():
    """A per-job scoped trace must not blind the always-on flight ring:
    spans recorded during the scope land in BOTH recorders."""
    from racon_tpu.obs import trace as obs_trace

    flight = obs_trace.install(FlightRecorder(capacity=64))
    try:
        with obs_trace.scoped() as rec:
            obs_trace.get_tracer().complete("during.scope", 0.0, 0.001)
            with obs_trace.span("via.module"):
                pass
        scoped_names = {e["name"] for e in rec.events()
                        if e["ph"] != "M"}
        ring_names = {e["name"] for e in flight.events()
                      if e["ph"] != "M"}
        assert {"during.scope", "via.module"} <= scoped_names
        assert {"during.scope", "via.module"} <= ring_names
        assert obs_trace.get_tracer() is flight  # restored on exit
    finally:
        obs_trace.reset()


def test_flight_window_and_dump(tmp_path):
    rec = FlightRecorder()
    t0 = time.perf_counter()
    rec.complete("early", t0, t0 + 0.001)
    cut = time.perf_counter()
    rec.complete("late", cut + 0.001, cut + 0.002)
    kept = window_events(rec, since=cut)
    names = {e["name"] for e in kept if e["ph"] != "M"}
    assert names == {"late"}
    assert any(e["ph"] == "M" for e in kept)  # thread meta preserved
    path = str(tmp_path / "dump.json")
    dump(rec, path, since=cut, flight={"job_id": "j1", "reason": "test"})
    doc = json.load(open(path))
    assert doc["flight"]["job_id"] == "j1"
    assert {e["name"] for e in doc["traceEvents"]
            if e["ph"] != "M"} == {"late"}


# ----------------------------------------------------------- SLO (queue)
def test_queue_slo_hit_and_miss_accounting():
    q = JobQueue(maxsize=4)
    hit = Job("h", "s", "o", "t", {}, deadline_s=30.0)
    q.submit(hit)
    assert q.pop(timeout=0.5) is hit
    assert q.task_done(hit, True, 0.01) is False
    miss = Job("m", "s", "o", "t", {}, deadline_s=0.01)
    q.submit(miss)
    job = q.pop(timeout=0.5)
    if job is not None:  # raced past the deadline -> consumed as expired
        time.sleep(0.02)
        assert q.task_done(job, True, 0.02) is True
        assert q.counters["deadline_miss"] == 1
    assert q.counters["deadline_hit"] == 1
    snap = q.snapshot()
    assert snap["recent"]["jobs"] >= 1
    assert snap["recent"]["p50_s"] >= 0


# ------------------------------------------------------- live serve scrape
def test_scrape_during_running_job_nonzero_latency(client, dataset,
                                                   server):
    """The acceptance gate: Prometheus text mid-job, parseable, with
    populated latency histogram buckets."""
    done = threading.Event()
    result: list = [None]

    def go():
        try:
            result[0] = client.submit(*dataset)
        finally:
            done.set()

    t = threading.Thread(target=go)
    t.start()
    texts = [client.scrape()]
    while not done.is_set() and len(texts) < 500:
        texts.append(client.scrape())
    t.join(timeout=60)
    assert result[0] is not None
    fams = parse_prom(texts[-1])
    hist_fams = {n: f for n, f in fams.items()
                 if f["type"] == "histogram"}
    assert hist_fams, "no histograms in scrape"
    populated = {n: check_histogram_family(f)
                 for n, f in hist_fams.items()}
    assert any(c > 0 for c in populated.values()), populated
    # the load-bearing families are present by name
    for want in ("racon_tpu_pipeline_pack_seconds",
                 "racon_tpu_job_queue_wait_seconds",
                 "racon_tpu_serve_iteration_seconds"):
        assert want in fams, sorted(hist_fams)
    assert check_histogram_family(
        fams["racon_tpu_serve_iteration_seconds"]) > 0


def test_scrape_rpc_matches_http(dataset, tmp_path):
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, metrics_port=0,
                       flight_dir=str(tmp_path / "fl")).start()
    try:
        import urllib.error
        import urllib.request

        assert srv.config.metrics_port > 0  # ephemeral port published
        cl = PolishClient(socket_path=srv.config.socket_path)
        cl.submit(*dataset)
        url = f"http://127.0.0.1:{srv.config.metrics_port}"
        body = urllib.request.urlopen(f"{url}/metrics",
                                      timeout=10).read().decode()
        fams_http = parse_prom(body)
        fams_rpc = parse_prom(cl.scrape())
        assert set(fams_http) == set(fams_rpc)
        health = json.loads(urllib.request.urlopen(
            f"{url}/healthz", timeout=10).read())
        assert health["ok"] is True and health["draining"] is False
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/nope", timeout=10)
        # the polish server is untouched by HTTP traffic
        assert cl.ping()["type"] == "pong"
    finally:
        srv.drain(timeout=10)


def test_scrape_during_drain_and_unwritable_metrics(dataset, tmp_path,
                                                    monkeypatch):
    """Neither an unwritable RACON_TPU_METRICS path nor a scrape issued
    mid-drain may take the server down."""
    monkeypatch.setenv("RACON_TPU_METRICS",
                       str(tmp_path / "no_such_dir" / "m.json"))
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, workers=1,
                       flight_dir=str(tmp_path / "fl")).start()
    cl = PolishClient(socket_path=srv.config.socket_path)
    cl.submit(*dataset)  # something worth flushing
    # pre-open a connection: drain closes the listener immediately, but
    # established connections are served until the drain completes
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(10.0)
    sock.connect(srv.config.socket_path)
    try:
        # an in-flight job with an injected hang keeps the drain open
        # long enough to scrape INTO it deterministically
        slow_result: list = [None]

        def go():
            try:
                slow_result[0] = cl.submit(
                    *dataset, fault_plan="device:chunk=0:hang=0.5")
            except Exception as exc:  # noqa: BLE001 — asserted below
                slow_result[0] = exc
        slow = threading.Thread(target=go)
        slow.start()
        deadline = time.monotonic() + 10
        while (srv.queue.counters["admitted"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)
        drainer = threading.Thread(target=srv.drain, kwargs={
            "timeout": 30})
        drainer.start()
        while not srv._draining.is_set():
            time.sleep(0.005)
        send_frame(sock, {"type": "scrape"})
        resp = recv_frame(sock)
        assert resp["type"] == "metrics"
        parse_prom(resp["text"])
        slow.join(timeout=30)
        drainer.join(timeout=30)
        assert srv._stopped.is_set()  # drained cleanly despite both
        assert not isinstance(slow_result[0], Exception), slow_result
    finally:
        sock.close()
    assert not os.path.exists(str(tmp_path / "no_such_dir"))


# ------------------------------------------------- flight dumps on failure
def test_failed_job_flight_dump_spans_match_stats(dataset, tmp_path):
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, workers=1,
                       flight_dir=str(tmp_path / "flight")).start()
    try:
        cl = PolishClient(socket_path=srv.config.socket_path)
        with pytest.raises(JobFailed) as exc_info:
            cl.submit(*dataset, fault_plan="unpack:chunk=0:corrupt",
                      strict=True)
        assert exc_info.value.error_type == "ChunkCorrupt"
        dumps = cl.debug()["dumps"]
        assert len(dumps) == 1 and "job-failed" in dumps[0]
        doc = json.load(open(dumps[0]))
        flight = doc["flight"]
        assert flight["reason"] == "job-failed"
        assert flight["error_type"] == "ChunkCorrupt"
        stats = flight["stage_stats"]
        assert stats["faults"] == 1
        assert stats["pack_s"] > 0  # chunk 0 packed before the poison
        # span sums pin to the embedded stage stats: same perf_counter
        # endpoints, so only serialization rounding separates them
        sums: dict = {}
        for ev in doc["traceEvents"]:
            for field in ("name", "ph", "pid", "tid"):
                assert field in ev
            if ev["ph"] == "X" and ev["name"].startswith("pipeline."):
                stage = ev["name"].split(".", 1)[1]
                sums[stage] = sums.get(stage, 0.0) + ev["dur"] / 1e6
        for stage, key in (("pack", "pack_s"), ("device", "device_s"),
                           ("unpack", "unpack_s"),
                           ("fallback", "fallback_s")):
            assert sums.get(stage, 0.0) == pytest.approx(
                stats[key], rel=0.05, abs=1e-3), \
                f"{stage}: {sums.get(stage)} vs {stats[key]}"
        # the server survives and the ring keeps recording
        assert cl.ping()["type"] == "pong"
        # the FAILED job's latency observations reached the lifetime
        # scrape view — p99s must not be built from healthy jobs only
        fams = parse_prom(cl.scrape())
        assert check_histogram_family(
            fams["racon_tpu_pipeline_pack_seconds"]) > 0
    finally:
        srv.drain(timeout=10)


def test_deadline_miss_counts_and_dumps(dataset, tmp_path):
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, workers=1,
                       flight_dir=str(tmp_path / "flight")).start()
    try:
        cl = PolishClient(socket_path=srv.config.socket_path)
        # the injected hang holds the job well past its deadline while
        # the idle worker pops it immediately: deterministic MISS (the
        # job still completes — distinct from expired-in-queue)
        r = cl.submit(*dataset, deadline_s=0.3,
                      fault_plan="device:chunk=0:hang=0.8")
        assert r.fasta  # ran to completion, late
        snap = cl.stats()
        assert snap["slo"]["deadline_miss"] == 1
        assert snap["slo"]["miss_rate"] == 1.0
        dumps = snap["flight"]["dumps"]
        assert len(dumps) == 1 and "deadline-miss" in dumps[0]
        doc = json.load(open(dumps[0]))
        assert doc["flight"]["reason"] == "deadline-miss"
        # an on-time job counts as a hit against the same numbers
        cl.submit(*dataset, deadline_s=60.0)
        snap = cl.stats()
        assert snap["slo"]["deadline_hit"] == 1
        assert snap["slo"]["miss_rate"] == 0.5
        assert snap["slo"]["recent"]["jobs"] == 2
    finally:
        srv.drain(timeout=10)


def test_invalid_metrics_port_rejected(monkeypatch):
    from racon_tpu.errors import RaconError
    from racon_tpu.serve import ServeConfig

    monkeypatch.setenv("RACON_TPU_SERVE_METRICS_PORT", "8o80")  # typo
    with pytest.raises(RaconError):
        ServeConfig()
    monkeypatch.delenv("RACON_TPU_SERVE_METRICS_PORT")
    with pytest.raises(RaconError):
        ServeConfig(metrics_port=-2)
    assert ServeConfig(metrics_port=0).metrics_port == 0
    assert ServeConfig().metrics_port is None


def test_debug_rpc_returns_ring(client, dataset):
    client.submit(*dataset)
    d = client.debug()
    assert d["type"] == "debug"
    assert d["flight_installed"]
    names = {e["name"] for e in d["events"]}
    assert any(n.startswith("pipeline.") for n in names), names
    capped = client.debug(max_events=5)
    assert len([e for e in capped["events"] if e["ph"] != "M"]) <= 5


def test_job_latency_namespace_in_polisher_metrics(dataset):
    from racon_tpu.core.polisher import PolisherType, create_polisher

    p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    p.initialize()
    p.polish()
    snap = p.metrics.snapshot()
    assert snap["latency"]["phase.consensus"]["count"] == 1
    assert snap["latency"]["phase.initialize"]["p50"] > 0
    assert snap["latency"]["pipeline.pack"]["count"] >= 1
    # ONE device sample per chunk (dispatch + wait summed), so the
    # device distribution is comparable with the other stages
    assert snap["latency"]["pipeline.device"]["count"] == \
        p.stage_stats["chunks"]
    flat = p.metrics.flat()
    assert "latency.phase.consensus.p99" in flat


# ----------------------------------------------- durable serve journal
def test_journal_rotation_and_reader(tmp_path):
    from racon_tpu.obs.journal import Journal, read_journal

    p = str(tmp_path / "j.jsonl")
    j = Journal(p, max_bytes=600)
    for i in range(60):
        j.record("tick", job=f"j{i}", i=i)
    assert j.events == 60 and j.dropped == 0
    j.close()
    assert os.path.exists(p + ".1")  # rotated exactly one generation
    assert os.path.getsize(p) <= 600
    entries = read_journal(p)
    assert entries, "reader lost everything"
    seq = [e["i"] for e in entries]
    # both generations read in order: a contiguous most-recent suffix
    assert seq == list(range(seq[0], 60))
    assert all(e["event"] == "tick" and "t" in e for e in entries)


def test_journal_stage_preserves_order(tmp_path):
    """stage() (the under-queue-lock path) keeps its relative order
    against later record() writes, and close() drains the tail."""
    from racon_tpu.obs.journal import Journal, read_journal

    p = str(tmp_path / "j.jsonl")
    j = Journal(p)
    j.record("received", job="a")
    j.stage("admitted", job="a")       # no disk I/O here
    j.record("started", job="a")       # drains the staged line first
    j.stage("admitted", job="b")
    j.close()                          # drains the tail
    events = [(e["job"], e["event"]) for e in read_journal(p)]
    assert events == [("a", "received"), ("a", "admitted"),
                      ("a", "started"), ("b", "admitted")]
    assert j.events == 4 and j.dropped == 0


def test_journal_consistency_checker():
    from racon_tpu.obs.journal import check_consistency

    def ev(event, job):
        return {"t": 0.0, "event": event, "job": job}

    ok = [ev("received", "a"), ev("admitted", "a"), ev("started", "a"),
          ev("finished", "a"),
          ev("received", "b"), ev("rejected-full", "b"),
          ev("received", "c"), ev("admitted", "c"), ev("expired", "c"),
          {"t": 0.0, "event": "serve-start"}]
    assert check_consistency(ok) == []
    # started but no terminal
    assert check_consistency([ev("received", "x"), ev("started", "x")])
    # two terminal states
    assert check_consistency(
        [ev("started", "x"), ev("finished", "x"), ev("failed", "x")])
    # finished without started, full lifecycle visible
    assert check_consistency([ev("received", "x"), ev("finished", "x")])
    # rotation cut the head: finished-without-started is NOT flagged
    # when `received` fell outside the window
    assert check_consistency([ev("finished", "x")]) == []


def test_serve_journal_lifecycle(dataset, tmp_path):
    from racon_tpu.obs.journal import check_consistency, read_journal

    jp = str(tmp_path / "journal.jsonl")
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, workers=1, journal=jp,
                       flight_dir=str(tmp_path / "fl")).start()
    try:
        cl = PolishClient(socket_path=srv.config.socket_path)
        ok_job = cl.submit(*dataset, trace_id="tid-journal")
        with pytest.raises(JobFailed):
            cl.submit(*dataset, fault_plan="unpack:chunk=0:corrupt",
                      strict=True)
        late = cl.submit(*dataset, deadline_s=0.3,
                         fault_plan="device:chunk=0:hang=0.8")
        assert late.fasta
    finally:
        srv.drain(timeout=15)
    entries = read_journal(jp)
    assert check_consistency(entries) == []
    events = [e["event"] for e in entries]
    assert events[0] == "serve-start" and events[-1] == "serve-stop"
    assert "drain" in events
    by_job: dict = {}
    for e in entries:
        if e.get("job"):
            by_job.setdefault(e["job"], []).append(e)
    assert len(by_job) == 3
    ok_events = [e["event"] for e in by_job[ok_job.job_id]]
    assert ok_events == ["received", "admitted", "started",
                         "part-streamed", "iterations", "finished"]
    # the trace id rides every line of its job
    assert all(e.get("trace") == "tid-journal"
               for e in by_job[ok_job.job_id])
    failed = next(evs for evs in by_job.values()
                  if any(e["event"] == "failed" for e in evs))
    assert next(e for e in failed if e["event"] == "failed")[
        "error_type"] == "ChunkCorrupt"
    missed = next(evs for evs in by_job.values()
                  if any(e["event"] == "deadline-miss" for e in evs))
    assert [e["event"] for e in missed][-1] == "finished"


def test_bad_flight_dir_or_journal_fails_start(tmp_path):
    from racon_tpu.errors import RaconError

    not_a_dir = tmp_path / "file"
    not_a_dir.write_text("x")
    with pytest.raises(RaconError, match="flight"):
        PolishServer(socket_path=str(tmp_path / "a.sock"),
                     warmup=False, flight_dir=str(not_a_dir)).start()
    with pytest.raises(RaconError, match="journal"):
        PolishServer(socket_path=str(tmp_path / "b.sock"),
                     warmup=False, flight_dir=str(tmp_path / "fl"),
                     journal=str(tmp_path / "missing" / "j.jsonl")
                     ).start()


def test_flight_dir_env_resolution(monkeypatch, tmp_path):
    from racon_tpu.serve import ServeConfig

    monkeypatch.delenv("RACON_TPU_SERVE_FLIGHT_DIR", raising=False)
    monkeypatch.setenv("RACON_TPU_FLIGHT_DIR", str(tmp_path / "proc"))
    assert ServeConfig().flight_dir == str(tmp_path / "proc")
    monkeypatch.setenv("RACON_TPU_SERVE_FLIGHT_DIR",
                       str(tmp_path / "serve"))
    assert ServeConfig().flight_dir == str(tmp_path / "serve")
    assert ServeConfig(flight_dir="").flight_dir == ""  # kwarg wins
    assert ServeConfig(flight_dir="/x").flight_dir_explicit
    monkeypatch.delenv("RACON_TPU_SERVE_FLIGHT_DIR")
    monkeypatch.delenv("RACON_TPU_FLIGHT_DIR")
    cfg = ServeConfig()
    assert cfg.flight_dir == "/tmp/racon_tpu_flight"
    # the built-in default is NOT strict-validated at startup: a plain
    # `racon_tpu serve` keeps the PR-6 best-effort-per-dump posture
    assert not cfg.flight_dir_explicit


def test_scrape_restart_and_queue_gauges(client, server):
    """The restart-detection series: uptime + wall-clock start time,
    plus the live queue-depth gauges."""
    fams = parse_prom(client.scrape())
    for name in ("racon_tpu_serve_uptime_seconds",
                 "racon_tpu_serve_start_time_seconds",
                 "racon_tpu_serve_queue_depth",
                 "racon_tpu_serve_queue_oldest_wait_seconds"):
        assert name in fams, sorted(fams)
        assert fams[name]["type"] == "gauge"
    start = fams["racon_tpu_serve_start_time_seconds"]["samples"][0][2]
    assert abs(start - time.time()) < 3600  # wall clock, recent
    uptime = fams["racon_tpu_serve_uptime_seconds"]["samples"][0][2]
    assert 0 < uptime < 3600


def test_obsreport_tool(dataset, tmp_path):
    """tools/obsreport.py renders the journal alongside flight dumps
    and its --check passes on a consistent journal."""
    jp = str(tmp_path / "journal.jsonl")
    fl = str(tmp_path / "flight")
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, workers=1, journal=jp,
                       flight_dir=fl).start()
    try:
        cl = PolishClient(socket_path=srv.config.socket_path)
        ok_job = cl.submit(*dataset)
        with pytest.raises(JobFailed):
            cl.submit(*dataset, fault_plan="unpack:chunk=0:corrupt",
                      strict=True)
    finally:
        srv.drain(timeout=15)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obsreport.py"),
         "--journal", jp, "--flight-dir", fl, "--check"],
        env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert f"job {ok_job.job_id}" in proc.stdout
    assert "finished" in proc.stdout and "failed" in proc.stdout
    assert "flight dump:" in proc.stdout  # the failed job's artifact
    assert "consistency: OK" in proc.stdout


# --------------------------------------------- progress bars through pipes
def test_bar_subprocess_pipe_one_line_per_phase():
    """The BENCH_r05 bloat pin: a subprocess whose stderr is a PIPE (the
    bench.py / servebench capture shape) must emit exactly ONE completion
    line per phase — no per-tick redraws, no carriage returns even after
    text-mode universal-newline translation."""
    code = (
        "import sys\n"
        "from racon_tpu.utils.logger import Logger\n"
        "lg = Logger()\n"
        "for phase in ('one', 'two'):\n"
        "    lg.log()\n"
        "    lg.bar_total(40)\n"
        "    for _ in range(40):\n"
        "        lg.bar('[phase] ' + phase)\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "\r" not in proc.stderr
    lines = proc.stderr.splitlines()
    assert len(lines) == 2, lines  # ONE line per phase, not one per 5%
    for phase, line in zip(("one", "two"), lines):
        assert line.startswith(
            f"[phase] {phase} [====================] 100% ")
