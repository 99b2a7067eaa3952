"""Serve-layer tests: frame protocol, job queue, cross-job batching
identity, per-job failure isolation, graceful drain, warm polisher
reuse, and the TTY-aware progress bars.

The load-bearing contracts, in the order the ISSUE states them:

  - a submitted job's polished FASTA is byte-identical to the one-shot
    path, INCLUDING when a second concurrent job shares its device
    batches (per-window consensus is batch-composition-independent);
  - malformed frames (truncated / oversized / garbage) produce typed
    error responses and never take the server or the connection down;
  - full-queue admission rejects carry `retry_after`; deadline-expired
    jobs are cancelled and counted;
  - a fault-plan-poisoned job fails with a typed error while the server
    survives and completes a subsequent clean job;
  - drain finishes in-flight jobs (the SIGTERM path is exercised in a
    real subprocess, marked slow).
"""

from __future__ import annotations

import io
import os
import socket
import struct
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from racon_tpu.core.polisher import PolisherType, create_polisher
from racon_tpu.serve import (PolishClient, PolishServer, WindowBatcher,
                             make_synth_dataset)
from racon_tpu.serve.client import JobFailed, ServeError
from racon_tpu.serve.protocol import (MAGIC, FrameGarbage, FrameTooLarge,
                                      FrameTruncated, recv_frame,
                                      send_frame)
from racon_tpu.serve.queue import Draining, Job, JobQueue, QueueFull


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    return make_synth_dataset(str(tmp_path_factory.mktemp("serve_data")))


def polish_solo(paths, **kw) -> bytes:
    p = create_polisher(*paths, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2, **kw)
    p.initialize()
    return b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                    for s in p.polish())


@pytest.fixture(scope="module")
def solo_bytes(dataset):
    return polish_solo(dataset)


@pytest.fixture(scope="module")
def server(dataset, tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("serve_sock") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=2).start()
    yield srv
    srv.drain(timeout=10)


@pytest.fixture(scope="module")
def client(server):
    return PolishClient(socket_path=server.config.socket_path)


# --------------------------------------------------------- frame protocol
def _pair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


def test_frame_roundtrip():
    a, b = _pair()
    try:
        send_frame(a, {"type": "ping", "blob": "é" * 10})
        assert recv_frame(b) == {"type": "ping", "blob": "é" * 10}
        a.close()
        assert recv_frame(b) is None  # clean EOF between frames
    finally:
        b.close()


def test_frame_truncated_mid_payload():
    a, b = _pair()
    try:
        a.sendall(struct.pack(">4sI", MAGIC, 100) + b"only-ten..")
        a.close()
        with pytest.raises(FrameTruncated):
            recv_frame(b)
    finally:
        b.close()


def test_frame_truncated_mid_header():
    a, b = _pair()
    try:
        a.sendall(b"RT")
        a.close()
        with pytest.raises(FrameTruncated):
            recv_frame(b)
    finally:
        b.close()


def test_frame_oversized_drains_and_stream_survives():
    a, b = _pair()
    try:
        big = b"x" * 4096
        a.sendall(struct.pack(">4sI", MAGIC, len(big)) + big)
        send_frame(a, {"type": "ping"})
        with pytest.raises(FrameTooLarge):
            recv_frame(b, max_frame=1024)
        # the oversized payload was drained: the next frame parses
        assert recv_frame(b, max_frame=1024) == {"type": "ping"}
    finally:
        a.close()
        b.close()


def test_frame_garbage_payload_keeps_stream():
    a, b = _pair()
    try:
        bad = b"{this is not json"
        a.sendall(struct.pack(">4sI", MAGIC, len(bad)) + bad)
        send_frame(a, {"ok": 1})
        with pytest.raises(FrameGarbage) as exc_info:
            recv_frame(b)
        assert exc_info.value.resync
        assert recv_frame(b) == {"ok": 1}
    finally:
        a.close()
        b.close()


def test_frame_bad_magic_desyncs():
    a, b = _pair()
    try:
        a.sendall(b"GET / HTTP/1.1\r\n\r\n" + b" " * 16)
        with pytest.raises(FrameGarbage) as exc_info:
            recv_frame(b)
        assert not exc_info.value.resync
    finally:
        a.close()
        b.close()


def test_frame_non_object_payload_rejected():
    a, b = _pair()
    try:
        payload = b"[1,2,3]"
        a.sendall(struct.pack(">4sI", MAGIC, len(payload)) + payload)
        with pytest.raises(FrameGarbage):
            recv_frame(b)
    finally:
        a.close()
        b.close()


# -------------------------------------------------------------- job queue
def _job(i, priority=0, deadline_s=None):
    return Job(f"j{i}", "s", "o", "t", {}, priority=priority,
               deadline_s=deadline_s)


def test_queue_full_reject_carries_retry_after():
    q = JobQueue(maxsize=2, workers=1)
    q.submit(_job(0))
    q.submit(_job(1))
    with pytest.raises(QueueFull) as exc_info:
        q.submit(_job(2))
    assert exc_info.value.retry_after > 0
    assert q.counters["rejected_full"] == 1
    assert q.counters["admitted"] == 2


def test_queue_fifo_within_priority():
    q = JobQueue(maxsize=8)
    q.submit(_job(0, priority=0))
    q.submit(_job(1, priority=0))
    q.submit(_job(2, priority=5))
    q.submit(_job(3, priority=5))
    order = [q.pop(timeout=0.1).id for _ in range(4)]
    assert order == ["j2", "j3", "j0", "j1"]


def test_queue_deadline_expired_cancelled_and_counted():
    q = JobQueue(maxsize=8)
    expired = _job(0, deadline_s=0.01)
    q.submit(expired)
    q.submit(_job(1))
    time.sleep(0.05)
    job = q.pop(timeout=0.5)
    assert job.id == "j1"  # the expired job was consumed, not returned
    assert q.counters["expired"] == 1
    assert expired.event.is_set()
    assert expired.response["code"] == "deadline-expired"


def test_queue_drain_stops_admission():
    q = JobQueue(maxsize=8)
    q.submit(_job(0))
    q.drain()
    with pytest.raises(Draining):
        q.submit(_job(1))
    # queued work still flows out
    assert q.pop(timeout=0.1).id == "j0"
    assert q.counters["rejected_draining"] == 1


# ----------------------------------------------- continuous batching
def _pool_jobs(srv, cl, dataset, n, admitted_before=0, **submit_kw):
    """Submit `n` jobs with the feeder HELD so all their windows pool,
    then release — every job's windows share the next iteration(s).
    Returns the joined results."""
    srv.batcher.hold()
    try:
        results = [None] * n

        def go(i):
            results[i] = cl.submit(*dataset, **submit_kw)

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while (srv.queue.counters["admitted"] < admitted_before + n
               and time.monotonic() < deadline):
            time.sleep(0.005)
        # admitted != pooled: give the workers a beat to run initialize
        # and enqueue their windows behind the held feeder
        time.sleep(0.5)
    finally:
        srv.batcher.release()
    for t in threads:
        t.join(timeout=60)
    return results


def test_cross_job_iteration_byte_identical(dataset, solo_bytes,
                                            tmp_path_factory):
    """Two concurrent jobs' windows merged into SHARED device
    iterations produce exactly the solo-run bytes each (the feeder is
    held until both jobs pooled, making the merge deterministic)."""
    sock = str(tmp_path_factory.mktemp("merge") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=2,
                       warmup=False).start()
    try:
        cl = PolishClient(socket_path=sock)
        results = _pool_jobs(srv, cl, dataset, 2)
        for r in results:
            assert r is not None
            assert r.fasta == solo_bytes
            assert r.serve["batch"]["shared_iterations"] >= 1
            assert not r.serve["batch"]["solo"]
        assert srv.batcher.counters["shared_iterations"] >= 1
        assert srv.batcher.counters["max_jobs_in_iteration"] == 2
    finally:
        srv.drain(timeout=10)


def test_late_job_joins_next_iteration_not_a_round(dataset, solo_bytes,
                                                   tmp_path_factory):
    """The round barrier is gone: with a small iteration bound, one
    job's windows spread over SEVERAL iterations — the continuous
    feeder dispatches bounded batches instead of one all-or-nothing
    round, which is exactly what lets a late job join mid-flight."""
    sock = str(tmp_path_factory.mktemp("iter") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=2, warmup=False,
                       iteration_windows=2).start()
    try:
        cl = PolishClient(socket_path=sock)
        r = cl.submit(*dataset)
        assert r.fasta == solo_bytes
        assert r.serve["batch"]["iterations"] >= 2
        assert len(r.serve["batch"]["iteration_ids"]) == \
            r.serve["batch"]["iterations"]
    finally:
        srv.drain(timeout=10)


def test_cross_job_identity_worker_lanes2(dataset, solo_bytes,
                                          tmp_path_factory):
    """THE worker-lanes acceptance pin (serve half): a --worker-lanes 2
    server — device list partitioned into two sub-mesh lanes, each with
    its own feeder — still produces exactly the solo-run bytes for
    concurrent jobs, streamed parts included."""
    sock = str(tmp_path_factory.mktemp("lanes") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=2, worker_lanes=2,
                       warmup=False).start()
    try:
        assert srv.batcher.worker_lanes == 2
        cl = PolishClient(socket_path=sock)
        results = _pool_jobs(srv, cl, dataset, 2)
        for r in results:
            assert r is not None
            assert r.fasta == solo_bytes
        # streamed submit on the lanes server: parts concat identical
        parts: list = []
        assert cl.submit(*dataset,
                         on_part=parts.append).fasta == solo_bytes
        assert b"".join(p["fasta"].encode("latin-1")
                        for p in parts) == solo_bytes
        snap = srv.batcher.snapshot()
        assert snap["worker_lanes"] == 2
        assert len(snap["lanes"]) == 2
        assert {ln["n_devices"] for ln in snap["lanes"]} == {4}
        assert sum(ln["iterations"] for ln in snap["lanes"]) == \
            snap["iterations"]
    finally:
        srv.drain(timeout=10)


def test_worker_lanes_isolation_job_fails_alone(dataset, solo_bytes,
                                                tmp_path_factory):
    """Lane-level fault isolation: a strict fault-plan job runs SOLO on
    one lane and fails typed, while a concurrent clean job (on the
    other lane) returns byte-identical output and the server survives."""
    sock = str(tmp_path_factory.mktemp("lanefault") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=2, worker_lanes=2,
                       warmup=False).start()
    try:
        cl = PolishClient(socket_path=sock)
        clean: dict = {}

        def clean_job():
            clean["r"] = cl.submit(*dataset, retries=3)

        t = threading.Thread(target=clean_job)
        t.start()
        with pytest.raises(JobFailed) as exc_info:
            # consensus-phase poison (host loop pack stage — the shape
            # the existing poisoned-job gate uses); strict, so the
            # isolation path runs it SOLO on one lane
            cl.submit(*dataset, strict=True,
                      fault_plan="pack:chunk=0:raise")
        assert exc_info.value.error_type == "DeviceError"
        t.join(60)
        assert clean["r"].fasta == solo_bytes
        # and the server still serves after the poisoned job
        assert cl.submit(*dataset).fasta == solo_bytes
    finally:
        srv.drain(timeout=10)


def test_tenant_quota_rejects_typed_with_retry_after():
    """Hard per-tenant admission quota (unit level): the tenant at its
    queued cap gets a typed reject with retry_after while OTHER tenants
    still admit; popped jobs free quota slots."""
    from racon_tpu.serve.queue import TenantQuotaExceeded

    q = JobQueue(maxsize=8, tenant_quota=2)
    q.submit(Job("a1", "s", "o", "t", {}, tenant="heavy"))
    q.submit(Job("a2", "s", "o", "t", {}, tenant="heavy"))
    with pytest.raises(TenantQuotaExceeded) as exc_info:
        q.submit(Job("a3", "s", "o", "t", {}, tenant="heavy"))
    assert exc_info.value.retry_after > 0
    assert "heavy" in str(exc_info.value)
    assert q.counters["rejected_quota"] == 1
    # another tenant is unaffected by heavy's cap
    q.submit(Job("b1", "s", "o", "t", {}, tenant="light"))
    # popping one of heavy's jobs frees a slot
    assert q.pop(timeout=0.5) is not None
    q.submit(Job("a4", "s", "o", "t", {}, tenant="heavy"))
    assert q.counters["admitted"] == 4


def test_tenant_quota_end_to_end(dataset, tmp_path_factory):
    """The quota over the wire: with RACON_TPU_SERVE_TENANT_QUOTA=1 a
    tenant's second QUEUED job answers `tenant-quota` with retry_after
    while a different tenant still admits."""
    from racon_tpu.serve import TenantQuota

    sock = str(tmp_path_factory.mktemp("quota") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=1, tenant_quota=1,
                       warmup=False).start()
    try:
        cl = PolishClient(socket_path=sock)
        srv.batcher.hold()  # keep the first job in flight
        try:
            outcomes: dict = {}

            def submit(key, tenant):
                try:
                    outcomes[key] = cl.submit(*dataset, tenant=tenant)
                except Exception as exc:  # noqa: BLE001 — asserted below
                    outcomes[key] = exc

            def wait_until(cond, what):
                deadline = time.monotonic() + 30
                while not cond():
                    assert time.monotonic() < deadline, what
                    time.sleep(0.01)

            t = threading.Thread(target=submit, args=("j1", "gold"))
            t.start()
            # job 1 must have been POPPED by the (single) worker — the
            # quota counts QUEUED jobs only, so its slot must be free
            wait_until(lambda: srv.queue.counters["admitted"] == 1
                       and len(srv.queue) == 0,
                       "job 1 never reached the worker")
            # job 2 queues (worker busy behind the held feeder)
            t2 = threading.Thread(target=submit, args=("j2", "gold"))
            t2.start()
            wait_until(lambda: len(srv.queue) == 1,
                       "job 2 never queued")
            # job 3 hits gold's quota of 1 queued job
            with pytest.raises(TenantQuota) as exc_info:
                cl.submit(*dataset, tenant="gold")
            assert exc_info.value.code == "tenant-quota"
            assert exc_info.value.retry_after > 0
            # a different tenant still admits past gold's cap
            t3 = threading.Thread(target=submit, args=("j3", "free"))
            t3.start()
            wait_until(lambda: len(srv.queue) == 2,
                       "free-tenant job never queued")
            assert srv.queue.counters["rejected_quota"] == 1
        finally:
            srv.batcher.release()
        for thread in (t, t2, t3):
            thread.join(60)
        for key in ("j1", "j2", "j3"):
            assert not isinstance(outcomes.get(key), Exception), \
                (key, outcomes.get(key))
    finally:
        srv.drain(timeout=10)


def test_batcher_mixed_params_do_not_merge(dataset):
    """Jobs whose engine parameters differ must not share an iteration
    — and both must still match their own solo bytes."""
    batcher = WindowBatcher()

    def build(match):
        p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                            match=match, num_threads=2)
        p.initialize()
        return p

    pa, pb = build(3), build(5)
    batcher.hold()
    ta = threading.Thread(target=batcher.consensus, args=(pa,))
    tb = threading.Thread(target=batcher.consensus, args=(pb,))
    ta.start()
    tb.start()
    time.sleep(0.3)  # both jobs' windows pooled under different keys
    batcher.release()
    ta.join(60)
    tb.join(60)
    assert pa.serve_batch["shared_iterations"] == 0
    assert pb.serve_batch["shared_iterations"] == 0
    assert batcher.counters["iterations"] == 2
    assert batcher.counters["max_jobs_in_iteration"] == 1
    out_a = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                     for s in pa._stitch(True)[0])
    out_b = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                     for s in pb._stitch(True)[0])
    assert out_a == polish_solo(dataset)
    assert out_b == polish_solo(dataset, match=5)
    assert out_a != out_b  # the scores genuinely differ on this input
    batcher.close()


def test_batcher_persistent_engine_cache_and_host_overhead(dataset):
    """The persistent dispatch loop: two same-key jobs reuse ONE cached
    (pipeline, engine) pair on the lane (engine construction leaves the
    per-iteration hot path), the measured per-iteration host overhead
    accumulates in the counters, and output stays byte-identical to a
    solo run."""
    batcher = WindowBatcher()

    def run_job():
        p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                            num_threads=2)
        p.initialize()
        batcher.consensus(p)
        return b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                        for s in p._stitch(True)[0])

    out1 = run_job()
    out2 = run_job()
    assert out1 == out2 == polish_solo(dataset)
    lanes = batcher._lanes
    assert lanes is not None
    # one engine key -> ONE cached pair across both iterations
    assert sum(len(lane.engines) for lane in lanes) == 1
    snap = batcher.snapshot()
    assert snap["iterations"] == 2
    assert snap["host_s"] >= 0.0
    # the merged pipeline view carries the iterations' stage seconds
    assert snap["pipeline"]["chunks"] >= 1
    batcher.close()
    # close() shut the cached pipelines' fallback executors down
    for lane in lanes:
        for pipeline, _ in lane.engines.values():
            assert pipeline._executor is None


def test_deprecated_round_knobs_warn_and_alias():
    """gather_window_s aliases to max_wait_s, min_gather is refused
    loudly — neither is a silent ignore."""
    from racon_tpu.serve import ServeConfig

    with pytest.warns(DeprecationWarning, match="gather_window_s"):
        cfg = ServeConfig(gather_window_s=0.25)
    assert cfg.max_wait_s == 0.25
    with pytest.warns(DeprecationWarning, match="min_gather"):
        ServeConfig(min_gather=4)


# ------------------------------------------------------------ end to end
def test_submit_byte_identical_to_oneshot(client, dataset, solo_bytes):
    result = client.submit(*dataset)
    assert result.fasta == solo_bytes
    assert result.serve["queue_wait_s"] >= 0
    assert "pipeline" in result.metrics


def test_submit_missing_file_typed_error(client, dataset):
    with pytest.raises(ServeError) as exc_info:
        client.submit(dataset[0], dataset[1], "/nonexistent/draft.fa.gz")
    assert exc_info.value.code == "bad-request"


def test_submit_unknown_option_typed_error(client, dataset):
    with pytest.raises(ServeError) as exc_info:
        client.submit(*dataset, options={"wndow_length": 500})
    assert exc_info.value.code == "bad-request"
    assert "wndow_length" in str(exc_info.value)


def test_poisoned_job_fails_typed_server_survives(client, dataset,
                                                  solo_bytes, server):
    """The acceptance gate: an injected DeviceError fails exactly one
    job with a typed error; the warm server then completes a clean job
    byte-identically. Both phases are poisoned in turn."""
    # alignment-phase poison (device aligner armed for this job only)
    with pytest.raises(JobFailed) as exc_info:
        client.submit(*dataset, fault_plan="device:chunk=0:raise",
                      strict=True, options={"tpu_aligner_batches": 1})
    assert exc_info.value.error_type == "DeviceError"
    # consensus-phase poison (host loop pack stage; isolation iteration)
    solo_before = server.batcher.counters["solo_iterations"]
    with pytest.raises(JobFailed) as exc_info:
        client.submit(*dataset, fault_plan="pack:chunk=0:raise",
                      strict=True)
    assert exc_info.value.error_type == "DeviceError"
    # the server survives and the next clean job is byte-identical
    assert client.submit(*dataset).fasta == solo_bytes
    assert client.ping()["type"] == "pong"
    assert server.batcher.counters["solo_iterations"] >= solo_before


def test_unpoisoned_fault_plan_degrades_within_job(client, dataset,
                                                   solo_bytes):
    """Without strict, the job's own resilience ladder absorbs its
    injected fault — output still byte-identical, fault counted in the
    job's OWN metrics, nothing leaks to the next job."""
    r = client.submit(*dataset, fault_plan="device:chunk=0:raise")
    assert r.fasta == solo_bytes
    assert r.metrics["resilience"]["faults"] == 1
    clean = client.submit(*dataset)
    assert clean.metrics["resilience"]["faults"] == 0


def test_job_trace_scoped_to_response(client, dataset):
    r = client.submit(*dataset, trace=True)
    assert isinstance(r.trace, list) and r.trace
    names = {ev["name"] for ev in r.trace}
    assert "polisher.initialize" in names
    # an untraced job's response carries no trace
    assert client.submit(*dataset).trace is None


def test_concurrent_traced_jobs_restore_tracer(client, dataset):
    """Overlapping trace=True jobs must not leak a dead per-job
    recorder into the process tracer (scoped() serializes): both get
    their own events, and the global tracer ends where it started."""
    from racon_tpu.obs import trace as obs_trace

    before = obs_trace.get_tracer()
    results = [None, None]

    def go(i):
        results[i] = client.submit(*dataset, trace=True)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for r in results:
        assert r is not None and r.trace
    assert obs_trace.get_tracer() is before


def test_tcp_ephemeral_port(dataset, solo_bytes):
    """--port 0 means ephemeral localhost TCP (not the unix socket);
    the bound port is published and serves byte-identical results."""
    srv = PolishServer(port=0, warmup=False).start()
    try:
        assert srv.config.port > 0
        cl = PolishClient(port=srv.config.port)
        assert cl.ping()["type"] == "pong"
        assert cl.submit(*dataset).fasta == solo_bytes
    finally:
        srv.drain(timeout=10)


def test_server_connection_survives_bad_frames(server):
    """Garbage and oversized frames on a live connection get typed error
    responses and the SAME connection keeps working."""
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(10.0)
    sock.connect(server.config.socket_path)
    try:
        # garbage JSON payload
        bad = b"!garbage!"
        sock.sendall(struct.pack(">4sI", MAGIC, len(bad)) + bad)
        resp = recv_frame(sock)
        assert resp["type"] == "error" and resp["code"] == "bad-frame"
        # same connection still serves
        send_frame(sock, {"type": "ping"})
        assert recv_frame(sock)["type"] == "pong"
        # unknown request type: typed, connection still alive
        send_frame(sock, {"type": "frobnicate"})
        resp = recv_frame(sock)
        assert resp["type"] == "error" and resp["code"] == "bad-request"
        send_frame(sock, {"type": "stats"})
        assert recv_frame(sock)["type"] == "stats"
    finally:
        sock.close()


def test_server_survives_truncated_frame_and_desync(server):
    """A client that dies mid-frame (and one that talks HTTP at us)
    costs only its own connection."""
    for payload in (struct.pack(">4sI", MAGIC, 1000) + b"partial",
                    b"GET / HTTP/1.1\r\n\r\n"):
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(10.0)
        sock.connect(server.config.socket_path)
        sock.sendall(payload)
        sock.close()
    # fresh connection: the server is untouched
    cl = PolishClient(socket_path=server.config.socket_path)
    assert cl.ping()["type"] == "pong"


def test_oversized_frame_typed_error(dataset, tmp_path_factory):
    sock_path = str(tmp_path_factory.mktemp("oversz") / "s.sock")
    srv = PolishServer(socket_path=sock_path, warmup=False,
                       max_frame=512).start()
    try:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(10.0)
        sock.connect(sock_path)
        big = b"y" * 2048
        sock.sendall(struct.pack(">4sI", MAGIC, len(big)) + big)
        resp = recv_frame(sock)
        assert resp["type"] == "error"
        assert resp["code"] == "frame-too-large"
        send_frame(sock, {"type": "ping"})
        assert recv_frame(sock)["type"] == "pong"
        sock.close()
    finally:
        srv.drain(timeout=5)


# ------------------------------------------------------------------ drain
def test_drain_finishes_inflight_then_rejects(dataset, solo_bytes,
                                              tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("drain") / "s.sock")
    srv = PolishServer(socket_path=sock, workers=1,
                       warmup=False).start()
    cl = PolishClient(socket_path=sock)
    result: list = [None]

    def go():
        result[0] = cl.submit(*dataset)

    t = threading.Thread(target=go)
    t.start()
    # wait until the job is actually admitted, then drain
    deadline = time.monotonic() + 10
    while (srv.queue.counters["admitted"] < 1
           and time.monotonic() < deadline):
        time.sleep(0.005)
    assert srv.drain(timeout=30)
    t.join(timeout=30)
    assert result[0] is not None and result[0].fasta == solo_bytes
    assert srv.queue.counters["completed"] == 1
    # post-drain: admission is closed (transport is gone)
    with pytest.raises((ServeError, OSError)):
        cl.submit(*dataset)


@pytest.mark.slow
def test_sigterm_drain_subprocess(dataset, solo_bytes, tmp_path):
    """Full SIGTERM path in a real `racon_tpu serve` process: an
    in-flight job finishes, the process exits 0."""
    import signal
    import subprocess

    sock = str(tmp_path / "s.sock")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__)))]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)
                   if p))
    proc = subprocess.Popen(
        [sys.executable, "-m", "racon_tpu.cli", "serve", "--socket",
         sock, "--workers", "1", "--no-warmup"],
        env=env, stderr=subprocess.PIPE)
    try:
        cl = PolishClient(socket_path=sock, timeout=30)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                cl.ping()
                break
            except (OSError, ServeError):
                time.sleep(0.2)
        else:
            pytest.fail("server never came up")
        result: list = [None]

        def go():
            result[0] = cl.submit(*dataset)

        t = threading.Thread(target=go)
        t.start()
        time.sleep(0.2)  # let the submit land
        proc.send_signal(signal.SIGTERM)
        t.join(timeout=60)
        rc = proc.wait(timeout=60)
        assert rc == 0
        assert result[0] is not None
        assert result[0].fasta == solo_bytes
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


# ------------------------------------------------- warm polisher reuse
def test_polisher_back_to_back_runs_byte_identical(dataset):
    fresh = polish_solo(dataset)
    p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    outs, stats = [], []
    for _ in range(2):
        p.initialize()
        outs.append(b"".join(b">" + s.name.encode() + b"\n" + s.data
                             + b"\n" for s in p.polish()))
        stats.append(p.stage_stats)
    assert outs[0] == fresh
    assert outs[1] == fresh
    # counters describe one run each, not a running total
    assert stats[0]["chunks"] == stats[1]["chunks"]
    assert stats[0]["launches"] == stats[1]["launches"]


def test_polisher_rebind_warm_reuse(dataset, tmp_path):
    """rebind() points a warm polisher at new inputs; output matches a
    fresh polisher on those inputs."""
    other = make_synth_dataset(str(tmp_path), seed=99)
    p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                        num_threads=2)
    p.initialize()
    p.polish()
    p.rebind(*other)
    p.initialize()
    warm = b"".join(b">" + s.name.encode() + b"\n" + s.data + b"\n"
                    for s in p.polish())
    assert warm == polish_solo(other)
    # per-run metrics followed the swap (fresh occupancy object)
    assert p.metrics.snapshot()["sched"] == p.scheduler.stats.snapshot()


def test_polisher_run_counters_reset_between_jobs(dataset):
    """A fault absorbed in run 1 must not appear in run 2's report."""
    from racon_tpu.resilience.faults import reset_fault_plan

    os.environ["RACON_TPU_FAULT_PLAN"] = "device:chunk=0:raise"
    reset_fault_plan()
    try:
        p = create_polisher(*dataset, PolisherType.kC, 500, 10.0, 0.3,
                            num_threads=2)
        p.initialize()
        p.polish()
        assert p.stage_stats["faults"] == 1
    finally:
        os.environ.pop("RACON_TPU_FAULT_PLAN", None)
        reset_fault_plan()
    p.initialize()
    p.polish()
    assert p.stage_stats["faults"] == 0


# ------------------------------------- end-to-end tracing & live progress
def _serve_pair(tmp_path_factory, transport, **kw):
    """A (server, client) pair on the requested transport."""
    kw.setdefault("warmup", False)
    if transport == "tcp":
        srv = PolishServer(port=0, **kw).start()
        return srv, PolishClient(port=srv.config.port)
    sock = str(tmp_path_factory.mktemp("ept") / "s.sock")
    srv = PolishServer(socket_path=sock, **kw).start()
    return srv, PolishClient(socket_path=sock)


@pytest.mark.parametrize("transport", ["unix", "tcp"])
def test_progress_frames_interleaved(dataset, solo_bytes,
                                     tmp_path_factory, transport):
    """The acceptance gate, on both transports: progress frames arrive
    before the result, seq and windows-done counts are monotonically
    non-decreasing, the stream ends at stitch, and the result bytes are
    untouched by the streaming."""
    srv, cl = _serve_pair(tmp_path_factory, transport)
    try:
        evs: list = []
        r = cl.submit(*dataset, on_progress=evs.append,
                      trace_id="tid-interleave")
        assert r.fasta == solo_bytes
        assert evs, "no progress frames before the result frame"
        assert all(e["type"] == "progress" for e in evs)
        assert all(e["job_id"] == r.job_id for e in evs)
        assert all(e["trace_id"] == "tid-interleave" for e in evs)
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        cons = [e for e in evs if e["phase"] == "consensus"]
        assert cons, "no consensus progress"
        dones = [e["done"] for e in cons]
        assert dones == sorted(dones), "windows-done ran backwards"
        assert cons[-1]["done"] == cons[-1]["total"] > 0
        assert "start" in {e["phase"] for e in evs}
        assert evs[-1]["phase"] == "stitch"
        # a plain submit on the same server gets NO progress frames
        # (off by default) and identical bytes
        assert cl.submit(*dataset).fasta == solo_bytes
    finally:
        srv.drain(timeout=10)


def test_progress_queue_position_while_pending(dataset,
                                               tmp_path_factory):
    """A job stuck behind a busy single worker streams queued-position
    frames before it ever starts."""
    srv, cl = _serve_pair(tmp_path_factory, "unix", workers=1)
    try:
        blocker_done = threading.Event()

        def blocker():
            try:
                cl.submit(*dataset,
                          fault_plan="device:chunk=0:hang=0.8")
            finally:
                blocker_done.set()

        t = threading.Thread(target=blocker)
        t.start()
        deadline = time.monotonic() + 10
        while (srv.queue.counters["admitted"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        time.sleep(0.1)  # let the worker pop it
        evs: list = []
        cl.submit(*dataset, on_progress=evs.append)
        queued = [e for e in evs if e["phase"] == "queued"]
        assert queued, f"no queued-position frames: {evs[:5]}"
        assert queued[0]["position"] >= 0
        assert queued[0]["depth"] >= 1
        # the queued frames precede every execution-phase frame
        assert evs.index(queued[-1]) < evs.index(
            next(e for e in evs if e["phase"] == "start"))
        t.join(timeout=30)
        assert blocker_done.is_set()
    finally:
        srv.drain(timeout=10)


def test_concurrent_jobs_no_progress_bleed(dataset, solo_bytes,
                                           tmp_path_factory):
    """Two concurrent progress-streaming jobs merged into SHARED device
    iterations: each stream carries only its own job id and trace id,
    both outputs stay byte-identical."""
    srv, cl = _serve_pair(tmp_path_factory, "unix", workers=2)
    srv.batcher.hold()
    try:
        evs: list = [[], []]
        results: list = [None, None]

        def go(i):
            results[i] = cl.submit(*dataset, on_progress=evs[i].append,
                                   trace_id=f"tid-{i}")

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while (srv.queue.counters["admitted"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)
        time.sleep(0.5)  # both jobs' windows pooled behind the hold
        srv.batcher.release()
        for t in threads:
            t.join(timeout=60)
        assert results[0] is not None and results[1] is not None
        assert results[0].job_id != results[1].job_id
        # truly shared iterations
        assert results[0].serve["batch"]["shared_iterations"] >= 1
        for i in (0, 1):
            assert results[i].fasta == solo_bytes
            assert evs[i], f"job {i} saw no progress"
            assert {e["job_id"] for e in evs[i]} == \
                {results[i].job_id}, "cross-job job_id bleed"
            assert {e["trace_id"] for e in evs[i]} == {f"tid-{i}"}, \
                "cross-job trace_id bleed"
            cons = [e for e in evs[i] if e["phase"] == "consensus"]
            dones = [e["done"] for e in cons]
            assert dones == sorted(dones)
            assert cons[-1]["done"] == cons[-1]["total"] > 0
    finally:
        srv.drain(timeout=10)


def test_bad_trace_id_rejected(client, dataset):
    with pytest.raises(ServeError) as exc_info:
        client.submit(*dataset, trace_id="no spaces allowed")
    assert exc_info.value.code == "bad-request"
    assert "trace_id" in str(exc_info.value)


def test_trace_out_merged_artifact(client, server, dataset, tmp_path):
    """The acceptance gate: one traced submit against the WARM module
    server produces a single valid Chrome-trace JSON holding both
    client- and server-side spans on one timeline, with the serve-side
    spans tagged by the minted trace id and the batch-round span
    duration pinned to the job's own round telemetry."""
    import json as _json

    path = str(tmp_path / "merged.json")
    result, doc = client.submit_traced(*dataset, trace_out=path)
    on_disk = _json.load(open(path))
    assert on_disk["traceEvents"] and "displayTimeUnit" in on_disk
    tid = doc["trace_context"]["trace_id"]
    assert tid and doc["trace_context"]["job_id"] == result.job_id

    by_pid: dict = {}
    for ev in doc["traceEvents"]:
        assert "ph" in ev and "pid" in ev
        if ev["ph"] != "M":
            assert ev["ts"] >= 0
            by_pid.setdefault(ev["pid"], set()).add(ev["name"])
    assert {"client.connect", "client.submit", "client.wait",
            "client.receive"} <= by_pid[1]
    assert {"serve.queue_wait", "serve.job",
            "polisher.initialize"} <= by_pid[2]
    # process-name metadata labels both tracks
    pnames = {ev["pid"]: ev["args"]["name"]
              for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert "client" in pnames[1] and "server" in pnames[2]
    # the serve-side spans carry the client's trace context
    qw = [ev for ev in doc["traceEvents"]
          if ev.get("name") == "serve.queue_wait"]
    assert len(qw) == 1 and qw[0]["args"]["trace_id"] == tid
    # span-duration pin: the job's iteration spans and its batch
    # telemetry are recorded from the same perf_counter endpoints —
    # the spans for the iterations this job rode sum to its device_s
    batch = result.serve["batch"]
    iters = [ev for ev in doc["traceEvents"]
             if ev.get("name") == "serve.iteration"
             and ev.get("args", {}).get("iteration")
             in batch["iteration_ids"]]
    assert len(iters) == batch["iterations"] >= 1
    assert sum(ev["dur"] for ev in iters) / 1e6 == pytest.approx(
        batch["device_s"], rel=0.05, abs=1e-3)
    assert all(tid in ev["args"]["trace_ids"] for ev in iters)
    # and the ordinary result is untouched
    assert result.fasta


def test_traced_strict_job_span_sums_pin_stage_stats(client, server,
                                                     dataset):
    """Server pipeline span sums inside the merged artifact equal the
    job's own stage stats (a strict job runs an isolation round on its
    own pipeline, so the returned metrics ARE this job's spans)."""
    result, doc = client.submit_traced(*dataset, strict=True)
    stats = result.metrics["pipeline"]
    sums: dict = {}
    for ev in doc["traceEvents"]:
        if (ev.get("ph") == "X" and ev.get("pid") == 2
                and ev["name"].startswith("pipeline.")):
            stage = ev["name"].split(".", 1)[1]
            sums[stage] = sums.get(stage, 0.0) + ev["dur"] / 1e6
    assert sums, "no pipeline spans in the server trace"
    for stage in ("pack", "device", "unpack"):
        assert sums.get(stage, 0.0) == pytest.approx(
            stats[f"{stage}_s"], rel=0.05, abs=1e-3), \
            f"{stage}: {sums.get(stage)} vs {stats[f'{stage}_s']}"


def test_trace_and_progress_over_tcp(dataset, solo_bytes,
                                     tmp_path_factory):
    """Trace-context propagation composes with progress streaming over
    localhost TCP: progress frames become client.progress instants in
    the merged artifact."""
    srv, cl = _serve_pair(tmp_path_factory, "tcp")
    try:
        evs: list = []
        result, doc = cl.submit_traced(*dataset,
                                       on_progress=evs.append)
        assert result.fasta == solo_bytes
        assert evs
        instants = [ev for ev in doc["traceEvents"]
                    if ev.get("name") == "client.progress"]
        assert len(instants) == len(evs)
        assert all(ev["pid"] == 1 for ev in instants)
    finally:
        srv.drain(timeout=10)


# --------------------------------------------- per-tenant fair scheduling
def _tjob(i, tenant, priority=0):
    return Job(f"{tenant}{i}", "s", "o", "t", {}, priority=priority,
               tenant=tenant)


def test_queue_drr_equal_weights_interleave():
    """A flooding tenant and a late light tenant with equal weights pop
    round-robin: the light tenant's first job is at most a couple of
    pops away, not behind the whole flood."""
    q = JobQueue(maxsize=32)
    for i in range(6):
        q.submit(_tjob(i, "heavy"))
    for i in range(2):
        q.submit(_tjob(i, "light"))
    assert q.position(q._classes[0].tenants["light"][0]) <= 3
    order = [q.pop(timeout=0.1).id for _ in range(8)]
    assert order.index("light0") <= 3
    assert order.index("light1") <= 5
    # FIFO within each tenant
    heavy_order = [j for j in order if j.startswith("heavy")]
    assert heavy_order == sorted(heavy_order)


def test_queue_drr_weighted_ratio():
    """A weight-3 tenant gets ~3 pops per rotation against a weight-1
    flood."""
    q = JobQueue(maxsize=32,
                 tenant_weights={"heavy": 1, "gold": 3})
    for i in range(6):
        q.submit(_tjob(i, "heavy"))
    for i in range(3):
        q.submit(_tjob(i, "gold"))
    order = [q.pop(timeout=0.1).id for _ in range(9)]
    # all three gold jobs pop within the first four slots
    assert {j for j in order[:4] if j.startswith("gold")} == \
        {"gold0", "gold1", "gold2"}


def test_queue_drr_priority_beats_weight():
    """Priority classes stay absolute: a higher-priority job pops
    before any lower-priority tenant regardless of weights."""
    q = JobQueue(maxsize=32, tenant_weights={"vip": 100})
    q.submit(_tjob(0, "vip", priority=0))
    q.submit(_tjob(0, "urgent", priority=5))
    assert q.pop(timeout=0.1).id == "urgent0"
    assert q.pop(timeout=0.1).id == "vip0"


def test_queue_single_tenant_stays_fifo():
    q = JobQueue(maxsize=8)
    for i in range(4):
        q.submit(_tjob(i, ""))
    assert [q.pop(timeout=0.1).id for _ in range(4)] == \
        ["0", "1", "2", "3"]


def test_tenant_fairness_light_tenant_bounded(dataset,
                                              tmp_path_factory):
    """The saturation-wave gate: one worker, a heavy tenant floods the
    queue, a light (weighted) tenant submits after — the light job must
    complete ahead of most of the heavy backlog, i.e. its latency is
    bounded by ~one job, not by the flood."""
    srv, cl = _serve_pair(tmp_path_factory, "unix", workers=1,
                          queue_depth=16,
                          tenant_weights={"light": 4, "heavy": 1})
    try:
        done_order: list = []
        threads = []

        def go(tenant, i, **kw):
            cl.submit(*dataset, tenant=tenant, **kw)
            done_order.append(tenant)

        # first heavy job hangs briefly so the rest of the flood is
        # queued when the light tenant arrives
        t = threading.Thread(target=go, args=("heavy", 0),
                             kwargs={"fault_plan":
                                     "device:chunk=0:hang=0.8"})
        t.start()
        threads.append(t)
        deadline = time.monotonic() + 10
        while (srv.queue.counters["admitted"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        time.sleep(0.1)  # worker popped the hanging job
        for i in range(1, 5):
            th = threading.Thread(target=go, args=("heavy", i))
            th.start()
            threads.append(th)
        deadline = time.monotonic() + 10
        while (srv.queue.counters["admitted"] < 5
               and time.monotonic() < deadline):
            time.sleep(0.005)
        th = threading.Thread(target=go, args=("light", 0))
        th.start()
        threads.append(th)
        for th in threads:
            th.join(timeout=60)
        assert len(done_order) == 6
        # the light job finished ahead of most of the heavy backlog:
        # at most the in-flight job plus one racing pop precede it
        assert done_order.index("light") <= 2, done_order
        snap = srv.queue.snapshot()
        assert snap["tenants"]["light"]["completed"] == 1
        assert snap["tenants"]["light"]["weight"] == 4.0
    finally:
        srv.drain(timeout=10)


# --------------------------------------------------- streamed result parts
def test_stream_parts_byte_identical(dataset, solo_bytes, client):
    """`result_part` frames arrive before the result, in contig order,
    and their concatenation is byte-identical to the buffered FASTA —
    while the final frame carries stats but no second copy."""
    parts: list = []
    r = client.submit(*dataset, on_part=parts.append)
    assert r.streamed and r.parts == len(parts) > 0
    assert all(p["type"] == "result_part" for p in parts)
    assert [p["part"] for p in parts] == \
        list(range(1, len(parts) + 1))
    concat = b"".join(p["fasta"].encode("latin-1") for p in parts)
    assert concat == solo_bytes
    assert r.fasta == solo_bytes  # assembled from the parts
    # a buffered submit on the same server still carries the body
    assert client.submit(*dataset).fasta == solo_bytes


def test_stream_with_progress_interleaved(dataset, solo_bytes,
                                          tmp_path_factory):
    """Streaming composes with live progress on one connection: the
    client sees progress frames, then each part, then the result — and
    time-to-first-byte (first part) precedes job completion."""
    srv, cl = _serve_pair(tmp_path_factory, "tcp")
    try:
        events: list = []
        r = cl.submit(*dataset,
                      on_progress=lambda ev: events.append(("p", ev)),
                      on_part=lambda fr: events.append(("part", fr)))
        assert r.fasta == solo_bytes
        kinds = [k for k, _ in events]
        assert "p" in kinds and "part" in kinds
        # every part precedes the end of the stream and parts are in
        # order
        part_ids = [fr["part"] for k, fr in events if k == "part"]
        assert part_ids == sorted(part_ids)
    finally:
        srv.drain(timeout=10)


def test_stream_identity_under_quarantine(dataset, solo_bytes,
                                          tmp_path_factory,
                                          monkeypatch):
    """Injected per-window faults (one window quarantined onto its
    draft backbone) must not break streaming: parts still arrive in
    order and their concatenation equals the buffered submit under the
    SAME injection — which genuinely differs from the clean bytes."""
    import racon_tpu.ops.poa as poa_mod

    real = poa_mod.poa_batch
    state = {"singles": 0}

    def flaky(packed, *a, **kw):
        if len(packed) > 1:
            raise RuntimeError("chunk poisoned")  # force singles
        state["singles"] += 1
        if state["singles"] == 2:
            raise RuntimeError("window poisoned")  # quarantine one
        return real(packed, *a, **kw)

    srv, cl = _serve_pair(tmp_path_factory, "unix", workers=1)
    try:
        monkeypatch.setattr(poa_mod, "poa_batch", flaky)
        state["singles"] = 0
        buffered = cl.submit(*dataset).fasta
        state["singles"] = 0
        parts: list = []
        streamed = cl.submit(*dataset, on_part=parts.append)
        assert [p["part"] for p in parts] == \
            list(range(1, len(parts) + 1))
        assert streamed.fasta == buffered
        assert buffered != solo_bytes  # the quarantine really landed
        b = srv.batcher.snapshot()
        assert b["pipeline"]["quarantined"] >= 2
    finally:
        srv.drain(timeout=10)


@pytest.mark.parametrize("worker_lanes", [1, 2])
def test_midstream_disconnect_kills_nothing(dataset, solo_bytes,
                                            tmp_path_factory,
                                            worker_lanes):
    """A streaming client that vanishes mid-job costs only its own
    connection: the job still completes and is accounted, the feeders
    and the next client are untouched — at one feeder lane and across
    the two-sub-mesh lane partition alike."""
    srv, cl = _serve_pair(tmp_path_factory, "unix", workers=2,
                          worker_lanes=worker_lanes)
    try:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(15.0)
        sock.connect(srv.config.socket_path)
        send_frame(sock, {"type": "submit",
                          "sequences": dataset[0],
                          "overlaps": dataset[1],
                          "target": dataset[2],
                          "progress": True, "stream": True})
        # read ONE interleaved frame to prove the stream started, then
        # vanish
        first = recv_frame(sock)
        assert first["type"] in ("progress", "result_part")
        sock.close()
        deadline = time.monotonic() + 30
        while (srv.queue.counters["completed"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert srv.queue.counters["completed"] == 1
        assert srv.queue.counters["failed"] == 0
        # the feeder and a fresh client both still work
        assert cl.submit(*dataset).fasta == solo_bytes
    finally:
        srv.drain(timeout=10)


def test_bad_tenant_rejected(client, dataset):
    with pytest.raises(ServeError) as exc_info:
        client.submit(*dataset, tenant="no spaces")
    assert exc_info.value.code == "bad-request"
    assert "tenant" in str(exc_info.value)


# ------------------------------------------- journal part-streamed events
def test_journal_part_streamed_and_obsreport_check(dataset, tmp_path):
    """Every successful serve job journals one `part-streamed` event
    per output contig; `obsreport --check` verifies the count equals
    the job's contig count and fails when a part line is missing."""
    import obsreport
    from racon_tpu.obs.journal import read_journal

    journal = str(tmp_path / "journal.jsonl")
    srv = PolishServer(socket_path=str(tmp_path / "s.sock"),
                       warmup=False, journal=journal).start()
    try:
        cl = PolishClient(socket_path=srv.config.socket_path)
        r1 = cl.submit(*dataset)
        parts: list = []
        r2 = cl.submit(*dataset, on_part=parts.append)
    finally:
        srv.drain(timeout=10)
    entries = read_journal(journal)
    by_job: dict = {}
    for e in entries:
        if e.get("event") == "part-streamed":
            by_job.setdefault(e["job"], []).append(e)
    assert len(by_job[r1.job_id]) == 1  # one contig in the synth set
    assert len(by_job[r2.job_id]) == len(parts) == 1
    assert by_job[r2.job_id][0]["contig"] == "draft"
    rc = obsreport.main(["--journal", journal,
                         "--flight-dir", str(tmp_path / "none"),
                         "--check"])
    assert rc == 0
    # drop one part-streamed line: the check must go red
    with open(journal) as fh:
        lines = [ln for ln in fh]
    kept = [ln for ln in lines
            if not ('"part-streamed"' in ln
                    and f'"{r2.job_id}"' in ln)]
    assert len(kept) < len(lines)
    with open(journal, "w") as fh:
        fh.writelines(kept)
    assert obsreport.main(["--journal", journal,
                           "--flight-dir", str(tmp_path / "none"),
                           "--check"]) == 1


# ------------------------------------------------- TTY-aware progress bars
class _FakeTTY(io.StringIO):
    def isatty(self):
        return True


def _drive_bar(stream, ticks=40):
    from racon_tpu.utils.logger import Logger

    old = sys.stderr
    sys.stderr = stream
    try:
        lg = Logger()
        lg.log()
        lg.bar_total(ticks)
        for _ in range(ticks):
            lg.bar("[phase] working")
    finally:
        sys.stderr = old
    return stream.getvalue()


def test_bar_non_tty_single_line():
    out = _drive_bar(io.StringIO())
    assert "\r" not in out
    assert out.count("\n") == 1
    assert out.startswith("[phase] working [====================] 100% ")


def test_bar_tty_byte_identical_to_classic():
    out = _drive_bar(_FakeTTY())
    # the classic protocol: 19 \r redraws then the completion line
    assert out.count("\r") == 19
    assert out.startswith("[phase] working [=>                  ] 5%\r")
    assert " 100% " in out and out.endswith("s\n")


def test_bar_quiet_level_silent():
    from racon_tpu.utils.logger import set_log_level

    set_log_level("quiet")
    try:
        out = _drive_bar(io.StringIO())
    finally:
        set_log_level(None)
    assert out == ""
