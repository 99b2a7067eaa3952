"""PolishClient: Python + CLI client for the warm polishing service.

One request = one connection (the server multiplexes concurrency across
connections, so a client that wants N jobs in flight opens N sockets —
exactly what `tools/servebench.py` does from a thread pool). Errors come
back as the protocol's typed error responses and are re-raised as the
exception hierarchy below, so callers branch on types, not message
strings:

    QueueFull       admission control rejected; `retry_after` seconds
    TenantQuota     this tenant's queued-job quota is full (QueueFull
                    subclass, same `retry_after` backoff contract)
    ServerDraining  server is shutting down, resubmit elsewhere
    JobFailed       the job ran and failed; `error_type` names the
                    errors.py class (DeviceError, DeviceTimeout, ...)
    JobCancelled    the job was cancelled (cancel RPC / cancel-on-
                    timeout) before finishing
    DeadlineDoomed  the server speculatively aborted: predicted finish
                    past the deadline by more than its abort margin
                    (carries `predicted_s` / `remaining_s`)
    ServeError      anything else typed (bad-request, bad-frame, ...)

`racon_tpu submit ...` (cli.py) is the CLI face: same three positional
inputs as the one-shot CLI, polished FASTA on stdout — byte-identical
to the one-shot run, just served warm. Two observability extras ride
the same submit (README "End-to-end tracing & progress"):

  - `--progress` / `submit(..., on_progress=cb)`: the server interleaves
    `progress` frames (queue position while pending, then phase /
    windows-done / total) before the final result frame — live
    visibility into a job that used to be a black box until its bytes
    arrived.
  - `--trace-out t.json` / `submit_traced(...)`: the client mints a
    `trace_id`, estimates the server's perf_counter offset from an
    RTT-bracketed ping handshake, records its OWN spans (connect /
    submit / wait / receive, progress instants), asks the server for
    the job's server-side trace, and merges both into one Chrome-trace
    JSON — two Perfetto process tracks on a single timeline.
  - `--stream` / `submit(..., on_part=cb)`: the server streams each
    polished contig as a `result_part` frame the moment its windows
    complete (continuous batching stitches per contig); the final
    result frame carries the stats and the concatenation of the parts
    is byte-identical to the buffered FASTA. Time-to-first-byte becomes
    the FIRST contig's finish time, not the job's.
"""

from __future__ import annotations

import json
import os
import random
import socket
import sys
import time
import uuid

from .protocol import WIRE_LIMIT, recv_frame, send_frame
from .server import DEFAULT_SOCKET

#: ceiling on any single retry sleep — a server advertising a huge
#: retry_after must not park a client for minutes
RETRY_DELAY_CAP_S = 30.0


def _retry_delay(retry_after: float, cap: float = RETRY_DELAY_CAP_S,
                 rng: random.Random | None = None) -> float:
    """Jittered backoff for full-queue retries: the server's
    `retry_after` hint spread by ±25% and capped. Every client waiting
    out the same hint sleeping EXACTLY retry_after would re-submit in
    one synchronized thundering herd the instant a restarted replica
    comes back — the jitter de-correlates the storm. Bounds are pinned
    by test: 0 <= delay <= cap, and within [0.75, 1.25] * hint when the
    hint is under the cap."""
    base = min(max(float(retry_after), 0.0), cap)
    r = (rng or random).random()
    return min(base * (0.75 + 0.5 * r), cap)


class ServeError(Exception):
    """Typed error response from the server."""

    def __init__(self, code: str, message: str, response: dict):
        super().__init__(f"[{code}] {message}")
        self.code = code
        self.response = response


class QueueFull(ServeError):
    def __init__(self, code, message, response):
        super().__init__(code, message, response)
        self.retry_after = float(response.get("retry_after", 1.0))


class ServerDraining(ServeError):
    pass


class TenantQuota(QueueFull):
    """Per-tenant admission quota hit; carries `retry_after` like a
    full-queue reject (and subclasses QueueFull, so `retries=` backoff
    in submit() covers it too)."""

    def __init__(self, code, message, response):
        super().__init__(code, message, response)
        self.tenant = response.get("tenant", "")


class JobFailed(ServeError):
    def __init__(self, code, message, response):
        super().__init__(code, message, response)
        self.error_type = response.get("error_type", "RaconError")


class JobCancelled(ServeError):
    """The job was cancelled before it finished — by an explicit
    `cancel` RPC or by this client's own `cancel_on_timeout`."""


class DeadlineDoomed(ServeError):
    """The server aborted speculatively: the predicted finish exceeds
    the job's deadline by more than the server's abort margin (at
    admission or mid-run)."""

    def __init__(self, code, message, response):
        super().__init__(code, message, response)
        self.predicted_s = float(response.get("predicted_s", 0.0))
        self.remaining_s = float(response.get("remaining_s", 0.0))


_ERROR_TYPES = {"queue-full": QueueFull, "draining": ServerDraining,
                "tenant-quota": TenantQuota, "job-failed": JobFailed,
                "cancelled": JobCancelled,
                "deadline-doomed": DeadlineDoomed}


class PolishResult:
    __slots__ = ("job_id", "fasta", "metrics", "serve", "trace",
                 "trace_base_mono", "trace_replicas", "streamed",
                 "parts", "router", "rounds")

    def __init__(self, resp: dict):
        self.job_id = resp.get("job_id")
        #: whether the FASTA arrived as streamed result_part frames
        #: (then the final frame carries stats only and `fasta` below
        #: is the parts' concatenation — byte-identical to the
        #: non-streamed body, test-pinned)
        self.streamed = bool(resp.get("streamed"))
        self.parts = resp.get("parts", 0)
        if self.streamed:
            self.fasta = b"".join(
                p.get("fasta", "").encode("latin-1")
                for p in resp.get("_parts") or [])
        else:
            self.fasta = resp.get("fasta", "").encode("latin-1")
        self.metrics = resp.get("metrics") or {}
        self.serve = resp.get("serve") or {}
        #: fan-out accounting when the job went through a shard-aware
        #: router (shards / requeues / parts / wall_s); {} for a direct
        #: replica submit
        self.router = resp.get("router") or {}
        #: per-round accounting when the submit asked for rounds=N
        #: (requested / completed / per_round walls + cache hit
        #: totals); {} on a plain single-pass job
        self.rounds = resp.get("rounds") or {}
        self.trace = resp.get("trace")
        #: the server-side recorder's time zero in SERVER perf_counter
        #: terms — merge_trace() needs it to rebase server spans
        self.trace_base_mono = resp.get("trace_base_mono")
        #: routed trace collection (router._attach_trace): one entry
        #: per participating replica — {replica, events, base_mono,
        #: offset_s (replica clock relative to the ROUTER), rtt_s};
        #: None for direct submits and untraced routed jobs
        self.trace_replicas = resp.get("trace_replicas")


class PolishClient:
    def __init__(self, socket_path: str | None = None,
                 port: int | None = None, timeout: float | None = None):
        self.socket_path = (socket_path
                            or os.environ.get("RACON_TPU_SERVE_SOCKET")
                            or DEFAULT_SOCKET)
        self.port = port
        self.timeout = timeout

    def _connect(self) -> socket.socket:
        if self.port:
            sock = socket.create_connection(("127.0.0.1", self.port),
                                            timeout=self.timeout)
        else:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(self.timeout)
            sock.connect(self.socket_path)
        return sock

    def request(self, obj: dict, on_progress=None, on_part=None,
                recorder=None) -> dict:
        """One round trip; raises the ServeError hierarchy on a typed
        error response. Interleaved `progress` frames (a `submit` with
        "progress": true) are handed to `on_progress` as they arrive,
        and streamed `result_part` frames (a `submit` with "stream":
        true) to `on_part`; the method returns on the first frame that
        is neither, with the collected parts attached as `_parts` so
        PolishResult can assemble the full FASTA. `recorder` (an
        obs.trace.TraceRecorder) captures client-side spans — connect /
        submit / wait / receive plus `client.progress` /
        `client.result_part` instants per interleaved frame — passed
        PER CALL so one client may serve concurrent threads without a
        traced request absorbing an unrelated request's spans."""
        rec = recorder
        t0 = time.perf_counter()
        sock = self._connect()
        if rec is not None:
            rec.complete("client.connect", t0, time.perf_counter())
        frames = 0
        parts: list[dict] = []
        try:
            t_send = time.perf_counter()
            send_frame(sock, obj)
            t_wait = time.perf_counter()
            if rec is not None:
                rec.complete("client.submit", t_send, t_wait,
                             {"type": obj.get("type")})
            while True:
                # results come from a trusted server: accept up to the
                # wire limit, not the server's anti-abuse request
                # ceiling — a multi-hundred-MiB polished assembly must
                # come back whole
                resp = recv_frame(sock, max_frame=WIRE_LIMIT)
                # stamped AFTER the recv: the blocking time (server
                # compute + transfer) belongs to client.wait — stamping
                # before would charge a whole no-progress polish to
                # client.receive and ~0 to wait
                t_frame = time.perf_counter()
                rtype = resp.get("type") if resp is not None else None
                if rtype == "result_part":
                    parts.append(resp)
                    if rec is not None:
                        rec.instant("client.result_part",
                                    {k: resp[k] for k in
                                     ("part", "name", "job_id")
                                     if k in resp})
                    if on_part is not None:
                        on_part(resp)
                    continue
                if rtype != "progress":
                    break
                frames += 1
                if rec is not None:
                    rec.instant("client.progress",
                                {k: resp[k] for k in
                                 ("phase", "done", "total", "position",
                                  "job_id") if k in resp})
                if on_progress is not None:
                    on_progress(resp)
            if rec is not None:
                now = time.perf_counter()
                rec.complete("client.wait", t_wait, t_frame,
                             {"progress_frames": frames,
                              "result_parts": len(parts)})
                rec.complete("client.receive", t_frame, now)
        finally:
            sock.close()
        if resp is None:
            raise ServeError("closed", "server closed the connection",
                             {})
        if resp.get("type") == "error":
            code = resp.get("code", "error")
            raise _ERROR_TYPES.get(code, ServeError)(
                code, resp.get("message", ""), resp)
        if parts:
            resp["_parts"] = parts
        return resp

    def clock_sync(self, samples: int = 3) -> dict:
        """Estimate the server's perf_counter offset from RTT-bracketed
        pings: for each sample, offset = server_mono - client RTT
        midpoint; the minimum-RTT sample wins (least queueing noise).
        Returns {"offset_s", "rtt_s"} — merge_trace() uses the offset
        to put server spans on the client timeline, good to ~rtt/2."""
        best = None
        for _ in range(max(1, samples)):
            t0 = time.perf_counter()
            pong = self.request({"type": "ping"})
            t1 = time.perf_counter()
            mono = pong.get("mono_s")
            if mono is None:
                raise ServeError(
                    "bad-response",
                    "server ping carries no mono_s clock sample "
                    "(pre-tracing server?)", pong)
            cand = {"offset_s": float(mono) - (t0 + t1) / 2.0,
                    "rtt_s": t1 - t0}
            if best is None or cand["rtt_s"] < best["rtt_s"]:
                best = cand
        return best

    # ------------------------------------------------------------ calls
    def submit(self, sequences: str, overlaps: str, target: str, *,
               options: dict | None = None, priority: int = 0,
               deadline_s: float | None = None,
               fault_plan: str | None = None, strict: bool | None = None,
               trace: bool = False, trace_id: str | None = None,
               tenant: str | None = None, rounds: int | None = None,
               fragment: bool = False,
               frag_lo: int | None = None, frag_hi: int | None = None,
               ingest: bool = False, subsample: dict | None = None,
               normalize: bool = False,
               on_progress=None, on_part=None, stream: bool = False,
               recorder=None, retries: int = 0,
               cancel_on_timeout: bool = False) -> PolishResult:
        """Polish one input triple on the server. Paths are resolved to
        absolute before they cross the wire (the server's cwd is not the
        client's). `retries` re-submits after `retry_after` on full-queue
        rejects — simple client-side backoff. `on_progress` (callable
        taking each progress frame dict) turns on the server's live
        progress stream; `on_part` (callable taking each `result_part`
        frame dict) or `stream=True` turns on per-contig streamed
        results — finished contigs arrive BEFORE the final frame, and
        `PolishResult.fasta` is their byte-identical concatenation.
        `tenant` names the fair-scheduling bucket this job bills to
        (queue.py weighted DRR); `trace_id` stamps the job's
        server-side spans, journal lines and interleaved frames with a
        client-chosen correlation id. `rounds=N` runs N serve-native
        polishing rounds — the server feeds round k's stitched contigs
        back as round k+1's draft without leaving the warm process —
        and `PolishResult.rounds` carries the per-round accounting.
        `cancel_on_timeout=True` (needs a client `timeout`) frees the
        server side when this client gives up: a socket timeout while
        the job is queued or running sends a `cancel` for the job's
        trace id on a FRESH connection — without it the abandoned job
        keeps its queue and quota slots until the worker pops it —
        then raises `JobCancelled`; the full-queue retry loop likewise
        stops retrying once the elapsed wall time would exceed the
        timeout budget."""
        if cancel_on_timeout and not trace_id:
            # the cancel RPC needs a handle the client knows BEFORE
            # the result frame arrives: mint the correlation id
            trace_id = uuid.uuid4().hex[:16]
        req = {"type": "submit",
               "sequences": os.path.abspath(sequences),
               "overlaps": os.path.abspath(overlaps),
               "target": os.path.abspath(target)}
        if options:
            req["options"] = options
        if priority:
            req["priority"] = int(priority)
        if deadline_s is not None:
            req["deadline_s"] = float(deadline_s)
        if fault_plan:
            req["fault_plan"] = fault_plan
        if strict is not None:
            req["strict"] = bool(strict)
        if trace:
            req["trace"] = True
        if trace_id:
            req["trace_id"] = str(trace_id)
        if tenant:
            req["tenant"] = str(tenant)
        if rounds is not None:
            req["rounds"] = int(rounds)
        if fragment:
            # fragment traffic class (`mode: "fragment"`): corrected
            # reads instead of polished contigs — PolisherType.kF with
            # bounded-group result_part streaming (protocol.py
            # "Fragment jobs")
            req["mode"] = "fragment"
        if frag_lo is not None:
            req["frag_lo"] = int(frag_lo)
        if frag_hi is not None:
            req["frag_hi"] = int(frag_hi)
        # admit-time ingest plane (serve/ingest.py): validate-only,
        # subsample-on-admit, paired-end normalization
        if ingest:
            req["ingest"] = True
        if subsample is not None:
            req["subsample"] = dict(subsample)
        if normalize:
            req["normalize"] = True
        if on_progress is not None:
            req["progress"] = True
        if stream or on_part is not None:
            req["stream"] = True
        attempt = 0
        t_first = time.perf_counter()
        while True:
            try:
                return PolishResult(
                    self.request(req, on_progress=on_progress,
                                 on_part=on_part, recorder=recorder))
            except QueueFull as exc:
                if attempt >= retries:
                    raise
                delay = _retry_delay(exc.retry_after)
                if self.timeout is not None and \
                        (time.perf_counter() - t_first + delay
                         > self.timeout):
                    # the client-side budget is spent: stop the backoff
                    # loop instead of overshooting it (the reject means
                    # the server holds NO state for this job — there is
                    # nothing to cancel)
                    raise
                attempt += 1
                time.sleep(delay)
            except TimeoutError:
                # the socket timed out with the job possibly queued or
                # running server-side: without a cancel it keeps its
                # queue and quota slots until the worker pops it
                if not cancel_on_timeout:
                    raise
                try:
                    self.cancel(trace_id=trace_id)
                except (ServeError, OSError):
                    pass  # best-effort: the job may have just finished
                raise JobCancelled(
                    "cancelled",
                    f"client timeout after {self.timeout}s: sent "
                    f"cancel for trace {trace_id}",
                    {"trace_id": trace_id}) from None

    def submit_traced(self, sequences: str, overlaps: str, target: str,
                      *, trace_out: str | None = None, on_progress=None,
                      **kw) -> tuple[PolishResult, dict]:
        """One end-to-end traced submit: mints a trace_id (unless `kw`
        carries one), handshakes the server clock offset, records
        client-side spans, requests the server-side per-job trace, and
        merges both into a single Chrome-trace JSON (written to
        `trace_out` when given). Returns (result, merged_doc)."""
        from ..obs.trace import TraceRecorder

        kw.pop("trace", None)
        trace_id = kw.pop("trace_id", None) or uuid.uuid4().hex[:16]
        clock = self.clock_sync()
        rec = TraceRecorder(None)
        result = self.submit(sequences, overlaps, target,
                             trace=True, trace_id=trace_id,
                             on_progress=on_progress, recorder=rec,
                             **kw)
        doc = merge_trace(result, rec, clock, trace_id=trace_id)
        if trace_out:
            with open(trace_out, "w") as fh:
                json.dump(doc, fh)
        return result, doc

    def cancel(self, job_id: str | None = None,
               trace_id: str | None = None) -> dict:
        """Cancel a queued or running job by id and/or trace id, on a
        FRESH connection (so it works while the submitting connection
        is blocked waiting for the result). Queued jobs are dequeued —
        their waiting submitter receives a typed `cancelled` error;
        running jobs are withdrawn at the next iteration/round
        boundary. Returns the server's ok body ({"cancelled":
        "queued"|"running", "job_id"}); raises ServeError code
        `unknown-job` when nothing matches (e.g. the job already
        finished)."""
        req: dict = {"type": "cancel"}
        if job_id:
            req["job_id"] = job_id
        if trace_id:
            req["trace_id"] = trace_id
        return self.request(req)

    def ping(self) -> dict:
        return self.request({"type": "ping"})

    def stats(self) -> dict:
        return self.request({"type": "stats"})

    def healthz(self) -> dict:
        """The replica health body ({ok, draining, queue_depth, ...})
        — `ok` false once the server started draining, mirroring the
        HTTP endpoint's 503."""
        return self.request({"type": "healthz"})

    def scrape(self) -> str:
        """Live Prometheus text exposition (the same body the optional
        `--metrics-port` HTTP endpoint serves) — counters, gauges and
        latency histograms, refreshed at call time."""
        return self.request({"type": "scrape"})["text"]

    def debug(self, max_events: int = 5000) -> dict:
        """The flight recorder's recent events plus the automatic dump
        artifacts written so far — the live post-mortem view. On a
        server with the identity-audit sentinel armed, the response
        additionally carries the `audit` counters snapshot."""
        return self.request({"type": "debug", "max_events": max_events})

    def audit_ack(self) -> dict:
        """Operator acknowledgement of the identity-audit alert: clears
        the racon_tpu_audit_alert gauge (and journals the typed clear)
        until the NEXT mismatch. Returns the server's post-ack audit
        snapshot."""
        return self.request({"type": "debug", "audit_ack": True,
                             "max_events": 0})

    def shutdown(self) -> dict:
        return self.request({"type": "shutdown"})


def merge_trace(result: PolishResult, client_rec, clock: dict,
                trace_id: str | None = None) -> dict:
    """Merge the server's per-job trace (`result.trace`, timestamps in
    the SERVER recorder's timeline) with the client recorder's events
    into one Chrome-trace document on the client clock: client spans on
    pid 1, server spans on pid 2, both labeled via process_name
    metadata. A server event at ts (µs past `result.trace_base_mono`)
    lands at server_mono - offset on the client's perf_counter, then
    rebases onto the client recorder's zero. Accuracy is the handshake's
    ±rtt/2 — microseconds on localhost, which is what the transports
    here are.

    Routed jobs extend the same construction fleet-wide: pid 2 is the
    ROUTER (its plan/dispatch/stream/merge spans), and every entry the
    router pulled into `result.trace_replicas` becomes its own process
    track on pid 3+. A replica event's clock chains TWO handshakes —
    replica→router (`offset_s`, measured by the router) and
    router→client (`clock`) — so all tracks land on the client
    timeline and the per-hop rtt brackets simply add. `trace_context`
    carries the per-replica clock metadata plus a `stats` snapshot
    (serve / router / rounds blocks), which is what
    tools/tracereport.py checks span sums against."""
    from ..obs.trace import rebase_events

    events = rebase_events(client_rec.events(), pid=1,
                           name="racon_tpu client")
    routed = bool(result.router)
    if result.trace and result.trace_base_mono is not None:
        shift_us = ((result.trace_base_mono - clock["offset_s"])
                    - client_rec._base) * 1e6
        events += rebase_events(
            result.trace, pid=2, shift_us=shift_us,
            name="racon_tpu router" if routed else "racon_tpu server")
    ctx_replicas = []
    for i, rep in enumerate(result.trace_replicas or []):
        base = rep.get("base_mono")
        if base is None:
            continue
        off = float(rep.get("offset_s") or 0.0)
        # replica mono -> router mono (-off) -> client mono (-clock
        # offset), then onto the client recorder's zero
        shift_us = ((base - off - clock["offset_s"])
                    - client_rec._base) * 1e6
        events += rebase_events(
            rep.get("events") or [], pid=3 + i, shift_us=shift_us,
            name=f"racon_tpu replica {rep.get('replica')}")
        ctx_replicas.append({"replica": rep.get("replica"),
                             "offset_s": rep.get("offset_s"),
                             "rtt_s": rep.get("rtt_s")})
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0.0)))
    ctx = {"trace_id": trace_id,
           "job_id": result.job_id,
           "clock_offset_s": round(clock["offset_s"], 6),
           "clock_rtt_s": round(clock["rtt_s"], 6)}
    if ctx_replicas:
        ctx["replicas"] = ctx_replicas
    stats: dict = {}
    if result.serve:
        stats["serve"] = result.serve
    if result.router:
        stats["router"] = result.router
    if result.rounds:
        stats["rounds"] = result.rounds
    if stats:
        ctx["stats"] = stats
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "trace_context": ctx}


class _ProgressPrinter:
    """stderr renderer for `submit --progress`: a \\r-redrawn status
    line on a tty, one line per phase transition when stderr is a pipe
    (so logs stay readable, mirroring the Logger bar discipline)."""

    def __init__(self):
        self._last_phase = None
        self._tty = sys.stderr.isatty()

    def __call__(self, ev: dict) -> None:
        phase = ev.get("phase", "?")
        if phase == "queued":
            text = (f"queued at position {ev.get('position', '?')} "
                    f"(depth {ev.get('depth', '?')})")
        elif ev.get("total"):
            unit = (" windows" if phase in ("consensus", "stitch")
                    else "")  # align counts overlap pairs
            text = f"{phase} {ev.get('done', 0)}/{ev['total']}{unit}"
        else:
            text = phase
        if self._tty:
            sys.stderr.write(f"\r[racon_tpu::submit] {text:<56}")
            sys.stderr.flush()
        elif phase != self._last_phase:
            print(f"[racon_tpu::submit] {text}", file=sys.stderr)
        self._last_phase = phase

    def close(self) -> None:
        if self._tty and self._last_phase is not None:
            sys.stderr.write("\n")
            sys.stderr.flush()


# ------------------------------------------------------------------ CLI
def submit_main(argv: list[str]) -> int:
    """`racon_tpu submit` entry point: send one job to a running server,
    polished FASTA on stdout (byte-identical to the one-shot CLI)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="racon_tpu submit",
        description="submit a polishing job to a running "
                    "`racon_tpu serve` instance")
    ap.add_argument("sequences")
    ap.add_argument("overlaps")
    ap.add_argument("target")
    ap.add_argument("--socket", default=None)
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=None,
                    help="socket timeout in seconds (default: none)")
    ap.add_argument("--priority", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="job deadline in seconds: a job not STARTED in "
                         "time is cancelled in queue (deadline-expired "
                         "error); one that runs but FINISHES late still "
                         "returns its result, counted as an SLO "
                         "deadline miss (server stats `slo` view + "
                         "flight-recorder dump)")
    ap.add_argument("--retries", type=int, default=0,
                    help="re-submit after retry_after on queue-full")
    ap.add_argument("--cancel-on-timeout", action="store_true",
                    help="with --timeout: when the client socket times "
                         "out, send a cancel for this job on a fresh "
                         "connection so it frees its queue/quota slot "
                         "(and its device time if running) instead of "
                         "lingering server-side until popped")
    ap.add_argument("--progress", action="store_true",
                    help="stream live progress to stderr while the job "
                         "runs: queue position while pending, then "
                         "phase / windows-done / total as the server "
                         "interleaves progress frames before the "
                         "result")
    ap.add_argument("--stream", action="store_true",
                    help="stream polished contigs to stdout AS THEY "
                         "FINISH (`result_part` frames): each contig's "
                         "FASTA is written the moment its windows "
                         "complete on the server, the final frame "
                         "carries only the stats — the concatenated "
                         "stream is byte-identical to the buffered "
                         "output. CAVEAT: a job that fails mid-stream "
                         "leaves the already-streamed contigs on "
                         "stdout (well-formed but partial); consumers "
                         "MUST check the exit status, which is "
                         "nonzero on any failure")
    ap.add_argument("--rounds", type=int, default=None,
                    help="serve-native polishing rounds: the server "
                         "feeds round k's stitched contigs back as "
                         "round k+1's draft without leaving the warm "
                         "process (in-process re-overlap, no external "
                         "mapper); the result carries per-round wall "
                         "clocks and window-cache hit counts")
    ap.add_argument("--tenant", default=None,
                    help="fair-scheduling tenant id this job bills to "
                         "(1-64 chars of [A-Za-z0-9._-]; server "
                         "weights via RACON_TPU_SERVE_TENANT_WEIGHTS)")
    ap.add_argument("--trace-id", default=None,
                    help="name this job with a caller-chosen trace id "
                         "so another terminal can `racon_tpu cancel "
                         "--trace-id ID` it while this submit blocks "
                         "(also the correlation key in the journal "
                         "and flight-recorder artifacts)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="end-to-end trace: record client-side spans, "
                         "fetch the job's server-side spans, and write "
                         "ONE merged Chrome-trace JSON (open in "
                         "Perfetto) with both sides on a handshake-"
                         "aligned timeline")
    ap.add_argument("-u", "--include-unpolished", action="store_true")
    ap.add_argument("-f", "--fragment-correction", action="store_true",
                    help="fragment (read) error correction instead of "
                         "contig polishing: submits the job with "
                         "mode \"fragment\" — corrected reads stream "
                         "in bounded groups, byte-identical to the "
                         "one-shot CLI's -f output")
    ap.add_argument("--ingest", action="store_true",
                    help="admit-time validation: the server streaming-"
                         "parses all three inputs before queueing, so "
                         "a malformed file fails typed at the door")
    ap.add_argument("--subsample", nargs=2, type=int, default=None,
                    metavar=("REF_LEN", "COV"),
                    help="subsample-on-admit: the server subsamples "
                         "the reads to ~REF_LEN*COV bases (seeded "
                         "rampler.subsample) before polishing")
    ap.add_argument("--subsample-seed", type=int, default=None,
                    help="explicit subsample shuffle seed (default: "
                         "the server's RACON_TPU_SUBSAMPLE_SEED, else "
                         "the fixed default)")
    ap.add_argument("--normalize", action="store_true",
                    help="paired-end header normalization on admit "
                         "(racon_tpu preprocess equivalent)")
    ap.add_argument("-w", "--window-length", type=int, default=None)
    ap.add_argument("-q", "--quality-threshold", type=float, default=None)
    ap.add_argument("-e", "--error-threshold", type=float, default=None)
    ap.add_argument("--no-trimming", action="store_true")
    ap.add_argument("-m", "--match", type=int, default=None)
    ap.add_argument("-x", "--mismatch", type=int, default=None)
    ap.add_argument("-g", "--gap", type=int, default=None)
    ap.add_argument("-c", "--tpupoa-batches", type=int, default=None)
    ap.add_argument("--tpualigner-batches", type=int, default=None)
    ap.add_argument("--tpu-engine", choices=("session", "fused"),
                    default=None)
    args = ap.parse_args(argv)

    options: dict = {}
    for key, val in (("include_unpolished", args.include_unpolished
                      or None),
                     ("window_length", args.window_length),
                     ("quality_threshold", args.quality_threshold),
                     ("error_threshold", args.error_threshold),
                     ("trim", False if args.no_trimming else None),
                     ("match", args.match),
                     ("mismatch", args.mismatch),
                     ("gap", args.gap),
                     ("tpu_poa_batches", args.tpupoa_batches),
                     ("tpu_aligner_batches", args.tpualigner_batches),
                     ("tpu_engine", args.tpu_engine)):
        if val is not None:
            options[key] = val

    client = PolishClient(socket_path=args.socket, port=args.port,
                          timeout=args.timeout)
    on_progress = _ProgressPrinter() if args.progress else None
    on_part = None
    if args.stream:
        # parts hit stdout the moment they arrive — time-to-first-byte
        # is the first finished contig, not the whole job
        def on_part(frame):
            sys.stdout.buffer.write(
                frame.get("fasta", "").encode("latin-1"))
            sys.stdout.buffer.flush()
    subsample = None
    if args.subsample is not None:
        subsample = {"reference_length": args.subsample[0],
                     "coverage": args.subsample[1]}
        if args.subsample_seed is not None:
            subsample["seed"] = args.subsample_seed
    common = dict(options=options, priority=args.priority,
                  deadline_s=args.deadline, retries=args.retries,
                  tenant=args.tenant, rounds=args.rounds,
                  trace_id=args.trace_id,
                  fragment=args.fragment_correction,
                  ingest=args.ingest, subsample=subsample,
                  normalize=args.normalize,
                  on_progress=on_progress, on_part=on_part,
                  cancel_on_timeout=args.cancel_on_timeout)
    trace_doc = None
    try:
        if args.trace_out:
            # trace_out deliberately NOT passed through: the artifact
            # is written below, AFTER the polished bytes reach stdout —
            # an unwritable trace path must not discard a completed
            # polish (same posture as the metrics/trace flush in
            # emit_observability)
            result, trace_doc = client.submit_traced(
                args.sequences, args.overlaps, args.target, **common)
        else:
            result = client.submit(args.sequences, args.overlaps,
                                   args.target, **common)
    except (ServeError, OSError) as exc:
        if on_progress is not None:
            on_progress.close()
        print(f"[racon_tpu::serve] error: {exc}", file=sys.stderr)
        return 1
    if on_progress is not None:
        on_progress.close()
    if not result.streamed:
        # the body was NOT streamed (or the server ignored the stream
        # request): write it now — `--stream` against a non-streaming
        # server must still produce the FASTA, never empty stdout
        sys.stdout.buffer.write(result.fasta)
        sys.stdout.buffer.flush()
    serve = result.serve
    if serve:
        print(f"[racon_tpu::serve] job {result.job_id}: queue wait "
              f"{serve.get('queue_wait_s', 0):.3f}s, exec "
              f"{serve.get('exec_s', 0):.3f}s", file=sys.stderr)
    if result.rounds:
        walls = ", ".join(f"r{r['round']}={r['wall_s']:.3f}s"
                          for r in result.rounds.get("per_round", []))
        cache = result.rounds.get("cache")
        tail = (f", cache hits {cache['hits']}/{cache['hits'] + cache['misses']}"
                if cache else "")
        print(f"[racon_tpu::serve] rounds "
              f"{result.rounds.get('completed')}/"
              f"{result.rounds.get('requested')}: {walls}{tail}",
              file=sys.stderr)
    if trace_doc is not None:
        try:
            with open(args.trace_out, "w") as fh:
                json.dump(trace_doc, fh)
            print(f"[racon_tpu::serve] merged client+server trace "
                  f"written to {args.trace_out} (open in "
                  "https://ui.perfetto.dev)", file=sys.stderr)
        except OSError as exc:
            print(f"[racon_tpu::serve] warning: could not write trace "
                  f"to {args.trace_out} ({exc}); polished FASTA is "
                  "unaffected", file=sys.stderr)
    return 0


def cancel_main(argv: list[str]) -> int:
    """`racon_tpu cancel` entry point: cancel a queued or running job
    on a live server (or through the router, which fans the cancel out
    to the job's shards) by job id or trace id."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="racon_tpu cancel",
        description="cancel a queued or running job on a running "
                    "`racon_tpu serve` instance (or through the "
                    "router) by --job-id or --trace-id")
    ap.add_argument("--socket", default=None)
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=None,
                    help="socket timeout in seconds (default: none)")
    ap.add_argument("--job-id", default=None)
    ap.add_argument("--trace-id", default=None,
                    help="the id passed to `submit --trace-id` (or "
                         "minted by --cancel-on-timeout)")
    args = ap.parse_args(argv)
    if not args.job_id and not args.trace_id:
        print("[racon_tpu::serve] error: cancel needs --job-id or "
              "--trace-id", file=sys.stderr)
        return 1
    client = PolishClient(socket_path=args.socket, port=args.port,
                          timeout=args.timeout)
    try:
        body = client.cancel(job_id=args.job_id,
                             trace_id=args.trace_id)
    except (ServeError, OSError) as exc:
        print(f"[racon_tpu::serve] error: {exc}", file=sys.stderr)
        return 1
    extra = (f", {body['shards_cancelled']} shard(s) cancelled"
             if "shards_cancelled" in body else "")
    print(f"[racon_tpu::serve] cancelled {body.get('cancelled')} job "
          f"{body.get('job_id', args.trace_id)}{extra}",
          file=sys.stderr)
    return 0
