"""Length-prefixed JSON frame protocol for the warm polishing service.

One frame = an 8-byte header (4-byte magic ``RTPU`` + 4-byte big-endian
payload length) followed by a UTF-8 JSON object payload. JSON keeps the
wire format debuggable (``socat`` + a hexdump is a full protocol
analyzer) and dependency-free; the length prefix makes framing O(1) and
lets the server bound memory BEFORE reading a payload. Polished FASTA
rides inside the JSON as a latin-1 string — lossless for arbitrary
bytes, so byte-identity survives the wire.

Malformed-input discipline (the server must outlive every bad client):

  - payload longer than ``max_frame``   -> the declared bytes are read
    and DISCARDED in bounded chunks (the stream stays in sync), then
    `FrameTooLarge`; the server answers with a typed error response and
    the connection remains usable.
  - payload that is not valid JSON (or not a JSON object) ->
    `FrameGarbage`; stream is still framed, connection remains usable.
  - bad magic -> `FrameGarbage` with ``resync=False``: the stream can
    no longer be trusted byte-for-byte, so the server answers the typed
    error and then closes THAT connection (the server itself is
    untouched).
  - EOF mid-frame -> `FrameTruncated`; the peer is gone, nothing can be
    answered — the handler cleans up the connection quietly.

Request types: ``submit`` / ``ping`` / ``stats`` / ``healthz`` /
``scrape`` / ``debug`` / ``trace_pull`` / ``cancel`` / ``shutdown``.
Response types: ``result`` /
``pong`` / ``stats`` / ``healthz`` (``ok`` false while draining — the
RPC twin of the HTTP endpoint's 503) / ``metrics`` (Prometheus text in
``text``) / ``debug``
(flight-recorder events + dump paths) / ``trace`` (flight-ring spans
windowed to one trace id) / ``ok`` / ``error`` (with a
machine-readable ``code``; ``queue-full`` errors carry ``retry_after``
seconds, ``job-failed`` errors carry ``error_type`` from the errors.py
hierarchy).

Cancellation & QoS (README "QoS & preemption"): a ``cancel`` request
carries ``job_id`` and/or ``trace_id`` and answers ``{"type": "ok",
"cancelled": "queued"|"running", "job_id"}`` — a queued job is
dequeued (its waiting submitter receives a typed ``cancelled`` error
response through its own connection), a running job is withdrawn at
the next iteration/round boundary and fails typed ``cancelled``; an
unmatched id answers ``error`` code ``unknown-job``. A submit whose
deadline is provably unmeetable (server started with an abort margin)
is refused typed ``deadline-doomed`` with ``predicted_s`` /
``remaining_s``; the same code can arrive mid-run when the
iteration-boundary estimate says the deadline is lost.

Trace context, live progress and streamed results (all opt-in per
submit, README "Serving"): a ``submit`` may carry a client-minted
``trace_id`` (1-64 chars of ``[A-Za-z0-9._-]``) that the server stamps
onto its spans, journal lines and interleaved frames; a ``tenant`` id
(same charset) naming the fair-scheduling bucket the job bills to;
``"progress": true``, which makes the server INTERLEAVE ``progress``
frames on the submitting connection before the final
``result``/``error`` frame — ``{"type": "progress", "job_id", "seq",
"phase", ...}`` with monotonically increasing ``seq``, queue
``position``/``depth`` while pending, then ``done``/``total`` window
counts per phase; and ``"stream": true``, which makes the server send
each polished contig as a ``{"type": "result_part", "job_id", "part",
"name", "fasta"}`` frame the moment its windows complete — the final
``result`` frame then carries ``streamed: true`` + ``parts`` and the
stats WITHOUT the fasta body (the parts' concatenation IS the body,
byte-identical to the buffered path). ``pong`` responses carry
``mono_s`` (the server's ``time.perf_counter``), the clock-handshake
sample clients RTT-bracket to merge client- and server-side spans onto
one timeline.

Iterative rounds (opt-in per submit, README "Iterative rounds & window
cache"): a ``submit`` may carry ``"rounds": N`` (1..64) asking the
server to run N serve-native polishing rounds — round k's stitched
contigs are fed back as round k+1's draft without leaving the warm
process (in-process re-overlap, ``core/remap.py``). The FASTA returned
(or streamed: only the FINAL round streams ``result_part`` frames) is
round N's output, byte-identical to N chained solo runs through
``Polisher.redraft``. The final ``result`` then adds a ``rounds`` block:
``{"requested", "completed", "per_round": [{"round", "wall_s",
"windows", "iterations", "sequences", "cache"?}], "cache": {"hits",
"misses"}?}`` (the ``cache`` entries appear only on servers with the
content-addressed window cache armed). Omitting ``rounds`` keeps the
classic single-pass contract untouched.

Child-job fields (router fan-out, serve/router.py): when a shard-aware
router splits one client submit across replicas, each child ``submit``
carries ``parent`` (the router-side parent job id), ``shard`` /
``shards`` (this child's slot in the contig fan-out), the parent's
``rounds`` field when set (each shard runs its own rounds over its
contig subset) and a derived ``trace_id`` of ``<parent trace>.s<k>`` — the "." is in the trace-id
charset precisely so child ids stay valid. The parent's QoS fields
ride every child frame too: ``priority`` and ``tenant`` verbatim, and
``deadline_s`` as the REMAINING parent budget recomputed at each
dispatch attempt (a requeued shard inherits what is left of the
parent's deadline, never a reset one); a parent-level cancel or
deadline-abort fans ``cancel`` frames out to all sibling shards by
child trace id. Replicas journal the three
fields on the child's ``received`` line for cross-correlation with the
router's ledger and otherwise ignore them, which also means a child
submit sent to a pre-router replica is handled as a plain job (unknown
top-level submit keys are ignored by contract). A router's
``result_part`` frames add a ``shard`` field and renumber ``part``
globally in contig order; its final ``result`` adds a ``router`` block
(``shards`` / ``requeues`` / ``parts`` / ``wall_s``).

Distributed tracing (README "Distributed tracing & cost accounting"):
a ``trace_pull`` request carries ``trace_id`` (trace-id charset; a
parent id matches its dotted ``<trace>.s<k>`` children too) and an
optional ``max_events`` cap (RACON_TPU_TRACE_PULL_EVENTS, default
2048); the ``trace`` response carries ``events`` (the replica's
always-on flight-ring spans windowed to that trace), ``base_mono``
(the ring recorder's time zero in that process's ``perf_counter``
terms, ``null`` when no ring is installed) and a fresh ``mono_s``
sample. Child submits deliberately do NOT carry ``trace: true`` —
replica spans come from the always-on ring via ``trace_pull``, never
from a per-job scoped recorder (which would serialize same-replica
shards). A ROUTED submit with ``trace: true`` answers with ``trace`` /
``trace_base_mono`` holding the ROUTER's own spans (plan / dispatch
with held-for-idle time / stream / merge / requeue / cancel fan-out),
``trace_replicas`` — one ``{replica, events, base_mono, offset_s,
rtt_s}`` entry per participating replica, clock-synced against the
router via the ping ``mono_s`` min-RTT bracket — and a per-shard
``shards_detail`` list inside the ``router`` block (queue_wait_s /
exec_s / batch per shard, the stage-stats side of tracereport's
span-sums consistency check). All three keys appear ONLY on traced
submits; untraced routed frames are byte-identical to the pre-tracing
wire shape.

Window-range child jobs (sub-contig sharding): when routable replicas
outnumber contigs, the router also splits single contigs by target
coordinate at window-grid boundaries. Such a child ``submit`` adds
``range_lo`` / ``range_hi`` (integers, ``0 <= lo < hi``): the replica
polishes only windows whose grid start ``j`` (multiples of
``window_length``) satisfies ``lo <= j < hi``, and streams the contig
*segment*. Range-child ``result_part`` frames differ from whole-contig
parts: ``fasta`` is the raw polished segment (latin-1 bytes, **no**
``>name`` header, no trailing newline — the concatenation-is-the-body
rule does not apply) plus a ``seg`` stats dict ``{"polished",
"windows", "total_windows", "coverage", "lo", "hi"}`` from which the
router reassembles the full contig in coordinate order and re-derives
the solo-identical header tags (LN/RC/XC). ``range_lo``/``range_hi``
cannot be combined with ``rounds`` (typed ``bad-request``). Because a
pre-range replica would silently ignore the keys and return the FULL
contig, the router treats a range part arriving without ``seg`` as a
typed ``replica-incompatible`` failure rather than merging garbage.

Fragment jobs (read error correction, README "Fragment correction &
ingest"): a ``submit`` may carry ``mode: "contig"`` (the default, a
no-op) or ``mode: "fragment"``, which routes the job into the
reference's second workload — ``PolisherType.kF`` read correction
(one-shot CLI ``-f``) — through the same warm-reuse / continuous-
batcher / QoS / audit / journal path contig jobs use. Because targets
are many small reads, a streaming fragment job ships its corrected
reads in BOUNDED GROUPS, never one frame per read: each
``result_part`` frame carries ``{"part", "reads", "frag": [lo, hi),
"fasta"}`` — ``fasta`` is the classic concatenation-is-the-body FASTA
of up to ``frag_group`` (RACON_TPU_FRAG_GROUP, default 64) consecutive
corrected reads, ``reads`` how many survived dropping, and ``frag``
the half-open GLOBAL target-index interval the group accounts for
(dropped reads still advance it, so consecutive frames' intervals
tile). Invalid combinations are typed ``bad-request``: an unknown
``mode`` value, ``mode: "fragment"`` with ``range_lo``/``range_hi``
(fragment jobs shard the read INDEX axis, not a coordinate axis), and
``mode: "fragment"`` with ``rounds > 1`` (corrected reads are not a
draft to re-map onto; ``rounds: 1`` is accepted). A submit WITHOUT a
``mode`` field is byte-identical to the pre-fragment wire contract —
including legacy ``options.fragment_correction`` jobs, which keep
their per-contig streaming shape.

Fragment child jobs (read-range sharding, serve/router.py): the
router's third planner shards a ``mode: "fragment"`` submit across
replicas by TARGET-INDEX slices at read boundaries — every child
shares the parent's original target path (no per-shard file rewrite)
and adds ``frag_lo`` / ``frag_hi`` (integers, ``0 <= lo < hi``,
require ``mode: "fragment"``, reject ``rounds``): the replica corrects
only the reads whose target-file index falls in ``[lo, hi)`` and
rebases its group frames' ``frag`` receipts to the GLOBAL read axis.
Slices are contiguous and ascending, so the router's shard-order merge
IS global read order, and the requeue/dedupe ledger (kill -9 failover,
preemption, tracing all unchanged) operates at read-group granularity
— the ``frag`` receipts across shards tile ``[0, n_reads)``. The
routed ``result`` adds ``fragment: true`` / ``frag_shards`` /
``reads`` to its ``router`` block.

Admit-time ingest (serve/ingest.py, README "Fragment correction &
ingest"): a ``submit`` may opt in with ``ingest: true`` (streaming-
validate all three inputs on admit — gzipped FASTA/FASTQ/SAM parsed in
bounded chunks; a malformed file fails typed ``bad-request`` with a
``rejected-ingest`` journal terminal, never mid-polish and never the
server), ``subsample: {"reference_length": int, "coverage": int,
"seed"?: int}`` (subsample-on-admit through the seeded
``rampler.subsample`` — deterministic, so resubmits and router
children agree byte-for-byte) and/or ``normalize: true`` (paired-end
header uniquification, the ``racon_tpu preprocess`` role). Jobs
without these keys never touch the ingest plane.
"""

from __future__ import annotations

import json
import os
import socket
import struct

MAGIC = b"RTPU"
_HEADER = struct.Struct(">4sI")

#: discard granularity while draining an oversized payload
_DRAIN_CHUNK = 1 << 16


def max_frame_bytes() -> int:
    """The SERVER's receive ceiling (RACON_TPU_SERVE_MAX_FRAME, default
    256 MiB) — it bounds what an untrusted client can make the server
    buffer. Clients reading RESULTS from a trusted server use the wire
    limit instead (PolishClient passes `WIRE_LIMIT`), so a polished
    assembly bigger than the server's request ceiling still comes back."""
    try:
        return int(os.environ.get("RACON_TPU_SERVE_MAX_FRAME", 0)) or \
            (256 << 20)
    except ValueError:
        return 256 << 20


class ProtocolError(Exception):
    """Base for frame-level failures; `code` is the wire error code."""

    code = "bad-frame"
    #: whether the stream is still framed after this error (the server
    #: may answer and keep the connection)
    resync = True

    def __init__(self, message: str, resync: bool | None = None):
        super().__init__(message)
        if resync is not None:
            self.resync = resync


class FrameTooLarge(ProtocolError):
    code = "frame-too-large"


class FrameGarbage(ProtocolError):
    code = "bad-frame"


class FrameTruncated(ProtocolError):
    code = "bad-frame"
    resync = False


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    """Read exactly `n` bytes; b"" on clean EOF at offset 0,
    FrameTruncated on EOF mid-read."""
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, _DRAIN_CHUNK))
        if not chunk:
            if got == 0:
                return b""
            raise FrameTruncated(
                f"connection closed mid-frame ({got}/{n} bytes)")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


#: hard wire limit: the length prefix is a u32
WIRE_LIMIT = 0xFFFFFFFF


def send_frame(sock: socket.socket, obj: dict) -> None:
    payload = json.dumps(obj, separators=(",", ":")).encode()
    if len(payload) > WIRE_LIMIT:
        # the u32 length prefix cannot carry it; raise typed (the
        # server handler answers with an error frame) instead of
        # letting struct.error escape mid-send
        raise FrameTooLarge(
            f"frame of {len(payload)} bytes exceeds the 4 GiB wire "
            "limit")
    sock.sendall(_HEADER.pack(MAGIC, len(payload)) + payload)


def recv_frame(sock: socket.socket,
               max_frame: int | None = None) -> dict | None:
    """Read one frame; None on clean EOF (peer closed between frames).
    Raises the ProtocolError hierarchy above on malformed input."""
    limit = max_frame if max_frame is not None else max_frame_bytes()
    header = _recv_exact(sock, _HEADER.size)
    if not header:
        return None
    magic, length = _HEADER.unpack(header)
    if magic != MAGIC:
        raise FrameGarbage(
            f"bad frame magic {magic!r} (stream desynced)", resync=False)
    if length > limit:
        # the client DID send these bytes: drain them so the stream
        # stays framed, then report — the connection survives
        left = length
        while left > 0:
            chunk = sock.recv(min(left, _DRAIN_CHUNK))
            if not chunk:
                raise FrameTruncated(
                    "connection closed draining oversized frame")
            left -= len(chunk)
        raise FrameTooLarge(
            f"frame of {length} bytes exceeds limit {limit}")
    payload = _recv_exact(sock, length)
    if length and not payload:
        raise FrameTruncated("connection closed before frame payload")
    try:
        obj = json.loads(payload.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as exc:
        raise FrameGarbage(f"frame payload is not JSON ({exc})") from None
    if not isinstance(obj, dict):
        raise FrameGarbage(
            f"frame payload is {type(obj).__name__}, expected object")
    return obj


def error_response(code: str, message: str, **extra) -> dict:
    out = {"type": "error", "code": code, "message": message}
    out.update(extra)
    return out
