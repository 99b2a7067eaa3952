"""Span tracing: the program's one span idiom.

The reference exposes only coarse phase timings (src/logger.cpp); a slow
or degraded run gives no way to see WHERE the time went. `span(name,
**args)` is a context that times one piece of work where it happens —
pipeline pack/device/unpack/fallback stages per chunk, the polisher's
phases and their parts, engine dispatch loops, watchdog backoff — and
hands it to two sinks:

  * the Chrome recorder (`TraceRecorder`), armed by
    RACON_TPU_TRACE=<out.json> (mirrored by the CLI's `--tpu-trace`),
    an explicit `configure()`, or the serve layer's flight ring. It
    also keeps instant events for every resilience counter bump and
    writes Chrome trace-event JSON loadable in Perfetto
    (https://ui.perfetto.dev) or chrome://tracing;
  * a running JAX profiler capture (`--tpu-jax-profile`, or any
    `jax.profiler.trace` around the run), where the span is a
    `jax.profiler.TraceAnnotation` named `racon.<name>` with the same
    arguments — on the capture's own clock, beside the device tracks.

Design constraints, in order:

  1. OFF BY DEFAULT, near-zero overhead when off: with no recorder and
     no capture, `span()` returns the shared null span after one
     `is None` check and one `TraceAnnotation.is_enabled()` call. The
     capture itself is the switch for the profiler sink.
  2. Low overhead when ON: events append to per-thread buffers (no lock
     on the hot path — each pipeline worker owns its list; the shared
     lock is taken once per thread, at buffer registration), timestamps
     come from the monotonic `time.perf_counter` clock, and
     serialization happens once, at `save()`. Sites that charge a
     counter open their span with `timed()` and charge it from the
     span's own `t0`/`t1`, so per-stage span-duration sums equal the
     stage wall-clock counters by construction (pinned by
     tests/test_obs.py).
  3. Thread-safe: concurrent pipeline threads (pack worker, dispatcher,
     unpack worker, fallback pool, watchdog workers) record freely;
     `events()` snapshots every buffer and sorts by timestamp.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

#: prefix of every span's name inside a JAX profiler capture
CAPTURE_PREFIX = "racon."

# the annotation class, resolved on the first span after jax is
# imported: no capture can run before that, and this module must not
# import jax itself (the serve client and the tools load it without)
_annotation_cls = None
# per-thread stack of the spans open in a capture (for tag())
_open = threading.local()


def capturing() -> bool:
    """Whether a JAX profiler capture is running in this process."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls.is_enabled()


class _Span:
    """One live span. Records its own `time.perf_counter` endpoints
    (`t0`, `t1`); on exit it feeds the armed recorder (if any), and
    while a profiler capture runs it is also the annotation
    `racon.<name>`. A span that exits by an exception is still
    recorded: the time went somewhere."""

    __slots__ = ("_rec", "name", "args", "_ann", "t0", "t1")

    def __init__(self, rec, name: str, args: dict | None, capture: bool):
        self._rec = rec
        self.name = name
        self.args = args
        self._ann = (_annotation_cls(CAPTURE_PREFIX + name, **(args or {}))
                     if capture else None)
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info) -> None:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            _open.stack.pop()
            self._ann.__exit__(*exc_info)
        if self._rec is not None:
            self._rec.complete(self.name, self.t0, self.t1, self.args)

    def set(self, **args) -> None:
        """Add arguments known only once the work is done."""
        if self._rec is not None:
            self.args = {**(self.args or {}), **args}
        if self._ann is not None:
            self._ann.set_metadata(**args)


class _NullSpan:
    """Shared no-op context for the nothing-recording path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc_info) -> None:
        pass

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class TraceRecorder:
    """Append-only per-thread event buffers with one shared time base."""

    def __init__(self, path: str | None = None):
        self.path = path
        self._pid = os.getpid()
        self._base = time.perf_counter()
        self._lock = threading.Lock()
        self._buffers: list[list] = []
        self._threads: dict[int, str] = {}
        self._next_tid = 1
        self._local = threading.local()

    # ------------------------------------------------------------ recording
    def _buf(self) -> list:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            # synthetic per-registration tid, NOT threading.get_ident():
            # the OS reuses idents, so the consensus phase's workers
            # would land on (and relabel) the dead align-phase workers'
            # tracks — every registered thread gets its own track
            # (obs/flight.py overrides this with a shared bounded ring
            # and name-keyed tids)
            t = threading.current_thread()
            buf = self._local.buf = []
            with self._lock:
                tid = self._next_tid
                self._next_tid += 1
                self._buffers.append(buf)
                self._threads[tid] = t.name
            self._local.tid = tid
        return buf

    def _us(self, t: float) -> float:
        # clamp: a caller-supplied endpoint can predate this recorder
        # (env-armed tracer created lazily mid-phase); negative ts would
        # fail the faultcheck gate and misrender in Perfetto
        return round(max(0.0, t - self._base) * 1e6, 3)

    def rebase(self, base: float) -> None:
        """Move the recorder's time zero EARLIER, to perf_counter
        `base`, so spans that predate its creation — a serve job's
        queue wait — keep their real offsets instead of clamping to 0.
        Only valid before events are recorded with the old base (the
        serve layer calls it first thing inside a fresh per-job scope);
        later-or-equal bases are ignored."""
        if base < self._base:
            self._base = base

    def complete(self, name: str, t0: float, t1: float,
                 args: dict | None = None) -> None:
        """Record a finished span from its `time.perf_counter` endpoints:
        the sink of every live span, and the way a span measured
        elsewhere (a remote recorder's, a client's) is merged in."""
        buf = self._buf()
        ev = {"name": name, "cat": "racon_tpu", "ph": "X",
              "ts": self._us(t0), "dur": round(max(0.0, t1 - t0) * 1e6, 3),
              "pid": self._pid, "tid": self._local.tid}
        if args:
            ev["args"] = args
        buf.append(ev)

    def instant(self, name: str, args: dict | None = None) -> None:
        buf = self._buf()
        ev = {"name": name, "cat": "racon_tpu", "ph": "i", "s": "t",
              "ts": self._us(time.perf_counter()),
              "pid": self._pid, "tid": self._local.tid}
        if args:
            ev["args"] = args
        buf.append(ev)

    # ------------------------------------------------------------ emission
    def events(self) -> list[dict]:
        """Timestamp-sorted snapshot of every buffer, prefixed with the
        thread-name metadata events Perfetto uses to label tracks."""
        with self._lock:
            buffers = list(self._buffers)
            threads = dict(self._threads)
        meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                 "tid": tid, "args": {"name": tname}}
                for tid, tname in sorted(threads.items())]
        evs: list[dict] = []
        for buf in buffers:
            evs.extend(list(buf))  # list() snapshots concurrent appends
        evs.sort(key=lambda e: e["ts"])
        return meta + evs

    def save(self, path: str | None = None) -> str:
        """Write the Chrome trace-event JSON object form (the format
        Perfetto and chrome://tracing both load)."""
        path = path or self.path
        if not path:
            raise ValueError("TraceRecorder.save: no output path")
        with open(path, "w") as fh:
            json.dump({"traceEvents": self.events(),
                       "displayTimeUnit": "ms"}, fh)
        return path


# ----------------------------------------------------------- module state
#: resolved-once process tracer: None (the common case — every hook is a
#: single `is None` check) or the armed recorder
_tracer: TraceRecorder | None = None
_resolved = False


def get_tracer() -> TraceRecorder | None:
    """The process tracer, armed lazily from RACON_TPU_TRACE on first
    call (None when unset — the zero-overhead clean path)."""
    global _tracer, _resolved
    if not _resolved:
        path = os.environ.get("RACON_TPU_TRACE")
        _tracer = TraceRecorder(path) if path else None
        _resolved = True
    return _tracer


def configure(path: str | None = None) -> TraceRecorder:
    """Explicitly arm (or re-arm) recording — tests and tools; the CLI
    path goes through the RACON_TPU_TRACE env so subprocesses inherit."""
    global _tracer, _resolved
    _tracer = TraceRecorder(path)
    _resolved = True
    return _tracer


def install(recorder: TraceRecorder) -> TraceRecorder:
    """Arm a caller-built recorder (e.g. the serve layer's bounded
    FlightRecorder, obs/flight.py) as the process tracer — every
    existing hook starts feeding it. Returns the recorder."""
    global _tracer, _resolved
    _tracer = recorder
    _resolved = True
    return recorder


def reset() -> None:
    """Drop the tracer and the env resolution (tests re-arm per case)."""
    global _tracer, _resolved
    _tracer = None
    _resolved = False


class _TeeRecorder:
    """Duck-typed recorder forwarding every event to several recorders
    — how a scoped per-job trace coexists with an already-armed
    process recorder (the serve layer's always-on flight ring,
    obs/flight.py): the job gets its own events AND the ring keeps
    recording, so a concurrent job's post-mortem dump has no blind
    window. Only the recording surface (`complete`/`instant`) fans
    out; `events`/`save` delegate to the primary recorder."""

    def __init__(self, primary: TraceRecorder, *others: TraceRecorder):
        self._recs = (primary,) + others
        self.path = primary.path

    def complete(self, name, t0, t1, args=None) -> None:
        for rec in self._recs:
            rec.complete(name, t0, t1, args)

    def instant(self, name, args=None) -> None:
        for rec in self._recs:
            rec.instant(name, args)

    def events(self) -> list[dict]:
        return self._recs[0].events()

    def save(self, path: str | None = None) -> str:
        return self._recs[0].save(path)


class scoped:
    """Context manager arming a fresh in-memory recorder and restoring
    the previous tracer state (armed or unresolved) on exit — the serve
    layer's per-job trace scoping. The recorder is process-global for
    the duration, so spans from concurrent jobs sharing the process land
    in it too (one process, shared device: documented, not hidden).
    When a recorder is ALREADY armed (the always-on flight ring, or an
    RACON_TPU_TRACE trace), the scope installs a tee so the outer
    recorder keeps seeing every span — a traced job must not open a
    blind window in a concurrent job's flight dump.

    Scopes SERIALIZE on a module lock: the save/restore of the global
    tracer is not reentrant (overlapping scopes restoring out of order
    would leave the process tracer pointing at a dead per-job recorder),
    so a second traced job waits for the first to finish."""

    _lock = threading.Lock()

    def __enter__(self) -> TraceRecorder:
        global _tracer, _resolved
        self._lock.acquire()
        prev = get_tracer()  # resolve the env posture BEFORE saving it
        self._prev = (_tracer, _resolved)
        rec = TraceRecorder(None)
        _tracer = rec if prev is None else _TeeRecorder(rec, prev)
        _resolved = True
        return rec

    def __exit__(self, *exc_info) -> None:
        global _tracer, _resolved
        _tracer, _resolved = self._prev
        self._lock.release()


def save(path: str | None = None) -> str | None:
    """Write the armed tracer's events to its configured path (or
    `path`); None when tracing is off or has nowhere to write — callers
    use this as the unconditional end-of-run hook."""
    tr = get_tracer()
    if tr is None or not (path or tr.path):
        return None
    return tr.save(path)


def rebase_events(events: list[dict], pid: int, shift_us: float = 0.0,
                  name: str | None = None) -> list[dict]:
    """Re-stamp a snapshot of trace events onto process `pid`, shifting
    span/instant timestamps by `shift_us` — how a REMOTE recorder's
    events (the serve layer's per-job trace, whose clock is the
    server's perf_counter) merge into a local timeline as their own
    Perfetto process track. Returns fresh event dicts (inputs are not
    mutated), prefixed with a `process_name` metadata event when `name`
    is given; thread metadata ("M") keeps its original timestampless
    shape so track labels survive the move."""
    out: list[dict] = []
    if name is not None:
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": name}})
    for ev in events:
        ev = dict(ev)
        ev["pid"] = pid
        if ev.get("ph") != "M" and "ts" in ev:
            ev["ts"] = round(max(0.0, ev["ts"] + shift_us), 3)
        out.append(ev)
    return out


def trace_matches(args: dict | None, trace_id: str) -> bool:
    """True when a span/instant's args tie it to `trace_id` or to one of
    its descendants — the router's child shards carry dotted ids
    (`<trace>.s<k>`), so a match is exact OR by dotted prefix. Two arg
    shapes exist in the fabric: per-job spans carry a single `trace_id`
    string, batched lane iterations carry a `trace_ids` list (one entry
    per co-scheduled job); either side matching counts."""
    if not args:
        return False

    def _hit(t) -> bool:
        return isinstance(t, str) and (
            t == trace_id or t.startswith(trace_id + "."))

    if _hit(args.get("trace_id")):
        return True
    tids = args.get("trace_ids")
    return isinstance(tids, (list, tuple)) and any(_hit(t) for t in tids)


def enabled() -> bool:
    """Whether a span opened now records anywhere: a recorder is armed
    or a profiler capture runs. Sites whose span arguments cost work to
    build check it once per loop."""
    return get_tracer() is not None or capturing()


def span(name: str, **args):
    """A live span when a recorder is armed or a capture runs, else the
    shared null span."""
    tr = get_tracer()
    capture = capturing()
    if tr is None and not capture:
        return _NULL_SPAN
    return _Span(tr, name, args or None, capture)


def timed(name: str, **args) -> _Span:
    """As span(), but always a live span with `t0`/`t1`: for sites that
    charge a counter or a histogram from the same endpoints."""
    return _Span(get_tracer(), name, args or None, capturing())


def tag(**args) -> None:
    """Add arguments to this thread's innermost span open in a profiler
    capture (none open: a no-op). How a measurement taken inside a span
    — the compile seconds its dispatch paid — reaches the capture."""
    stack = getattr(_open, "stack", None)
    if stack:
        stack[-1].set(**args)


def instant(name: str, **args) -> None:
    tr = get_tracer()
    if tr is not None:
        tr.instant(name, args or None)
