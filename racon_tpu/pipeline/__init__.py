"""Double-buffered async dispatch pipeline.

The reference keeps every CUDA device saturated by running several batch
objects per GPU off a shared work index, so host-side fill/fetch of one
batch overlaps device compute of another (cudapolisher.cpp:165-199,
228-345). Both of our hot phases were strictly synchronous instead: pack a
chunk on host, block on the device call, unpack on host, repeat — with all
host-fallback work serialized after the device pass. `DispatchPipeline` is
the TPU-shaped equivalent of the reference's per-device batch threads:

  - a PACK worker thread builds chunk k+1's padded operands while
  - the caller's thread DISPATCHES chunk k to the device (JAX dispatch is
    async — the call returns as soon as the program is enqueued) while
  - an UNPACK worker thread blocks on chunk k-1's results and finishes
    them on host (CIGAR traceback for the aligner, C++ consensus for the
    fused POA engine) while
  - a small FALLBACK thread pool chews host-only work (envelope-tail
    windows, band-clipped overlaps) as soon as it is discovered instead
    of after the device pass.

`depth` bounds how many chunks sit packed-but-undispatched and
dispatched-but-unwaited (double buffering at the default depth=2);
`depth=0` is the fully synchronous single-threaded path — byte-identical
output, kept for bisection — in which `submit_fallback` also runs inline.

Stage wall-clock is accumulated into a `PipelineStats` (shareable across
phases): pack / device / unpack / fallback seconds plus chunk, launch and
error counts. "device seconds" is time spent against the compute stage:
dispatching (which for a host compute engine is the blocking native call
itself) plus the time the unpack worker spends blocked on results — with
real overlap, pack+unpack+device stage seconds exceed the phase's wall
time; in a dead (synchronous) pipeline they are additive. bench.py
publishes the counters in its JSON artifact so the overlap is measurable,
not anecdotal.

Error discipline: without `on_error`, the first stage exception aborts the
run and re-raises (the RACON_TPU_STRICT posture). With `on_error(item,
exc)`, the failed chunk is skipped and the run continues — callers route
the chunk's items to their host fallback, the per-window GPU->CPU
discipline of cudapolisher.cpp:354-383 at chunk granularity. `on_error`
itself raising aborts the run with that exception.

Resilience (racon_tpu/resilience/): the pipeline is the arming point for
the deterministic fault-injection harness (RACON_TPU_FAULT_PLAN hooks at
the pack/device/unpack stages and the fallback pool) and for the device
watchdog — with a `Watchdog` configured, dispatch runs under its deadline
with bounded retry + exponential backoff, the result wait under the
deadline only (re-waiting on a hung handle would just burn a second
deadline), and fallback jobs get the same bounded retry. Both default
from the environment and stay None when unconfigured, so the clean path
pays a single `is None` check per stage.

RACON_TPU_DEVICE_LATENCY_S / RACON_TPU_DEVICE_LATENCY_X (default
unset) sleep a simulated accelerator round-trip per chunk — a fixed
floor after the result wait, or a multiplier on the chunk's measured
dispatch time. The CPU dev posture's device stage is pure host compute;
these reproduce the device-dominated regime (off-CPU waits that
overlap across replicas) the serve fleet benches measure their scaling
against.
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

from ..errors import RaconError
from ..obs import trace
from ..resilience import Watchdog, get_fault_plan

_STOP = object()


def _env_device_latency(name: str) -> float:
    """Simulated-device pacing knobs, both slept OFF-CPU per chunk (the
    CPU dev posture has no real accelerator, so its device stage is pure
    host compute; these reproduce the device-DOMINATED regime — waits a
    caller can overlap across replicas — the serve fleet benches scale
    against):

      RACON_TPU_DEVICE_LATENCY_S  fixed seconds added after each
                                  chunk's result wait (round-trip floor)
      RACON_TPU_DEVICE_LATENCY_X  multiplier on each chunk's measured
                                  dispatch time (a device whose
                                  round-trip scales with batch size)

    Unset/0 is the default and costs one comparison per run."""
    raw = os.environ.get(name, "")
    if not raw:
        return 0.0
    try:
        lat = float(raw)
    except ValueError:
        raise RaconError(
            "pipeline.DispatchPipeline",
            f"invalid {name} {raw!r} (expected a float)!") from None
    if lat < 0:
        raise RaconError(
            "pipeline.DispatchPipeline", f"{name} must be >= 0!")
    return lat

#: PipelineStats keys whose bumps are semantic events, mirrored as trace
#: instant events when the tracer is armed — the counter and the trace
#: can never disagree because both come from the same bump
_INSTANT_KEYS = frozenset(("faults", "retries", "timeouts",
                           "breaker_trips", "quarantined", "cancelled"))

#: stage-seconds keys mirrored into latency histograms when a
#: HistogramSet is attached (obs/hist.py): each bump is one chunk's
#: stage duration, so the histogram is the per-chunk distribution of
#: the same wall-clock the counters total. device_s is NOT here: it is
#: bumped twice per chunk (dispatch + wait segments), so the loops
#: observe `pipeline.device` themselves as the per-chunk SUM — one
#: sample per chunk, comparable with the other stages
_HIST_KEYS = {"pack_s": "pipeline.pack",
              "unpack_s": "pipeline.unpack",
              "fallback_s": "pipeline.fallback"}


class PipelineStats:
    """Thread-safe per-stage counters, shareable across pipeline phases.

    The first two key groups are the PR-1 overlap counters; the
    resilience group (faults injected, watchdog retries/timeouts, backoff
    seconds slept, circuit-breaker trips, quarantined windows, cancelled
    fallback futures) is the degradation report — all zero on a clean
    run, published together in bench.py's JSON artifact."""

    _FLOAT_KEYS = ("pack_s", "device_s", "unpack_s", "fallback_s",
                   "backoff_s")
    _INT_KEYS = ("launches", "chunks", "errors",
                 "faults", "retries", "timeouts", "breaker_trips",
                 "quarantined", "cancelled")
    KEYS = _FLOAT_KEYS + _INT_KEYS

    def __init__(self, hists=None):
        self._lock = threading.Lock()
        self._v = {k: 0.0 for k in self._FLOAT_KEYS}
        self._v.update({k: 0 for k in self._INT_KEYS})
        #: optional obs.hist.HistogramSet: per-chunk stage durations
        #: observed as latency distributions (None — one `is None`
        #: check per bump — when nothing is watching)
        self.hists = hists

    def bump(self, key: str, amount=1) -> None:
        with self._lock:
            self._v[key] += amount
        if self.hists is not None:
            name = _HIST_KEYS.get(key)
            if name is not None:
                self.hists.observe(name, amount)
        if key in _INSTANT_KEYS:
            tr = trace.get_tracer()
            if tr is not None:
                tr.instant(f"resilience.{key}", {"n": amount})

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._v)


class DispatchPipeline:
    """Stage driver for one device-batched loop (see module docstring).

    run(items, pack, dispatch, wait, unpack):
      pack(item) -> operands            host work, pack worker thread
      dispatch(item, operands) -> h     caller's thread (async device call)
      wait(h) -> result                 blocks on the device, unpack thread
      unpack(item, result) -> None      host work, unpack worker thread

    Items flow through the stages in order; unpack order equals dispatch
    order (FIFO), so result assembly is deterministic. All device calls
    stay on the caller's thread — the only JAX interaction off it is
    blocking on/fetching finished results in `wait`.
    """

    def __init__(self, depth: int = 2, fallback_workers: int = 2,
                 stats: PipelineStats | None = None, watchdog=None,
                 faults=None):
        self.depth = max(0, int(depth))
        self.fallback_workers = max(1, int(fallback_workers))
        self.stats = stats if stats is not None else PipelineStats()
        # resilience hooks: explicit objects win (the polisher threads its
        # CLI knobs through); otherwise the env posture applies so every
        # pipeline in the process is injectable/guarded. Both are None —
        # zero-overhead — when nothing is configured. `faults=False`
        # DISABLES injection entirely, ignoring even the env plan — the
        # audit sentinel's oracle re-execution must reproduce ground
        # truth, never re-fire the fault it is trying to detect.
        self.watchdog = watchdog if watchdog is not None \
            else Watchdog.from_env()
        self.faults = (None if faults is False
                       else faults if faults is not None
                       else get_fault_plan())
        self.device_latency_s = _env_device_latency(
            "RACON_TPU_DEVICE_LATENCY_S")
        self.device_latency_x = _env_device_latency(
            "RACON_TPU_DEVICE_LATENCY_X")
        self._fb_counter = itertools.count()
        self._executor: ThreadPoolExecutor | None = None
        self._futures: list[Future] = []

    # ------------------------------------------------------------ stages
    def run(self, items, pack, dispatch, wait, unpack, on_error=None,
            label: str | None = None, describe=None) -> None:
        """`label` names this loop in the trace (aligner / fused /
        host_poa); `describe(item) -> dict` supplies per-chunk span args
        (engine, bucket, job count). Both are ignored — zero cost — when
        no span records (no recorder armed, no profiler capture)."""
        items = list(items)
        if self.device_latency_x > 0.0:
            # wrapped before instrumentation so the stall counts as
            # device time under the watchdog deadline, exactly as a
            # real accelerator round-trip would
            inner_dispatch, x = dispatch, self.device_latency_x

            def dispatch(item, ops, _d=inner_dispatch, _x=x):
                t0 = time.perf_counter()
                handle = _d(item, ops)
                time.sleep((time.perf_counter() - t0) * _x)
                return handle
        if self.device_latency_s > 0.0:
            inner_wait, lat = wait, self.device_latency_s

            def wait(handle, _wait=inner_wait, _lat=lat):
                res = _wait(handle)
                time.sleep(_lat)
                return res
        if self.faults is not None or self.watchdog is not None:
            pack, dispatch, wait, unpack = self._instrument(
                pack, dispatch, wait, unpack)
        def args_of(idx, item) -> dict:
            return {}

        if trace.enabled():
            def args_of(idx, item) -> dict:
                a = {"chunk": idx}
                if label:
                    a["loop"] = label
                if describe is not None:
                    a.update(describe(item))
                return a
        if self.depth == 0:
            self._run_sync(items, pack, dispatch, wait, unpack, on_error,
                           args_of)
            return
        self._run_async(items, pack, dispatch, wait, unpack, on_error,
                        args_of)

    def _instrument(self, pack, dispatch, wait, unpack):
        """Wrap the stage callbacks with the resilience hooks: fault
        injection fires as each stage starts its Nth item (each stage is
        single-threaded, so a plain per-stage counter is the submission
        order), and the watchdog applies its policy per stage — dispatch
        under deadline + retry (faults are one-shot, so a retried
        dispatch finds its injected fault consumed: the transient-fault
        shape), the result wait under the deadline only, and the
        idempotent host stages (pack/unpack: pure functions of their
        inputs) under retry only."""
        faults, wd, stats = self.faults, self.watchdog, self.stats
        counters = {s: itertools.count() for s in ("pack", "device",
                                                   "unpack")}

        def fire(stage, idx):
            if faults is not None:
                faults.fire(stage, idx, stats=stats)

        cancel = faults.cancel_hangs if faults is not None else None

        def staged(stage, fn, retry=True, deadline=False):
            idx = next(counters[stage])

            def attempt():
                fire(stage, idx)
                return fn()

            if wd is None:
                return attempt()
            return wd.call(attempt, stats=stats, retry=retry,
                           deadline=deadline, on_timeout=cancel)

        def pack_w(item):
            return staged("pack", lambda: pack(item))

        def dispatch_w(item, ops):
            return staged("device", lambda: dispatch(item, ops),
                          deadline=True)

        def wait_w(handle):
            if wd is None:
                return wait(handle)
            return wd.call(lambda: wait(handle), stats=stats, retry=False,
                           on_timeout=cancel)

        def unpack_w(item, res):
            return staged("unpack", lambda: unpack(item, res))

        return pack_w, dispatch_w, wait_w, unpack_w

    def _run_sync(self, items, pack, dispatch, wait, unpack, on_error,
                  args_of):
        # each counter is charged from its span's own endpoints, so
        # per-stage span-duration sums equal the stage wall-clock
        # counters by construction (tests/test_obs.py)
        stats = self.stats
        for idx, item in enumerate(items):
            a = args_of(idx, item)
            try:
                with trace.timed("pipeline.pack", **a) as sp:
                    ops = pack(item)
                stats.bump("pack_s", sp.t1 - sp.t0)
                with trace.timed("pipeline.device", seg="dispatch",
                                 **a) as sp:
                    handle = dispatch(item, ops)
                disp_dt = sp.t1 - sp.t0
                stats.bump("device_s", disp_dt)
                stats.bump("chunks")
                # the wait ends once the result is on the host
                with trace.timed("pipeline.device", seg="wait", **a) as sp:
                    res = wait(handle)
                stats.bump("device_s", sp.t1 - sp.t0)
                if stats.hists is not None:
                    stats.hists.observe("pipeline.device",
                                        disp_dt + (sp.t1 - sp.t0))
                with trace.timed("pipeline.unpack", **a) as sp:
                    unpack(item, res)
                stats.bump("unpack_s", sp.t1 - sp.t0)
            except Exception as exc:
                stats.bump("errors")
                if on_error is None:
                    raise
                on_error(item, exc)

    def _run_async(self, items, pack, dispatch, wait, unpack, on_error,
                   args_of):
        stats = self.stats
        fatal: list[BaseException] = []
        abort = threading.Event()

        def guard(item, exc):
            stats.bump("errors")
            if on_error is None:
                fatal.append(exc)
                abort.set()
                return
            try:
                on_error(item, exc)
            except BaseException as handler_exc:
                fatal.append(handler_exc)
                abort.set()

        packed_q: queue.Queue = queue.Queue(maxsize=self.depth)
        waiting_q: queue.Queue = queue.Queue(maxsize=self.depth)

        def packer():
            try:
                for idx, item in enumerate(items):
                    if abort.is_set():
                        break
                    try:
                        with trace.timed("pipeline.pack",
                                         **args_of(idx, item)) as sp:
                            ops = pack(item)
                        stats.bump("pack_s", sp.t1 - sp.t0)
                    except Exception as exc:
                        guard(item, exc)
                        continue
                    packed_q.put((idx, item, ops))
            finally:
                packed_q.put(_STOP)

        def unpacker():
            while True:
                entry = waiting_q.get()
                if entry is _STOP:
                    return
                if abort.is_set():
                    continue
                idx, item, handle, disp_dt = entry
                a = args_of(idx, item)
                try:
                    # the wait ends once the result is on the host
                    with trace.timed("pipeline.device", seg="wait",
                                     **a) as sp:
                        res = wait(handle)
                    stats.bump("device_s", sp.t1 - sp.t0)
                    if stats.hists is not None:
                        stats.hists.observe("pipeline.device",
                                            disp_dt + (sp.t1 - sp.t0))
                    with trace.timed("pipeline.unpack", **a) as sp:
                        unpack(item, res)
                    stats.bump("unpack_s", sp.t1 - sp.t0)
                except Exception as exc:
                    guard(item, exc)

        def drain(q):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    return

        t_pack = threading.Thread(target=packer, name="racon-tpu-pack",
                                  daemon=True)
        t_unpack = threading.Thread(target=unpacker, name="racon-tpu-unpack",
                                    daemon=True)
        t_pack.start()
        t_unpack.start()
        try:
            # the dispatch loop always drains packed_q to its sentinel and
            # waiting_q always gets one, so neither worker can deadlock on
            # a bounded-queue put even when abort fires mid-stream
            while True:
                entry = packed_q.get()
                if entry is _STOP:
                    break
                if abort.is_set():
                    continue
                idx, item, ops = entry
                try:
                    with trace.timed("pipeline.device", seg="dispatch",
                                     **args_of(idx, item)) as sp:
                        handle = dispatch(item, ops)
                    stats.bump("device_s", sp.t1 - sp.t0)
                    stats.bump("chunks")
                except Exception as exc:
                    guard(item, exc)
                    continue
                waiting_q.put((idx, item, handle, sp.t1 - sp.t0))
        except BaseException:
            # exceptional exit (KeyboardInterrupt is the real case): the
            # workers may be blocked on the bounded queues, so a plain
            # join would deadlock. Set abort, keep the queues draining
            # while the packer winds down, and never block indefinitely —
            # an unpacker stuck inside a hung device wait() is a daemon
            # thread and is abandoned rather than hanging the caller.
            abort.set()
            while t_pack.is_alive():
                drain(packed_q)
                t_pack.join(timeout=0.1)
            drain(waiting_q)
            try:
                waiting_q.put_nowait(_STOP)
            except queue.Full:
                pass
            t_unpack.join(timeout=2.0)
            raise
        waiting_q.put(_STOP)
        t_unpack.join()
        t_pack.join()
        if fatal:
            raise fatal[0]

    # ---------------------------------------------------- fallback pool
    def submit_fallback(self, fn, *args, **kwargs) -> Future:
        """Schedule host-only work concurrently with the device stages
        (inline at depth 0). Returns a Future; collect with `.result()`
        after `drain_fallback()`. Fallback jobs are an injection point
        (`fallback:chunk=<N>` counts submissions) and share the
        watchdog's bounded retry — without its deadline: host work is
        CPU-bound and finite, and abandoning it would leak the thread."""
        stats = self.stats
        faults, wd = self.faults, self.watchdog
        idx = next(self._fb_counter)

        def job():
            if faults is not None:
                faults.fire("fallback", idx, stats=stats)
            return fn(*args, **kwargs)

        def timed():
            sp = trace.timed("pipeline.fallback", job=idx)
            try:
                with sp:
                    if wd is None:
                        return job()
                    return Watchdog(timeout=0.0, retries=wd.retries,
                                    backoff=wd.backoff).call(job,
                                                             stats=stats)
            finally:
                stats.bump("fallback_s", sp.t1 - sp.t0)

        if self.depth == 0:
            fut: Future = Future()
            try:
                fut.set_result(timed())
            except BaseException as exc:
                fut.set_exception(exc)
        else:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self.fallback_workers,
                    thread_name_prefix="racon-tpu-fallback")
            fut = self._executor.submit(timed)
        self._futures.append(fut)
        return fut

    def map_fallback(self, idxs, fn, chunk: int = 256) -> list:
        """Submit `fn(sub)` for successive `chunk`-sized slices of `idxs`.
        Returns [(sub, future), ...]; collect each future's result (one
        entry per index in `sub`) after drain_fallback() — the shared
        submit half of the reject-fallback protocol both hot phases use."""
        out = []
        for s in range(0, len(idxs), chunk):
            sub = list(idxs[s:s + chunk])
            out.append((sub, self.submit_fallback(fn, sub)))
        return out

    def drain_fallback(self, ignore_errors: bool = False) -> None:
        """Block until every submitted fallback job finished; re-raises
        the first failure unless `ignore_errors` (the abandon path)."""
        futures, self._futures = self._futures, []
        first: BaseException | None = None
        for fut in futures:
            try:
                fut.result()
            except BaseException as exc:
                if first is None:
                    first = exc
        if first is not None and not ignore_errors:
            raise first

    def cancel_fallback(self) -> tuple[int, int]:
        """Abandon the fallback queue: cancel every not-yet-started job
        and block until the running ones finish (their results and
        errors are discarded). Returns (cancelled, drained) counts.

        This is the device-failure reset path: before the caller
        restarts a whole phase on host, no orphaned fallback thread may
        keep working (and bumping a just-restarted progress bar) and no
        queued job may still start and burn host threads the restart
        needs."""
        futures, self._futures = self._futures, []
        cancelled = sum(1 for fut in futures if fut.cancel())
        drained = 0
        for fut in futures:
            if fut.cancelled():
                continue
            try:
                fut.result()
            except BaseException:
                pass
            drained += 1
        if cancelled:
            self.stats.bump("cancelled", cancelled)
        return cancelled, drained

    def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None

    def __enter__(self) -> "DispatchPipeline":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()
