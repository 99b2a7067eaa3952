"""The program's own spans in a JAX profiler trace: each span's self
time, the device's idle time inside each span, and idle gaps named by
the program span open in them.

racon_tpu writes every span it opens (`racon_tpu/obs/trace.py`) into a
running capture as an annotation named `racon.<span>`, with its
arguments, on the `/host:CPU` plane: one line per host thread, on the
same clock as the device's program events. `trace_reduce` reads the
device and the harness's `bench.*` spans; this module adds the program
spans beside them:

    python3 benchmark/span_reduce.py <capture dir or .xplane.pb> \
        [--windows N]

prints one JSON object: the window read (the harness's `bench.job`
span if the capture has one, else the extent of the program spans),
the reduction below, and per-window readings when `--windows` is given.
An operator's `--tpu-jax-profile` capture reads the same way.

Definitions, all over the window [lo, hi] and in seconds:

- self time of a span: its duration minus what its child spans on the
  same thread cover (spans on one thread nest);
- idle inside a span: the part of its interval in which no program ran
  on the device, averaged over the devices;
- an idle gap's name: the harness span open at its midpoint, as
  `trace_reduce` names it, followed by `/<program span>` when a
  program span is open there; the innermost program span is the
  shortest one open, on any thread;
- idle by innermost span: every idle stretch cut at the program spans'
  ends and charged to the innermost program span open in each piece
  (`-` where none is).
"""

from __future__ import annotations

import bisect
import heapq
import os
import sys

import trace_reduce

PREFIX = "racon."

#: spans whose work runs on the host alone, with nothing on the device
#: waiting on it inside the span: the input parsers, the window build,
#: the breaking-point walk and the stitch
HOST_ONLY = ("polisher.load_targets", "polisher.load_reads",
             "polisher.load_overlaps", "polisher.build_windows",
             "polisher.breaking_points", "polisher.stitch")


def program_spans(path: str) -> list[tuple]:
    """[(start_ns, end_ns, name without the prefix, thread, args)] of
    the capture's `racon.*` annotations; `thread` numbers the host
    plane's lines."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name[len(PREFIX):], thread,
                                {k: v for k, v in e.stats}))
    out.sort(key=lambda s: (s[0], -s[1]))
    return out


def load(path: str):
    """(trace_reduce.Trace, program spans) of one capture file."""
    return trace_reduce.load(path), program_spans(path)


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def self_s(spans, lo: float, hi: float) -> dict[str, float]:
    """Self time per span name."""
    ns: dict[str, float] = {}
    by_thread: dict = {}
    for sp in spans:
        by_thread.setdefault(sp[3], []).append(sp)
    for th in by_thread.values():
        th.sort(key=lambda sp: (sp[0], -sp[1]))
        stack: list[list] = []  # [start, end, name, children's cover]

        def close():
            s, e, name, cover = stack.pop()
            d = _clip(s, e, lo, hi)
            ns[name] = ns.get(name, 0.0) + d - cover
            if stack:
                stack[-1][3] += d

        for s, e, name, *_ in th:
            while stack and stack[-1][1] <= s:
                close()
            stack.append([s, e, name, 0.0])
        while stack:
            close()
    return {k: v / 1e9 for k, v in ns.items()}


class Busy:
    """One device's busy intervals inside [lo, hi], for busy time in
    any sub-interval by bisection."""

    def __init__(self, programs, lo: float, hi: float):
        self.iv = trace_reduce.union(programs, lo, hi)
        self.starts = [s for s, _ in self.iv]
        self.ends = [e for _, e in self.iv]
        self.cum = [0.0]
        for s, e in self.iv:
            self.cum.append(self.cum[-1] + e - s)

    def within(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)
        j = bisect.bisect_left(self.starts, b)
        if j <= i:
            return 0.0
        busy = self.cum[j] - self.cum[i]
        busy -= max(0.0, a - self.starts[i])
        busy -= max(0.0, self.ends[j - 1] - b)
        return busy

    def idle(self, lo: float, hi: float) -> list[tuple[float, float]]:
        edges = [lo] + [x for iv in self.iv for x in iv] + [hi]
        return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def idle_in_s(busy: list[Busy], spans, lo: float,
              hi: float) -> dict[str, float]:
    """Device idle time inside each span, summed per span name."""
    out: dict[str, float] = {}
    for s, e, name, *_ in spans:
        a, b = max(s, lo), min(e, hi)
        if b <= a:
            continue
        idle = sum((b - a) - dev.within(a, b) for dev in busy) / len(busy)
        out[name] = out.get(name, 0.0) + idle / 1e9
    return out


def idle_by_innermost_s(busy: list[Busy], spans, lo: float,
                        hi: float) -> dict[str, float]:
    """Idle time charged to the innermost program span open in it."""
    cuts = sorted({x for s, e, *_ in spans for x in (s, e)
                   if lo < x < hi})
    pieces = []
    for dev in busy:
        for s, e in dev.idle(lo, hi):
            i = bisect.bisect_right(cuts, s)
            j = bisect.bisect_left(cuts, e)
            edges = [s] + cuts[i:j] + [e]
            pieces.extend(zip(edges, edges[1:]))
    pieces.sort(key=lambda p: p[0] + p[1])  # by midpoint, for the sweep
    order = sorted(spans, key=lambda sp: sp[0])
    out: dict[str, float] = {}
    active: list = []  # heap of (end, duration, name)
    k = 0
    for s, e in pieces:
        t = (s + e) / 2
        while k < len(order) and order[k][0] <= t:
            sp = order[k]
            heapq.heappush(active, (sp[1], sp[1] - sp[0], sp[2]))
            k += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        name = min(active, key=lambda a: a[1])[2] if active else "-"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return {k: v / len(busy) for k, v in out.items()}


def idle_gaps(tr: trace_reduce.Trace, busy: list[Busy], spans,
              lo: float, hi: float, top: int = 10) -> list:
    """The longest idle gaps, each named `<harness span>/<program
    span>`, or by the harness span alone where no program span is
    open."""
    gaps = sorted(((e - s, s, e) for dev in busy
                   for s, e in dev.idle(lo, hi)), reverse=True)[:top]
    program = [sp[:3] for sp in spans]
    out = []
    for d, s, e in gaps:
        mid = (s + e) / 2
        name = trace_reduce.innermost_span(tr.spans, mid)
        inner = trace_reduce.innermost_span(program, mid)
        out.append([name if inner == "none" else f"{name}/{inner}",
                    d / 1e9])
    return out


def wait_lags_s(programs, spans, program: str, wait: str = "pipeline.device",
                loop: str = "aligner") -> list[float]:
    """How far each blocking result wait ended after the end of the
    device program it waited for. The k-th execution of the programs
    named `program` (a jit name without its id) is the k-th wait of the
    loop: one device runs a loop's chunks in dispatch order. A wait
    that began after its program had ended did not block and is left
    out."""
    runs = sorted((s, e) for s, e, name in programs
                  if trace_reduce._ID_SUFFIX.sub("", name) == program)
    waits = [sp for sp in spans if sp[2] == wait
             and sp[4].get("seg") == "wait" and sp[4].get("loop") == loop]
    waits.sort(key=lambda sp: sp[4].get("chunk", 0))
    return [(w[1] - r[1]) / 1e9 for w, r in zip(waits, runs)
            if w[0] < r[1]]


def reduce(tr: trace_reduce.Trace, spans, lo: float, hi: float,
           top: int = 10) -> dict | None:
    """Readings over [lo, hi] (ns); None when no device ran in it."""
    if hi <= lo or not tr.programs:
        return None
    busy = [Busy(dev, lo, hi) for dev in tr.programs]
    if not any(b.iv for b in busy):
        return None
    return {
        "window_s": (hi - lo) / 1e9,
        "span_self_s": self_s(spans, lo, hi),
        "span_idle_s": idle_in_s(busy, spans, lo, hi),
        "idle_by_innermost_s": idle_by_innermost_s(busy, spans, lo, hi),
        "idle_gaps": idle_gaps(tr, busy, spans, lo, hi, top),
    }


def per_window(r: dict, windows: int) -> dict:
    """The three per-window readings (ms/window) of a reduction."""
    def ms(s):
        return 1e3 * s / windows

    return {
        "align_idle_ms_per_win":
            ms(r["span_idle_s"].get("polisher.align_overlaps", 0.0)),
        "poa_idle_ms_per_win":
            ms(r["span_idle_s"].get("polisher.consensus", 0.0)),
        "host_only_ms_per_win":
            ms(sum(r["span_self_s"].get(n, 0.0) for n in HOST_ONLY)),
    }


def main(argv=None) -> int:
    import argparse
    import glob
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a capture directory or .xplane.pb")
    ap.add_argument("--windows", type=int, default=0,
                    help="windows polished in the window read")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            print(f"{len(files)} captures under {path}; name one",
                  file=sys.stderr)
            return 1
        path = files[0]
    tr, spans = load(path)
    jobs = [s for s in tr.spans if s[2] == "job"]
    if jobs:
        lo, hi = jobs[0][:2]
    elif spans:
        lo, hi = min(s[0] for s in spans), max(s[1] for s in spans)
    else:
        print("no program spans and no bench.job span", file=sys.stderr)
        return 1
    r = reduce(tr, spans, lo, hi)
    if r is None:
        print("no device program ran in the window", file=sys.stderr)
        return 1
    if args.windows:
        r.update(per_window(r, args.windows))
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
