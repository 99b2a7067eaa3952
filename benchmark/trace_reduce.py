"""Reduce a JAX profiler trace to device busy time, per-program device
time, the heaviest device operations and the host's spans over idle
gaps.

The trace is the `.xplane.pb` that `jax.profiler.trace` writes; it is
read with `jax.profiler.ProfileData`. Device planes are named
`/device:TPU:<n>`. On each, the line `XLA Modules` holds one event per
program execution, named `<jit name>(<program id>)`; the traced run
turns per-operation events off, so these are the device's events. Host
spans are the harness's own `jax.profiler.TraceAnnotation`s, all named
`bench.<what>`, on the `/host:CPU` plane. Every event's time is in
nanoseconds on the trace's one clock.

Busy time is the union of a device's program intervals inside the
window; the idle share is one minus busy over the window, averaged over
the devices used.
"""

from __future__ import annotations

import dataclasses
import re

SPAN_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    #: per device: [(start_ns, end_ns, "<jit name>(<program id>)")]
    programs: list[list[tuple[float, float, str]]]
    #: [(start_ns, end_ns, span name without the prefix)]
    spans: list[tuple[float, float, str]]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    programs, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    programs.append([(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name) for e in ln.events])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
    return Trace(programs, spans)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clipped_sum(intervals, lo, hi) -> dict[str, float]:
    out: dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out


def innermost_span(spans, t: float) -> str:
    """Name of the shortest span open at time t (spans nest)."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "none"


def reduce(tr: Trace, lo: float, hi: float, top: int = 10) -> dict | None:
    """Readings over the window [lo, hi] (ns). None when no device ran
    an operation in it."""
    if hi <= lo or not tr.programs:
        return None
    window = hi - lo
    busy_per_dev = []
    gaps = []
    for dev in tr.programs:
        busy = union(dev, lo, hi)
        busy_per_dev.append(sum(e - s for s, e in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, innermost_span(tr.spans, (s + e) / 2)))
    if not any(busy_per_dev):
        return None
    per_program: dict[str, float] = {}
    for dev in tr.programs:
        for k, v in _clipped_sum(dev, lo, hi).items():
            per_program[k] = per_program.get(k, 0.0) + v
    by_name: dict[str, float] = {}
    for k, v in per_program.items():
        name = _ID_SUFFIX.sub("", k)
        by_name[name] = by_name.get(name, 0.0) + v
    n_dev = len(tr.programs)
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy_per_dev) / n_dev / 1e9,
        "window_s": window / 1e9,
        # device seconds per jit name, all its compiled shapes together
        "program_s": {k: v / n_dev / 1e9 for k, v in by_name.items()},
        # the compiled programs (one per shape) that took most time
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(per_program.items(),
                              key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, d / 1e9] for d, name in gaps[:top]],
    }
