"""Occupancy telemetry: per-bucket padding-waste counters.

The padding a shape ladder imposes was invisible until now — the bench
measured windows/sec but not how much of each dispatched batch was real
work. `OccupancyStats` makes padding waste a first-class, tracked metric:
every dispatched batch records its bucket, lane count and useful-vs-total
cells (cells = DP area for the aligner and session engine, layers for the
fused engine — each engine's natural unit of padded compute), plus the
per-engine compile count and the wall seconds the first dispatch of each
new shape cost (trace + XLA compile; ~0 when the persistent compile
cache is warm).

The snapshot flows through `polisher.occupancy_stats` into bench.py's
JSON artifact next to the pipeline stage counters, so a ladder change
shows up as a measured occupancy delta, not an anecdote.

Invariant the tests pin: per bucket, useful_cells + padded_cells ==
lanes * capacity(bucket) — the counters sum to exactly the cells the
device was asked to process. The session engine's capacity is the rows
a batch's program sweeps (its largest graph, not the bucket's node
count) times the bucket's length.
"""

from __future__ import annotations

import threading

#: program shapes already charged to compile telemetry. Process-wide by
#: design: jit caches are per-process, so a second engine instance (or a
#: second polisher) dispatching an already-built shape really does pay
#: no compile — charging it again would overreport.
_seen_shapes: set = set()


def _copy_bucket(b: dict) -> dict:
    """Deep-enough bucket copy for reads escaping the lock: the
    shard_useful LIST must be copied under the lock too, or a
    concurrent record() mutates it mid-read and exports torn per-shard
    sums."""
    return {k: (list(v) if isinstance(v, list) else v)
            for k, v in b.items()}


def accumulate_cells(acc: list, vals) -> list:
    """Element-wise accumulate `vals` into `acc`, extending past the
    end — THE shard-list accumulation, shared by record()/merge_from()/
    snapshot() and synthbench's cross-engine scale aggregation (one
    copy, so the semantics cannot drift between them)."""
    for i, v in enumerate(vals):
        if i < len(acc):
            acc[i] += int(v)
        else:
            acc.append(int(v))
    return acc


class OccupancyStats:
    """Thread-safe per-(engine, bucket) occupancy counters.

    Counter semantics per bucket:
      jobs          real (non-pad) jobs dispatched
      batches       device batches dispatched
      lanes         total batch rows incl. round-up padding lanes
      useful_cells  cells covered by real job shapes
      padded_cells  cells burned on padding (bucket edge - job shape,
                    plus whole padding lanes)
    Per engine:
      compiles      distinct program shapes built this process
      compile_s     wall seconds spent in those shapes' first dispatch
      pairs         (aligner) overlaps the polisher asked it to align
      host_pairs    (aligner) of those, the ones aligned off the device,
                    split by reason in host_pairs_by_reason
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._buckets: dict[tuple[str, str], dict] = {}
        self._compiles: dict[str, dict] = {}
        self._pairs: dict[str, dict] = {}
        #: optional obs.hist.HistogramSet: per-engine compile wall time
        #: observed as a latency distribution (`compile.<engine>`) —
        #: the "how long does a new shape stall a round" view the serve
        #: scrape exposes; None when nothing is watching
        self.hists = None

    def record(self, engine: str, bucket, jobs: int, lanes: int,
               useful_cells: int, total_cells: int,
               kernel: str | None = None, dtype: str | None = None,
               n_devices: int | None = None,
               shard_useful=None,
               full_mesh_cells: int | None = None,
               swept_steps: int | None = None,
               bucket_steps: int | None = None) -> None:
        """Account one dispatched batch. `bucket` is any hashable shape
        descriptor (stringified for the snapshot); `total_cells` is the
        batch's full dispatched capacity (>= useful_cells). `kernel`
        ('xla' | 'pallas') and `dtype` ('int32' | 'int16') record the
        bucket's dispatched program choice — the device-kernel plane's
        per-bucket decision, surfaced next to the occupancy numbers in
        the bench JSON and synthbench report (constant per bucket within
        a run; last write wins).

        The mesh view (all optional, so host-only engines stay
        unchanged): `n_devices` is the dispatching mesh width,
        `shard_useful` the per-device-shard useful-cell split of this
        batch (accumulated element-wise — the per-shard balance number
        synthbench's scale curve gates on), and `full_mesh_cells` what
        the batch WOULD have dispatched under full-mesh `round_batch`
        rounding — the baseline the sub-mesh tail dispatch is measured
        against (equal to `total_cells` when no sub-mesh was taken).

        `swept_steps` / `bucket_steps` (the session engine) are the node
        steps the batch's program swept and the bucket's node count it
        could have swept, summed per bucket: how far the sweep bound
        cut the program's steps."""
        key = (engine, str(bucket))
        with self._lock:
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = {
                    "jobs": 0, "batches": 0, "lanes": 0,
                    "useful_cells": 0, "padded_cells": 0}
            b["jobs"] += int(jobs)
            b["batches"] += 1
            b["lanes"] += int(lanes)
            b["useful_cells"] += int(useful_cells)
            b["padded_cells"] += int(total_cells) - int(useful_cells)
            if kernel is not None:
                b["kernel"] = kernel
            if dtype is not None:
                b["dtype"] = dtype
            if n_devices is not None:
                b["n_devices"] = int(n_devices)
            if shard_useful is not None:
                accumulate_cells(b.setdefault("shard_useful", []),
                                 shard_useful)
            if full_mesh_cells is not None:
                b["full_mesh_cells"] = (b.get("full_mesh_cells", 0)
                                        + int(full_mesh_cells))
            if swept_steps is not None:
                b["swept_steps"] = b.get("swept_steps", 0) + int(swept_steps)
                b["bucket_steps"] = (b.get("bucket_steps", 0)
                                     + int(bucket_steps))

    def record_pairs(self, engine: str, pairs: int,
                     host: dict[str, int]) -> None:
        """Account one alignment pass: `pairs` the engine was asked to
        align, and `host` ({reason: count}) of them aligned off the
        device instead."""
        with self._lock:
            self._add_pairs(engine, pairs, host)

    def _add_pairs(self, engine: str, pairs: int, host: dict) -> None:
        c = self._pairs.setdefault(engine, {
            "pairs": 0, "host_pairs": 0, "host_pairs_by_reason": {}})
        c["pairs"] += int(pairs)
        by = c["host_pairs_by_reason"]
        for reason, n in host.items():
            c["host_pairs"] += int(n)
            by[reason] = by.get(reason, 0) + int(n)

    def record_compile(self, engine: str, seconds: float,
                       count: int = 1) -> None:
        with self._lock:
            c = self._compiles.setdefault(
                engine, {"compiles": 0, "compile_s": 0.0})
            c["compiles"] += count
            c["compile_s"] += float(seconds)
        if self.hists is not None:
            self.hists.observe(f"compile.{engine}", float(seconds))

    def record_compile_once(self, engine: str, key,
                            seconds: float) -> bool:
        """Charge `seconds` as compile wall iff `key` (the FULL program
        identity, including the batch dimension — jit programs are
        shape-keyed on it, so a tail chunk with a different lane count
        is a separate compile) is new to this process. The shared
        first-dispatch idiom of all three engines: time the dispatch,
        call this, and the first occurrence of each shape is charged."""
        k = (engine, key)
        with self._lock:
            if k in _seen_shapes:
                return False
            _seen_shapes.add(k)
        self.record_compile(engine, seconds)
        # the charge is made right after the first dispatch returned,
        # so the compile is the span [now - seconds, now]: the Chrome
        # recorder takes it as such (the Perfetto view of "where did
        # the first chunk's stall go"); a profiler capture cannot take
        # a span after the fact, so there the dispatch span open on
        # this thread carries the seconds
        from ..obs import trace

        trace.tag(compile_s=float(seconds))
        tr = trace.get_tracer()
        if tr is not None:
            import time

            now = time.perf_counter()
            tr.complete("xla.compile", now - float(seconds), now,
                        {"engine": engine, "shape": str(key)})
        return True

    def merge_from(self, other: "OccupancyStats") -> None:
        """Fold another instance's counters into this one. The serve
        batcher keeps ONE OccupancyStats per worker lane — so each
        lane's per-iteration compile delta is exact under lane
        concurrency (a shared instance would charge one lane's compile
        to whichever other lane's delta window it landed in) — and
        merges them through a scratch instance for the lifetime
        occupancy view."""
        with other._lock:
            buckets = {k: _copy_bucket(v)
                       for k, v in other._buckets.items()}
            compiles = {k: dict(v) for k, v in other._compiles.items()}
            pairs = {k: (v["pairs"], dict(v["host_pairs_by_reason"]))
                     for k, v in other._pairs.items()}
        with self._lock:
            for engine, (n, host) in pairs.items():
                self._add_pairs(engine, n, host)
            for key, b in buckets.items():
                mine = self._buckets.get(key)
                if mine is None:
                    self._buckets[key] = b
                    continue
                for k, v in b.items():
                    if k == "n_devices" or isinstance(v, str):
                        mine[k] = v  # descriptors: last write wins
                    elif isinstance(v, list):
                        accumulate_cells(mine.setdefault(k, []), v)
                    else:
                        mine[k] = mine.get(k, 0) + v
            for engine, c in compiles.items():
                mine = self._compiles.setdefault(
                    engine, {"compiles": 0, "compile_s": 0.0})
                mine["compiles"] += c["compiles"]
                mine["compile_s"] += c["compile_s"]

    def snapshot(self) -> dict:
        """{engine: {"buckets": {bucket: {..., "occupancy_pct"}},
                     "occupancy_pct", "compiles", "compile_s",
                     "pairs", "host_pairs", "host_pairs_by_reason"}} —
        JSON-ready; empty dict when nothing was dispatched."""
        with self._lock:
            buckets = {k: _copy_bucket(v)
                       for k, v in self._buckets.items()}
            compiles = {k: dict(v) for k, v in self._compiles.items()}
            pairs = {k: dict(v, host_pairs_by_reason=dict(
                v["host_pairs_by_reason"])) for k, v in self._pairs.items()}
        out: dict = {}
        for (engine, bucket), b in sorted(buckets.items()):
            e = out.setdefault(engine, {"buckets": {}})
            total = b["useful_cells"] + b["padded_cells"]
            e["buckets"][bucket] = dict(
                b, occupancy_pct=round(100.0 * b["useful_cells"] / total, 2)
                if total else 0.0)
        for engine, e in out.items():
            useful = sum(b["useful_cells"] for b in e["buckets"].values())
            total = useful + sum(b["padded_cells"]
                                 for b in e["buckets"].values())
            e["occupancy_pct"] = (round(100.0 * useful / total, 2)
                                  if total else 0.0)
            # the mesh view, aggregated across buckets that carry it:
            # per-shard useful-cell balance (max/min over the engine's
            # element-wise shard sums) and the padded-cell fraction vs
            # what full-mesh round_batch rounding would have dispatched
            # — the numbers the scale-curve perfgate gates. RAW sums
            # (useful/total/full-mesh cells) ride along so cross-engine
            # consumers (synthbench _scale_point) can combine fractions
            # without re-walking buckets.
            shards: list[int] = []
            fm_cells = fm_useful = 0
            for b in e["buckets"].values():
                accumulate_cells(shards, b.get("shard_useful", ()))
                if "full_mesh_cells" in b:
                    fm_cells += b["full_mesh_cells"]
                    fm_useful += b["useful_cells"]
            if shards:
                e["shard_useful"] = shards
                if min(shards) > 0:
                    e["shard_balance"] = round(
                        max(shards) / min(shards), 4)
            if total:
                e["useful_cells"] = useful
                e["total_cells"] = total
                e["padded_frac"] = round((total - useful) / total, 6)
            if fm_cells:
                e["full_mesh_cells"] = fm_cells
                e["full_mesh_useful"] = fm_useful
                e["padded_frac_full_mesh"] = round(
                    (fm_cells - fm_useful) / fm_cells, 6)
        for engine, c in compiles.items():
            e = out.setdefault(engine, {"buckets": {}})
            e["compiles"] = c["compiles"]
            e["compile_s"] = round(c["compile_s"], 3)
        for engine, c in pairs.items():
            out.setdefault(engine, {"buckets": {}}).update(c)
        return out

    def summary(self) -> str | None:
        """One-line per-engine occupancy report for stderr, or None when
        nothing was dispatched (the common host-only case: silence)."""
        snap = self.snapshot()
        parts = []
        for engine, e in snap.items():
            if not e.get("buckets"):
                continue
            jobs = sum(b["jobs"] for b in e["buckets"].values())
            batches = sum(b["batches"] for b in e["buckets"].values())
            s = (f"{engine} {e['occupancy_pct']:.1f}% "
                 f"({jobs} jobs / {batches} batches"
                 f" / {len(e['buckets'])} shapes")
            if "compiles" in e:
                s += f", {e['compiles']} compiles {e['compile_s']:.1f}s"
            parts.append(s + ")")
        return "; ".join(parts) if parts else None
