"""Occupancy-aware batch scheduler shared by all three device engines.

Every device hot path pads jobs up to a shape ladder: the overlap aligner
(`ops/align.BatchAligner.BUCKETS`, 8 length edges), the session POA
engine (`ops/poa_graph.BUCKETS`, a 4-entry (nodes, len) grid) and the
fused POA engine (`ops/poa_fused.DEPTH_BUCKETS`, 4 depth buckets). The
static ladders are sized for the worst case, so easy inputs burn the
worst case's FLOPs — the occupancy problem cudapoa solves with its
add_windows-until-full batch sizing (cudabatch.cpp:77-270), transplanted
to XLA's static-shape world. `BatchScheduler` packages the three answers:

  1. ADAPTIVE LADDERS (`--tpu-adaptive-buckets` /
     RACON_TPU_ADAPTIVE_BUCKETS, default OFF — the static ladders remain
     the fallback): at run start each engine hands the scheduler its
     actual job-shape histogram and gets back a ladder of at most K
     shapes (K = the static ladder's size, so adaptive mode never
     compiles more programs than static mode) minimizing total padded
     cells — the exact DPs in `ladder.py`. Data-derived shapes recompile
     per dataset, which is why the flag composes with the persistent
     compile cache below: the second run of a dataset (or any dataset
     quantizing to the same edges) pays zero XLA.

  2. LENGTH-SORTED PACKING: with the scheduler enabled, jobs are sorted
     by shape before chunking, so each dispatched batch is
     shape-homogeneous instead of inheriting arrival order. Results are
     committed back by original index (every engine already assembles
     results positionally), so output stays byte-identical — the tests
     in tests/test_sched.py pin this on all three engines.

  3. OCCUPANCY TELEMETRY (`telemetry.OccupancyStats`, always on — the
     counters are a few adds per dispatched batch): per-bucket jobs /
     batches / lanes / useful-vs-padded cells / occupancy %% and
     per-engine compile count + seconds, flowing through
     `polisher.occupancy_stats` into bench.py's JSON artifact.

The persistent compile cache (`enable_compile_cache`) wires jax's
compilation cache (`jax_compilation_cache_dir`) so repeated runs —
including adaptive-ladder runs with data-derived shapes — skip
recompiles entirely. It is placed from outside: JAX_COMPILATION_CACHE_DIR,
when set, is the only directory; otherwise `--tpu-compile-cache DIR` /
RACON_TPU_COMPILE_CACHE, else the checkout's own `.jax_cache`.

The scheduler deliberately changes only WHICH static shapes exist and
HOW jobs are ordered into chunks; chunk dispatch still flows through
`pipeline.DispatchPipeline`, so the resilience layer's per-chunk fault
hooks, watchdog, and fallback/quarantine routing apply unchanged to
repacked chunks (pinned by tests).
"""

from __future__ import annotations

import os

from .ladder import ladder_1d, ladder_2d, padded_cost_1d, round_up
from .telemetry import OccupancyStats

__all__ = ["BatchScheduler", "OccupancyStats", "default_cache_dir",
           "enable_compile_cache",
           "ladder_1d", "ladder_2d", "pack_iteration", "padded_cost_1d",
           "round_up", "shard_interleave"]


def shard_interleave(items: list, n_devices: int) -> list:
    """Strided round-robin of a shape-sorted row list across `n`
    device shards: shard s receives items s, s+n, s+2n, ... — so a
    sorted batch's large rows spread evenly across the mesh instead of
    piling the heaviest work onto the last shard (contiguous split of
    a sorted list = systematically imbalanced per-device wall time).
    Pure permutation: per-row results are position-independent, so the
    caller's output bytes cannot change."""
    n = int(n_devices)
    if n <= 1 or len(items) <= n:
        return list(items)
    out: list = []
    for s in range(n):
        out.extend(items[s::n])
    return out


def pack_iteration(items: list, cap: int, shape_key, age_key,
                   lane_multiple: int = 1):
    """Incremental packing entry point for the continuous serve feeder
    (serve/batcher.py): from a pending pool, pick ONE bounded,
    shape-homogeneous batch that still guarantees progress for the
    oldest work.

    The pool is sorted by `shape_key` (the quantities the ladders
    bucket on — depth, length), then the contiguous slab of at most
    `cap` items CONTAINING the item with the minimal `age_key` is
    taken: the batch lands in few ladder buckets (the same
    minimal-padding win as the per-run sorted packing, applied per
    iteration) while the oldest item always ships this iteration — no
    starvation however the shapes interleave.

    `lane_multiple` is the dispatching mesh's device count: when the
    pool is deep enough, the slab is rounded DOWN to a multiple of it
    so the engine's per-device shards split evenly without padding
    lanes (the trimmed items lead the very next iteration — they only
    ever wait one extra dispatch). A pool smaller than one multiple
    ships whole; the engines then dispatch it on a sub-mesh
    (`BatchRunner.for_batch`) rather than padding up to the full mesh.

    Returns `(batch, rest)`; `rest` preserves the sorted order, ready
    to re-pool."""
    if not items:
        return [], []
    ordered = sorted(items, key=shape_key)
    cap = max(1, int(cap))
    size = min(cap, len(ordered))
    m = max(1, int(lane_multiple))
    if size > m and size % m:
        size = (size // m) * m
    oldest = min(range(len(ordered)), key=lambda i: age_key(ordered[i]))
    start = min(oldest, max(0, len(ordered) - size))
    return (ordered[start:start + size],
            ordered[:start] + ordered[start + size:])


#: the checkout root — the compile cache's fixed home when nothing else
#: places it (a path that moves between runs never hits)
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_cache_dir() -> str:
    """The compile-cache directory in force when no option names one:
    JAX_COMPILATION_CACHE_DIR if set, else `<repo>/.jax_cache`."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO, ".jax_cache"))


def enable_compile_cache(path: str | None = None) -> str:
    """Point jax's persistent compilation cache at its directory and
    return it. JAX_COMPILATION_CACHE_DIR, when set, is the only
    directory and `path` yields to it; otherwise `path` (the
    --tpu-compile-cache / RACON_TPU_COMPILE_CACHE choice), else
    `<repo>/.jax_cache`. Idempotent; exported via the environment so
    child processes inherit it. The min-compile-time threshold is
    dropped to 0 so even fast-compiling shapes persist — the cache
    exists to make the SECOND run cheap, whatever the first cost."""
    import jax

    path = os.path.abspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or path or default_cache_dir())
    if jax.config.jax_compilation_cache_dir == path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax memoizes the cache object on first use: a process that already
    # compiled something (e.g. the CLI redirecting mid-init) needs the
    # memo dropped so the new directory actually takes effect
    from jax.experimental.compilation_cache.compilation_cache import \
        reset_cache

    reset_cache()
    return path


class BatchScheduler:
    """Shared scheduler handle threaded from the polisher into every
    engine: the adaptive on/off posture, the occupancy counters, and the
    per-engine ladder derivations (thin wrappers over ladder.py with
    each engine's quanta and cost model).

    One instance per polisher run; engines constructed standalone (tests,
    tools) default to `BatchScheduler.from_env()`, so the env knob alone
    arms the whole stack.
    """

    def __init__(self, adaptive: bool = False,
                 stats: OccupancyStats | None = None):
        self.adaptive = bool(adaptive)
        self.stats = stats if stats is not None else OccupancyStats()

    @classmethod
    def from_env(cls, adaptive: bool | None = None,
                 compile_cache: str | None = None) -> "BatchScheduler":
        """Build from the environment posture. Explicit arguments (the
        CLI flags) win over RACON_TPU_ADAPTIVE_BUCKETS /
        RACON_TPU_COMPILE_CACHE; either cache choice yields to
        JAX_COMPILATION_CACHE_DIR (enable_compile_cache)."""
        if adaptive is None:
            adaptive = bool(os.environ.get("RACON_TPU_ADAPTIVE_BUCKETS"))
        cache = compile_cache or os.environ.get("RACON_TPU_COMPILE_CACHE")
        if cache:
            enable_compile_cache(cache)
        return cls(adaptive=adaptive)

    # ------------------------------------------------- ladder derivation
    #: compile-shape quanta: aligner edges land on multiples of 256 (the
    #: wavefront count is 2*edge+1; coarse edges make near-identical
    #: datasets share persistent-cache entries), session grids on 64s
    #: (node rows / layer columns), depth buckets on exact integers
    ALIGNER_QUANTUM = 256
    POA_QUANTUM = 64

    def aligner_ladder(self, lengths, k: int,
                       max_length: int) -> tuple[int, ...] | None:
        """Length-bucket edges for BatchAligner from a pair-length
        histogram (max(len(q), len(t)) per pair; the aligner calls this
        once per occupied static bucket with a split budget, so bands —
        which follow the static rule — stay constant per derived group).
        Cost model: within one derivation call the band is a constant
        (pinned to the static bucket's rule), so per-lane DP area is
        proportional to the wavefront count 2e+1 — exactly what the
        kernel executes at edge e."""
        if not self.adaptive:
            return None
        eligible = [v for v in lengths if 0 < v <= max_length]
        edges = ladder_1d(eligible, k, quantum=self.ALIGNER_QUANTUM,
                          cost=lambda e: 2 * e + 1)
        return tuple(edges) or None

    def poa_grid(self, shapes, k: int, max_nodes: int,
                 max_len: int) -> tuple[tuple[int, int], ...] | None:
        """(nodes, len) bucket grid for the session engine from predicted
        job shapes (poa_graph derives the prediction from the window
        set). Shapes beyond the envelope are dropped (those jobs host-
        fallback and never dispatch); the caller appends the envelope
        bucket itself, its existing safety-net discipline."""
        if not self.adaptive:
            return None
        fit = [(n, l) for n, l in shapes if n <= max_nodes and l <= max_len]
        grid = ladder_2d(fit, k, quantum_a=self.POA_QUANTUM,
                         quantum_b=self.POA_QUANTUM,
                         area=lambda ea, eb: ea * (eb + 1))
        return tuple(grid) or None

    def depth_ladder(self, depths, k: int) -> tuple[int, ...] | None:
        """Depth buckets for the fused engine from the actual chunk-max
        depths (known exactly at run start: windows are depth-sorted
        before chunking). Every chained call of depth D costs B * D
        layer steps regardless of real layer count, so the cost of an
        edge is the edge itself."""
        if not self.adaptive:
            return None
        edges = ladder_1d(depths, k, quantum=1)
        return tuple(edges) or None

    def order(self, idxs, key):
        """Length-sorted packing: a stable shape-sort of job indices
        before chunking (identity when the scheduler is off, preserving
        arrival-order packing exactly)."""
        if not self.adaptive:
            return list(idxs)
        return sorted(idxs, key=key)
