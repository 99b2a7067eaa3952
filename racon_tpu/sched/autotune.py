"""Persisted per-bucket kernel autotuner: profile once, dispatch forever.

`profile_production` measures XLA-vs-Pallas (and int32-vs-int16) for
every production bucket on the live backend; this library makes the
measurement durable and load-bearing:

  - `Autotuner.profile_session_bucket` / `profile_aligner_bucket` time
    the candidate programs for one bucket on the LIVE backend (XLA scan
    vs Pallas resident kernel, int32 vs envelope-proof int16), verify
    the candidates agree bit-for-bit on synthetic jobs, and record the
    fastest (kernel, dtype) pair;
  - the winner table persists as JSON next to the XLA compile cache in
    force (RACON_TPU_AUTOTUNE_CACHE, else `<compile cache>/{BASENAME}`,
    see sched.enable_compile_cache), keyed by (backend, engine, bucket
    shape, score params) — a table profiled on chip never leaks into a
    CPU run and vice versa;
  - under RACON_TPU_PALLAS=auto all three engine dispatchers
    (`BatchAligner`, `DeviceGraphPOA`, `FusedPOA`) consult the table
    per bucket via `winner()`: profile once (`profile_production`, or any
    explicit profile call), then every warm serve job and CLI run dispatches the
    measured winner. A cold run without a table dispatches the XLA
    programs exactly as today.

The same table arbitrates the fused engine's CHUNK DISPATCH under
RACON_TPU_FUSED=auto (engine "fused_loop", keyed (nodes, len,
depth-bucket)): `profile_fused_bucket` times the split chained-call
path against the single-launch fused align→window-slice→POA program
(ops/poa_fused.py) under the same identity veto, and
FusedPOA._fused_plan dispatches the measured winner per bucket — a
cold table dispatches the split path exactly as before.

Profiling is explicit, never ambient: engines only READ the table, so
the steady-state hot path costs one dict lookup per bucket and a cold
process never stalls mid-run to benchmark. A bucket already in the
table is not re-profiled (`profile_* -> fresh=False`), which is what
makes the warm second profiling run free (test-pinned, like the
compile-cache warm path).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading

import numpy as np

BASENAME = "racon_tpu_autotune.json"

#: schema version: bump when entry semantics change so a stale table is
#: ignored rather than misread
VERSION = 1


def default_table_path() -> str:
    """Where the winner table lives (see module docstring)."""
    explicit = os.environ.get("RACON_TPU_AUTOTUNE_CACHE")
    if explicit:
        return explicit
    import jax

    from . import default_cache_dir

    return os.path.join(jax.config.jax_compilation_cache_dir
                        or default_cache_dir(), BASENAME)


def _backend() -> str:
    import jax

    return jax.default_backend()


def posture_key() -> tuple:
    """The process's kernel/dtype posture fingerprint: every mode knob
    that can change which kernel plane produces a window's consensus
    bytes, plus the backend the mesh resolves to. The serve window
    cache (serve/wincache.py) folds this into its content-addressed
    key so a posture change — a different RACON_TPU_PALLAS/DTYPES/
    FUSED/PACK_BASES arming, a different device kind — can never
    return bytes cached under the old posture."""
    from ..ops.dtypes import dtype_mode
    from ..ops.encode import pack_bases_enabled
    from ..ops.poa_fused import fused_mode
    from ..ops.poa_pallas import pallas_mode

    try:
        backend = _backend()
    except Exception:  # noqa: BLE001 — a backend-less process still
        # has a well-defined (host) posture
        backend = "none"
    return (pallas_mode(), dtype_mode(), fused_mode(),
            pack_bases_enabled(), backend)


class Autotuner:
    """One winner table: load-on-construct, explicit save, dict lookups
    in between. Entries:

        {"kernel": "pallas"|"xla", "dtype": "int16"|"int32",
         "ms": {candidate: milliseconds, ...}, "identical": bool}

    A table that fails to parse (corrupt write, schema drift) is
    treated as absent — the autotuner must never take a run down."""

    def __init__(self, path: str | None = None):
        self.path = path or default_table_path()
        self.table: dict[str, dict] = {}
        #: per-decision consult counters: (engine, kernel, dtype) ->
        #: times `winner()` handed that decision to a dispatcher
        #: (kernel "none" = a cold bucket, the XLA-default path). The
        #: serve scrape exports them as labeled counters so a fleet
        #: view can tell which buckets run which kernel plane.
        self.consults: dict[tuple[str, str, str], int] = {}
        self._consult_lock = threading.Lock()
        try:
            with open(self.path) as fh:
                doc = json.load(fh)
            if (isinstance(doc, dict)
                    and doc.get("version") == VERSION
                    and isinstance(doc.get("winners"), dict)):
                self.table = doc["winners"]
        except (OSError, ValueError):
            pass

    # ------------------------------------------------------------ keys
    @staticmethod
    def key(engine: str, bucket, params=(), backend: str | None = None
            ) -> str:
        b = backend if backend is not None else _backend()
        bs = "x".join(str(v) for v in (bucket if isinstance(
            bucket, (tuple, list)) else (bucket,)))
        ps = ",".join(str(v) for v in params)
        return f"{b}|{engine}|{bs}|{ps}"

    def winner(self, engine: str, bucket, params=()) -> dict | None:
        """The measured entry for one bucket on THIS backend, or None
        (cold — the dispatcher keeps today's XLA default). Every call
        bumps the per-decision consult counter the scrape exports."""
        ent = self.table.get(self.key(engine, bucket, params))
        decision = (engine, str((ent or {}).get("kernel") or "none"),
                    str((ent or {}).get("dtype") or ""))
        with self._consult_lock:
            self.consults[decision] = self.consults.get(decision, 0) + 1
        return ent

    def consult_counts(self) -> list[tuple[dict, int]]:
        """Labeled samples for the scrape: ({engine, decision, dtype},
        count) per distinct decision handed out so far."""
        with self._consult_lock:
            items = sorted(self.consults.items())
        return [({"engine": eng, "decision": kern, "dtype": dt}, n)
                for (eng, kern, dt), n in items]

    def record(self, engine: str, bucket, params, entry: dict) -> None:
        self.table[self.key(engine, bucket, params)] = entry

    #: per-engine oracle candidate a demoted entry falls back to (the
    #: same candidate `_pick` uses as its identity reference)
    _ORACLE_KERNEL = {"fused_loop": "split"}

    def demote(self, engine: str | None = None, bucket=None, params=None,
               backend: str | None = None) -> list[str]:
        """ONLINE identity veto: rewrite matching winner entries to the
        oracle candidate (`xla`/`split` at int32) with `identical` False
        and `demoted` True, then atomically persist the table — the
        serve-time twin of the profile-time veto in `_pick`, invoked by
        the audit sentinel (obs/audit.py) when a shadow re-execution
        catches a production mismatch. `engine`/`bucket`/`params` narrow
        the match (None = every entry of this backend / engine); entries
        already dispatching the oracle are left alone. Returns the
        demoted keys (empty = nothing matched, nothing written).

        In-process dispatchers see the demotion IMMEDIATELY (`winner()`
        reads the same dict); the atomic rewrite makes it durable, so a
        restarted replica — or a sibling process sharing the cache —
        never re-dispatches the vetoed candidate."""
        b = backend if backend is not None else _backend()
        want_key = (self.key(engine, bucket, params or (), backend=b)
                    if engine is not None and bucket is not None
                    else None)
        demoted: list[str] = []
        for key, ent in list(self.table.items()):
            if want_key is not None:
                if key != want_key:
                    continue
            else:
                parts = key.split("|", 2)
                if len(parts) < 3 or parts[0] != b:
                    continue
                if engine is not None and parts[1] != engine:
                    continue
            if not isinstance(ent, dict):
                continue
            oracle = self._ORACLE_KERNEL.get(
                key.split("|", 2)[1], "xla")
            if (ent.get("kernel") == oracle
                    and ent.get("dtype") == "int32"):
                continue  # already the oracle candidate
            self.table[key] = {"kernel": oracle, "dtype": "int32",
                               "ms": ent.get("ms", {}),
                               "identical": False, "demoted": True}
            demoted.append(key)
        if demoted:
            try:
                self.save()
            except OSError:
                # the in-process veto stands even when the table file
                # is unwritable; durability is best-effort here
                pass
        return demoted

    def save(self) -> str:
        """Atomic write (tmp + rename) so a concurrent reader never sees
        a torn table; returns the path."""
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        doc = {"version": VERSION, "winners": self.table}
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(self.path) or ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump(doc, fh, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return self.path

    # ------------------------------------------------------- profiling
    @staticmethod
    def _time(fn, args, reps: int, materialize: bool = True):
        """-> (mean milliseconds, last output): one warm call first
        (absorbs the compile), then `reps` materialized calls.
        `materialize=False` for candidates that already fetch their
        device results internally (the fused-loop profile returns
        plain host data that numpy cannot — and need not — coerce)."""
        import time

        def run():
            out = fn(*args)
            if materialize:
                if isinstance(out, tuple):
                    for o in out:
                        np.asarray(o)
                else:
                    np.asarray(out)
            return out

        run()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run()
        return (time.perf_counter() - t0) / max(1, reps) * 1e3, out

    def profile_session_bucket(self, n_nodes: int, seq_len: int,
                               max_pred: int, match: int, mismatch: int,
                               gap: int, rows: int = 32, reps: int = 3,
                               seed: int = 7) -> tuple[dict, bool]:
        """Time the session engine's candidates for one (nodes, len)
        bucket — XLA scan (ring-carried, the shipped configuration) vs
        the Pallas window sweep, each at int32 and (when the envelope
        proof holds) int16 — on synthetic linear-graph jobs. Returns
        (entry, fresh); fresh=False means the table already had it and
        NOTHING was run (the warm path)."""
        from ..ops.dtypes import poa_int16_ok
        from ..ops.poa_graph import RING, graph_aligner
        from ..ops.poa_pallas import fits_vmem, window_sweep

        params = (match, mismatch, gap, max_pred)
        existing = self.winner("session", (n_nodes, seq_len), params)
        if existing is not None:
            return existing, False

        args = _session_jobs(n_nodes, seq_len, max_pred, rows, seed)
        nnodes = (np.asarray(args[0]) != 5).sum(axis=1).astype(np.int32)
        ring = RING if n_nodes > RING else 0
        dtypes = ["int32"]
        if poa_int16_ok(n_nodes, seq_len, match, mismatch, gap):
            dtypes.append("int16")
        interp = _backend() != "tpu"

        ms: dict[str, float] = {}
        outs: dict[str, np.ndarray] = {}
        for dt in dtypes:
            kwargs = {} if dt == "int32" else {"score_dtype": dt}
            fn = graph_aligner(n_nodes, seq_len, max_pred, match,
                               mismatch, gap, ring=ring, **kwargs)
            ms[f"xla:{dt}"], out = self._time(fn, args, reps)
            outs[f"xla:{dt}"] = np.asarray(out)
            if fits_vmem(n_nodes, seq_len, max_pred, dt):
                pfn = window_sweep(n_nodes, seq_len, max_pred, match,
                                   mismatch, gap, interpret=interp,
                                   **kwargs)
                ms[f"pallas:{dt}"], pout = self._time(
                    pfn, args + (nnodes,), reps)
                outs[f"pallas:{dt}"] = np.asarray(pout)
        entry = self._pick(ms, outs, "xla:int32")
        self.record("session", (n_nodes, seq_len), params, entry)
        return entry, True

    def profile_aligner_bucket(self, edge: int, band: int,
                               rows: int = 8, reps: int = 3,
                               seed: int = 11) -> tuple[dict, bool]:
        """Time the aligner's candidates for one (edge, band) bucket —
        the XLA wavefront scan vs the Pallas resident kernel, int32 and
        (under the envelope proof) int16 — on synthetic mutated pairs.
        Identity is compared on EVERYTHING BatchAligner consumes: the
        decoded op runs AND the touched-edge flags AND the distances —
        the latter two drive the accept/reject (host-realign) decision,
        so a candidate that gets only the path right must still be
        vetoed."""
        from ..ops import align_pallas
        from ..ops.align import _kernel_for, band_offsets, decode_paths
        from ..ops.dtypes import aligner_int16_ok
        from ..ops.encode import encode_padded

        existing = self.winner("aligner", (edge, band))
        if existing is not None:
            return existing, False

        n_waves = 2 * edge + 1
        pairs = _aligner_pairs(edge, rows, seed)
        q_arr, q_lens = encode_padded([p[0] for p in pairs], edge)
        t_arr, t_lens = encode_padded([p[1] for p in pairs], edge)
        offs = np.stack([band_offsets(int(ql), int(tl), band, n_waves)
                         for ql, tl in zip(q_lens, t_lens)])
        ql32 = q_lens.astype(np.int32)
        tl32 = t_lens.astype(np.int32)
        dtypes = ["int32"]
        if aligner_int16_ok(edge):
            dtypes.append("int16")
        interp = _backend() != "tpu"

        # distances compare normalized: the sentinel magnitude differs
        # per dtype (1<<28 vs 1<<14) but both mean "never reached (M,N)"
        def _dist_norm(d):
            return ["inf" if v >= (1 << 14) else int(v)
                    for v in np.asarray(d).astype(np.int64)]

        def _decoded(op_arr, meta):
            meta = np.asarray(meta)
            return (decode_paths(op_arr, meta[:, 0]),
                    [bool(t) for t in meta[:, 2] > 0],
                    _dist_norm(meta[:, 1]))

        ms: dict[str, float] = {}
        outs: dict[str, tuple] = {}
        for dt in dtypes:
            fn = _kernel_for(band, n_waves, dt, False)
            ms[f"xla:{dt}"], out = self._time(
                fn, (q_arr, t_arr, ql32, tl32, offs), reps)
            outs[f"xla:{dt}"] = _decoded(np.asarray(out[0]).T, out[1])
            if align_pallas.fits_vmem(edge, band, dt):
                pfn = align_pallas.wavefront_align(edge, band, dt, False,
                                                   interpret=interp)
                qx, tx = align_pallas.build_ext(q_arr, t_arr, band)
                ms[f"pallas:{dt}"], pout = self._time(
                    pfn, (qx, tx, ql32, tl32, offs), reps)
                outs[f"pallas:{dt}"] = _decoded(np.asarray(pout[0]),
                                                pout[1])
        entry = self._pick(ms, outs, "xla:int32")
        self.record("aligner", (edge, band), (), entry)
        return entry, True

    def profile_fused_bucket(self, n_nodes: int, seq_len: int,
                             depth: int, max_pred: int, match: int,
                             mismatch: int, gap: int, rows: int = 4,
                             reps: int = 2,
                             seed: int = 13) -> tuple[dict, bool]:
        """Time the fused engine's chunk-dispatch candidates for one
        (nodes, len, depth-bucket) key: the SPLIT chained-call path
        (host-side window slicing, one launch per chain bucket) vs the
        FUSED single-launch program (device-side slicing, the whole
        chain in one jitted scan — ops/poa_fused `device_slice`). The
        synthetic chunk is 1.5x the bucket deep so the split path
        genuinely chains (greedy plan [depth, ...]) while the fused
        candidate runs once; the profiled key is the chunk's LEADING
        chain bucket — exactly what FusedPOA._fused_plan consults under
        RACON_TPU_FUSED=auto. The identity veto compares the finalized
        consensus (bytes + coverages + statuses) bit-for-bit; a fast
        but diverging candidate is disqualified and flagged."""
        from ..ops.poa_fused import FusedPOA

        params = (match, mismatch, gap, max_pred)
        existing = self.winner("fused_loop", (n_nodes, seq_len, depth),
                               params)
        if existing is not None:
            return existing, False

        windows = _fused_windows(n_nodes, seq_len,
                                 depth + max(1, depth // 2), rows, seed)
        eng = FusedPOA(match, mismatch, gap, max_nodes=n_nodes,
                       max_len=seq_len, max_pred=max_pred,
                       batch_rows=rows)
        chunk = list(range(len(windows)))
        plan = eng._chain_plan(max(len(w) - 1 for w in windows))
        total = sum(plan)

        def finalize(np_state):
            results: list = [None] * len(windows)
            statuses = np.ones(len(windows), np.int32)
            eng._finalize_chunk(chunk, np_state, results, statuses)
            return ([(r[0], np.asarray(r[1]).tolist())
                     if r is not None else None for r in results],
                    statuses.tolist())

        def run_split():
            state, calls = eng._pack_chunk(windows, chunk)
            for d, ops, done in calls:
                state = eng._call(d, state, *ops, done)
            return finalize(tuple(np.asarray(x) for x in state))

        def run_fused():
            state, ops = eng._pack_chunk_fused(windows, chunk, total)
            out = eng._call_fused(total, state, *ops)
            return finalize(tuple(np.asarray(x) for x in out))

        dt = eng.score_dtype
        ms: dict[str, float] = {}
        outs: dict = {}
        ms[f"split:{dt}"], outs[f"split:{dt}"] = self._time(
            run_split, (), reps, materialize=False)
        ms[f"fused:{dt}"], outs[f"fused:{dt}"] = self._time(
            run_fused, (), reps, materialize=False)
        entry = self._pick(ms, outs, f"split:{dt}")
        self.record("fused_loop", (n_nodes, seq_len, depth), params,
                    entry)
        return entry, True

    @staticmethod
    def _pick(ms: dict, outs: dict, oracle: str) -> dict:
        """Winner selection with the identity veto: any candidate that
        does not reproduce the int32 XLA oracle bit-for-bit is
        disqualified (and flagged — that's a kernel bug, not a perf
        datum)."""
        ref = outs[oracle]

        def same(o) -> bool:
            if isinstance(ref, np.ndarray):
                return bool(np.array_equal(o, ref))
            return o == ref

        ok = {k: v for k, v in ms.items() if same(outs[k])}
        identical = len(ok) == len(ms)
        best = min(ok, key=ok.get) if ok else oracle
        kernel, dtype = best.split(":")
        return {"kernel": kernel, "dtype": dtype,
                "ms": {k: round(v, 3) for k, v in ms.items()},
                "identical": identical}


def _session_jobs(n_nodes: int, seq_len: int, max_pred: int, rows: int,
                  seed: int):
    """Linear-chain POA jobs (sequence-as-graph + a deletion-bearing
    layer), densified exactly the way the C++ session does."""
    rng = np.random.default_rng(seed)
    codes = np.full((rows, n_nodes), 5, dtype=np.int8)
    preds = np.full((rows, n_nodes, max_pred), -1, dtype=np.int16)
    centers = np.zeros((rows, n_nodes), dtype=np.int16)
    sinks = np.zeros((rows, n_nodes), dtype=np.uint8)
    seqs = np.full((rows, seq_len), 5, dtype=np.int8)
    lens = np.zeros(rows, dtype=np.int32)
    band = np.zeros(rows, dtype=np.int32)
    for k in range(rows):
        t_len = int(rng.integers(n_nodes // 2, n_nodes - 1))
        t = rng.integers(0, 4, t_len).astype(np.int8)
        q = np.concatenate([t[: t_len // 2], t[t_len // 2 + 10:]])
        q = q[:seq_len]
        codes[k, :t_len] = t
        preds[k, 0, 0] = 0
        preds[k, 1:t_len, 0] = np.arange(1, t_len)
        centers[k, :t_len] = np.arange(1, t_len + 1)
        sinks[k, t_len - 1] = 1
        seqs[k, : len(q)] = q
        lens[k] = len(q)
    return codes, preds, centers, sinks, seqs, lens, band


def _fused_windows(n_nodes: int, seq_len: int, depth: int, rows: int,
                   seed: int):
    """Spanning synthetic POA windows (backbone + substitution-mutated
    layers) for the fused-loop profile. Substitutions only — aligned
    alternates cap the graph at <= 4 nodes per backbone column, so a
    backbone of n_nodes // 5 can never overflow the (n_nodes) envelope
    however deep the chunk, and no lane ever falls back mid-profile."""
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    bb_len = max(16, min(seq_len - 8, n_nodes // 5))
    windows = []
    for _ in range(rows):
        bb = bases[rng.integers(0, 4, bb_len)].tobytes()
        win = [(bb, None, 0, 0)]
        for _ in range(depth):
            arr = np.frombuffer(bb, np.uint8).copy()
            sub = rng.random(bb_len) < 0.03
            arr[sub] = bases[rng.integers(0, 4, int(sub.sum()))]
            win.append((arr.tobytes(), None, 0, bb_len - 1))
        windows.append(win)
    return windows


def _aligner_pairs(edge: int, rows: int, seed: int):
    """Mutated (query, target) pairs filling ~the bucket."""
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    pairs = []
    for _ in range(rows):
        n = int(rng.integers(max(2, edge // 2), edge))
        t = bases[rng.integers(0, 4, n)]
        keep = rng.random(n) >= 0.05
        sub = rng.random(n) < 0.05
        q = t.copy()
        q[sub] = bases[rng.integers(0, 4, int(sub.sum()))]
        pairs.append((q[keep].tobytes()[:edge], t.tobytes()))
    return pairs


_cached: dict[str, Autotuner] = {}


def get_autotuner() -> Autotuner:
    """Process-cached table handle, keyed by the resolved path (tests
    repoint RACON_TPU_AUTOTUNE_CACHE; runs resolve it once per path)."""
    path = default_table_path()
    at = _cached.get(path)
    if at is None:
        at = _cached[path] = Autotuner(path)
    return at


def reset_autotuner_cache() -> None:
    """Drop the process cache (tests that rewrite the table on disk)."""
    _cached.clear()


def profile_production(at: Autotuner | None = None, log=print) -> str:
    """Profile every production bucket at the keys the default-built
    dispatchers consult under `auto`, then save the table (returns its
    path). Buckets already in the table are not re-timed.

    - session buckets: every `poa_graph.BUCKETS` entry at the polisher/
      CLI default scoring (3, -5, -4) and the engine's MAX_PRED — the
      exact `DeviceGraphPOA._plan` lookup;
    - aligner buckets: every band the auto rule can dispatch per edge
      (`BatchAligner._band_for` quantizes 10% of the bucket's mean pair
      length up to a multiple of 128, so edge `e` requests a band in
      128..round128(e * 0.1));
    - fused-loop buckets: split chained vs single-launch fused dispatch
      per depth bucket at (env_max_nodes(), MAX_LEN), the key
      `FusedPOA._fused_plan` consults."""
    from ..ops.poa_fused import DEPTH_BUCKETS
    from ..ops.poa_graph import BUCKETS, MAX_LEN, MAX_PRED, env_max_nodes

    at = at if at is not None else Autotuner()

    def report(name, key, ent, fresh):
        log(f"{name} {key}: winner {ent['kernel']}:{ent['dtype']} "
            f"identical={ent['identical']} "
            f"fresh={'yes' if fresh else 'no'} ms={ent['ms']}")

    for nb, lb in BUCKETS:
        ent, fresh = at.profile_session_bucket(nb, lb, MAX_PRED, 3, -5, -4,
                                               rows=32)
        report("session", (nb, lb), ent, fresh)
    for edge in (512, 1024, 2048, 4096):
        top = max(128, (int(edge * 0.1) + 127) // 128 * 128)
        for band in range(128, top + 128, 128):
            ent, fresh = at.profile_aligner_bucket(edge, band)
            report("aligner", (edge, band), ent, fresh)
    n = env_max_nodes()
    for d in DEPTH_BUCKETS:
        ent, fresh = at.profile_fused_bucket(n, MAX_LEN, d, MAX_PRED,
                                             3, -5, -4)
        report("fused_loop", (n, MAX_LEN, d), ent, fresh)
    path = at.save()
    log(f"winner table ({len(at.table)} entries) -> {path}")
    return path
