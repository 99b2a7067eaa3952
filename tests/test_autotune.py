"""Persisted per-bucket kernel autotuner (sched/autotune.py) + the
kernel-plane acceptance pins.

The autotuner's contract: profile once on the live backend, persist the
winner table next to the XLA compile cache, and have every later process
dispatch the measured winner under RACON_TPU_PALLAS=auto WITHOUT running
a single candidate again. And whatever the table says, the polished
FASTA must not move: the kernel plane is a pure perf decision, pinned
byte-identical across every (pallas, dtype, depth) posture here.
"""

import json
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from racon_tpu.sched import autotune
from racon_tpu.sched.autotune import (Autotuner, default_table_path,
                                      get_autotuner,
                                      reset_autotuner_cache)


@pytest.fixture(autouse=True)
def _isolated_table(tmp_path, monkeypatch):
    """Every test gets its own on-disk table; the process cache is
    dropped around each so no test sees another's winners."""
    monkeypatch.setenv("RACON_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    reset_autotuner_cache()
    yield
    reset_autotuner_cache()


# ------------------------------------------------------------- the table

def test_table_roundtrip_persists_across_instances(tmp_path):
    path = str(tmp_path / "t.json")
    at = Autotuner(path)
    entry = {"kernel": "pallas", "dtype": "int16",
             "ms": {"xla:int32": 1.5, "pallas:int16": 0.5},
             "identical": True}
    at.record("session", (192, 128), (3, -5, -4, 8), entry)
    assert at.save() == path
    # a different instance on the same path (a new process, as far as
    # the table is concerned) sees the same winner
    again = Autotuner(path)
    assert again.winner("session", (192, 128), (3, -5, -4, 8)) == entry
    assert again.winner("session", (192, 128), (5, -4, -8, 8)) is None
    assert again.winner("aligner", (192, 128)) is None


def test_key_is_backend_scoped():
    k_cpu = Autotuner.key("session", (96, 96), (3, -5, -4), backend="cpu")
    k_tpu = Autotuner.key("session", (96, 96), (3, -5, -4), backend="tpu")
    assert k_cpu != k_tpu  # a table profiled on chip never leaks to CPU
    assert Autotuner.key("aligner", 512, backend="cpu") \
        == Autotuner.key("aligner", (512,), backend="cpu")


def test_corrupt_or_stale_table_treated_as_absent(tmp_path):
    path = tmp_path / "t.json"
    path.write_text("{not json")
    assert Autotuner(str(path)).table == {}
    path.write_text(json.dumps({"version": -1, "winners": {"k": {}}}))
    assert Autotuner(str(path)).table == {}
    path.write_text(json.dumps({"version": autotune.VERSION,
                                "winners": {"k": {"kernel": "xla"}}}))
    assert Autotuner(str(path)).table == {"k": {"kernel": "xla"}}


def test_default_table_path_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv("RACON_TPU_AUTOTUNE_CACHE", "/x/y.json")
    assert default_table_path() == "/x/y.json"
    monkeypatch.delenv("RACON_TPU_AUTOTUNE_CACHE")
    # next to the XLA compile cache in force, so both warm together
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert default_table_path() == str(tmp_path / autotune.BASENAME)
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env"))
        assert default_table_path() == str(tmp_path / "env" /
                                           autotune.BASENAME)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------- profiling

def test_profile_buckets_then_warm_second_process_profiles_nothing(
        monkeypatch):
    """The acceptance pin: a cold profile measures every candidate and
    verifies identity; a second process (fresh Autotuner on the saved
    table) returns the persisted entry WITHOUT timing anything."""
    at = get_autotuner()
    entry, fresh = at.profile_session_bucket(96, 96, 4, 3, -5, -4,
                                             rows=4, reps=1)
    assert fresh
    assert entry["kernel"] in ("xla", "pallas")
    assert entry["dtype"] in ("int32", "int16")
    # every candidate ran: both kernels x both dtypes (the proof holds
    # at this bucket), and all reproduced the int32 XLA oracle
    assert set(entry["ms"]) == {"xla:int32", "xla:int16",
                                "pallas:int32", "pallas:int16"}
    assert entry["identical"] is True

    a_entry, fresh = at.profile_aligner_bucket(128, 32, rows=4, reps=1)
    assert fresh
    assert set(a_entry["ms"]) == {"xla:int32", "xla:int16",
                                  "pallas:int32", "pallas:int16"}
    assert a_entry["identical"] is True
    at.save()

    # same process, same instance: warm
    _, fresh = at.profile_session_bucket(96, 96, 4, 3, -5, -4)
    assert not fresh

    # "second process": drop the cache, reload from disk, and make any
    # attempt to actually time a candidate blow up
    reset_autotuner_cache()
    monkeypatch.setattr(Autotuner, "_time", staticmethod(
        lambda *a, **k: pytest.fail("warm profile ran a candidate")))
    warm = get_autotuner()
    e2, fresh = warm.profile_session_bucket(96, 96, 4, 3, -5, -4)
    assert not fresh and e2 == entry
    e3, fresh = warm.profile_aligner_bucket(128, 32)
    assert not fresh and e3 == a_entry


def test_profile_fused_bucket_warm_second_process_profiles_nothing(
        monkeypatch):
    """The fused-loop plane joins the autotuner contract: a cold
    profile times split-vs-fused on the live backend under the identity
    veto; a second process returns the persisted entry without running
    a candidate."""
    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    at = get_autotuner()
    entry, fresh = at.profile_fused_bucket(192, 96, 8, 4, 3, -5, -4,
                                           rows=2, reps=1)
    assert fresh
    assert entry["kernel"] in ("split", "fused")
    dt = entry["dtype"]
    assert set(entry["ms"]) == {f"split:{dt}", f"fused:{dt}"}
    assert entry["identical"] is True
    at.save()

    _, fresh = at.profile_fused_bucket(192, 96, 8, 4, 3, -5, -4)
    assert not fresh

    reset_autotuner_cache()
    monkeypatch.setattr(Autotuner, "_time", staticmethod(
        lambda *a, **k: pytest.fail("warm profile ran a candidate")))
    warm = get_autotuner()
    e2, fresh = warm.profile_fused_bucket(192, 96, 8, 4, 3, -5, -4)
    assert not fresh and e2 == entry


def test_pick_vetoes_non_identical_candidates():
    ms = {"xla:int32": 2.0, "pallas:int16": 0.1}
    outs = {"xla:int32": np.arange(4), "pallas:int16": np.arange(4) + 1}
    entry = Autotuner._pick(ms, outs, "xla:int32")
    # the fast candidate disagreed with the oracle: disqualified AND
    # flagged — never dispatched, however fast
    assert entry["kernel"] == "xla" and entry["dtype"] == "int32"
    assert entry["identical"] is False
    outs["pallas:int16"] = np.arange(4)
    entry = Autotuner._pick(ms, outs, "xla:int32")
    assert entry["kernel"] == "pallas" and entry["dtype"] == "int16"
    assert entry["identical"] is True


# ------------------------------------------- dispatchers under `auto`

def test_session_engine_plan_follows_winner_table(monkeypatch):
    from racon_tpu.ops.poa_graph import DeviceGraphPOA

    monkeypatch.setenv("RACON_TPU_PALLAS", "auto")

    def engine():
        return DeviceGraphPOA(3, -5, -4, max_nodes=96, max_len=96,
                              buckets=((96, 96),), batch_rows=4)

    # cold: no table entry -> XLA exactly as off (dtype still shrinks by
    # the proof alone)
    eng = engine()
    assert eng.pallas_posture == "auto"
    assert eng._plan(96, 96) == (False, "int16")

    # a measured winner flips the SAME construction to the pallas
    # kernel, at the measured dtype (int32 here: the table beats the
    # proof's default-narrow)
    at = get_autotuner()
    at.record("session", (96, 96), (3, -5, -4, eng.max_pred),
              {"kernel": "pallas", "dtype": "int32", "ms": {},
               "identical": True})
    at.save()
    reset_autotuner_cache()
    assert engine()._plan(96, 96) == (True, "int32")


def test_fused_engine_dtype_follows_winner_table(monkeypatch):
    from racon_tpu.ops.poa_fused import FusedPOA

    monkeypatch.setenv("RACON_TPU_PALLAS", "auto")
    kw = dict(max_nodes=256, max_len=128, batch_rows=4,
              depth_buckets=(4,))
    assert FusedPOA(3, -5, -4, **kw).score_dtype == "int16"
    at = get_autotuner()
    at.record("fused", (256, 128), (3, -5, -4, 8),
              {"kernel": "xla", "dtype": "int32", "ms": {},
               "identical": True})
    at.save()
    reset_autotuner_cache()
    assert FusedPOA(3, -5, -4, **kw).score_dtype == "int32"


def test_profile_production_writes_keys_engines_consult():
    """The cold->warm weld: the buckets/params `profile_production`
    profiles must be EXACTLY the keys the default-constructed
    production dispatchers look up under `auto` — a table written under
    any other (scoring, max_pred, band) tuple is dead weight and `auto`
    stays permanently cold."""
    from racon_tpu.ops.align import BatchAligner
    from racon_tpu.ops.poa_graph import BUCKETS, MAX_PRED

    calls = {"session": [], "aligner": [], "fused_loop": []}

    class Rec:
        table = {}

        def profile_session_bucket(self, nb, lb, mp, m, x, g, **kw):
            calls["session"].append((nb, lb, mp, m, x, g))
            return {"kernel": "xla", "dtype": "int32", "ms": {},
                    "identical": True}, True

        def profile_aligner_bucket(self, edge, band, **kw):
            calls["aligner"].append((edge, band))
            return {"kernel": "xla", "dtype": "int32", "ms": {},
                    "identical": True}, True

        def profile_fused_bucket(self, nb, lb, d, mp, m, x, g, **kw):
            calls["fused_loop"].append((nb, lb, d, mp, m, x, g))
            return {"kernel": "split", "dtype": "int32", "ms": {},
                    "identical": True}, True

        def save(self):
            return "<recorded>"

    assert autotune.profile_production(Rec(), log=lambda _: None) \
        == "<recorded>"

    # session: every static bucket at the polisher/CLI default scoring
    # and the engine's MAX_PRED — the exact _plan() lookup tuple
    assert set(calls["session"]) >= {
        (nb, lb, MAX_PRED, 3, -5, -4) for nb, lb in BUCKETS}
    # aligner: whatever band the auto rule derives for pairs anywhere in
    # a profiled bucket must have been profiled for that bucket
    ba = BatchAligner()
    profiled = set(calls["aligner"])
    edges = sorted({e for e, _ in profiled})
    for edge, prev in zip(edges, [0] + edges):
        for length in (prev + 1, (prev + edge) // 2 + 1, edge):
            pairs = [(b"A" * length, b"A" * length)]
            assert (edge, ba._band_for(pairs, [0])) in profiled, \
                f"auto band for len {length} not profiled at edge {edge}"
    # fused-loop: whatever consult key FusedPOA._fused_plan derives for
    # ANY chunk depth (N, L, leading chain bucket at the default
    # scoring/MAX_PRED) must have been profiled — the weld that lets
    # RACON_TPU_FUSED=auto go warm at production dispatch keys
    from racon_tpu.ops.poa_fused import FUSED_LOOP_MAX_DEPTH, FusedPOA

    eng = FusedPOA(3, -5, -4)
    fused_profiled = set(calls["fused_loop"])
    for depth in range(1, FUSED_LOOP_MAX_DEPTH + 1):
        plan = eng._chain_plan(depth)
        assert (eng.N, eng.L, plan[0], eng.P, 3, -5, -4) \
            in fused_profiled, \
            f"fused consult key for chunk depth {depth} not profiled"


# --------------------------------------- the byte-identity acceptance pin

class _ForcedTable:
    """A winner table that answers 'pallas, int16' for every bucket —
    the most aggressive posture `auto` could ever take. The envelope
    proofs and VMEM gates still apply downstream, so this drives every
    legally-narrowable bucket onto the narrow resident kernel."""

    def winner(self, engine, bucket, params=()):
        return {"kernel": "pallas", "dtype": "int16", "ms": {},
                "identical": True}


@pytest.mark.parametrize("engine", ["session", "fused"])
def test_polisher_fasta_identical_across_kernel_plane_modes(
        engine, tmp_path, monkeypatch):
    """THE acceptance pin: polished FASTA byte-identical across
    RACON_TPU_PALLAS={0,1,auto} x dtype {int32, shrunk} x pipeline
    depth {0,2}, aligner + POA device engines armed, interpret-mode
    kernels on the CPU backend. The kernel plane may move every perf
    number; it may not move one output byte."""
    from test_pipeline import _synth_dataset

    from racon_tpu.core.polisher import PolisherType, create_polisher

    monkeypatch.setenv("RACON_TPU_MAX_DEVICES", "1")
    paths = [str(x) for x in _synth_dataset(tmp_path, random.Random(23))]

    def run(pallas, dtype, depth):
        monkeypatch.setenv("RACON_TPU_PALLAS", pallas)
        monkeypatch.setenv("RACON_TPU_DTYPE", dtype)
        if pallas == "auto":
            # a table that forces the aggressive plane everywhere; the
            # cold-table `auto` == off case is covered separately below
            monkeypatch.setattr(autotune, "get_autotuner",
                                lambda: _ForcedTable())
        else:
            monkeypatch.setattr(autotune, "get_autotuner", get_autotuner)
        p = create_polisher(*paths, PolisherType.kC, 500, -1.0, 0.3,
                            num_threads=2, tpu_aligner_batches=1,
                            tpu_poa_batches=1, tpu_engine=engine,
                            tpu_pipeline_depth=depth)
        p.initialize()
        return [(s.name, s.data) for s in p.polish()]

    ref = run("0", "int32", 0)
    assert ref and all(d for _, d in ref)
    # the matrix, minus the reference itself: every pallas posture at
    # both depths, wide and shrunk
    for pallas in ("0", "1", "auto"):
        for dtype, depth in (("int32", 2), ("auto", 0), ("auto", 2)):
            if pallas == "0" and (dtype, depth) == ("int32", 0):
                continue
            assert run(pallas, dtype, depth) == ref, \
                f"FASTA diverged at pallas={pallas} dtype={dtype} " \
                f"depth={depth}"
    # cold-table auto: no entries -> dispatches exactly like off
    monkeypatch.setenv("RACON_TPU_PALLAS", "auto")
    monkeypatch.setenv("RACON_TPU_DTYPE", "auto")
    monkeypatch.setattr(autotune, "get_autotuner", get_autotuner)
    reset_autotuner_cache()
    assert run("auto", "auto", 0) == ref
