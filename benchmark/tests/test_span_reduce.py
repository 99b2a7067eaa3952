"""The program-span reduction: self time, idle inside a span, idle by
innermost span, gap names and the per-window readings, on synthetic
traces, and the reading of `racon.*` annotations from a capture."""

import pytest

import span_reduce as sr
import trace_reduce as tr


def synthetic():
    """One device; harness spans job > initialize > align, polish; the
    program's spans on two threads (0 the caller, 1 a pack worker)."""
    programs = [[(100, 200, "jit_a(1)"), (400, 500, "jit_b(7)"),
                 (900, 950, "jit_b(8)")]]
    bench = [(0, 1000, "job"), (0, 600, "initialize"), (300, 600, "align"),
             (600, 1000, "polish")]
    spans = [
        (0, 600, "polisher.initialize", 0, {}),
        (0, 50, "polisher.load_targets", 0, {}),
        (250, 600, "polisher.align_overlaps", 0, {}),
        (260, 320, "aligner.plan", 0, {}),
        (330, 380, "pipeline.pack", 1, {"chunk": 0}),
        (520, 600, "polisher.breaking_points", 0, {}),
        (600, 1000, "polisher.consensus", 0, {}),
        (950, 1000, "polisher.stitch", 0, {}),
    ]
    return tr.Trace(programs, bench), spans


def test_self_time_subtracts_children_on_the_same_thread():
    _, spans = synthetic()
    st = sr.self_s(spans, 0, 1000)
    # initialize 600 - load_targets 50 - align_overlaps 350
    assert st["polisher.initialize"] == pytest.approx(200e-9)
    # align_overlaps 350 - plan 60 - breaking_points 80; the pack on
    # the worker thread is no child of it
    assert st["polisher.align_overlaps"] == pytest.approx(210e-9)
    assert st["pipeline.pack"] == pytest.approx(50e-9)
    assert st["polisher.consensus"] == pytest.approx(350e-9)


def test_self_time_clips_to_the_window():
    _, spans = synthetic()
    st = sr.self_s(spans, 300, 1000)
    assert st["polisher.initialize"] == pytest.approx(0.0)
    assert st["aligner.plan"] == pytest.approx(20e-9)
    assert st["polisher.load_targets"] == 0.0


def test_idle_inside_a_span():
    t, spans = synthetic()
    busy = [sr.Busy(d, 0, 1000) for d in t.programs]
    idle = sr.idle_in_s(busy, spans, 0, 1000)
    # align_overlaps [250, 600]: busy [400, 500] -> idle 250
    assert idle["polisher.align_overlaps"] == pytest.approx(250e-9)
    # consensus [600, 1000]: busy [900, 950] -> idle 350
    assert idle["polisher.consensus"] == pytest.approx(350e-9)
    assert idle["aligner.plan"] == pytest.approx(60e-9)
    assert busy[0].within(150, 450) == 100


def test_idle_by_innermost_span_tiles_all_idle():
    t, spans = synthetic()
    busy = [sr.Busy(d, 0, 1000) for d in t.programs]
    by = sr.idle_by_innermost_s(busy, spans, 0, 1000)
    assert by == pytest.approx({
        "polisher.load_targets": 50e-9,       # [0, 50]
        "polisher.initialize": 100e-9,        # [50, 100], [200, 250]
        # [250, 260], [320, 330], [380, 400], [500, 520]
        "polisher.align_overlaps": 60e-9,
        "aligner.plan": 60e-9,
        "pipeline.pack": 50e-9,
        "polisher.breaking_points": 80e-9,
        "polisher.consensus": 300e-9,         # [600, 900]
        "polisher.stitch": 50e-9,             # [950, 1000]
    })
    # every idle nanosecond is charged exactly once
    assert sum(by.values()) == pytest.approx(1000e-9 - 250e-9)


def test_idle_by_innermost_span_without_program_spans():
    t, _ = synthetic()
    busy = [sr.Busy(d, 0, 1000) for d in t.programs]
    assert sr.idle_by_innermost_s(busy, [], 0, 1000) == pytest.approx(
        {"-": 750e-9})


def test_gap_names_carry_the_program_span():
    t, spans = synthetic()
    busy = [sr.Busy(d, 0, 1000) for d in t.programs]
    gaps = sr.idle_gaps(t, busy, spans, 0, 1000)
    # [500, 900] mid 700: harness polish, program consensus
    assert gaps[0] == ["polish/polisher.consensus", 400e-9]
    # [200, 400] mid 300: align, innermost program span aligner.plan
    assert gaps[1] == ["align/aligner.plan", 200e-9]


def test_gap_names_without_program_spans_are_the_harness_names():
    t, _ = synthetic()
    busy = [sr.Busy(d, 0, 1000) for d in t.programs]
    got = sr.idle_gaps(t, busy, [], 0, 1000)
    assert got == tr.reduce(t, 0, 1000)["idle_gaps"]


def test_per_window_readings():
    t, spans = synthetic()
    r = sr.reduce(t, spans, 0, 1000)
    got = sr.per_window(r, 2)
    assert got["align_idle_ms_per_win"] == pytest.approx(1e3 * 250e-9 / 2)
    assert got["poa_idle_ms_per_win"] == pytest.approx(1e3 * 350e-9 / 2)
    # load_targets 50 + breaking_points 80 + stitch 50 of self time
    assert got["host_only_ms_per_win"] == pytest.approx(1e3 * 180e-9 / 2)
    assert sr.reduce(t, spans, 600, 880) is None


def test_wait_lags_pair_chunks_with_program_runs():
    programs = [(0, 100, "jit_k(1)"), (100, 300, "jit_k(2)"),
                (300, 320, "jit_other(3)"), (320, 400, "jit_k(1)")]
    waits = [(50, 110, "pipeline.device", 2,
              {"seg": "wait", "loop": "aligner", "chunk": 0}),
             (120, 305, "pipeline.device", 2,
              {"seg": "wait", "loop": "aligner", "chunk": 1}),
             # began after its program ended: did not block
             (450, 460, "pipeline.device", 2,
              {"seg": "wait", "loop": "aligner", "chunk": 2}),
             (60, 70, "pipeline.device", 0,
              {"seg": "dispatch", "loop": "aligner", "chunk": 1})]
    lags = sr.wait_lags_s(programs, waits, "jit_k")
    assert lags == pytest.approx([10e-9, 5e-9])


def test_capture_spans_read_back(tmp_path):
    """Annotations named `racon.*` come back with their threads and
    arguments; the harness's `bench.*` ones stay trace_reduce's."""
    import threading

    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.job"):
            with jax.profiler.TraceAnnotation("racon.outer", edge=512):
                t = threading.Thread(target=lambda: jax.profiler.
                                     TraceAnnotation("racon.worker")
                                     .__enter__().__exit__(None, None,
                                                           None))
                t.start()
                t.join(timeout=10)
    import glob

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t, spans = sr.load(path)
    assert [s[2] for s in t.spans] == ["job"]
    by = {s[2]: s for s in spans}
    assert set(by) == {"outer", "worker"}
    assert by["outer"][4] == {"edge": 512}
    assert by["outer"][3] != by["worker"][3]


def test_recorded_v5e_trace_with_program_spans(tmp_path):
    """A traced 16 kb contig job recorded on one v5e chip with the
    program's `racon.*` spans, reduced here as it was there: the phases
    nest, every idle gap under `align` or `consensus` names a program
    span, and the harness's own reduction still reads the trace."""
    import gzip
    import json
    import os
    import shutil

    data = os.path.join(os.path.dirname(__file__), "data")
    with open(os.path.join(data, "small_job_spans.reduced.json")) as f:
        rec = json.load(f)
    path = tmp_path / "small_job_spans.xplane.pb"
    gz = os.path.join(data, "small_job_spans.xplane.pb.gz")
    with gzip.open(gz) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t, spans = sr.load(str(path))
    job, = [s for s in t.spans if s[2] == "job"]
    assert list(job[:2]) == rec["job_ns"]
    r = sr.reduce(t, spans, job[0], job[1])
    assert r == rec["reduced"]
    names = {s[2] for s in spans}
    assert {"polisher.initialize", "polisher.load_targets",
            "polisher.align_overlaps", "polisher.breaking_points",
            "polisher.consensus", "session.commit",
            "polisher.stitch"} <= names
    for g, _ in r["idle_gaps"]:
        harness = g.split("/")[0]
        assert harness not in ("align", "consensus") or "/" in g, g
    # the pipeline's aligner chunks carry their shape
    packs = [s[4] for s in spans if s[2] == "pipeline.pack"]
    assert packs and all({"edge", "band", "lanes", "lane_cap",
                          "kernel"} <= set(a) for a in packs)
    pw = sr.per_window(r, rec["windows"])
    assert all(v > 0 for v in pw.values())
    assert tr.reduce(t, job[0], job[1])["busy_s"] > 0
