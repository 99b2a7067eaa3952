"""The trace reduction: busy union, idle share, per-program device time
and idle gaps named by the open harness span."""

import trace_reduce as tr


def synthetic():
    # one device; programs in ns; host spans job > initialize > align
    programs = [[(100, 200, "jit_a(1)"), (150, 250, "jit_a(1)"),
                 (400, 500, "jit_b(7)"), (900, 950, "jit_b(8)")]]
    spans = [(0, 1000, "job"), (0, 600, "initialize"), (300, 600, "align"),
             (600, 1000, "polish")]
    return tr.Trace(programs, spans)


def test_busy_union_and_idle_share():
    r = tr.reduce(synthetic(), 0, 1000)
    # busy: [100, 250] + [400, 500] + [900, 950] = 300 ns
    assert r["busy_s"] == 300e-9 and r["window_s"] == 1000e-9
    assert r["program_s"] == {"jit_a": 200e-9, "jit_b": 150e-9}
    assert r["device_ops"] == [["jit_a(1)", 200e-9], ["jit_b(7)", 100e-9],
                               ["jit_b(8)", 50e-9]]


def test_idle_gaps_named_by_innermost_span():
    r = tr.reduce(synthetic(), 0, 1000)
    # gaps: [0,100] init, [250,400] align, [500,900] polish, [950,1000]
    assert r["idle_gaps"][0] == ["polish", 400e-9]
    assert r["idle_gaps"][1] == ["align", 150e-9]
    assert {g[0] for g in r["idle_gaps"]} == {"polish", "align",
                                              "initialize"}


def test_window_clips_and_empty_window_reads_nothing():
    r = tr.reduce(synthetic(), 120, 220)
    assert r["busy_s"] == 100e-9
    assert tr.reduce(synthetic(), 600, 880) is None
    assert tr.reduce(tr.Trace([], []), 0, 10) is None


def test_recorded_v5e_trace_reduces_as_on_the_chip(tmp_path):
    """A traced 16 kb contig job recorded on one v5e chip (program events
    only, as the traced run records them), reduced here as it was
    there."""
    import gzip
    import json
    import os
    import shutil

    data = os.path.join(os.path.dirname(__file__), "data")
    with open(os.path.join(data, "small_job.reduced.json")) as f:
        rec = json.load(f)
    path = tmp_path / "small_job.xplane.pb"
    with gzip.open(os.path.join(data, "small_job.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = tr.load(str(path))
    job = [s for s in t.spans if s[2] == "job"]
    assert len(job) == 1 and list(job[0][:2]) == rec["job_ns"]
    assert {"initialize", "align", "polish", "consensus",
            "stitch"} <= {s[2] for s in t.spans}
    r = tr.reduce(t, job[0][0], job[0][1])
    assert r == rec["reduced"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert {"jit_align", "jit__unknown"} <= set(r["program_s"])
