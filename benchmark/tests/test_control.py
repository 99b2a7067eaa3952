"""The control at a size a test run can hold: the cell's own entry and
argv on the CPU, with one read in four kept (the depth-cap break of
the configuration's every-layer guarantee). It has to read `correct`
false where the same job with every read reads true."""

import os

import control
import gen
import run

SIZE = {"genome_bp": 48_000}
SEED = 2**32 + 901


def readings(job, cfg, d):
    res = run.run_job(job.name, run.job_argv(job.write(d), cfg, 4))
    assert res.ok, res.why
    return run.check([job], [res], cfg)


def test_control_fails_where_every_read_passes(tmp_path):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, cfg, traffic, _, _ = run.cell_spec(bench, "ecoli-ont30-w500.paf")
    cfg = dict(cfg, **SIZE)
    job = gen.make_jobs(SEED, cfg, traffic, 1)[0]
    assert run.is_correct(readings(job, cfg, str(tmp_path)))
    checks = readings(control.thin(job, control.KEEP), cfg, str(tmp_path))
    assert not run.is_correct(checks), checks
