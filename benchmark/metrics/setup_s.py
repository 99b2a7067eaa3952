"""Seconds from the harness's start to the window's: imports, job
generation, the warm-up (one small job polished, every window job
initialized) and its compiles or cache loads (host clock)."""


def read(run):
    return run.setup_s
