"""Polished windows of every completed job over the window's wall time,
from its start to the end of the last job (host clock)."""


def read(run):
    windows = sum(j.windows for j in run.done)
    return windows / run.window_s if windows and run.window_s > 0 else None
