"""Milliseconds in `Polisher.polish()` (POA consensus and stitch) per
polished window, summed over the window's completed jobs; timed by the
harness around the call."""


def read(run):
    windows = sum(j.windows for j in run.done)
    return 1e3 * sum(j.polish_s for j in run.done) / windows if windows \
        else None
