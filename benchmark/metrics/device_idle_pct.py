"""Share of the traced job's wall time in which no program ran on the
device: one minus the union of the device's program intervals over the
job's span, from the profiler trace."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
