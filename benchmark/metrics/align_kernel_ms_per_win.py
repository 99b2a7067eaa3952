"""Device milliseconds of the banded overlap aligner's program
(`ops/align.py` `_banded_nw_kernel`: wavefront scan and device
traceback in one program) per window polished in the traced job, from
the profiler trace's program events."""

import re

#: jit name of the aligner's program on the trace. The program is
#: `jax.jit(functools.partial(_banded_nw_kernel, ...))`, and JAX names a
#: partial `<unknown>`: on a v5e trace its modules read
#: `jit__unknown(<program id>)`, one id per (edge, band, lanes) shape.
#: No other program of the session path is a jitted partial.
PATTERN = re.compile(r"^jit__unknown$")


def read(run):
    t = run.trace
    if not t or not run.traced_windows:
        return None
    s = sum(v for k, v in t["program_s"].items() if PATTERN.search(k))
    return 1e3 * s / run.traced_windows if s > 0 else None
