"""Device milliseconds of the session POA program (`ops/poa_graph.py`
`graph_aligner`, whose jitted function is `align`) per window polished
in the traced job, from the profiler trace's program events."""

import re

#: jit name of the session POA program on the trace: its modules read
#: `jit_align(<program id>)` on a v5e trace, one id per (nodes, length)
#: bucket.
PATTERN = re.compile(r"^jit_align$")


def read(run):
    t = run.trace
    if not t or not run.traced_windows:
        return None
    s = sum(v for k, v in t["program_s"].items() if PATTERN.search(k))
    return 1e3 * s / run.traced_windows if s > 0 else None
