"""Faults planted where the program produces its answer
(`Polisher._stitch`, which assembles the windows' consensus into
records), for the checks that a broken timed path reads `correct`
false: `benchmark/tests/test_faults.py` at a test's size on the CPU,
`control.py --fault` at the cell's own size on the chip.

- `unchanged`: a step that returns its state unchanged; every window
  keeps its unpolished backbone.
- `half`: half of the batch left out; only the first half of the
  windows is stitched.
- `altered`: an answer altered where it is produced; the consensus of
  the longest window is garbled.
"""

import contextlib

GARBLE = bytes.maketrans(b"ACGT", b"CATG")


def unchanged(p):
    for w in p.windows:
        w.consensus = w.sequences[0]


def half(p):
    del p.windows[len(p.windows) // 2:]


def altered(p):
    # the longest window: a read's last window can be a few bases long
    w = max(p.windows, key=lambda w: len(w.consensus))
    w.consensus = w.consensus.translate(GARBLE)


FAULTS = {"unchanged": unchanged, "half": half, "altered": altered}


@contextlib.contextmanager
def planted(name: str | None):
    """Every polisher's stitch runs the fault first, while inside."""
    if name is None:
        yield
        return
    from racon_tpu.core import polisher as polisher_mod

    fault = FAULTS[name]
    real = polisher_mod.Polisher._stitch

    def broken(self, *a, **kw):
        fault(self)
        return real(self, *a, **kw)
    polisher_mod.Polisher._stitch = broken
    try:
        yield
    finally:
        polisher_mod.Polisher._stitch = real
