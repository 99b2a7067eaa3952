"""Regenerate the committed synthetic golden polished FASTA (tests/data/).

The reference's GPU CI pins a whole-run golden output and requires an exact
byte diff (ci/gpu/cuda_test.sh:30-44, ci/gpu/golden-output.txt, 5.2 MB).
This repo's analogue: the host engine's polished FASTA of a seeded 50 kb,
20x ONT-like workload simulated in-repo (tools/synthbench.py, seed 42),
which every engine must reproduce byte-for-byte
(tests/test_golden.py::test_synth_genome_golden_exact_diff). Nothing is
downloaded; the simulation is deterministic per seed.

Run from the repo root after an intentional algorithm change:
    python tools/make_golden.py
and commit the updated file with the change that caused it.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import synthbench  # noqa: E402

OUT = os.path.join(REPO, "tests", "data", "synth_50kb_golden.fasta")


def main() -> int:
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    rc = synthbench.main(["--genome-kb", "50", "--coverage", "20",
                          "--seed", "42", "--golden-out", OUT])
    if rc == 0:
        print(f"wrote {OUT} ({os.path.getsize(OUT)} bytes)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
