"""The faults the star cell can have, planted under a run of ``run.py``:
``faults.py``'s two over ``lineorder`` (an answer altered where it is
produced, the last eighth of every batch of fact rows left out), and the
one only a star deployment has: **a dimension left short**. The last
eighth of ``part`` never reaches the stores while the reference keeps
it, so the lines that name those parts drop out of Q2.1's and Q4.1's
joins. ``test_run_faults_star.py`` drives the three at a rehearsal's
size on the CPU; run as a script this file drives one on the chip at the
cell's own size through ``run.py``'s own comparison:

    python benchmarks/tests/faults_star.py \\
        --fault altered|left_out|short_dimension --seed <n> --seconds <s>

It prints ``run.py``'s line, whose ``correct`` must read false."""

import os
import sys

import faults

CELL = "ssb_star_sf10_1chip.star"
FACT = "lineorder"
DIMENSION = "part"


FAULTS = {
    "altered": lambda: faults.altered(FACT),
    "left_out": lambda: faults.left_out(FACT),
    # the same cut as ``left_out``, of a table that is loaded whole
    "short_dimension": lambda: faults.left_out(DIMENSION),
}


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(here), os.path.dirname(os.path.dirname(here))]
    import run as bench_run

    argv = sys.argv[1:]
    fault = argv[argv.index("--fault") + 1]
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    with FAULTS[fault]():
        sys.exit(bench_run.main(["--workload", CELL] + argv))
