"""The faults the Q5 cell can have, planted under a run of ``run.py``:
``faults.py``'s two over ``lineitem`` (an answer altered where it is
produced, the last eighth of every batch of fact rows left out), and the
one only a join with several key pairs has: **the second pair dropped**.
The customer join looks its row up by ``c_custkey`` and no longer asks
that ``c_nationkey = s_nationkey``, so every line of a supplier in the
region counts, whatever its customer's nation: about five times the
revenue of each nation (25 nations, 5 in a region).
``test_run_faults_q5.py`` drives the three at a rehearsal's size on the
CPU; run as a script this file drives one on the chip at the cell's own
size through ``run.py``'s own comparison:

    python benchmarks/tests/faults_q5.py \\
        --fault altered|left_out|second_pair_dropped --seed <n> --seconds <s>

It prints ``run.py``'s line, whose ``correct`` must read false."""

import os
import sys

import faults

CELL = "tpch_q5_sf10_1chip.q5"
FACT = "lineitem"


def second_pair_dropped():
    """Every pair but the one that drives the lookup goes unchecked."""
    from opentenbase_tpu.executor import fused_dag

    return faults.patched(
        fused_dag, "_all_equal", lambda pairs, mask: mask
    )


FAULTS = {
    "altered": lambda: faults.altered(FACT),
    "left_out": lambda: faults.left_out(FACT),
    "second_pair_dropped": second_pair_dropped,
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
