"""The faults the Q10 cell can have, planted under a run of ``run.py``:
``faults.py``'s two over ``lineitem`` (an answer altered where it is
produced, the last eighth of every batch of fact rows left out), and the
one only a final that recovers dropped group keys has: **a recovered
attribute taken from a neighbouring row**. Q10's program sorts
``c_custkey`` alone and reads the six attributes that key determines at
its twenty output rows, each through the row id that rode the sort and
the joins' own row indices; here ``c_phone`` is read seven rows on (past
the order's own lines, so at another order's customer): every sum and
every other cell is right, the phone is someone else's.
``test_run_faults_q10.py`` drives the three at a rehearsal's size on the
CPU; run as a script this file drives one on the chip at the cell's own
size through ``run.py``'s own comparison:

    python benchmarks/tests/faults_q10.py \\
        --fault altered|left_out|neighbour_row --seed <n> --seconds <s>

It prints ``run.py``'s line, whose ``correct`` must read false."""

import os
import sys

import faults

CELL = "tpch_q10_sf10_1chip.q10"
FACT = "lineitem"
# ``c_phone`` in the projection under Q10's aggregate (c_custkey, c_name,
# c_address, c_phone, c_acctbal, c_comment, the two money columns, n_name)
C_PHONE = 3
ROWS_ON = 7  # an order has at most seven lines


def neighbour_row():
    """The final's own read of ``c_phone`` (not the reads it makes on
    its way through the joins) lands ``ROWS_ON`` probe rows further."""
    from opentenbase_tpu.executor import fused_dag

    real = fused_dag._col_at_rows
    depth = [0]

    def col_at_rows(env, i, rows):
        final = depth[0] == 0 and isinstance(env, fused_dag._LazyEnv)
        depth[0] += 1
        try:
            if final and i == C_PHONE:
                rows = rows + ROWS_ON
            return real(env, i, rows)
        finally:
            depth[0] -= 1

    return faults.patched(fused_dag, "_col_at_rows", col_at_rows)


FAULTS = {
    "altered": lambda: faults.altered(FACT),
    "left_out": lambda: faults.left_out(FACT),
    "neighbour_row": neighbour_row,
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
