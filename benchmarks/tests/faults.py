"""The faults a one-chip query cell can have, planted under a run of
``run.py``: an answer altered where it is produced, and part of the data
left out of what the statements read. ``test_run_faults.py`` drives them
at a rehearsal's size on the CPU; run as a script this file drives one
on the chip at the cell's own size through ``run.py``'s own comparison:

    python benchmarks/tests/faults.py --fault altered|left_out \\
        --workload <cell> --seed <n> --seconds <s>

It prints ``run.py``'s line, whose ``correct`` must read false."""

import contextlib
import os
import sys

FACT = {"tpch_sf10_1chip.scan": "lineitem",
        "tpch_sf10_1chip.join": "lineitem",
        "ssb_sf10_1chip.flight1": "lineorder"}


@contextlib.contextmanager
def patched(obj, name: str, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def altered(fact: str):
    """A millionth off the largest sum of the last row of every answer
    over the fact table (a float for decimal sums; SSB's integer money
    arrives as an int)."""
    from opentenbase_tpu.engine import Session

    real = Session.execute

    def execute(self, sql):
        res = real(self, sql)
        if fact in sql and "pg_stat" not in sql and res.rows and (
            sql.lstrip().lower().startswith("select")
        ):
            row = list(res.rows[-1])
            floats = [j for j, v in enumerate(row) if isinstance(v, float)]
            ints = [j for j, v in enumerate(row) if type(v) is int]
            i = max(floats or ints, key=lambda j: abs(row[j]))
            if isinstance(row[i], float):
                row[i] *= 1.0 + 1e-6
            else:
                row[i] += max(1, row[i] // 10**6)
            res.rows[-1] = tuple(row)
        return res

    return patched(Session, "execute", execute)


def left_out(fact: str):
    """The last eighth of every batch of fact rows never reaches the
    stores; the reference still has them."""
    from harness import loader

    real = loader.Deployment.append

    def append(self, table, arrays, dicts):
        if table == fact:
            n = len(next(iter(arrays.values())))
            arrays = {k: v[: n - n // 8] for k, v in arrays.items()}
        real(self, table, arrays, dicts)

    return patched(loader.Deployment, "append", append)


FAULTS = {"altered": altered, "left_out": left_out}


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(here), os.path.dirname(os.path.dirname(here))]
    import run as bench_run

    argv = sys.argv[1:]
    fault = argv[argv.index("--fault") + 1]
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    workload = argv[argv.index("--workload") + 1]
    with FAULTS[fault](FACT[workload]):
        sys.exit(bench_run.main(argv))
