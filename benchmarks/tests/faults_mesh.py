"""The fault only a mesh can have, planted under a run of ``run.py``:
what one exchange sends to one destination never arrives. The device at
the mesh's last position reads its buckets of the statement's first
redistribute as empty, so the rows routed to it drop out of the join
while the reference still has them. ``test_run_faults_mesh.py`` drives
it at a rehearsal's size on four virtual CPU devices; run as a script
this file drives it on the chips at the cell's own size through
``run.py``'s own comparison:

    python benchmarks/tests/faults_mesh.py \\
        --workload tpch_sf30_4chip.join --seed <n> --seconds <s>

It prints ``run.py``'s line, whose ``correct`` must read false."""

import os
import sys

import numpy as np

import faults


def bucket_left_out():
    import jax

    from opentenbase_tpu.executor import fused_dag

    real = fused_dag.DagRunner._run_exchange
    first: list = []

    def run_exchange(self, frag, *a, **kw):
        out = real(self, frag, *a, **kw)
        if not first:
            first.append(frag.index)
        if frag.index != first[0]:
            return out
        counts = out["counts"]  # [dest * D + src] rows received
        D = self.fx.mesh.shape["dn"]
        emptied = np.array(counts)
        emptied[(D - 1) * D:] = 0  # the last device got nothing
        return dict(out, counts=jax.device_put(emptied, counts.sharding))

    return faults.patched(fused_dag.DagRunner, "_run_exchange", run_exchange)


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(here), os.path.dirname(os.path.dirname(here))]
    import run as bench_run

    with bucket_left_out():
        sys.exit(bench_run.main(sys.argv[1:]))
