"""Compile a benchmark cell's DAG program at the cell's real shapes for
a described TPU v5e chip, in the sandbox, and list its gathers with the
memory space of every operand.

Nothing runs at real size and no chip is needed: the cell's deployment
is built at a toy scale on one CPU device (the tests' own fixtures, with
their SF10 statistics, so the plan and every formulation are the
cell's), the statement runs once there, and its final program is then
traced again with abstract arrays of the real widths on a mesh of the
described chip (``on-chip-measurement`` guide, section 2.3) and
compiled by the TPU compiler that is installed here. What comes out is
what a trace of the cell shows after a chip run, minus the times:

- ``joins``: the join record of the launch (compare it with the cell's
  in ``PERF.md`` section 5: if it differs, the toy plan is not the cell's);
- every fusion that holds a gather, with its stage and each operand as
  ``dtype[dims]`` and ``S(1)`` where the compiler placed it in that
  memory space. A gather whose table is outside ``S(1)`` costs 14-22 ns
  an element on the chip for 8.6 (PRs 35-37): PR 37's chip traces found
  the parent's three such tables where this tool shows them.

    python tools/compile_cell_program.py star q31
    python tools/compile_cell_program.py q5 q5 --hlo /root/scratch/q5.hlo
    python tools/compile_cell_program.py q10 q10

- every ``sort`` of at least that width, with its stage, its operands
  and their memory space (a gagg's one sort, a sort-merge's two).

It reaches into the runner (the mesh, the row estimates, the leaf
scan's static width): a scratch instrument, to be repaired when those
move, never imported by the program. A compile is not a chip run.
"""

from __future__ import annotations

import argparse
import inspect
import os
import re
import sys
import textwrap

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks"),
                os.path.join(ROOT, "tests")]

CELLS = {  # cell -> its configuration (the fixture's key)
    "star": "ssb_star_sf10_1chip", "flight1": "ssb_sf10_1chip",
    "join": "tpch_sf10_1chip", "q5": "tpch_q5_sf10_1chip",
    "q10": "tpch_q10_sf10_1chip",
}


def deployment(cell: str, kind: str):
    """(fixture, statement text) at a toy scale, statistics as at SF10."""
    if cell == "q5":
        import test_tpch_q5 as t

        fix = t.Q5(fact_rows=36_000)  # (60 suppliers: the arm folds)
        fix.set_stats(10)
        return fix, fix.text("ASIA", 1994)
    if cell == "q10":
        import test_tpch_q10 as t

        fix = t.Q10()
        fix.set_stats(10)
        return fix, fix.text("1993-10-01")
    import test_multikey_join as t

    fix = t.Cell(CELLS[cell])
    fix.set_stats(10)
    return fix, fix.texts[kind]


def gather_fusions(text: str, min_width: int):
    """[(op, result, stage, [(operand, shape, producer)])] of every
    fusion of ``text`` (``compiled.as_text()``) that holds a gather and
    yields at least ``min_width`` rows."""
    bodies = dict(re.findall(
        r"\n%([\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text, re.S))
    defs = {
        m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
            r"\n\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) (\w[\w\-]*)\(",
            text)
    }
    out = []
    for line in text.split("\n"):
        m = re.search(r"(%[\w.\-]+) = (\S+) fusion\((.*?)\), kind=.*?"
                      r"calls=%([\w.\-]+)", line)
        if not m or " gather(" not in bodies.get(m.group(4), ""):
            continue
        width = re.search(r"\[(\d+)", m.group(2))
        if width is None or int(width.group(1)) < min_width:
            continue
        stage = re.search(r'op_name="([^"]*)"', line)
        stage = re.sub(r"jit\(\w+\)/|shard_map/|otb/|/jit\(_take\)|/gather$",
                       "", stage.group(1) if stage else "")
        ops = [o.strip().split("*/")[-1] for o in m.group(3).split(",")]
        out.append((m.group(1), m.group(2), stage,
                    [(o,) + defs.get(o, ("?", "?")) for o in ops]))
    return out


def sorts(text: str, min_width: int):
    """[(op, stage, [operand shape])] of every ``sort`` of ``text`` over
    at least ``min_width`` rows."""
    out = []
    for line in text.split("\n"):
        m = re.search(r"(%[\w.\-]+) = (\(.*?\)|\S+) sort\(", line)
        if not m:
            continue
        shapes = re.findall(r"\w+\[[\d,]*\](?:\{[^}]*\})?", m.group(2))
        width = re.search(r"\[(\d+)", m.group(2))
        if width is None or int(width.group(1)) < min_width:
            continue
        stage = re.search(r'op_name="([^"]*)"', line)
        stage = re.sub(r"jit\(\w+\)/|shard_map/|otb/|/sort$", "",
                       stage.group(1) if stage else "")
        out.append((m.group(1), stage, shapes))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", choices=sorted(CELLS))
    ap.add_argument("kind", help="the statement's kind in the cell's "
                    "traffic mix: q21 | q31 | q41 | q11 | q3 | q5 | q10 ...")
    ap.add_argument("--min-width", type=int, default=10_000,
                    help="least rows a listed gather yields")
    ap.add_argument("--hlo", help="write compiled.as_text() here")
    args = ap.parse_args()

    import numpy as np
    import opentenbase_tpu.ops  # noqa: F401  (x64)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from opentenbase_tpu.executor import fused, fused_dag
    from opentenbase_tpu.ops import filter as filt_ops
    from opentenbase_tpu.plan import logical as L

    jax.config.update("jax_enable_compilation_cache", False)
    one_device = fused.build_mesh
    fused.build_mesh = lambda _devs=None: one_device(jax.devices()[:1])
    fix, sql = deployment(args.cell, args.kind)
    catalog = fix.dep.cluster.catalog

    def real_rows(table: str) -> int:
        return int(catalog.get(table).stats["rows"])

    # the gates' row estimates, as the stores would give them at SF10
    stores_rows = fused_dag.DagRunner._est_rows
    fused_dag.DagRunner._est_rows = lambda self, node: (
        real_rows(node.table) if isinstance(node, L.Scan)
        else stores_rows(self, node))
    # a leaf scan that takes its width from the arrays it is handed
    src = textwrap.dedent(inspect.getsource(fused_dag._Builder._leaf_scan))
    assert src.count("rmax = rmax0") == 1, "the leaf scan moved: repair me"
    ns = dict(vars(fused_dag))
    exec(src.replace("rmax = rmax0", "rmax = cols[0].shape[1]"), ns)
    fused_dag._Builder._leaf_scan = ns["_leaf_scan"]

    fx = fix.dep.cluster.fused_executor()
    fix.dep.sql(sql)  # at toy size, on the CPU: the device cache is warm
    runner = fx._dag

    # the same statement once more, bound for the described chip and
    # stopped at its final launch
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = Mesh(np.asarray(topo.devices[:1]), ("dn",))
    fx.mesh = chip
    runner._programs.clear()
    roots, launched = [], []
    collect_arrays = fused_dag._collect_arrays

    def note_root(fx_, root, exchanged, D):
        roots.append(root)
        return collect_arrays(fx_, root, exchanged, D)

    class Stop(Exception):
        pass

    def stop(self_, prog, build_args, late=None, **kw):
        launched.append((prog, build_args()))
        raise Stop()

    fused_dag._collect_arrays = note_root
    fused.Launcher.__call__ = stop
    import logging

    logging.disable(logging.CRITICAL)  # (the abort is logged as a demotion)
    try:
        fix.dep.sql(sql)
    except Exception:
        pass
    logging.disable(logging.NOTSET)
    if not launched or not roots:
        print("no DAG final program was launched for this statement")
        return 1
    prog, (arrays, params, snap) = launched[-1]
    leaves = list(fused_dag._walk_leaves(roots[-1]))

    def abstract(a, shape=None):
        a = jnp.asarray(a)
        spec = getattr(getattr(a, "sharding", None), "spec", P())
        return jax.ShapeDtypeStruct(
            a.shape if shape is None else shape, a.dtype,
            sharding=NamedSharding(chip, spec))

    wide = []
    for leaf, block in zip(leaves, arrays):
        k, toy = block[0][0].shape
        real = filt_ops.bucket_size(-(-real_rows(leaf.table) // k))
        print(f"{leaf.table}: {k} x {toy} -> {k} x {real}")
        wide.append(jax.tree.map(
            lambda a: abstract(a, (a.shape[0], real))
            if a.ndim == 2 and a.shape[1] == toy else abstract(a), block))
    lowered = prog.lower(
        tuple(wide), jax.tree.map(abstract, params), abstract(snap))
    print(f"{prog.__name__} joins: "
          + ";".join(f"{k}={v}" for k, v in sorted(prog.joins.items())))
    compiled = lowered.compile()
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    print(f"arguments {mem.argument_size_in_bytes:,} B, temporaries "
          f"{mem.temp_size_in_bytes:,} B, code "
          f"{mem.generated_code_size_in_bytes:,} B")
    outside = 0
    for op, result, stage, operands in gather_fusions(text, args.min_width):
        print(f"{op} {result.split('{')[0]} [{stage}]")
        for name, shape, producer in operands:
            space = "S(1)" if "S(1)" in shape else "hbm"
            print(f"    {shape.split('{')[0]:24s} @{space:5s}"
                  f"{name} <- {producer}")
        outside += "S(1)" not in operands[0][1]
    print(f"{outside} gather table(s) outside S(1)")
    for op, stage, shapes in sorts(text, args.min_width):
        print(f"{op} sort [{stage}]")
        for shape in shapes:
            space = "S(1)" if "S(1)" in shape else "hbm"
            print(f"    {shape.split('{')[0]:24s} @{space}")
    getattr(fix, "close", fix.dep.close)()
    return 0


if __name__ == "__main__":
    sys.exit(main())
