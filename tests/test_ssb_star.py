"""SSB's star flights on the normal path (configuration
``ssb_star_sf10_1chip``, traffic ``star_q21_q31_q41``): the deployment
the benchmark's cell builds, at a toy scale, its three statements over
the wire against the benchmark's plain reference; the DAG runner
answers with a dimension fold and the ``grouped`` final; a dimension
with a hole leaves the fold and the answer holds; the grouped final's
stages are named in the program (the direct-addressed formulation's
where the packed keys' range is small, the sort's where the data force
it wide) and its launch says which it holds and what it was sized
for."""

import copy
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

KINDS = ("q21", "q31", "q41")
GROUP_KEYS = {"q21": "2 (1 text)", "q31": "3 (2 text)", "q41": "2 (1 text)"}
DIRECT_STAGES = ("keys", "pack", "slot", "limbs", "onehot", "recombine")
SORT_STAGES = ("keys", "pack", "sort", "segreduce")
STAGES = tuple(dict.fromkeys(DIRECT_STAGES + SORT_STAGES))
FACT_ROWS = 4000
ROWS_PER_SF = 6_000_000


class Star:
    """The configuration's five tables on 2 datanodes behind the wire
    server, data and reference from benchmarks/datasets/ssb_star.py."""

    def __init__(self):
        from harness import compare, loader, traffic

        self.compare = compare
        cfg = loader.read_config("ssb_star_sf10_1chip")
        assert cfg["datanodes"] == 2 and cfg["chips"] == 1
        self.mix = traffic.read_mix("star_q21_q31_q41")
        assert tuple(self.mix["rotation"]) == KINDS
        self.data = loader.generate(
            cfg, 2_147_483_777, FACT_ROWS / ROWS_PER_SF
        )
        self.dep = loader.Deployment(cfg)
        self.dep.create_tables()
        self.dep.load(self.data)

    @property
    def dag(self):
        return self.dep.cluster._fused._dag

    def text(self, kind: str) -> str:
        return self.mix["statements"][kind]["text"]

    def verdict(self, kind: str, rows, glob=None) -> dict:
        ref = self.data.module.reference(
            kind, {}, self.data.blocks, glob or self.data.glob, exact=True,
        )
        assert ref["rows"], "the toy answer is empty: nothing compared"
        return self.compare.compare_statement(rows, ref)

    def fused_rows(self) -> dict:
        rows: dict = {}
        for ev, detail in self.dep.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            rows.setdefault(ev, []).append(detail)
        return rows

    def answered_by_the_dag(self, before: dict, after: dict) -> None:
        assert (int(after["fused_statements"][-1])
                == int(before.get("fused_statements", ["0"])[-1]) + 1)
        assert after["last_mode"][-1] == "grouped"
        assert "fold" in after["last_join_modes"][-1].split(",")
        # (a tripped density flag answers again: the same program twice)
        assert set(after["last_programs"][-1].split(",")) == {
            "program_dag_grouped"}
        assert [u for u in after.get("unsupported", [])
                if u != "trivial scan"] == []
        assert not after.get("demoted")


@pytest.fixture(scope="module")
def star():
    s = Star()
    yield s
    s.dep.close()


def test_the_deployment_is_the_configurations(star):
    rows = star.dep.shard_rows()
    assert sum(rows["lineorder"]) == 3999  # 1,000 orders of 1..7 lines
    assert min(rows["lineorder"]) > 1500  # sharded on lo_orderkey
    for dim in ("customer", "supplier", "part", "dates"):
        assert rows[dim][0] == rows[dim][1] == star.data.rows(dim)  # whole
    assert rows["dates"][0] == 2556
    meta = star.dep.cluster.catalog.get
    assert list(meta("lineorder").schema) == [
        "lo_orderkey", "lo_custkey", "lo_partkey", "lo_suppkey",
        "lo_orderdate", "lo_revenue", "lo_supplycost"]
    # the seven text attributes are dictionary-coded, as the engine
    # stores text
    for table, cols in star.data.module.DICTIONARIES.items():
        for col, values in cols.items():
            d = meta(table).dictionaries[col]
            assert len(d.encode(values)) == len(values)


@pytest.mark.parametrize("kind", KINDS)
def test_statement_over_the_wire_equals_the_reference(star, kind):
    before = star.fused_rows()
    res = star.dep.sql(star.text(kind))
    after = star.fused_rows()
    got = star.verdict(kind, res.rows)
    assert got["wrong"] is None and got["sum_gap"] == 0.0, (got, res.rows[:3])
    star.answered_by_the_dag(before, after)
    # every dimension on a dense key folds; ``dates`` (yyyymmdd) cannot
    njoins = len(star.mix["statements"][kind]["reads"]) - 1
    assert len(star.dag.last_folded) == njoins - 1


@pytest.fixture
def launched(monkeypatch):
    """Every DAG program launched, with its arguments and span args."""
    from opentenbase_tpu.executor import fused_dag

    out = []
    real = fused_dag.DagRunner._launch

    def launch(self, prog, arrays, params, snap, **args):
        out.append((prog, (tuple(arrays), params, snap), args))
        return real(self, prog, arrays, params, snap, **args)

    monkeypatch.setattr(fused_dag.DagRunner, "_launch", launch)
    return out


def _traced_launch(star, sql):
    star.dep.sql("set trace_queries = on")
    try:
        res = star.dep.sql(sql)
    finally:
        star.dep.sql("set trace_queries = off")
    tr = next(x for x in reversed(star.dep.cluster.tracer.last(4))
              if x.query == sql)
    return res, [s for s in tr.spans if s.name == "fused.launch"]


def _counters(star) -> tuple:
    rows = star.fused_rows()
    return (int(rows["grouped_direct"][-1]), int(rows["grouped_sorted"][-1]))


@pytest.mark.parametrize("kind", ["q21", "q31"])
def test_grouped_final_names_its_stages_and_its_size(star, kind, launched):
    """The packed keys of a star statement span a few thousand slots at
    most: the program holds the direct-addressed formulation's stages
    and none of the sort's (nor the one scope both replaced); the
    launch span says so with the slot capacity and the keys, and
    pg_stat_fused counts the final as direct."""
    from opentenbase_tpu.executor import fused_dag

    sql = star.text(kind)
    before = _counters(star)
    _res, (sp,) = _traced_launch(star, sql)
    prog, args, _span_args = launched[-1]
    assert prog.__name__ == "program_dag_grouped"
    text = prog.lower(*args).as_text(debug_info=True)
    for stage in DIRECT_STAGES:
        assert f"otb/final/grouped/{stage}" in text, stage
    for stage in ("sort", "segreduce", "reduce"):
        assert f"otb/final/grouped/{stage}" not in text, stage
    # (the fold sorts its dimension: no sort and no scatter in the FINAL)
    assert not re.search(r'"[^"]*otb/final/[^"]*(sort|scatter)', text)
    assert sp.args["mode"] == "grouped"
    assert sp.args["grouping"] == f"direct/{fused_dag.DIRECT_START_SLOTS}"
    assert sp.args["groups"] == fused_dag.DIRECT_START_SLOTS
    assert sp.args["group_keys"] == GROUP_KEYS[kind]
    assert "retry_of" not in sp.args
    assert _counters(star) == (before[0] + 1, before[1])


def test_a_group_key_of_high_cardinality_takes_the_sort(
        star, launched, monkeypatch):
    """The same function, the other formulation: an order key's range is
    past the bound (here a toy one), so the first answer is refused on
    its span, the sort formulation's program answers (its stages, none
    of the direct's) and is counted as such; the repeat asks for it at
    once."""
    from opentenbase_tpu.executor import fused_dag

    monkeypatch.setattr(fused_dag, "DIRECT_START_SLOTS", 64)
    monkeypatch.setattr(fused_dag, "DIRECT_MAX_SLOTS", 64)
    sql = ("select lo_orderkey, sum(lo_revenue) from lineorder, dates "
           "where lo_orderdate = d_datekey group by lo_orderkey "
           "order by lo_orderkey")
    before = _counters(star)
    res, spans = _traced_launch(star, sql)
    assert [sp.args["grouping"] for sp in spans] == ["direct/64", "sort"]
    assert "span" in spans[1].args["reason"]
    assert spans[1].args["groups"] == fused_dag.OPTIMISTIC_GROUP_CAP
    lo = star.data.blocks[0]["lineorder"]
    assert len(res.rows) == len(set(lo["lo_orderkey"].tolist())) > 64
    assert sum(r[1] for r in res.rows) == int(lo["lo_revenue"].sum())
    prog, args, _span_args = launched[-1]
    text = prog.lower(*args).as_text(debug_info=True)
    for stage in SORT_STAGES:
        assert f"otb/final/grouped/{stage}/" in text, stage
    for stage in ("slot", "limbs", "onehot", "recombine"):
        assert f"otb/final/grouped/{stage}" not in text, stage
    _res, (again,) = _traced_launch(star, sql)
    assert again.args["grouping"] == "sort" and "retry_of" not in again.args
    assert _counters(star) == (before[0], before[1] + 2)


def test_ungrouped_final_keeps_its_scope_and_carries_no_group_args(
        star, launched):
    """Flight 1's shape (a ``scalar`` final) goes through the same
    block under a stage of its own name, ``final/scalar/reduce``."""
    star.dep.sql("select sum(lo_revenue) from lineorder, dates where "
                 "lo_orderdate = d_datekey and d_year = 1993")
    prog, args, span_args = launched[-1]
    assert prog.__name__ == "program_dag_scalar"
    text = prog.lower(*args).as_text(debug_info=True)
    assert "otb/final/scalar/reduce/" in text
    assert "otb/final/grouped/" not in text
    assert span_args == {"mode": "scalar"}


def test_a_dimension_with_a_hole_leaves_the_fold_and_the_answer_holds():
    """One ``part`` row deleted: its keys are no gap-free range, the
    density flag sends that join off the fold (the others stay), and
    the lines of the deleted part drop out as an inner join drops them."""
    s = Star()
    try:
        mod, glob = s.data.module, s.data.glob
        lo = s.data.blocks[0]["lineorder"]
        part, supp = glob["part"], glob["supplier"]
        # a part whose lines are in Q2.1's answer
        keep = (
            (part["p_category"][lo["lo_partkey"] - 1]
             == mod.CATEGORIES.index("MFGR#12"))
            & (supp["s_region"][lo["lo_suppkey"] - 1]
               == mod.REGIONS.index("AMERICA"))
        )
        gone = int(lo["lo_partkey"][keep][0])
        whole = s.dep.sql(s.text("q21")).rows
        folded_whole = set(s.dag.last_folded)
        assert len(folded_whole) == 2  # part and supplier
        s.dep.sql(f"delete from part where p_partkey = {gone}")
        before = s.fused_rows()
        res = s.dep.sql(s.text("q21"))
        after = s.fused_rows()
        s.answered_by_the_dag(before, after)  # supplier still folds
        assert after["last_programs"][-1].count("program_dag_grouped") == 2
        assert len(s.dag.last_folded) == 1
        assert set(s.dag.last_folded) < folded_whole
        short = copy.deepcopy(glob)
        short["part"]["p_category"][gone - 1] = -1  # matches no literal
        got = s.verdict("q21", res.rows, short)
        assert got["wrong"] is None and got["sum_gap"] == 0.0, got
        assert res.rows != whole
        assert s.verdict("q21", whole)["wrong"] is None
    finally:
        s.dep.close()
