"""TPC-H Q5 on the normal path (configuration ``tpch_q5_sf10_1chip``,
traffic ``q5``): the deployment the benchmark's cell builds, at a toy
scale, the spec's six-table text over the wire for all five regions
against the benchmark's plain reference; the DAG runner answers, its
customer join on two key pairs (``c_custkey`` drives a fold,
``c_nationkey = s_nationkey`` is checked on the matched row under a
scope of its own); the join order is the same at toy statistics as at
SF2's and SF10's, with no join on the nation key alone; the launch says
which pair drives and ``pg_stat_fused`` counts the join once a
program."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
FACT_ROWS = 24_000
ROWS_PER_SF = 6_000_000
SEED = 2_147_483_777

# TPC-H's row counts and distinct values a scale factor (cl.4.2), as
# ANALYZE reads them: a key's ndv is its table's rows
SF1_STATS = {
    "lineitem": (6_000_000, {"l_orderkey": 1_500_000, "l_suppkey": 10_000,
                             "l_extendedprice": 900_000, "l_discount": 11}),
    "orders": (1_500_000, {"o_orderkey": 1_500_000, "o_custkey": 100_000,
                           "o_orderdate": 2_406}),
    "customer": (150_000, {"c_custkey": 150_000, "c_nationkey": 25}),
    "supplier": (10_000, {"s_suppkey": 10_000, "s_nationkey": 25}),
}


class Q5:
    """The configuration's six tables on 2 datanodes behind the wire
    server, data and reference from benchmarks/datasets/tpch_q5.py."""

    def __init__(self, devices: int = 1, fact_rows: int = FACT_ROWS):
        """``devices``: how many of the test's eight virtual devices the
        coordinator's mesh takes: the configuration's one chip, where
        the fragments inline to one program (the default), or more.
        ``fact_rows``: ``lineitem``'s; the other tables scale with it."""
        import jax
        from harness import compare, loader, traffic
        from opentenbase_tpu.executor import fused

        self.compare, self.traffic = compare, traffic
        self._patch = pytest.MonkeyPatch()
        real = getattr(fused.build_mesh, "real", fused.build_mesh)

        def build_mesh(_devs=None):
            return real(jax.devices()[:devices])

        build_mesh.real = real  # (a Q5 inside a Q5's lifetime)
        self._patch.setattr(fused, "build_mesh", build_mesh)
        cfg = loader.read_config("tpch_q5_sf10_1chip")
        assert cfg["datanodes"] == 2 and cfg["chips"] == 1
        self.mix = traffic.read_mix("q5")
        assert self.mix["rotation"] == ["q5"]
        assert self.mix["statements"]["q5"]["parameter_sets"] == 2
        self.data = loader.generate(cfg, SEED, fact_rows / ROWS_PER_SF)
        self.dep = loader.Deployment(cfg)
        self.dep.create_tables()
        self.dep.load(self.data)

    def close(self) -> None:
        self.dep.close()
        self._patch.undo()

    def text(self, region: str, year: int) -> str:
        stmt = self.mix["statements"]["q5"]
        values = {"region": region, "year": year}
        for name, rule in stmt["derived"].items():
            values[name] = self.traffic.derive(rule, values)
        return stmt["text"].format(**values)

    def fused_rows(self) -> dict:
        rows: dict = {}
        for ev, detail in self.dep.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            rows.setdefault(ev, []).append(detail)
        return rows

    def explain(self, sql: str) -> list:
        return [r[0].strip() for r in self.dep.sql("explain " + sql).rows]

    def set_stats(self, sf: float) -> None:
        """The catalog's statistics as ANALYZE leaves them at ``sf``."""
        for table, (rows, ndv) in SF1_STATS.items():
            meta = self.dep.cluster.catalog.get(table)
            meta.stats = {
                "rows": int(rows * sf),
                "ndv": {c: (int(v * sf) if v >= 10_000 else v)
                        for c, v in ndv.items()},
            }


@pytest.fixture(scope="module")
def q5():
    q = Q5()
    yield q
    q.close()


def test_the_deployment_is_the_configurations(q5):
    rows = q5.dep.shard_rows()
    assert sum(rows["lineitem"]) == 24_000  # 6,000 orders of 1..7 lines
    assert min(rows["lineitem"]) > 10_000  # sharded on l_orderkey
    assert sum(rows["orders"]) == 6_000 and sum(rows["customer"]) == 600
    for dim, n in (("supplier", 40), ("nation", 25), ("region", 5)):
        assert rows[dim] == [n, n]  # replicated: whole on both
    meta = q5.dep.cluster.catalog.get
    assert list(meta("lineitem").schema) == [
        "l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"]
    assert list(meta("customer").schema) == ["c_custkey", "c_nationkey"]
    assert list(meta("nation").schema) == [
        "n_nationkey", "n_name", "n_regionkey"]
    assert len(meta("nation").dictionaries["n_name"].encode(
        q5.data.module.NATION_NAMES)) == 25


@pytest.mark.parametrize("region", REGIONS)
def test_q5_over_the_wire_equals_the_reference(q5, region):
    year = 1993 + REGIONS.index(region)
    before = q5.fused_rows()
    res = q5.dep.sql(q5.text(region, year))
    after = q5.fused_rows()
    ref = q5.data.module.reference(
        "q5", {"region": region, "year": year}, q5.data.blocks,
        q5.data.glob, exact=True,
    )
    assert ref["rows"], "the toy answer is empty: nothing compared"
    got = q5.compare.compare_statement(res.rows, ref)
    assert got["wrong"] is None and got["sum_gap"] <= 1e-12, (got, res.rows)
    # answered by the DAG runner: no host answer, no demotion
    assert (int(after["fused_statements"][-1])
            == int(before.get("fused_statements", ["0"])[-1]) + 1)
    assert after["last_mode"][-1] == "grouped"
    assert "fold" in after["last_join_modes"][-1].split(",")
    assert set(after["last_programs"][-1].split(",")) == {
        "program_dag_grouped"}
    assert [u for u in after.get("unsupported", [])
            if u != "trivial scan"] == []
    assert not after.get("demoted")


@pytest.mark.parametrize("sf", [None, 2, 10])
def test_join_order_is_the_same_at_every_scale(sf):
    """region, nation, supplier, then lineitem on the supplier key,
    orders on the order key, and customer LAST on both of its pairs:
    never a join on the nation key alone (customer and supplier would
    meet many-to-many, which no lookup join runs)."""
    q = Q5()
    try:
        if sf is not None:
            q.set_stats(sf)
        plan = q.explain(q.text("ASIA", 1994))
    finally:
        q.close()
    joins = [ln for ln in plan if ln.startswith("Join")]
    assert joins == [  # (a fragment's joins print outermost first)
        "Join inner on l_orderkey=o_orderkey",
        "Join inner on s_suppkey=l_suppkey",
        "Join inner on n_nationkey=s_nationkey",
        "Join inner on r_regionkey=n_regionkey",
        "Join inner on o_custkey=c_custkey, s_nationkey=c_nationkey",
    ]
    scans = [ln.split()[2] for ln in plan if ln.startswith("Scan on")]
    assert scans == ["region", "nation", "supplier", "lineitem", "orders",
                     "customer"]
    assert any("Aggregate groups=[n_name]" in ln for ln in plan)


def _traced_launches(q5, sql):
    q5.dep.sql("set trace_queries = on")
    try:
        res = q5.dep.sql(sql)
    finally:
        q5.dep.sql("set trace_queries = off")
    tr = next(x for x in reversed(q5.dep.cluster.tracer.last(4))
              if x.query == sql)
    return res, [s for s in tr.spans if s.name == "fused.launch"]


def test_launch_names_the_driving_pair_and_the_counter_counts_programs(
        q5, monkeypatch):
    """A parameter set of its own: its program is traced here, so
    ``multi_key_joins`` moves by one a traced program (a refused fold of
    ``orders`` makes it two) and not at all on the repeat; the launch's
    ``joins`` says the customer join has two pairs and ``c_custkey``
    drives; the other pair's comparison is in the program under
    ``join<i>/fold/residual``."""
    from opentenbase_tpu.executor import fused_dag

    launched = []
    real = fused_dag.DagRunner._launch

    def launch(self, prog, arrays, params, snap, **args):
        launched.append((prog, (tuple(arrays), params, snap)))
        return real(self, prog, arrays, params, snap, **args)

    monkeypatch.setattr(fused_dag.DagRunner, "_launch", launch)
    sql = q5.text("EUROPE", 1997)
    c0 = int(q5.fused_rows()["multi_key_joins"][-1])
    _res, spans = _traced_launches(q5, sql)
    c1 = int(q5.fused_rows()["multi_key_joins"][-1])
    assert 1 <= c1 - c0 == len(spans) <= 2
    joins = spans[-1].args["joins"].split(";")
    assert [j.split("=")[0] for j in joins] == [
        f"join{i}" for i in range(5)]
    (two,) = [j for j in joins if "keys=" in j]
    assert two.startswith("join4=fold:") and two.endswith("keys=2:c_custkey")
    assert "fold" in spans[-1].args["join_modes"].split("+")
    prog, args = launched[-1]
    text = prog.lower(*args).as_text(debug_info=True)
    assert "otb/join4/fold/residual/" in text
    assert "otb/join2/fold/residual/" not in text  # one pair: no such scope
    # the repeat binds the cached program: nothing traced, nothing counted
    c2 = int(q5.fused_rows()["multi_key_joins"][-1])
    _res, (again,) = _traced_launches(q5, sql)
    assert again.args["joins"] == spans[-1].args["joins"]
    assert "retry_of" not in again.args
    assert int(q5.fused_rows()["multi_key_joins"][-1]) == c2


def test_on_the_whole_mesh_the_dag_answers_too():
    """Eight devices: the replicated chain (region, nation, supplier)
    stays replicated through its own joins and meets ``lineitem`` in
    place, the joined rows move once onto ``customer``'s placement, and
    the probe side of the ``orders`` join counts as sharded though
    replicated tables were folded into it."""
    q = Q5(devices=8)
    try:
        plan = q.explain(q.text("AMERICA", 1996))
        assert [ln.split(" on nodes")[0] + ln.split("->")[1]
                for ln in plan if ln.startswith("Fragment")] == [
            "Fragment 0redistribute(12) to shard:customer:",
            "Fragment 1gather:"]
        res = q.dep.sql(q.text("AMERICA", 1996))
        rows = q.fused_rows()
        assert rows["fused_statements"][-1] == "1"
        programs = rows["last_programs"][-1].split(",")
        assert "program_dag_exchange" in programs
        assert programs[-1] == "program_dag_grouped"
        assert [u for u in rows.get("unsupported", [])
                if u != "trivial scan"] == []
        ref = q.data.module.reference(
            "q5", {"region": "AMERICA", "year": 1996}, q.data.blocks,
            q.data.glob, exact=True,
        )
        got = q.compare.compare_statement(res.rows, ref)
        assert ref["rows"] and got["wrong"] is None, got
        assert got["sum_gap"] <= 1e-12
    finally:
        q.close()


def test_a_profile_keeps_every_join_of_the_launch(q5, tmp_path):
    """``otb_trace --xplane`` over a profiler trace of one Q5: the
    launch's ``joins`` arrives whole (five joins; the profiler cuts a
    TraceMe's arguments at commas, so they are set apart by ``;``) and
    names the two-pair join's driving key."""
    import jax

    from opentenbase_tpu.obs import profile

    sql = q5.text("ASIA", 1995)
    q5.dep.sql(sql)  # traced and compiled outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        q5.dep.sql(sql)
        q5.dep.sql("set trace_queries = off")  # closes the last span
    finally:
        jax.profiler.stop_trace()
    report = profile.reduce(profile.load(profile.find_xplane(str(tmp_path))))
    joins = [j for c in report["classes"].values() for j in c["joins"]]
    assert len(joins) == 1, joins
    parts = joins[0].split(";")
    assert len(parts) == 5 and parts[4].startswith("join4=fold:")
    assert parts[4].endswith("keys=2:c_custkey")
    assert "keys=2:c_custkey" in profile.render(report)
