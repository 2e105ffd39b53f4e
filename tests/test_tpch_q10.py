"""TPC-H Q10 on the normal path (configuration ``tpch_q10_sf10_1chip``,
traffic ``q10``): the deployment the benchmark's cell builds, at a toy
scale, the spec's text over the wire (``interval '3' month`` included)
against the host executor and the benchmark's plain reference on three
seeds. The DAG runner answers by ONE ``program_dag_gagg``: of the seven
group keys ``c_custkey`` alone is packed and sorted, the six it
determines (five through the customer join's key pair, ``n_name``
through ``c_nationkey``, which the projection under the aggregate no
longer has) are read back at the twenty output rows through the joins'
own row indices, never gathered at the probe's width. Where the proof
is missing (a build side with duplicate keys, a key that is an
expression, an outer join) nothing is dropped and the answer is the
host's."""

from __future__ import annotations

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

FACT_ROWS = 24_000
ROWS_PER_SF = 6_000_000
SEEDS = (2_147_483_777, 39, 3_000_000_019)
DATES = ("1993-10-01", "1994-01-01", "1993-02-01", "1995-01-01",
         "1994-06-01", "1994-11-01")
GROUPING = "gagg/1of7"
GROUP_KEYS = "7 (5 text)"
LADDER = ("topk off", "packing off", "robust on")

# TPC-H's row counts and distinct values a scale factor (cl.4.2), as
# ANALYZE reads them: a key's ndv is its table's rows
SF1_STATS = {
    "lineitem": (6_000_000, {"l_orderkey": 1_500_000,
                             "l_extendedprice": 900_000, "l_discount": 11,
                             "l_returnflag": 3}),
    "orders": (1_500_000, {"o_orderkey": 1_500_000, "o_custkey": 100_000,
                           "o_orderdate": 2_406}),
    "customer": (150_000, {"c_custkey": 150_000, "c_nationkey": 25,
                           "c_name": 150_000, "c_address": 150_000,
                           "c_phone": 150_000, "c_acctbal": 140_000,
                           "c_comment": 150_000}),
}


def _mesh_of(patch, devices: int) -> None:
    """The coordinator's mesh takes ``devices`` of the test's eight."""
    import jax
    from opentenbase_tpu.executor import fused

    real = getattr(fused.build_mesh, "real", fused.build_mesh)

    def build_mesh(_devs=None):
        return real(jax.devices()[:devices])

    build_mesh.real = real  # (a patch inside a patch's lifetime)
    patch.setattr(fused, "build_mesh", build_mesh)


class Q10:
    """The configuration's four tables on 2 datanodes behind the wire
    server, data and reference from benchmarks/datasets/tpch_q10.py."""

    def __init__(self, seed: int = SEEDS[0], fact_rows: int = FACT_ROWS,
                 devices: int = 1):
        """``devices``: how many of the test's eight virtual devices the
        coordinator's mesh takes: the configuration's one chip, where
        the fragments inline to one program (the default), or more."""
        from harness import compare, loader, traffic

        self.compare, self.traffic = compare, traffic
        self._patch = pytest.MonkeyPatch()
        _mesh_of(self._patch, devices)
        cfg = loader.read_config("tpch_q10_sf10_1chip")
        assert cfg["datanodes"] == 2 and cfg["chips"] == 1
        self.mix = traffic.read_mix("q10")
        assert self.mix["rotation"] == ["q10"]
        assert self.mix["statements"]["q10"]["parameter_sets"] == 2
        self.data = loader.generate(cfg, seed, fact_rows / ROWS_PER_SF)
        self.dep = loader.Deployment(cfg)
        self.dep.create_tables()
        self.dep.load(self.data)

    def close(self) -> None:
        self.dep.close()
        self._patch.undo()

    def text(self, date: str) -> str:
        return self.mix["statements"]["q10"]["text"].format(date=date)

    def reference(self, date: str) -> dict:
        return self.data.module.reference(
            "q10", {"date": date}, self.data.blocks, self.data.glob,
            exact=True,
        )

    def fused_rows(self) -> dict:
        rows: dict = {}
        for ev, detail in self.dep.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            rows.setdefault(ev, []).append(detail)
        return rows

    def stat(self, name: str) -> int:
        return int(self.fused_rows().get(name, ["0"])[-1])

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

    def traced_launches(self, sql: str):
        self.dep.sql("set trace_queries = on")
        try:
            res = self.dep.sql(sql)
        finally:
            self.dep.sql("set trace_queries = off")
        tr = next(x for x in reversed(self.dep.cluster.tracer.last(4))
                  if x.query == sql)
        return res, [s for s in tr.spans if s.name == "fused.launch"]


@pytest.fixture(scope="module")
def q10():
    q = Q10()
    yield q
    q.close()


@pytest.fixture
def reductions(monkeypatch):
    """Every ``_fd_reduce`` of the test: (root, kept, dropped)."""
    from opentenbase_tpu.executor import fused_dag

    out = []
    real = fused_dag._fd_reduce

    def fd_reduce(root, orientation, agg):
        kept, dropped = real(root, orientation, agg)
        out.append((root, kept, dropped))
        return kept, dropped

    monkeypatch.setattr(fused_dag, "_fd_reduce", fd_reduce)
    return out


@pytest.fixture
def launched(monkeypatch):
    """Every DAG program launched, with its arguments."""
    from opentenbase_tpu.executor import fused_dag

    out = []
    real = fused_dag.DagRunner._launch

    def launch(self, prog, arrays, params, snap, **args):
        out.append((prog, (tuple(arrays), params, snap)))
        return real(self, prog, arrays, params, snap, **args)

    monkeypatch.setattr(fused_dag.DagRunner, "_launch", launch)
    return out


def test_the_deployment_is_the_configurations(q10):
    rows = q10.dep.shard_rows()
    assert sum(rows["lineitem"]) == 24_000  # 6,000 orders of 1..7 lines
    assert min(rows["lineitem"]) > 10_000  # sharded on l_orderkey
    assert sum(rows["orders"]) == 6_000 and sum(rows["customer"]) == 600
    assert rows["nation"] == [25, 25]  # replicated: whole on both
    meta = q10.dep.cluster.catalog.get
    assert list(meta("lineitem").schema) == [
        "l_orderkey", "l_extendedprice", "l_discount", "l_returnflag"]
    assert list(meta("orders").schema) == [
        "o_orderkey", "o_custkey", "o_orderdate"]
    assert list(meta("customer").schema) == [
        "c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
        "c_acctbal", "c_comment"]
    assert list(meta("nation").schema) == ["n_nationkey", "n_name"]
    # one dictionary entry a row (but for a chance repeat) in four columns
    for col in q10.data.module.TEXT_COLUMNS:
        assert len(meta("customer").dictionaries[col]) > 590, col


def _answers(q: Q10, date: str):
    """(device rows, host rows, reference) of one Q10."""
    sql = q.text(date)
    dev = q.dep.sql(sql)
    q.dep.sql("set enable_fused_execution = off")
    try:
        host = q.dep.sql(sql)
    finally:
        q.dep.sql("set enable_fused_execution = on")
    return dev.rows, host.rows, q.reference(date)


@pytest.mark.parametrize("seed", SEEDS)
def test_q10_equals_the_host_executor_and_the_reference(q10, seed):
    q = q10 if seed == SEEDS[0] else Q10(seed)
    try:
        date = DATES[SEEDS.index(seed)]
        before = q.fused_rows()
        dev, host, ref = _answers(q, date)
        after = q.fused_rows()
        assert len(ref["rows"]) == 20, "fewer than LIMIT groups: a toy too small"
        for rows in (dev, host):
            got = q.compare.compare_statement(rows, ref)
            assert got["wrong"] is None and got["sum_gap"] <= 1e-12, (
                got, rows[:2])
        assert [r[:2] + r[4:] for r in dev] == [r[:2] + r[4:] for r in host]
        # answered by the DAG runner's gagg: no host answer, no demotion
        assert (int(after["fused_statements"][-1])
                == int(before.get("fused_statements", ["0"])[-1]) + 1)
        assert after["last_mode"][-1] == "gagg"
        assert set(after["last_programs"][-1].split(",")) == {
            "program_dag_gagg"}
        assert [u for u in after.get("unsupported", [])
                if u != "trivial scan"] == []
        assert not after.get("demoted")
    finally:
        if q is not q10:
            q.close()


def test_one_gagg_sorts_the_customer_key_alone(q10, reductions, launched):
    """A parameter set of its own, so its programs are traced here. The
    first run may retry for what the data decides (the fold of the
    joined orders onto ``lineitem`` fails its density flag) and for
    nothing the plan could have known; the warm run is one launch of
    one ``program_dag_gagg`` whose record says one of seven keys was
    packed, as 32-bit words, and twenty rows leave the device."""
    sql = q10.text(DATES[3])
    finals0, dropped0 = q10.stat("gagg_finals"), q10.stat("gagg_keys_dropped")
    _res, first = q10.traced_launches(sql)
    reasons = [sp.args.get("reason", "") for sp in first]
    assert not [r for r in reasons if any(w in r for w in LADDER)], reasons
    assert all("fold off" in r for r in reasons if r), reasons
    assert {sp.args["program"] for sp in first} == {"program_dag_gagg"}
    assert q10.stat("gagg_finals") == finals0 + 1
    # six keys left out of the packing, once a traced program
    assert q10.stat("gagg_keys_dropped") == dropped0 + 6 * len(first)
    assert reductions and all(
        (kept, dropped) == ([0], [1, 2, 3, 4, 5, 6])
        for _root, kept, dropped in reductions)

    res, (warm,) = q10.traced_launches(sql)
    assert warm.args["program"] == "program_dag_gagg"
    assert warm.args["mode"] == "gagg" and warm.args["attempt"] == 1
    assert "retry_of" not in warm.args and "reason" not in warm.args
    assert warm.args["grouping"] == GROUPING
    assert warm.args["group_keys"] == GROUP_KEYS
    assert warm.args["narrow"] is True and warm.args["rows_out"] == 20
    assert len(res.rows) == 20
    assert q10.stat("gagg_finals") == finals0 + 2
    assert q10.stat("gagg_keys_dropped") == dropped0 + 6 * len(first)
    assert q10.fused_rows()["last_programs"][-1] == "program_dag_gagg"

    # the program: its stages, the recovery among them, and no dropped
    # key gathered at the probe's width (the packed key's own column is
    # the one build column that crosses the top join)
    prog, args = launched[-1]
    text = prog.lower(*args).compile().as_text()
    for stage in ("pack", "sort", "scan", "topk", "recover"):
        assert f"otb/final/gagg/{stage}/" in text, stage
    gathers = re.findall(
        r"= \w+\[(\d+)[^ ]* gather\(.*?op_name=\"[^\"]*?otb/"
        r"(join\d/\w+/gather|final/gagg/recover)/", text)
    probe = max(int(w) for w, _stage in gathers)
    assert probe == max(a.shape[0] * a.shape[1] for blk in args[0]
                        for a in blk[0])  # lineitem's padded rows
    crossing = [st for w, st in gathers if int(w) == probe]
    assert len(crossing) == 1 and crossing[0].startswith("join2/"), gathers
    assert sum(st == "final/gagg/recover" and int(w) == 20
               for w, st in gathers) >= 6, gathers


@pytest.mark.parametrize("sf", [None, 10])
def test_join_order_at_toy_and_at_sf10_statistics(sf):
    """orders filtered and moved to ``customer``'s placement, ``nation``
    joined to ``customer``, their rows joined to the orders and
    broadcast to ``lineitem``: three fragments, inlined to one program
    on the configuration's one device."""
    q = Q10()
    try:
        if sf is not None:
            q.set_stats(sf)
        plan = q.explain(q.text(DATES[0]))
    finally:
        q.close()
    assert [ln for ln in plan if ln.startswith("Join")] == [
        "Join inner on c_custkey=o_custkey",
        "Join inner on n_nationkey=c_nationkey",
        "Join inner on o_orderkey=l_orderkey",
    ]
    assert [ln.split()[2] for ln in plan if ln.startswith("Scan on")] == [
        "orders", "nation", "customer", "lineitem"]
    assert any(ln.startswith("Aggregate groups=[c_custkey, c_name, "
                             "c_acctbal, c_phone, n_name, c_address, "
                             "c_comment]") for ln in plan)


def test_on_the_whole_mesh_the_dag_answers_too():
    """Eight devices: the orders move to ``customer``'s placement, the
    joined rows are broadcast to ``lineitem``, and the groups, whole on
    no device, are merged by the coordinator (the ``grouped`` final: the
    one-device ``gagg`` needs whole groups a device). The answer is the
    reference's."""
    q = Q10(devices=8)
    try:
        res = q.dep.sql(q.text(DATES[5]))
        rows = q.fused_rows()
        assert rows["fused_statements"][-1] == "1"
        programs = rows["last_programs"][-1].split(",")
        assert "program_dag_exchange" in programs
        assert "program_dag_broadcast" in programs
        assert programs[-1] == "program_dag_grouped"
        assert [u for u in rows.get("unsupported", [])
                if u != "trivial scan"] == []
        got = q.compare.compare_statement(res.rows, q.reference(DATES[5]))
        assert got["wrong"] is None and got["sum_gap"] <= 1e-12, got
    finally:
        q.close()


def test_the_windowed_gagg_takes_the_same_reduction(
        q10, reductions, monkeypatch):
    """``_compile_wgagg`` shares ``_fd_reduce``: with a window budget
    below the probe's operands the same statement streams ``lineitem``
    in windows, packs the customer key alone and answers the same."""
    from opentenbase_tpu.plan import batchplan

    monkeypatch.setattr(batchplan, "DEFAULT_WINDOW_BUDGET", 150_000)
    res = q10.dep.sql(q10.text(DATES[4]))
    rows = q10.fused_rows()
    assert rows["last_mode"][-1] == "wgagg", rows["last_mode"]
    assert reductions and all(
        (kept, dropped) == ([0], [1, 2, 3, 4, 5, 6])
        for _root, kept, dropped in reductions)
    got = q10.compare.compare_statement(res.rows, q10.reference(DATES[4]))
    assert got["wrong"] is None and got["sum_gap"] <= 1e-12, got


# ---------------------------------------------------------------------------
# where the proof is missing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def unproven():
    """``f`` (unique ``k``) beside ``twice`` (every ``k`` on two rows
    with two ``a``) and ``once`` (unique ``k``), both replicated."""
    from opentenbase_tpu.engine import Cluster

    patch = pytest.MonkeyPatch()
    _mesh_of(patch, 1)  # (a gagg wants whole groups a device)
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table f (k bigint, v bigint) distribute by roundrobin")
    for t in ("twice", "once"):
        s.execute(f"create table {t} (k bigint, a bigint) "
                  "distribute by replication")
    s.execute("insert into f values " + ",".join(
        f"({k},{(k * 37) % 101})" for k in range(1, 301)))
    s.execute("insert into twice values " + ",".join(
        f"({k},{a})" for k in range(1, 61) for a in (k % 5, k % 5 + 10)))
    s.execute("insert into once values " + ",".join(
        f"({k},{k % 7})" for k in range(1, 201)))
    s.execute("analyze")
    yield s
    for sess in list(c.sessions):
        sess.close()
    patch.undo()


UNPROVEN = {
    # the build side repeats its key: its flag refuses every program
    # that took ``a`` for a function of ``k``; the orientation that
    # answers has ``twice`` as the probe, whose columns nothing
    # determines
    "duplicate build keys": (
        "select twice.k, a, sum(v) s from f, twice where f.k = twice.k "
        "group by twice.k, a order by s desc, twice.k, a limit 7",
        ([0, 1], [])),
    # the key that would determine ``a`` is no bare column
    "the group key an expression": (
        "select once.k + 0 kk, a, sum(v) s from f, once where f.k = once.k "
        "group by once.k + 0, a order by s desc, kk limit 7",
        ([0, 1], [])),
    # an outer join proves nothing (the reduction, asked before the
    # program is built, drops nothing; the DAG then declines the join
    # and the host answers, as today)
    "an outer join": (
        "select f.k, a, sum(v) s from f left join once on f.k = once.k "
        "group by f.k, a order by s desc, f.k limit 7",
        ([0, 1], [])),
}


@pytest.mark.parametrize("case", sorted(UNPROVEN))
def test_without_the_proof_nothing_is_dropped(unproven, reductions, case):
    sql, want_reduction = UNPROVEN[case]
    s = unproven
    s.execute("set enable_fused_execution = off")
    want = s.query(sql)
    s.execute("set enable_fused_execution = on")
    got = s.query(sql)
    assert got == want and len(got) == 7, (got, want)
    # the program that answered (the last compiled) dropped nothing
    assert reductions and (
        reductions[-1][1], reductions[-1][2]) == want_reduction, [
        r[1:] for r in reductions]


def test_fd_map_knows_the_key_pair_and_closes_before_a_projection(q10):
    """``_fd_map`` on hand-made trees over the deployment's catalog:
    the build side's key determines its columns as the probe key does,
    each key the other; a projection that drops both determinants keeps
    what they determined between them (``_fd_reduce`` on Q10's own
    tree, above: ``n_name`` by ``c_custkey`` through ``c_nationkey``)."""
    from opentenbase_tpu.executor.fused_dag import _fd_map
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.sql.parser import parse

    catalog = q10.dep.cluster.catalog

    plan = optimize_statement(analyze_statement(parse(
        "select c_name, n_name from customer, nation "
        "where c_nationkey = n_nationkey")[0], catalog), catalog)
    join = getattr(plan, "root", plan)
    while not isinstance(join, L.Join):
        join = join.child
    names = [c.name for c in join.schema]
    nk, ck = names.index("n_nationkey"), names.index("c_nationkey")
    nn = names.index("n_name")
    right_is_nation = nk >= len(join.left.schema)
    fd = _fd_map(join, ("R" if right_is_nation else "L",))
    assert fd[nn] == {nk, ck}  # by its own key and by the probe's
    assert nk in fd[ck] and ck in fd[nk]  # the pair is an equivalence
    # customer probes: nothing determines its other columns
    assert names.index("c_name") not in fd
    # the other orientation proves the other side, never both
    fd2 = _fd_map(join, ("L" if right_is_nation else "R",))
    assert nn not in fd2 and fd2[names.index("c_name")] == {nk, ck}


@pytest.mark.parametrize("text,folded", [
    ("interval '3' month", "interval '3 month'"),
    ("interval '1' year", "interval '1 year'"),
    ("interval '90' day", "interval '90 day'"),
    ("interval '2' week", "interval '2 week'"),
])
def test_the_standards_interval_form_folds_like_the_quoted_unit(
        q10, text, folded):
    """``interval '<n>' <unit>`` (the spec's text) parses to what
    ``interval '<n> <unit>'`` does, and both fold with the date."""
    from opentenbase_tpu.sql.parser import parse

    def stmt(iv):
        return f"select date '1993-11-30' + {iv} as d"

    assert parse(stmt(text)) == parse(stmt(folded))
    assert q10.dep.sql(stmt(text)).rows == q10.dep.sql(stmt(folded)).rows
    # a unit word after a quantity WITH a unit is an alias, as it was
    assert parse("select interval '3 month' month")[0].items[0].alias == (
        "month")
