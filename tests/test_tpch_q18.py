"""TPC-H Q18 on the normal path (configuration ``tpch_q18_sf10_1chip``,
traffic ``q18``): the deployment the benchmark's cell builds, at a toy
scale, the spec's text over the wire against the host executor and the
benchmark's plain reference on three seeds.

Its ``o_orderkey in (select l_orderkey from lineitem group by
l_orderkey having sum(l_quantity) > Q)`` is a semi join whose build
side is a grouped key set. The DAG runner answers it by two programs,
launched one after the other with no host sync between them:
``program_dag_semiagg`` (one sort of ``lineitem`` by its order key, the
sums at the runs' ends, HAVING there, the qualifying keys compacted
into a static capacity) and the ``program_dag_gagg`` that reads those
keys as the semi join's build side: of its five group keys
``o_orderkey`` alone is packed and sorted, the ORDER BY's
``o_totalprice`` and ``o_orderdate`` are read by the row id at the
sorted rows' live prefix, never gathered at the probe's width. A key
set past its capacity is answered again at the power of two that holds
it; on a mesh the host answers."""

from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from join_crossings import crossings  # noqa: E402
from test_tpch_q10 import _mesh_of  # noqa: E402

FACT_ROWS = 24_000
ROWS_PER_SF = 6_000_000
SEEDS = (2_147_483_777, 41, 3_000_000_019)
# QUANTITY at this scale: 6,000 orders, so a threshold under the spec's
# 312..315 (which only some 0.0007 % of orders pass) keeps a hundred-odd
# in the answer; 150 cuts it to the LIMIT, 200 leaves fewer than that
QUANTITIES = (150, 200, 175)
PROGRAMS = "program_dag_semiagg,program_dag_gagg"
# TPC-H's row counts and distinct values at SF1 (cl.4.2), as ANALYZE
# reads them: a key's ndv is its table's rows
SF1_STATS = {
    "lineitem": (6_000_000, {"l_orderkey": 1_500_000, "l_quantity": 50}),
    "orders": (1_500_000, {"o_orderkey": 1_500_000, "o_custkey": 100_000,
                           "o_totalprice": 1_460_000,
                           "o_orderdate": 2_406}),
    "customer": (150_000, {"c_custkey": 150_000, "c_name": 150_000}),
}


class Q18:
    """The configuration's three tables on 2 datanodes behind the wire
    server, data and reference from benchmarks/datasets/tpch_q18.py."""

    def __init__(self, seed: int = SEEDS[0], devices: int = 1):
        from harness import compare, loader, traffic

        self.compare = compare
        self._patch = pytest.MonkeyPatch()
        _mesh_of(self._patch, devices)
        cfg = loader.read_config("tpch_q18_sf10_1chip")
        assert cfg["datanodes"] == 2 and cfg["chips"] == 1
        self.mix = traffic.read_mix("q18")
        assert self.mix["rotation"] == ["q18"]
        assert self.mix["statements"]["q18"]["parameter_sets"] == 2
        self.data = loader.generate(cfg, seed, FACT_ROWS / ROWS_PER_SF)
        self.dep = loader.Deployment(cfg)
        self.dep.create_tables()
        self.dep.load(self.data)

    def close(self) -> None:
        self.dep.close()
        self._patch.undo()

    def text(self, quantity: int) -> str:
        return self.mix["statements"]["q18"]["text"].format(
            quantity=quantity)

    def reference(self, quantity: int) -> dict:
        return self.data.module.reference(
            "q18", {"quantity": quantity}, self.data.blocks,
            self.data.glob, exact=True,
        )

    def set_stats(self, sf: float) -> None:
        """The catalog's statistics as ANALYZE leaves them at ``sf``."""
        for table, (rows, ndv) in SF1_STATS.items():
            meta = self.dep.cluster.catalog.get(table)
            meta.stats = {
                "rows": int(rows * sf),
                "ndv": {c: (int(v * sf) if v >= 10_000 else v)
                        for c, v in ndv.items()},
            }

    def fused_rows(self) -> dict:
        rows: dict = {}
        for ev, detail in self.dep.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            rows.setdefault(ev, []).append(detail)
        return rows

    def stat(self, name: str) -> int:
        return int(self.fused_rows().get(name, ["0"])[-1])

    def traced(self, sql: str):
        """(result, the statement's fused.launch and fused.collect
        spans)."""
        self.dep.sql("set trace_queries = on")
        try:
            res = self.dep.sql(sql)
        finally:
            self.dep.sql("set trace_queries = off")
        tr = next(x for x in reversed(self.dep.cluster.tracer.last(4))
                  if x.query == sql)
        return res, [s for s in tr.spans if s.name == "fused.launch"], [
            s for s in tr.spans if s.name == "fused.collect"]

    def host(self, sql: str):
        self.dep.sql("set enable_fused_execution = off")
        try:
            return self.dep.sql(sql).rows
        finally:
            self.dep.sql("set enable_fused_execution = on")

    def agrees(self, rows, quantity: int) -> None:
        got = self.compare.compare_statement(rows, self.reference(quantity))
        assert got["wrong"] is None and got["sum_gap"] <= 1e-12, (
            got, rows[:2])


@pytest.fixture(scope="module")
def q18():
    q = Q18()
    yield q
    q.close()


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


def test_the_deployment_is_the_configurations(q18):
    rows = q18.dep.shard_rows()
    assert sum(rows["lineitem"]) == 24_000  # 6,000 orders of 1..7 lines
    assert min(rows["lineitem"]) > 10_000  # sharded on l_orderkey
    assert sum(rows["orders"]) == 6_000 and sum(rows["customer"]) == 600
    meta = q18.dep.cluster.catalog.get
    assert list(meta("lineitem").schema) == ["l_orderkey", "l_quantity"]
    assert list(meta("orders").schema) == [
        "o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"]
    assert list(meta("customer").schema) == ["c_custkey", "c_name"]
    assert len(meta("customer").dictionaries["c_name"]) == 600


def test_the_data_set_admits_this_engine():
    """``datasets/tpch_q18.requires_key_sets`` refuses, before a row is
    made, an engine that would answer Q18 on the host (at SF10 some
    250 s and 30 GB a statement beside the loaded data: a process the
    chip host ends for memory). This engine passes it: a rename of
    what it looks for fails here, not on the chip."""
    from datasets import tpch_q18

    tpch_q18.requires_key_sets()


@pytest.mark.parametrize("seed", [11, 2**31 + 12345])
def test_o_totalprice_is_its_lines_priced_with_tax_and_discount(seed):
    """cl.4.2.3: the sum over the order's lines of ``l_extendedprice *
    (1 + l_tax) * (1 - l_discount)``, here summed line by line in exact
    fractions and rounded half up to the cent once; every other column
    is ``tpch.py``'s."""
    from fractions import Fraction

    from datasets import tpch, tpch_q18

    sf = 0.002
    b = tpch_q18.make_block(seed, sf, 0)
    base = tpch.make_block(seed, sf, 0)
    o, li = base["orders"], base["lineitem"]
    for col in ("o_orderkey", "o_custkey", "o_orderdate"):
        assert np.array_equal(b["orders"][col], o[col]), col
    for col in ("l_orderkey", "l_quantity"):
        assert np.array_equal(b["lineitem"][col], li[col]), col
    lines: dict = {}
    for k, e, tax, d in zip(*(li[c].tolist() for c in (
            "l_orderkey", "l_extendedprice", "l_tax", "l_discount"))):
        price = Fraction(e, 100) * (1 + Fraction(tax, 100)) * (
            1 - Fraction(d, 100))
        lines[k] = lines.get(k, 0) + price
    want = [int((lines[k] * 100 + Fraction(1, 2)) // 1)
            for k in o["o_orderkey"].tolist()]
    assert b["orders"]["o_totalprice"].tolist() == want
    assert b["orders"]["o_totalprice"].dtype == np.int64


@pytest.mark.parametrize("seed", SEEDS)
def test_q18_equals_the_host_executor_and_the_reference(q18, seed):
    q = q18 if seed == SEEDS[0] else Q18(seed)
    try:
        quantity = QUANTITIES[SEEDS.index(seed)]
        sql = q.text(quantity)
        before = q.fused_rows()
        dev = q.dep.sql(sql).rows
        after = q.fused_rows()
        host = q.host(sql)
        ref = q.reference(quantity)
        assert 50 <= len(ref["rows"]) <= 100, len(ref["rows"])
        q.agrees(dev, quantity)
        q.agrees(host, quantity)
        assert [r[:4] for r in dev] == [r[:4] for r in host]
        # answered by the key set's program and gagg: no host answer
        assert (int(after["fused_statements"][-1])
                == int(before.get("fused_statements", ["0"])[-1]) + 1)
        assert after["last_mode"][-1] == "gagg"
        # (a first run may answer the gagg twice: the orders fold's
        # density flag on dbgen's sparse keys)
        programs = after["last_programs"][-1].split(",")
        assert programs[0] == "program_dag_semiagg"
        assert set(programs[1:]) == {"program_dag_gagg"}
        assert [u for u in after.get("unsupported", [])
                if u != "trivial scan"] == []
        assert not after.get("demoted")
        assert q.stat("semiagg_programs") == int(
            before.get("semiagg_programs", ["0"])[-1]) + 1
    finally:
        if q is not q18:
            q.close()


def test_one_semiagg_then_one_gagg(q18, launched, monkeypatch):
    """A parameter set of its own, so its programs are traced here. The
    first run may retry for what the data decides (the orders fold onto
    ``lineitem`` fails its density flag on dbgen's sparse keys); the
    warm run is one ``program_dag_semiagg`` and one ``program_dag_gagg``
    and no retry. The gagg packs one of five keys, and ranks by the two
    it dropped read at the live prefix: in its program no gather of a
    final stage is as wide as the probe."""
    from opentenbase_tpu.ops import filter as filt_ops

    sql = q18.text(QUANTITIES[1])
    _res, first, _c = q18.traced(sql)
    reasons = [sp.args.get("reason", "") for sp in first]
    assert all("fold off" in r for r in reasons if r), reasons
    assert first[0].args["program"] == "program_dag_semiagg"

    res, warm, (collect,) = q18.traced(sql)
    semi, final = warm
    assert semi.args["program"] == "program_dag_semiagg"
    assert semi.args["attempt"] == 1 and semi.args["frag"] == "semiagg"
    rows, cap = (int(x) for x in semi.args["semiagg"].split(":"))
    assert rows == 32_768 and cap >= 16
    assert final.args["program"] == "program_dag_gagg"
    assert final.args["attempt"] == 2 and "reason" not in final.args
    assert final.args["grouping"] == "gagg/1of5"
    assert final.args["group_keys"] == "5 (1 text)"
    assert final.args["rows_out"] == 100
    assert final.args["ranks"] == "o_orderdate:prefix+o_totalprice:prefix"
    # the prefix: the key set's capacity times twice the probe's four
    # rows an order, narrower than the 32,768 padded probe rows (the
    # cell's 65,536 keys x 8 = 524,288 of 67.1M)
    prefix = final.args["rank_rows"]
    assert prefix == filt_ops.bucket_size(cap * 8) < 32_768
    assert "join_semi=merge:" in final.args["joins"]
    kept = int(collect.args["semiagg_keys"])
    assert 0 < kept <= cap and len(res.rows) <= kept
    q18.agrees(res.rows, QUANTITIES[1])

    # the key set's program: its five stages, one sort, no scatter
    prog, args = launched[-2]
    assert prog.__name__ == "program_dag_semiagg"
    text = prog.lower(*args).compile().as_text()
    for stage in ("pack", "sort", "scan", "having", "compact"):
        assert f"otb/semiagg/{stage}" in text, stage
    assert " scatter(" not in text
    # the final: the dropped keys it ranks by read at the prefix's
    # rows and at the 100 output rows, never at the probe's
    prog, args = launched[-1]
    text = prog.lower(*args).compile().as_text()
    for stage in ("pack", "sort", "scan", "topk", "recover"):
        assert f"otb/final/gagg/{stage}/" in text, stage
    widths = [int(w) for w in re.findall(
        r"= \w+\[(\d+)[^ ]* gather\(.*?op_name=\"[^\"]*?otb/final/gagg/",
        text)]
    assert widths and max(widths) <= prefix, widths
    # and no join gathers a dropped key at its probe width: the customer
    # fold crosses the orders' rows with its carrier (``c_custkey``)
    # alone, ``c_name`` waits for the output rows; the top join crosses
    # the lineitem rows with ``o_orderkey``
    assert crossings(text, final.args["joins"]) == CROSSINGS


# join -> the stages of the gathers at its own probe width that move
# build columns, in the warm final at the toy's widths
CROSSINGS = {"join0": ["fold/probe"], "join1": ["radix/gather"]}


def test_a_pallas_probe_under_a_deferring_gagg_returns_no_words(
        pallas_probe, launched):
    """The orders join probed by the Pallas kernel, as on a chip where
    its radix table is dimension-sized: the ``gagg`` still defers
    ``c_name``, the kernel returns no build word, and every join's probe
    width is crossed by what it was crossed by before."""
    q = Q18()
    try:
        sql = q.text(QUANTITIES[1])
        words0 = q.stat("radix_words_in_kernel")
        q.dep.sql(sql)
        res, (_semi, final), _c = q.traced(sql)
        assert final.args["program"] == "program_dag_gagg"
        joins = final.args["joins"]
        assert "join1=pallas+radix:" in joins and " words=" not in joins
        assert q.stat("radix_words_in_kernel") == words0
        prog, args = launched[-1]
        text = prog.lower(*args).compile().as_text()
        assert crossings(text, joins) == CROSSINGS
        q.agrees(res.rows, QUANTITIES[1])
    finally:
        q.close()


def test_live_rows_past_the_prefix_retry_once_with_the_keys_carried(
        q18, monkeypatch):
    """Where the qualifying keys hold more probe rows than the bound
    supposes (here a prefix forced to 64 rows), the final's fifth check
    refuses its answer once (``prefix off``); the dropped keys it ranks
    by then ride the sort, the answer is right, and the next run starts
    there."""
    from opentenbase_tpu.executor import fused_dag

    monkeypatch.setattr(
        fused_dag.DagRunner, "_rank_prefix_rows", lambda self: 64)
    quantity = 185
    sql = q18.text(quantity)
    res, launches, _c = q18.traced(sql)
    q18.agrees(res.rows, quantity)
    finals = [sp.args for sp in launches
              if sp.args["program"] == "program_dag_gagg"]
    off = [a for a in finals if "prefix off" in a.get("reason", "")]
    assert len(off) == 1, finals
    assert "prefix went false" in off[0]["reason"]
    assert off[0]["ranks"] == "o_orderdate:carried+o_totalprice:carried"
    assert "rank_rows" not in off[0]
    assert finals[0]["rank_rows"] == 64
    res2, warm, _c = q18.traced(sql)
    assert res2.rows == res.rows
    assert [sp.args["program"] for sp in warm] == PROGRAMS.split(",")
    assert "reason" not in warm[1].args
    assert warm[1].args["ranks"] == "o_orderdate:carried+o_totalprice:carried"


def test_a_key_set_with_no_qualifying_key(q18):
    """No order holds more than seven lines of at most 50: nothing
    passes 400, the key set is empty and so is the answer."""
    sql = q18.text(400)
    res = q18.dep.sql(sql)
    assert res.rows == [] and q18.host(sql) == []
    assert q18.reference(400)["rows"] == []
    rows = q18.fused_rows()
    assert rows["last_programs"][-1].startswith("program_dag_semiagg,")
    assert rows["last_mode"][-1] == "gagg"


def test_keys_past_the_first_capacity_retry_once(q18, monkeypatch):
    """A first capacity of 16 keys: the key set's count, fetched with
    the final's answer, refuses it once (``semiagg keys over cap``);
    the program at the power of two that holds them answers, and the
    next run starts there."""
    from opentenbase_tpu.executor import fused_dag

    monkeypatch.setattr(fused_dag, "SEMIAGG_START_KEYS", 16)
    quantity = 190
    sql = q18.text(quantity)
    over0 = q18.stat("semiagg_overflows")
    res, launches, _c = q18.traced(sql)
    q18.agrees(res.rows, quantity)
    retries = [sp for sp in launches
               if sp.args.get("reason") == "semiagg keys over cap"]
    assert len(retries) == 1, [sp.args for sp in launches]
    assert retries[0].args["program"] == "program_dag_semiagg"
    caps = [int(sp.args["semiagg"].split(":")[1]) for sp in launches
            if sp.args["program"] == "program_dag_semiagg"]
    assert caps[0] == 16 and caps[-1] > 16
    assert q18.stat("semiagg_overflows") == over0 + 1
    # the final's prefix grows with the key set: no ``prefix off``
    finals = [sp.args for sp in launches
              if sp.args["program"] == "program_dag_gagg"]
    assert not [a for a in finals if "prefix off" in a.get("reason", "")]
    assert finals[-1]["rank_rows"] > finals[0]["rank_rows"]
    res2, warm, _c = q18.traced(sql)
    assert res2.rows == res.rows
    assert [sp.args["program"] for sp in warm] == PROGRAMS.split(",")
    assert int(warm[0].args["semiagg"].split(":")[1]) == caps[-1]


@pytest.fixture
def nulls():
    """``o`` (unique ``k``) and ``l`` (several rows a ``k``, some with a
    NULL ``k``) on one device."""
    from opentenbase_tpu.engine import Cluster

    patch = pytest.MonkeyPatch()
    _mesh_of(patch, 1)
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table o (k bigint, v bigint) distribute by shard(k)")
    s.execute("create table l (k bigint, q decimal(15,2)) "
              "distribute by shard(k)")
    s.execute("insert into o values " + ",".join(
        f"({k},{(k * 37) % 101})" for k in range(1, 301)))
    s.execute("insert into l values " + ",".join(
        f"({k},{(k * 13) % 9 + 0.5})" for k in range(1, 301)
        for _ in range(k % 4 + 1)))
    # NULL keys, one group of them passing any threshold
    s.execute("insert into l values " + ",".join(
        "(null, 99.0)" for _ in range(20)))
    s.execute("analyze")
    yield s
    for sess in list(c.sessions):
        sess.close()
    patch.undo()


def test_null_group_keys_match_nothing(nulls):
    """The NULL group passes HAVING but no probe key equals NULL: the
    key set leaves it out, as the host does."""
    s = nulls
    sql = ("select k, v from o where k in (select k from l group by k "
           "having sum(q) > 20) order by v desc, k limit 50")
    s.execute("set enable_fused_execution = off")
    want = s.query(sql)
    s.execute("set enable_fused_execution = on")
    got = s.query(sql)
    assert got == want and 10 < len(want) <= 50
    rows = dict(s.query("select event, detail from pg_stat_fused"))
    assert rows.get("last_programs", "").startswith("program_dag_semiagg")


@pytest.mark.parametrize("sql", [
    "select count(*), sum(v) from o where k in (select k from l "
    "group by k having sum(q) > 20)",
    "select v, count(*) from o where k in (select k from l group by k "
    "having count(*) > 2 and max(q) < 8) group by v order by v",
])
def test_a_key_set_under_other_finals(nulls, sql):
    """The key set is the semi join's build side whatever final reads
    the rows: a scalar aggregate, a grouped one, HAVING over a count
    and a maximum."""
    s = nulls
    s.execute("set enable_fused_execution = off")
    want = s.query(sql)
    s.execute("set enable_fused_execution = on")
    assert s.query(sql) == want and want
    rows = dict(s.query("select event, detail from pg_stat_fused"))
    assert rows.get("last_programs", "").startswith("program_dag_semiagg")


@pytest.mark.parametrize("devices", [2, 4])
def test_on_a_mesh_the_host_answers(devices):
    """On several devices a key set is not built (its groups and the
    probe rows that meet them would have to share a device): the DAG
    declines with the reason and the host answers right."""
    q = Q18(devices=devices)
    try:
        res = q.dep.sql(q.text(QUANTITIES[1]))
        q.agrees(res.rows, QUANTITIES[1])
        rows = q.fused_rows()
        assert "semi join on a grouped key set: one device only" in rows[
            "unsupported"]
        assert rows.get("fused_statements", ["0"])[-1] == "0"
    finally:
        q.close()


def test_other_aggregates_under_a_join_are_refused_by_name(q18):
    """An aggregate under an inner join is no key set: the DAG says so
    and the host answers."""
    sql = ("select o_orderkey, t from orders, (select l_orderkey k, "
           "sum(l_quantity) t from lineitem group by l_orderkey) s "
           "where o_orderkey = k order by t desc, o_orderkey limit 5")
    res = q18.dep.sql(sql)
    assert res.rows == q18.host(sql) and len(res.rows) == 5
    unsupported = q18.fused_rows().get("unsupported", [])
    assert any(u.startswith("Aggregate under a join") for u in unsupported)
