"""Inner joins with more than one key pair in the DAG runner, in each
formulation it has (dimension fold, radix table, sort-merge), against
the host executor: one pair drives the lookup, the others are equalities
the matched row must also pass (or further sort keys of the sort-merge),
NULL keys match nothing, and a build side that repeats its driving key
but not the whole tuple is still looked up exactly.

And what the cells in the benchmark keep: a single-pair join lowers to
the text it had (the sort-merge lookup alone, and the programs of Q3,
flight 1 and the star statements over the benchmark's own deployments
at a toy scale), and their join orders at SF10's statistics are the
ones the parent planned."""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

NKEYS = 200  # build rows: key index 1..NKEYS, second key index % 7


@pytest.fixture(scope="module")
def mk():
    """``p`` probes three build tables over one key index r: ``dense``
    (a = r: folds), ``sparse`` (a = 7r + 3: no dense range) and ``dup``
    (a = r twice, b = 0 and 1: unique on (a, b) alone). The build
    tables are replicated, so each is whole wherever ``p``'s rows are."""
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table p (a_d bigint, a_s bigint, b_eq bigint, "
              "b_some bigint, b_never bigint, b_null bigint, b01 bigint, "
              "v bigint) distribute by roundrobin")
    for t in ("dense", "sparse", "dup"):
        s.execute(f"create table {t} (a bigint, b bigint, w bigint) "
                  "distribute by replication")
    rng = np.random.default_rng(35)

    def sqlv(x):
        return "null" if x is None else str(int(x))

    def insert(table, rows):
        s.execute(f"insert into {table} values " + ",".join(
            "(" + ",".join(sqlv(x) for x in r) + ")" for r in rows
        ))

    def b_of(r):  # a NULL second key on a few build rows
        return None if r % 53 == 0 else r % 7

    insert("dense", [(r, b_of(r), 10 * r) for r in range(1, NKEYS + 1)]
           + [(None, 3, 999_999)])
    insert("sparse", [(7 * r + 3, b_of(r), 10 * r)
                      for r in range(1, NKEYS + 1)] + [(None, 3, 999_999)])
    insert("dup", [(r, b, 10 * r + b) for r in range(1, NKEYS // 2 + 1)
                   for b in (0, 1)])
    rows = []
    for i in range(3000):
        r = int(rng.integers(1, NKEYS + 60))  # some keys match no row
        null_a = i % 17 == 0
        rows.append((
            None if null_a else r, None if null_a else 7 * r + 3,
            r % 7, int(rng.integers(0, 7)), r % 7 + 100,
            None if i % 11 == 0 else r % 7,
            None if i % 13 == 0 else int(rng.integers(0, 2)), i,
        ))
    insert("p", rows)
    s.execute("analyze")
    yield c
    for sess in list(c.sessions):
        sess.close()


# formulation -> (build table, probe's first key, join_mode)
FORMULATIONS = {
    "fold": ("dense", "a_d", "auto"),
    "radix": ("sparse", "a_s", "radix"),
    "merge": ("sparse", "a_s", "sortmerge"),
}
SECOND_PAIR = {
    "always_equal": "b_eq", "sometimes_equal": "b_some",
    "never_equal": "b_never", "null_in_a_key": "b_null",
}
CASES = [(f, sc) for f in FORMULATIONS for sc in SECOND_PAIR] + [
    ("merge", "duplicates_on_the_driving_key"),
    ("radix", "duplicates_on_the_driving_key"),
    ("fold", "duplicates_on_the_driving_key"),
]


def _fused_stat(s, name):
    rows = s.query(
        f"select detail from pg_stat_fused where event = '{name}'"
    )
    return rows[-1][0] if rows else None


@pytest.mark.parametrize("formulation,scenario", CASES)
def test_two_pair_join_equals_the_host_executor(mk, formulation, scenario):
    table, acol, mode = FORMULATIONS[formulation]
    if scenario == "duplicates_on_the_driving_key":
        # every driving key twice: the fold and the table refuse the
        # build and the sort-merge over both pairs answers, whatever was
        # asked for first
        table, acol, bcol = "dup", "a_d", "b01"
    else:
        bcol = SECOND_PAIR[scenario]
    q = (f"select p.v, {table}.w from p, {table} where p.{acol} = "
         f"{table}.a and p.{bcol} = {table}.b order by p.v")
    s = mk.session()
    try:
        s.execute("set enable_fused_execution = off")
        want = s.query(q)
        s.execute("set enable_fused_execution = on")
        s.execute(f"set join_mode = {mode}")
        n0 = int(_fused_stat(s, "fused_statements") or 0)
        got = s.query(q)
        assert got == want, (got[:5], want[:5])
        assert int(_fused_stat(s, "fused_statements")) == n0 + 1
        modes = _fused_stat(s, "last_join_modes").split(",")
        if scenario == "duplicates_on_the_driving_key":
            assert modes == ["merge"]
            assert len(got) > 500  # one of the two rows of a key, each
        else:
            assert formulation in modes, modes
            if scenario == "never_equal":
                assert got == []
            else:
                assert len(got) > 100
            if scenario == "sometimes_equal":  # and most rows drop out
                assert len(got) < 1000
        assert int(_fused_stat(s, "multi_key_joins")) >= 1
        unsupported = s.query(
            "select detail from pg_stat_fused where event = 'unsupported'"
        )
        assert [u for (u,) in unsupported if u != "trivial scan"] == []
    finally:
        s.execute("set join_mode = auto")
        s.close()


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_two_pair_semi_and_anti_joins_run_too(mk, kind):
    """An existence probe sorts on every pair like the inner join's
    sort-merge: EXISTS over two correlated keys and its NOT EXISTS twin
    run in the DAG
    and equal the host executor."""
    q = {
        "semi": "select count(*), sum(v) from p where exists (select 1 "
                "from sparse where sparse.a = p.a_s and sparse.b = p.b_some)",
        "anti": "select count(*), sum(v) from p where not exists (select 1 "
                "from sparse where sparse.a = p.a_s and sparse.b = p.b_some)",
    }[kind]
    s = mk.session()
    try:
        s.execute("set enable_fused_execution = off")
        want = s.query(q)
        s.execute("set enable_fused_execution = on")
        n0 = int(_fused_stat(s, "fused_statements") or 0)
        assert s.query(q) == want
        assert want[0][0] > 0
        assert int(_fused_stat(s, "fused_statements")) == n0 + 1
    finally:
        s.close()


def test_sortmerge_over_two_pairs_equals_a_row_at_a_time_join():
    """``_lookup_sortmerge`` with a second pair against a dictionary
    keyed by the tuple: a build side that repeats its first key, NULLs
    in either key of either side, dead rows; and ``dup`` only where the
    whole tuple repeats."""
    from opentenbase_tpu.executor.fused_dag import _lookup_sortmerge

    rng = np.random.default_rng(5)
    nb, npr = 64, 400
    ba = rng.integers(0, 20, nb).astype(np.int64)
    bb = np.arange(nb, dtype=np.int64) // 20  # (a, b) unique by chance?
    seen, keep = set(), np.ones(nb, bool)
    for i, k in enumerate(zip(ba.tolist(), bb.tolist())):
        keep[i] = k not in seen  # dead where the tuple would repeat
        seen.add(k)
    bbv = rng.random(nb) > 0.1
    pa = rng.integers(0, 22, npr).astype(np.int64)
    pb = rng.integers(0, 4, npr).astype(np.int64)
    pav, pbv = rng.random(npr) > 0.1, rng.random(npr) > 0.1
    pmask = rng.random(npr) > 0.05

    def run(bmask):
        return jax.jit(
            lambda: _lookup_sortmerge(
                (jnp.asarray(pa), jnp.asarray(pav)), jnp.asarray(pmask),
                (jnp.asarray(ba), None), jnp.asarray(bmask), True,
                extra=[((jnp.asarray(pb), jnp.asarray(pbv)),
                        (jnp.asarray(bb), jnp.asarray(bbv)))],
            )
        )()

    matched, bidx, dup = (np.asarray(x) for x in run(keep))
    assert not bool(dup)
    table = {
        (int(ba[i]), int(bb[i])): i
        for i in range(nb) if keep[i] and bbv[i]
    }
    for j in range(npr):
        hit = (
            table.get((int(pa[j]), int(pb[j])))
            if pmask[j] and pav[j] and pbv[j] else None
        )
        assert bool(matched[j]) == (hit is not None), j
        if hit is not None:
            assert int(bidx[j]) == hit, j
    assert matched.sum() > 50
    # the first key alone repeats all over the build side; the tuple
    # does once every row is live
    assert len(set(ba[keep].tolist())) < keep.sum()
    if not keep.all():
        assert bool(np.asarray(run(np.ones(nb, bool))[2]))


# ---------------------------------------------------------------------------
# what the cells in the benchmark keep
# ---------------------------------------------------------------------------

# sha256 of the lowered text, debug info off, as the parent of the PR
# that brought joins with several key pairs lowers the same thing
# (commit 0c5714d). A digest that moves re-keys an accepted cell's
# program in the compile cache and owes that cell a measurement.
SORTMERGE_DIGEST = (
    "2aa4a825ef90c1ee9025c4cccededf10d9888efdb378a4bee8f91c1446efd4db"
)


def _sortmerge_text() -> str:
    from opentenbase_tpu.executor.fused_dag import _lookup_sortmerge

    def one_pair(pd, pv, pmask, bd, bmask):
        return _lookup_sortmerge((pd, pv), pmask, (bd, None), bmask, True)

    i64 = jax.ShapeDtypeStruct((4096,), jnp.int64)
    b4096 = jax.ShapeDtypeStruct((4096,), jnp.bool_)
    b64 = jax.ShapeDtypeStruct((1024,), jnp.int64)
    bb = jax.ShapeDtypeStruct((1024,), jnp.bool_)
    return jax.jit(one_pair).lower(i64, b4096, b4096, b64, bb).as_text(
        debug_info=False)


def test_single_pair_sortmerge_lowers_to_the_text_it_had():
    """53 % of the one-chip Q3 and both big joins of the four-chip one:
    with no further pair the lookup is op for op what it was."""
    digest = hashlib.sha256(_sortmerge_text().encode()).hexdigest()
    assert digest == SORTMERGE_DIGEST


# (configuration, traffic mix): the statements of every DAG cell
CELLS = {
    "tpch_sf10_1chip": "join_q3",
    "ssb_sf10_1chip": "flight1_q11_q12_q13",
    "ssb_star_sf10_1chip": "star_q21_q31_q41",
    "tpch_q10_sf10_1chip": "q10",
}
# kind -> digests of the programs its statement launches, in order.
# The sort-merge's co-sort of 32-bit keys (where both keys' ranges fit)
# re-pinned none: on this file's CPU devices the DAG looks its joins up
# by binary search (``_lookup``), which reads its keys as ``int64``
# whatever the sort-merge's form; SORTMERGE_DIGEST holds that form's
# wide text, tests/test_sortmerge_key_word.py its narrow operands.
# PR 40 (a 64-bit build column read after a join crosses it as one
# 32-bit word where its resident range fits) re-pinned none: no program
# here holds such a column. The star's and flight 1's gathered columns
# are 32-bit already (dictionary codes, dates, ints; their one
# ``bigint``, ``lo_orderkey``, is on the probe side), q3's eight-device
# programs read their build sides through motions, which keep their
# width, and the one-chip Q3's ``gsort`` says nothing of what it reads,
# so its joins narrow nothing. The programs that do hold one (the Q5
# and Q10 cells') are held by tests/test_join_word_narrowing.py.
# Re-pinned ON PURPOSE by PR 39 for q3's third and fourth programs alone,
# the two ``gagg``s of the eight-device toy (the first folds ``orders``
# onto ``lineitem`` and is refused by its density flag on every run, the
# second answers): a ``gagg`` now returns which of its four checks went
# false (one bit each, where it returned their conjunction), names its
# prefix scans' stage, and reads the group keys it dropped from the
# packed key (here ``o_orderdate`` and ``o_shippriority``, which
# ``l_orderkey`` determines) at its LIMIT output rows through the join's
# own row index, where it gathered each at the probe's width first
# (PERF.md §6 PR 39). No cell of the benchmark times a ``gagg`` but the
# Q10 cell that PR added: the one-chip Q3's timed program is the
# ``gsort`` pinned by ``test_one_chip_q3_keeps_the_program_it_times``
# below, the four-chip Q3's likewise, and neither moved. Before it PR 38
# (PR 36 before that, ROADMAP.md Design 2d) re-pinned the programs that
# hold a fold WITH A CARRIER: q21, q31, q41 and the third of q3's four
# (tests/test_fold_slot_probe.py holds the gather count; PERF.md §6
# PR 38). Flight 1's three, q3's count and broadcast programs and
# SORTMERGE_DIGEST did not move in either. Q10 is not pinned here: on
# this file's eight devices it takes the mesh's path (two motions and
# the ``grouped`` final), not the one-device ``gagg`` its cell times;
# tests/test_tpch_q10.py holds that program's shape.
PROGRAM_DIGESTS = {
 'q11': ['program_dag_scalar:3adf94616349d696'],
 'q12': ['program_dag_scalar:5cc378cd711b20d2'],
 'q13': ['program_dag_scalar:e68a2a2291412bb5'],
 'q21': ['program_dag_grouped:643be789d9f7aa64'],
 'q3': ['program_dag_count:2dc092c5d0bfe138',
        'program_dag_broadcast:a1292c7f75dd922a',
        'program_dag_gagg:b56ff9be65b59ab1',
        'program_dag_gagg:de6ce60808328967'],
 'q31': ['program_dag_grouped:e390f5619239e334'],
 'q41': ['program_dag_grouped:025ca4f4abcf563b']}
# kind -> where each fold's match bit comes from (``bit=`` of the
# launch's ``joins``): the first 32-bit integer column of the dimension
# the plan reads after the lookup, its own gather where there is none
# (at this scale ``supplier`` and ``customer`` come before ``dates``,
# which sort-merges; on the chip ``dates`` is ``join0``, a radix table)
FOLD_BITS = {
 'q21': {'join0': 'own', 'join1': 'p_brand1'},
 'q31': {'join0': 's_nation', 'join1': 'c_nation'},
 'q41': {'join0': 'own', 'join1': 'c_nation', 'join3': 'own'},
}
# TPC-H's and SSB's row counts and distinct values at SF1 (a key's ndv
# is its table's rows); what scales is multiplied by the scale factor
SF1_STATS = {
    "tpch_sf10_1chip": {
        "lineitem": (6_000_000, {"l_orderkey": 1_500_000}),
        "orders": (1_500_000, {"o_orderkey": 1_500_000,
                               "o_custkey": 100_000}),
        "customer": (150_000, {"c_custkey": 150_000, "c_mktsegment": 5}),
    },
    "ssb_star_sf10_1chip": {
        "lineorder": (6_000_000, {
            "lo_orderkey": 1_500_000, "lo_custkey": 30_000,
            "lo_partkey": 80_000, "lo_suppkey": 2_000,
            "lo_orderdate": 2_406}),
        "customer": (30_000, {"c_custkey": 30_000, "c_nation": 25,
                              "c_region": 5}),
        "supplier": (2_000, {"s_suppkey": 2_000, "s_nation": 25,
                             "s_region": 5}),
        "part": (80_000, {"p_partkey": 80_000, "p_mfgr": 5,
                          "p_category": 25, "p_brand1": 1_000}),
        "dates": (2_556, {"d_datekey": 2_556, "d_year": 7}),
    },
}
SF1_STATS["tpch_q10_sf10_1chip"] = {
    **SF1_STATS["tpch_sf10_1chip"],
    "customer": (150_000, {
        "c_custkey": 150_000, "c_nationkey": 25, "c_name": 150_000,
        "c_address": 150_000, "c_phone": 150_000, "c_acctbal": 140_000,
        "c_comment": 150_000}),
}  # (nation keeps what ANALYZE measured: 25 rows at every scale)
SF1_STATS["ssb_sf10_1chip"] = {
    t: SF1_STATS["ssb_star_sf10_1chip"][t] for t in ("lineorder", "dates")
}
# kind -> the joins and scans of its EXPLAIN at SF10's statistics
JOIN_ORDERS = {
 'q11': ['Join inner on lo_orderdate=d_datekey',
         'Scan on lineorder',
         'Scan on dates'],
 'q12': ['Join inner on lo_orderdate=d_datekey',
         'Scan on lineorder',
         'Scan on dates'],
 'q13': ['Join inner on lo_orderdate=d_datekey',
         'Scan on lineorder',
         'Scan on dates'],
 'q10': ['Scan on orders',
         'Join inner on c_custkey=o_custkey',
         'Join inner on n_nationkey=c_nationkey',
         'Scan on nation',
         'Scan on customer',
         'Join inner on o_orderkey=l_orderkey',
         'Scan on lineitem'],
 'q21': ['Join inner on lo_suppkey=s_suppkey',
         'Join inner on lo_partkey=p_partkey',
         'Join inner on d_datekey=lo_orderdate',
         'Scan on dates',
         'Scan on lineorder',
         'Scan on part',
         'Scan on supplier'],
 'q3': ['Scan on orders',
        'Join inner on c_custkey=o_custkey',
        'Scan on customer',
        'Join inner on o_orderkey=l_orderkey',
        'Scan on lineitem'],
 'q31': ['Join inner on lo_custkey=c_custkey',
         'Join inner on lo_suppkey=s_suppkey',
         'Join inner on d_datekey=lo_orderdate',
         'Scan on dates',
         'Scan on lineorder',
         'Scan on supplier',
         'Scan on customer'],
 'q41': ['Join inner on lo_partkey=p_partkey',
         'Join inner on lo_custkey=c_custkey',
         'Join inner on lo_suppkey=s_suppkey',
         'Join inner on d_datekey=lo_orderdate',
         'Scan on dates',
         'Scan on lineorder',
         'Scan on supplier',
         'Scan on customer',
         'Scan on part']}


class Cell:
    """A configuration's deployment at a toy scale, as the benchmark
    builds it."""

    def __init__(self, config: str):
        from harness import loader, traffic

        cfg = loader.read_config(config)
        self.mix = traffic.read_mix(CELLS[config])
        self.config = config
        self.texts = {
            kind: text for kind, text, _p in
            traffic.warm_up(self.mix, 2_147_483_777)[::-1]
        }  # (one parameter set a kind: the first)
        self.dep = loader.Deployment(cfg)
        self.dep.create_tables()
        self.dep.load(loader.generate(cfg, 2_147_483_777, 4_000 / 6_000_000))

    def set_stats(self, sf: int) -> None:
        for table, (rows, ndv) in SF1_STATS[self.config].items():
            meta = self.dep.cluster.catalog.get(table)
            fixed = table == "dates"
            meta.stats = {
                "rows": rows if fixed else rows * sf,
                "ndv": {c: ndv.get(c, 100) * (
                    sf if ndv.get(c, 100) >= 1_000 and not fixed else 1
                ) for c in meta.schema},
            }

    def join_order(self, kind: str) -> list:
        rows = self.dep.sql("explain " + self.texts[kind]).rows
        return [
            " ".join(r[0].split()[:3]) if r[0].strip().startswith("Scan")
            else r[0].strip()
            for r in rows if r[0].strip().startswith(("Join", "Scan on"))
        ]

    def program_digests(self, kind: str, monkeypatch) -> list:
        from opentenbase_tpu.executor import fused

        seen = []
        real = fused.Launcher.__call__

        def call(self_, prog, build_args, late=None, **args):
            built = build_args()
            seen.append((prog, built))
            return real(self_, prog, lambda: built, late=late, **args)

        monkeypatch.setattr(fused.Launcher, "__call__", call)
        self.dep.sql(self.texts[kind])
        monkeypatch.setattr(fused.Launcher, "__call__", real)
        self.launched = [prog for prog, _built in seen]
        self.built = seen[-1][1] if seen else None
        return [
            prog.__name__ + ":" + hashlib.sha256(
                prog.lower(*built).as_text(debug_info=False).encode()
            ).hexdigest()[:16]
            for prog, built in seen
        ]


KIND_CELL = {
    "q3": "tpch_sf10_1chip",
    "q11": "ssb_sf10_1chip", "q12": "ssb_sf10_1chip",
    "q13": "ssb_sf10_1chip",
    "q21": "ssb_star_sf10_1chip", "q31": "ssb_star_sf10_1chip",
    "q41": "ssb_star_sf10_1chip",
    "q10": "tpch_q10_sf10_1chip",
}


@pytest.fixture(scope="module")
def cells():
    built: dict = {}

    def get(kind: str) -> Cell:
        config = KIND_CELL[kind]
        if config not in built:
            built[config] = Cell(config)
        return built[config]

    yield get
    for c in built.values():
        c.dep.close()


@pytest.mark.parametrize("kind", sorted(PROGRAM_DIGESTS))
def test_cells_programs_lower_to_the_text_they_had(cells, kind, monkeypatch):
    """Q3, flight 1's three and the star cell's three statements over
    the benchmark's own deployments at a toy scale: every program they
    launch lowers to the pinned text (the last PR to move one on purpose
    says so beside ``PROGRAM_DIGESTS``)."""
    cell = cells(kind)
    assert cell.program_digests(kind, monkeypatch) == PROGRAM_DIGESTS[kind]
    if kind in FOLD_BITS:
        (prog,) = cell.launched
        assert {
            j: rec.split(" bit=")[1] for j, rec in prog.joins.items()
            if rec.startswith("fold:")
        } == FOLD_BITS[kind]


@pytest.fixture(scope="module")
def star_at_sf10():
    """The star cell's deployment at a toy scale with SF10's
    statistics: ``dates`` is ``join0``, as on the chip, and under
    ``join_mode = radix`` a radix table, as the chip's estimates make it
    (the toy's stores hold too few ``lineorder`` rows for ``auto`` to
    gate one). Its programs are its own (the tests above share
    ``cells``)."""
    cell = Cell("ssb_star_sf10_1chip")
    cell.set_stats(10)
    cell.dep.sql("set join_mode = radix")
    yield cell
    cell.dep.close()


@pytest.mark.parametrize("kind", ["q21", "q31", "q41"])
def test_star_reads_d_year_out_of_the_pallas_probe(
        star_at_sf10, kind, pallas_probe, monkeypatch):
    """A star statement with its ``dates`` join probed by the Pallas
    kernel (interpreted here; the chip's path): the kernel returns
    ``d_year``, the one build column read after the lookup, out of its
    own contraction, so no gather under ``join0/radix/gather`` is left
    in the compiled program, and the rows are the host executor's."""
    import re

    cell = star_at_sf10

    def words_in_kernel() -> int:
        return int(dict(cell.dep.sql(
            "select event, detail from pg_stat_fused").rows).get(
                "radix_words_in_kernel", 0))

    before = words_in_kernel()
    cell.program_digests(kind, monkeypatch)  # (runs it, keeps the launch)
    (prog,) = cell.launched
    assert prog.joins["join0"].startswith("pallas+radix:")
    assert prog.joins["join0"].endswith(" words=d_year")
    assert words_in_kernel() == before + 1
    cell.dep.sql("set enable_fused_execution = off")
    try:
        host = cell.dep.sql(cell.texts[kind]).rows
    finally:
        cell.dep.sql("set enable_fused_execution = on")
    assert host and cell.dep.sql(cell.texts[kind]).rows == host
    built = cell.built
    text = prog.lower(*built).compile().as_text()
    assert "otb/join0/pallas/probe/" in text
    assert not re.search(
        r"gather\(.*?op_name=\"[^\"]*?otb/join0/radix/gather/", text)


def test_one_chip_q3_keeps_the_program_it_times(monkeypatch):
    """``tpch_sf10_1chip.join`` on its one device, at SF10's statistics:
    the ``gsort`` every timed Q3 runs folds ``customer``, which the plan
    only filters by, so its match bit keeps a gather of its own and the
    program the text its parent lowered (PR 37, commit 60ccd05). The
    first attempt, a ``gagg`` that folds ``orders`` too and is refused
    by its density flag once a warm-up, reads ``o_orderdate``: its bit
    rides there and its text moved with PR 38."""
    from opentenbase_tpu.executor import fused

    real = fused.build_mesh
    monkeypatch.setattr(
        fused, "build_mesh", lambda _devs=None: real(jax.devices()[:1]))
    cell = Cell("tpch_sf10_1chip")
    try:
        cell.set_stats(10)
        digests = cell.program_digests("q3", monkeypatch)
        assert [d.split(":")[0] for d in digests] == [
            "program_dag_gagg", "program_dag_gsort"]
        assert digests[1] == "program_dag_gsort:cd9296a4bf022cdc"
        first, timed = cell.launched
        assert first.joins == {"join0": "fold:128x1024 bit=own",
                               "join1": "fold:1024x8192 bit=o_orderdate"}
        assert timed.joins == {"join0": "fold:128x1024 bit=own"}
        assert timed.join_modes == {"fold", "merge"}
    finally:
        cell.dep.close()


def test_q5s_two_big_folds_carry_their_bits():
    """TPC-H Q5 over its cell's deployment at a toy scale (60 suppliers:
    the least at which the snowflake arm folds as it does at SF10): the
    arm's folds (``join1`` onto ``supplier``, ``join2`` onto
    ``lineitem``) read ``n_name`` and ``s_nationkey`` after the lookup
    and carry their bit in the 32-bit dictionary code; the customer fold
    (``join4``) reads ``c_nationkey`` only in its own second key pair,
    which is a read after the lookup all the same; ``region`` is only
    filtered by. And the answer is the reference's."""
    from test_tpch_q5 import Q5

    q = Q5(fact_rows=36_000)
    try:
        res = q.dep.sql(q.text("ASIA", 1994))
        rows = q.fused_rows()
        assert rows["last_programs"][-1].split(",")[-1] == \
            "program_dag_grouped"
        runner = q.dep.cluster.fused_executor()._dag
        (joins,) = [
            entry[0].joins for key, entry in runner._programs.items()
            if key[0] == "final" and "fold" in entry[0].joins["join4"]
            and "fold" not in entry[0].joins["join3"]
        ]  # (the one that answered: ``orders`` is no dense range)
        assert {j: rec.split(":")[0] + rec[rec.index(" bit="):]
                for j, rec in joins.items() if " bit=" in rec} == {
            "join0": "fold bit=own", "join1": "fold bit=n_name",
            "join2": "fold bit=n_name narrow=s_nationkey",
            "join4": "fold bit=c_nationkey narrow=c_nationkey keys=2:c_custkey",
        }  # (PR 40: both int64s cross as one 32-bit word)
        assert int(rows["fold_bits_carried"][-1]) >= 3
        ref = q.data.module.reference(
            "q5", {"region": "ASIA", "year": 1994}, q.data.blocks,
            q.data.glob, exact=True,
        )
        got = q.compare.compare_statement(res.rows, ref)
        assert ref["rows"] and got["wrong"] is None, got
        assert got["sum_gap"] <= 1e-12
    finally:
        q.close()


@pytest.mark.parametrize("kind", sorted(KIND_CELL))
def test_cells_join_orders_at_sf10_statistics(cells, kind):
    """The join order prices a key by its measured ndv and puts key =
    foreign-key edges first: at SF10's statistics neither moves the
    joins of a statement the benchmark measures."""
    cell = cells(kind)
    catalog = cell.dep.cluster.catalog
    saved = {t: catalog.get(t).stats for t in SF1_STATS[cell.config]}
    cell.set_stats(10)
    try:
        assert cell.join_order(kind) == JOIN_ORDERS[kind]
    finally:
        for t, st in saved.items():
            catalog.get(t).stats = st
