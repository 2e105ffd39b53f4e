"""A sort-merge lookup sorts its key as one 32-bit word where both keys'
ranges fit (``fused_dag._lookup_sortmerge``'s ``narrow`` form).

The wide form co-sorts ``(int64 key, int8 side, int32 position)`` on
two keys: four 32-bit operands on the TPU, which moves an ``int64`` as
two halves (509.7 ms at 83.9M rows on a v5e, where the restore sort's
two 32-bit operands take 254.7). Where the join has one key pair
and both keys are bare columns whose resident range lies in
``[-(2^31-1), 2^31-1]`` (a table's scanned in the fragment, or a key
set's that its program proved), the co-sort is ``(int32 key, int32
position)``: a dead or NULL row takes ``-2^31``, and build rows lead
their run by their positions. The two forms return the same
``(matched, bidx, dup)``; a key that arrives through a motion stays
wide; data grown past 32 bits re-keys the program to the wide form.

On the CPU the DAG's joins look up by binary search (``_lookup``); the
tests at program level put the sort-merge in its place, so the narrow
form runs inside the cells' own programs."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opentenbase_tpu.executor import fused_dag
from opentenbase_tpu.executor.fused_dag import _lookup, _lookup_sortmerge

I32 = 2**31 - 1


def _case(name: str, rng):
    """(probe key, probe valid, probe mask, build key, build valid,
    build mask) as numpy arrays."""
    nb, npr = 300, 1200
    if name == "empty_build":
        nb = 0
    bk = rng.integers(-50, 50, nb)
    pk = rng.integers(-60, 60, npr)
    if name == "edges":  # the ends of the range, both signs, on both sides
        ends = np.array([I32, -I32, I32 - 1, -I32 + 1, 0])
        bk[: len(ends)] = ends
        pk[rng.integers(0, npr, 200)] = rng.choice(ends, 200)
    if name == "unique":  # no repeated build key: dup stays false
        bk = rng.permutation(np.arange(-nb // 2, nb - nb // 2))
    bv = rng.random(nb) > 0.1  # NULL build keys
    pv = rng.random(npr) > 0.1  # NULL probe keys
    bmask = rng.random(nb) > 0.15  # dead build rows
    pmask = rng.random(npr) > 0.15  # dead probe rows
    if name == "all_dead_build":
        bmask[:] = False
    # a dead or NULL row's data is don't-care: anything, even past 32 bits
    bk = np.where(bmask & bv, bk, rng.integers(-(2**40), 2**40, nb))
    pk = np.where(pmask & pv, pk, rng.integers(-(2**40), 2**40, npr))
    return (pk.astype(np.int64), pv, pmask, bk.astype(np.int64), bv, bmask)


CASES = ["random", "unique", "edges", "empty_build", "all_dead_build"]


def _both(arrays, check_dup: bool):
    pk, pv, pmask, bk, bv, bmask = (jnp.asarray(a) for a in arrays)

    def run(narrow):
        return jax.device_get(jax.jit(lambda: _lookup_sortmerge(
            (pk, pv), pmask, (bk, bv), bmask, check_dup, narrow=narrow))())

    return run(False), run(True)


@pytest.mark.parametrize("check_dup", [True, False], ids=["inner", "semi"])
@pytest.mark.parametrize("name", CASES)
def test_narrow_and_wide_forms_return_the_same(name, check_dup):
    arrays = _case(name, np.random.default_rng(CASES.index(name)))
    wide, narrow = _both(arrays, check_dup)
    for w, n, what in zip(wide, narrow, ("matched", "bidx", "dup")):
        assert np.array_equal(w, n), what
    matched, bidx, dup = narrow
    pk, pv, pmask, bk, bv, bmask = arrays
    live = bmask & bv
    want_dup = len(set(bk[live].tolist())) < live.sum()
    assert bool(dup) == (check_dup and want_dup)
    # every matched probe row points at a live build row of its key, the
    # last one by position where several hold it
    last = {int(k): i for i, k in enumerate(bk.tolist()) if live[i]}
    for j in range(len(pk)):
        hit = last.get(int(pk[j])) if pmask[j] and pv[j] else None
        assert bool(matched[j]) == (hit is not None), j
        if hit is not None:
            assert int(bidx[j]) == hit, j
    if name in ("empty_build", "all_dead_build"):
        assert not matched.any()
    else:
        assert matched.sum() > 100
    if name == "edges":
        assert matched[np.abs(pk) >= I32 - 1].sum() > 0


def test_unique_build_keys_agree_with_the_binary_search():
    """``_lookup`` (the CPU's) answers the same where no key repeats."""
    arrays = _case("unique", np.random.default_rng(7))
    pk, pv, pmask, bk, bv, bmask = (jnp.asarray(a) for a in arrays)
    want = jax.device_get(_lookup((pk, pv), pmask, (bk, bv), bmask, True))
    got = jax.device_get(_lookup_sortmerge(
        (pk, pv), pmask, (bk, bv), bmask, True, narrow=True))
    assert np.array_equal(want[0], got[0]) and want[0].sum() > 100
    assert np.array_equal(want[1][want[0]], got[1][got[0]])
    assert not bool(want[2]) and not bool(got[2])


SORT = re.compile(r'"stablehlo\.sort"\((.*?)\) <\{.*?\}> \(\{.*?\}\) : '
                  r'\((.*?)\) ->', re.S)


def _sorts(narrow: bool, extra: bool = False) -> list:
    """The operand types of each sort in the lowered lookup."""
    i64 = jax.ShapeDtypeStruct((4096,), jnp.int64)
    b4096 = jax.ShapeDtypeStruct((4096,), jnp.bool_)
    b64 = jax.ShapeDtypeStruct((1024,), jnp.int64)
    bb = jax.ShapeDtypeStruct((1024,), jnp.bool_)

    def one(pd, pv, pmask, bd, bmask):
        xs = [((pd, None), (bd, None))] if extra else ()
        return _lookup_sortmerge((pd, pv), pmask, (bd, None), bmask, True,
                                 extra=xs, narrow=narrow)

    text = jax.jit(one).lower(i64, b4096, b4096, b64, bb).as_text()
    return [re.findall(r"tensor<\d+x(\w+)>", types)
            for _ops, types in SORT.findall(text)]


def test_the_narrow_co_sort_carries_two_words():
    """Wide: ``(i64, i8, i32)`` then the restore's ``(i32, i32)``;
    narrow: ``(i32, i32)`` twice. A second pair keeps the wide form."""
    assert _sorts(False) == [["i64", "i8", "i32"], ["i32", "i32"]]
    assert _sorts(True) == [["i32", "i32"], ["i32", "i32"]]
    assert _sorts(True, extra=True) == _sorts(False, extra=True) == [
        ["i64", "i64", "i8", "i32"], ["i32", "i32"]]


# ---------------------------------------------------------------------------
# inside the programs: the sort-merge in place of the CPU's binary search
# ---------------------------------------------------------------------------

@pytest.fixture
def sortmerge(monkeypatch):
    """The DAG's joins look up by ``_lookup_sortmerge``, as on a TPU."""
    monkeypatch.setattr(fused_dag, "_lookup", _lookup_sortmerge)


def _launched(monkeypatch) -> list:
    out = []
    real = fused_dag.DagRunner._launch

    def launch(self, prog, arrays, params, snap, **args):
        out.append(prog)
        return real(self, prog, arrays, params, snap, **args)

    monkeypatch.setattr(fused_dag.DagRunner, "_launch", launch)
    return out


def _stat(rows: dict, name: str) -> int:
    return int(rows.get(name, ["0"])[-1])


def _q5():
    from test_tpch_q5 import Q5

    q = Q5(fact_rows=36_000)
    params = {"region": "ASIA", "year": 1994}
    ref = q.data.module.reference("q5", params, q.data.blocks, q.data.glob,
                                  exact=True)
    return q, q.text("ASIA", 1994), ref


def _q10():
    import test_tpch_q10 as t

    q = t.Q10()
    return q, q.text(t.DATES[1]), q.reference(t.DATES[1])


def _q18():
    import test_tpch_q18 as t

    q = t.Q18()
    return q, q.text(t.QUANTITIES[1]), q.reference(t.QUANTITIES[1])


# cell -> (its fixture, the joins of its final whose key is an order key,
# each with the record it ends in)
CELLS = {
    "q5": (_q5, {"join3": "key=i32 narrow=o_custkey"}),
    "q10": (_q10, {"join2": "key=i32 narrow=c_custkey"}),
    "q18": (_q18, {"join1": "key=i32 narrow=o_orderkey",
                   "join_semi": "key=i32"}),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_sort_their_order_key_as_one_word(
        cell, sortmerge, monkeypatch):
    """Q5's ``orders`` onto ``lineitem``, Q10's and Q18's likewise, and
    Q18's semi join against its key set (whose program proved its keys
    are ``l_orderkey``'s values): ``key=i32`` on each record, counted by
    ``pg_stat_fused``, and the answer is the reference's."""
    make, want = CELLS[cell]
    q, sql, ref = make()
    try:
        # (at this scale the estimates would admit a radix table where
        # the cell's widths take sort-merge)
        q.dep.sql("set join_mode = sortmerge")
        launched = _launched(monkeypatch)
        before = q.fused_rows()
        res = q.dep.sql(sql)
        after = q.fused_rows()
        got = q.compare.compare_statement(res.rows, ref)
        assert ref["rows"] and got["wrong"] is None, got
        assert got["sum_gap"] <= 1e-12, got
        final = launched[-1]
        assert {j: rec.partition(" ")[2] for j, rec in final.joins.items()
                if rec.startswith("merge:")} == want, final.joins
        narrowed = (_stat(after, "merge_keys_narrowed")
                    - _stat(before, "merge_keys_narrowed"))
        assert narrowed >= len(want)
    finally:
        q.close()


def test_a_probe_key_from_a_motion_stays_wide(sortmerge, monkeypatch):
    """The four-chip Q3 at a toy scale on four devices: fragment 1 joins
    ``customer`` to the orders moved onto its placement. ``o_custkey``
    arrived through a motion and nothing proves its range, so that
    sort-merge keeps its wide key; the answer is the reference's."""
    from test_mesh_placement import PARAM_SETS, Tpch4

    t = Tpch4(sf30_estimates=True)
    try:
        launched = _launched(monkeypatch)
        before = t.fused_rows()
        res = t.dep.sql(t.text(PARAM_SETS[0]))
        after = t.fused_rows()
        got = t.compare.compare_statement(res.rows, t.reference(PARAM_SETS[0]))
        assert got["wrong"] is None, got
        merges = [rec for prog in launched
                  for rec in getattr(prog, "joins", {}).values()
                  if rec.startswith("merge:")]
        assert merges and not [r for r in merges if " key=i32" in r], merges
        assert (_stat(after, "merge_keys_wide")
                > _stat(before, "merge_keys_wide"))
        assert (_stat(after, "merge_keys_narrowed")
                == _stat(before, "merge_keys_narrowed"))
    finally:
        t.close()


OVER_32_BITS = {
    "inner": "select o_v, count(*), sum(l_q) from o, l where o_key = l_key "
             "group by o_v order by o_v",
    # (the key set's keys: its program proves them by ``l_key``'s range)
    "semi": "select o_v, count(*) from o where o_key in (select l_key from l "
            "group by l_key having sum(l_q) > 40) group by o_v order by o_v",
}


@pytest.mark.parametrize("kind", sorted(OVER_32_BITS))
def test_an_order_key_past_32_bits_binds_the_wide_program(
        kind, sortmerge, monkeypatch):
    """``o`` onto ``l`` by sort-merge, as an inner join and as a semi
    join on ``l``'s grouped key set: narrow while the keys fit, and a
    program of its own once an insert puts a key of ``l`` past
    ``2^31-1``. Its low word is ``o``'s key ``-2^31+5``, a value that
    fits: a key cut to 32 bits would match it. The rows are the host
    executor's."""
    from opentenbase_tpu.engine import Cluster
    from test_tpch_q10 import _mesh_of

    _mesh_of(monkeypatch, 1)
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    try:
        s.execute("set join_mode = sortmerge")
        s.execute("create table o (o_key bigint, o_v bigint) "
                  "distribute by shard(o_key)")
        s.execute("create table l (l_key bigint, l_q bigint) "
                  "distribute by shard(l_key)")
        big = 2**31 + 5
        alias = big - 2**32  # (its low word, read as a signed int32)
        s.execute("insert into o values " + ",".join(
            f"({7 * k + 1},{k % 13})" for k in range(400))
            + f",({alias},77)")
        s.execute("insert into l values " + ",".join(
            f"({7 * (k % 450) + 1},{k % 50})" for k in range(1600)))
        launched = _launched(monkeypatch)
        sql = OVER_32_BITS[kind]

        def both():
            dev = s.query(sql)
            s.execute("set enable_fused_execution = off")
            try:
                host = s.query(sql)
            finally:
                s.execute("set enable_fused_execution = on")
            assert dev == host and dev
            (rec,) = [r for r in launched[-1].joins.values()
                      if r.startswith("merge:")]
            return dev, rec

        before, rec = both()
        assert " key=i32" in rec, rec
        s.execute(f"insert into l values ({big}, 30), ({big}, 20)")
        dev, rec = both()
        assert " key=i32" not in rec, rec
        assert dev == before and 77 not in [r[0] for r in dev]
    finally:
        s.close()
