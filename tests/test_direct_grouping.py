"""The DAG's grouped final by direct addressing (executor/fused_dag.py
``_direct_grouped``, ops/agg.py ``_direct_group_reduce_impl``): where the
packed group key's live range fits the program's slots the key IS the
slot and sums and counts are one-hot matmuls over exact 8-bit limbs.
Held against the sort formulation (``_group_ids_impl`` +
``_group_reduce_impl``) on the same inputs as sets of (keys, sums,
counts); the runner takes the next capacity once where the range passes
the program's, the sort past the bound, and remembers either."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.executor import fused_dag
from opentenbase_tpu.ops import agg as agg_ops

SPECS = ("sum", "count", "count_star", "sum")


def _direct(keys, vals, mask, cap, specs=SPECS, low=None):
    def fn(keys, vals, mask):
        st = fused_dag._Stage()
        try:
            if low is None:
                return fused_dag._direct_grouped(
                    keys, vals, mask, cap, specs, st
                )
            # (another split of the slot: the same answer)
            packed, ok, layout = fused_dag._pack_group_keys(keys, mask)
            slot = jnp.where(mask, packed, cap).astype(jnp.int32)
            ov, got, ng = agg_ops._direct_group_reduce_impl(
                vals, slot, cap, specs, low
            )
            ok_ = [
                (d, got if v is None else v & got)
                for d, v in fused_dag._unpack_group_keys(
                    layout, cap, [d.dtype for d, _v in keys]
                )
            ]
            return ok_, ov, got, ng, layout[-1]
        finally:
            st.done()

    return jax.device_get(jax.jit(fn)(keys, vals, mask))


def _sorted(keys, vals, mask, cap, specs=SPECS):
    def fn(keys, vals, mask):
        perm, seg, ng = agg_ops._group_ids_impl(keys, mask)
        return agg_ops._group_reduce_impl(
            keys, vals, perm, seg, cap, specs
        ) + (ng,)

    return jax.device_get(jax.jit(fn)(keys, vals, mask))


def _as_set(out_keys, out_vals, gvalid) -> set:
    """Groups as a set of tuples; a NULL cell reads None."""
    rows = set()
    for g in np.nonzero(np.asarray(gvalid))[0]:
        cells = []
        for d, v in list(out_keys) + list(out_vals):
            cells.append(int(d[g]) if bool(v[g]) else None)
        rows.add(tuple(cells))
    return rows


def _col(rng, n, dtype, lo, hi, nulls=0.0):
    d = rng.integers(lo, hi, n).astype(dtype)
    v = None if not nulls else jnp.asarray(rng.random(n) >= nulls)
    return (jnp.asarray(d), v)


def _vals(rng, n, null_args=True):
    a = _col(rng, n, np.int64, -(2**61), 2**61, 0.3 if null_args else 0.0)
    b = _col(rng, n, np.int32, -(2**31), 2**31)
    return [a, a, None, b]


KEY_CASES = {
    "one_int32": [(np.int32, -5, 60, 0.0)],
    "int64_and_codes": [(np.int64, 10**12, 10**12 + 7, 0.0),
                        (np.int32, 1, 30, 0.0)],
    "three_with_a_null_bucket": [(np.int32, 0, 6, 0.2),
                                 (np.int64, -3, 4, 0.0),
                                 (np.int32, 100, 110, 0.0)],
    "four_keys_two_nullable": [(np.int32, 0, 4, 0.1), (np.int64, 5, 9, 0.0),
                               (np.int32, -2, 2, 0.25), (np.int32, 7, 10, 0.0)],
}


@pytest.mark.parametrize("case", sorted(KEY_CASES))
def test_direct_equals_sorted_as_sets(case):
    """One to four keys mixing int32 / int64 / dictionary codes, NULL
    keys (their bucket) and NULL arguments, negative values and int64
    sums past 2^53."""
    rng = np.random.default_rng(len(case))
    n = 5000
    keys = [_col(rng, n, *spec) for spec in KEY_CASES[case]]
    vals = _vals(rng, n)
    mask = jnp.asarray(rng.random(n) < 0.7)
    cap = 1024
    dk, dv, dg, dn, span = _direct(keys, vals, mask, cap)
    sk, sv, sg, sn = _sorted(keys, vals, mask, cap)
    assert int(span) <= cap, "the case must fit the capacity"
    want = _as_set(sk, sv, sg)
    assert _as_set(dk, dv, dg) == want and len(want) == int(dn) == int(sn)
    assert any(abs(r[len(keys)] or 0) > 2**53 for r in want)
    # the count of a NULLable argument is not the count of the rows
    assert any(r[len(keys) + 1] != r[len(keys) + 2] for r in want)


@pytest.mark.parametrize("low", [1, 4, 128])
def test_any_split_of_the_slot_gives_the_same_sums(low):
    rng = np.random.default_rng(low)
    n = 3000
    keys = [_col(rng, n, np.int32, 0, 50), _col(rng, n, np.int64, 0, 5, 0.2)]
    vals = _vals(rng, n)
    mask = jnp.asarray(rng.random(n) < 0.9)
    dk, dv, dg, dn, _span = _direct(keys, vals, mask, 512, low=low)
    sk, sv, sg, _sn = _sorted(keys, vals, mask, 512)
    assert _as_set(dk, dv, dg) == _as_set(sk, sv, sg)


@pytest.mark.parametrize("live", [0, 1])
def test_no_live_row_and_one(live):
    rng = np.random.default_rng(11)
    n = 700
    keys = [_col(rng, n, np.int32, -9, 9, 0.1), _col(rng, n, np.int64, 0, 3)]
    vals = _vals(rng, n)
    mask = np.zeros(n, bool)
    mask[:live] = True
    mask = jnp.asarray(mask)
    dk, dv, dg, dn, span = _direct(keys, vals, mask, 256)
    sk, sv, sg, sn = _sorted(keys, vals, mask, 256)
    assert int(dn) == int(sn) == live and int(np.sum(dg)) == live
    assert _as_set(dk, dv, dg) == _as_set(sk, sv, sg)
    assert 1 <= int(span) <= 2  # a lone row's keys: a range of one each


@pytest.mark.parametrize("past", [0, 1])
def test_a_range_at_the_capacity_and_one_past_it(past):
    """``cap`` distinct packed values fill every slot; one more and the
    program counts no row and reports the span."""
    cap = 256
    n = cap + past
    keys = [(jnp.arange(n, dtype=jnp.int32) + 1000, None)]
    vals = [(jnp.arange(n, dtype=jnp.int64) - 7, None)] * 2 + [
        None, (jnp.ones(n, jnp.int32), None)]
    mask = jnp.ones(n, bool)
    dk, dv, dg, dn, span = _direct(keys, vals, mask, cap)
    assert int(span) == n
    if past:
        assert int(dn) == 0 and not np.asarray(dg).any()
        return
    assert int(dn) == cap and np.asarray(dg).all()
    assert (np.asarray(dk[0][0]) == np.arange(cap) + 1000).all()
    assert (np.asarray(dv[0][0]) == np.arange(cap) - 7).all()


@pytest.mark.parametrize("specs, dtypes, ok", [
    (("sum", "count", "count_star"), (np.int64, np.float64, None), True),
    (("sum",), (np.int32,), True),
    (("min",), (np.int32,), False),
    (("max", "sum"), (np.int64, np.int64), False),
    (("sum",), (np.float64,), False),
    (("sum",), (np.float32,), False),
])
def test_eligibility_rule(specs, dtypes, ok):
    assert agg_ops.direct_group_eligible(
        specs, [None if d is None else np.dtype(d) for d in dtypes]
    ) is ok


# ---------------------------------------------------------------------------
# the runner: capacity by retry, remembered; the sort past the bound
# ---------------------------------------------------------------------------


@pytest.fixture()
def sess(monkeypatch):
    """One datanode, a fact table joined to a replicated dimension (the
    DAG runner's shape); toy capacities so that a few hundred rows cross
    them: the program starts at 16 slots and may grow to 64."""
    monkeypatch.setattr(fused_dag, "DIRECT_START_SLOTS", 16)
    monkeypatch.setattr(fused_dag, "DIRECT_MAX_SLOTS", 64)
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    s.execute("create table d (dk int, g int, x float8, t text) "
              "distribute by replication")
    s.execute("create table f (k int, v bigint, w float8) "
              "distribute by roundrobin")
    s.execute("insert into d values " + ",".join(
        f"({k},{'null' if k % 11 == 3 else k % 7},{k}.5,"
        f"{'null' if k % 13 == 5 else repr('t%d' % (k % 5))})"
        for k in range(300)
    ))
    rng = np.random.default_rng(5)
    s.execute("insert into f values " + ",".join(
        f"({k},{'null' if v == 0 else v},{v}.25)" for k, v in zip(
            rng.integers(0, 300, 900), rng.integers(-50, 50, 900))
    ))
    s.execute("set enable_fused_execution = on")
    return s


def _stat(s, name) -> int:
    """A counter row of pg_stat_fused (absent before the first device
    statement builds the executor)."""
    return int(([r[1] for r in s.query(
        "select event, detail from pg_stat_fused") if r[0] == name]
        or ["0"])[-1])


def _retries(s) -> float:
    """The ledger's ``fused_retries`` over every statement so far."""
    return sum(float(r[0]) for r in s.query(
        "select fused_retries from pg_stat_statements"))


def _run(s, q):
    """(rows, how the final grouped, retries of this execution)."""
    fx = s.cluster.fused_executor()
    seen = []
    real = fused_dag.DagRunner._launch

    def launch(self, prog, arrays, params, snap, **args):
        seen.append(args.get("grouping"))
        return real(self, prog, arrays, params, snap, **args)

    before = _retries(s)
    fused_dag.DagRunner._launch = launch
    try:
        rows = s.query(q)
    finally:
        fused_dag.DagRunner._launch = real
    assert fx._dag is not None and fx._dag.last_mode == "grouped"
    return rows, seen, _retries(s) - before


def _host(s, q):
    s.execute("set enable_fused_execution = off")
    try:
        return s.query(q)
    finally:
        s.execute("set enable_fused_execution = on")


@pytest.mark.parametrize("where, first, again", [
    # 16 distinct packed values: the starting capacity holds them
    ("k >= 100 and k < 116", ["direct/16"], ["direct/16"]),
    # one past it: the next power of two, once, then remembered
    ("k >= 100 and k < 117", ["direct/16", "direct/32"], ["direct/32"]),
    # past the bound: the sort formulation, remembered as such
    ("k >= 100 and k < 165", ["direct/16", "sort"], ["sort"]),
])
def test_runner_takes_the_capacity_the_range_needs(sess, where, first, again):
    q = (f"select k, sum(v), count(*) from f, d where k = dk and {where} "
         "group by k order by k")
    want = _host(sess, q)
    direct0 = _stat(sess, "grouped_direct")
    sorted0 = _stat(sess, "grouped_sorted")
    rows, seen, retries = _run(sess, q)
    assert rows == want and len(rows) > 10
    assert seen == first and retries == len(first) - 1
    rows, seen, retries = _run(sess, q)
    assert rows == want and seen == again and retries == 0
    ran_direct = again[0] != "sort"
    assert _stat(sess, "grouped_direct") - direct0 == 2 * ran_direct
    assert _stat(sess, "grouped_sorted") - sorted0 == 2 * (
        not ran_direct)


@pytest.mark.parametrize("select, group", [
    ("g, min(v)", "g"),  # min: no one-hot sum gives it
    ("g, sum(w)", "g"),  # a float sum is not limb-splittable
    ("x, count(*)", "x"),  # a float key is not packable
])
def test_min_float_sum_and_float_key_decline(sess, select, group):
    q = (f"select {select} from f, d where k = dk and k < 40 "
         f"group by {group} order by 1")
    want = _host(sess, q)
    sorted0 = _stat(sess, "grouped_sorted")
    rows, seen, retries = _run(sess, q)
    assert rows == want and seen == ["sort"] and retries == 0
    assert _stat(sess, "grouped_sorted") == sorted0 + 1


def test_text_and_int_keys_with_a_null_bucket_over_sql(sess):
    """Dictionary-coded text beside an int key, both with NULLs: the
    keys come back from the slot index as their own codes, NULL as NULL,
    and a NULL argument is counted by count(*) alone."""
    q = ("select t, g, count(*), sum(v), count(v) from f, d where k = dk "
         "group by t, g order by t, g")
    want = _host(sess, q)
    rows, seen, _retried = _run(sess, q)
    assert rows == want
    assert any(r[0] is None for r in rows) and any(r[1] is None for r in rows)
    assert any(r[2] != r[4] for r in rows)
    assert seen[-1].startswith("direct/")


# ---------------------------------------------------------------------------
# the programs that bypass the grouped final did not move
# ---------------------------------------------------------------------------

# sha256 of the lowered text, debug info off, as the tree before the
# direct-addressed final (PR 33's) lowers the same two statements over
# the same rows. ops/agg.py and _compile_final are shared with both: an
# edit there that changes either program re-keys it in the compile cache
# and owes the accepted cells a measurement, so change a digest only
# with that in hand.
BYPASSING = {
    "program_scan_xla_hash": (
        "select d, sum(q), count(q), sum(p) from li group by d order by d",
        "hash/64/k4",
        "3b3bd03fcafde53217765782369eb7ae2e1afad07d2444b9852560592183b7e1",
    ),
    "program_dag_scalar": (
        "select sum(li.p) from li, o where li.k = o.k and li.d < 20",
        "scalar",
        "1c99e108a0fc0de5bce53711fb511fbd6f0c84b6425ec4639a137ed41d5254de",
    ),
}


@pytest.fixture(scope="module")
def bypass_sess():
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    s.execute("create table li (k bigint, q bigint, p bigint, d bigint, "
              "f text) distribute by shard(k)")
    s.execute("create table o (k bigint, c bigint) distribute by replication")
    s.execute("insert into li values " + ",".join(
        f"({i},{i % 50},{100 + i % 7},{i % 30},'{'AB'[i % 2]}')"
        for i in range(400)
    ))
    s.execute("insert into o values " + ",".join(
        f"({i},{i % 5})" for i in range(0, 400, 2)
    ))
    s.execute("analyze")
    return s


@pytest.mark.parametrize("program", sorted(BYPASSING))
def test_bypassing_programs_lower_to_the_text_they_had(
        bypass_sess, program, monkeypatch):
    """Q1's shape (the scan path's one-hot group reduce, through the
    helpers ops/agg.py now shares with the direct final) and flight 1's
    (``_compile_final``'s scalar branch) lower op for op as before."""
    import hashlib

    from opentenbase_tpu.executor import fused

    sql, mode, digest = BYPASSING[program]
    seen = []
    real = fused.Launcher.__call__

    def call(self, prog, build_args, late=None, **args):
        built = build_args()
        seen.append((prog, built, args))
        return real(self, prog, lambda: built, late=late, **args)

    monkeypatch.setattr(fused.Launcher, "__call__", call)
    bypass_sess.query(sql)
    prog, built, args = seen[-1]
    assert prog.__name__ == program and args["mode"] == mode
    assert "grouping" not in args
    text = prog.lower(*built).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == digest
