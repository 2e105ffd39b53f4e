"""Device-join differential suite + demotion observability.

Covers the PR-6 join stack end to end, all tier-1 safe on
JAX_PLATFORMS=cpu:

- ops-level byte-parity: the bucket-padded radix hash join
  (ops/join.py radix_* + emit_pairs) against the encode+sort-merge
  formulation over duplicate keys, NULL keys, skewed build sides, and
  empty inputs — identical PAIR SEQUENCES, not just identical sets;
- SQL-level parity: inner/left/semi/anti joins through the host
  executor under OTB_JOIN_MODE=radix vs =sortmerge, and through the
  fused DAG under the join_mode GUC — every path must agree with every
  other, and EXPLAIN must say which formulation answered;
- the Pallas MXU bucket-probe kernel (ops/pallas_join.py) in
  interpreter mode against the XLA probe;
- the spill-aware batch planner's sizing and multi-pass splitting
  (plan/batchplan.py + fused_dag._lookup_radix);
- the shape rule of ``join_mode = auto``: a radix table only where it is
  dimension-sized (pallas_join.eligible), sort-merge above that;
- the emit_pairs int32->int64 offset overflow fix;
- demotion observability: a pallas->XLA demotion emits a warning into
  pg_cluster_logs and moves the otb_pallas_demotions_total exporter
  counter; otb_device_platform renders on every scrape.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.ops import filter as filt_ops
from opentenbase_tpu.ops import join as join_ops
from opentenbase_tpu.plan import batchplan


# ---------------------------------------------------------------------------
# ops-level byte parity
# ---------------------------------------------------------------------------


def _sort_path(bk, breal, pk, preal):
    bids, pids = join_ops.encode_keys(
        [(jnp.asarray(bk), jnp.asarray(breal))],
        [(jnp.asarray(pk), jnp.asarray(preal))],
        None, None,
    )
    return join_ops.match_counts(bids, pids)


def _radix_path(bk, breal, pk, preal):
    plan = batchplan.plan_radix_join(
        len(bk), len(pk), batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    # the planner declines an empty build (production falls back to the
    # sort path there); the table itself handles nb=0 — probe it anyway
    partitions, bucket = (
        (plan.partitions, plan.bucket) if plan is not None else (1, 8)
    )
    for _ in range(3):
        bo, lo, cnt, tot, ovf = join_ops.radix_match_counts(
            jnp.asarray(bk), jnp.asarray(breal),
            jnp.asarray(pk), jnp.asarray(preal),
            partitions, bucket,
        )
        if not bool(ovf):
            return bo, lo, cnt, tot
        bucket *= 4
    raise AssertionError("radix table overflowed at 16x quantum")


def _pairs(build_order, lo, counts, total, outer=False):
    out = filt_ops.bucket_size(max(int(total) + len(np.asarray(counts)), 1))
    pi, bi, m, v = join_ops.emit_pairs(
        build_order, lo, counts, out, outer
    )
    keep = np.asarray(v)
    return list(zip(
        np.asarray(pi)[keep].tolist(),
        np.asarray(bi)[keep].tolist(),
        np.asarray(m)[keep].tolist(),
    ))


SCENARIOS = {
    "duplicates": lambda r: (
        np.repeat(r.integers(-50, 50, 60), 3).astype(np.int64),
        np.ones(180, bool),
        r.integers(-60, 60, 700).astype(np.int64),
        np.ones(700, bool),
    ),
    "null_keys": lambda r: (
        r.integers(0, 40, 120).astype(np.int64),
        r.random(120) > 0.3,
        r.integers(0, 40, 500).astype(np.int64),
        r.random(500) > 0.3,
    ),
    "skewed_build": lambda r: (
        np.concatenate([
            np.zeros(150, np.int64),  # one hot key
            r.integers(10**9, 10**12, 50),
        ]).astype(np.int64),
        np.ones(200, bool),
        np.concatenate([
            np.zeros(400, np.int64),
            r.integers(10**9, 10**12, 200),
        ]).astype(np.int64),
        np.ones(600, bool),
    ),
    "empty_build": lambda r: (
        np.zeros(0, np.int64), np.zeros(0, bool),
        r.integers(0, 10, 100).astype(np.int64), np.ones(100, bool),
    ),
    "empty_probe": lambda r: (
        r.integers(0, 10, 100).astype(np.int64), np.ones(100, bool),
        np.zeros(0, np.int64), np.zeros(0, bool),
    ),
    "all_dead": lambda r: (
        r.integers(0, 10, 50).astype(np.int64), np.zeros(50, bool),
        r.integers(0, 10, 50).astype(np.int64), np.zeros(50, bool),
    ),
    "wide_values": lambda r: (
        r.integers(-2**62, 2**62, 300).astype(np.int64),
        np.ones(300, bool),
        r.integers(-2**62, 2**62, 300).astype(np.int64),
        np.ones(300, bool),
    ),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
@pytest.mark.parametrize("outer", [False, True])
def test_radix_byte_equals_sort_path(name, outer):
    rng = np.random.default_rng(hash(name) % 2**31)
    bk, breal, pk, preal = SCENARIOS[name](rng)
    # overlap half the probe keys with build keys so matches exist
    if len(pk) and len(bk):
        take = rng.integers(0, len(bk), len(pk) // 2)
        pk = pk.copy()
        pk[: len(take)] = bk[take]
    ref = _sort_path(bk, breal, pk, preal)
    got = _radix_path(bk, breal, pk, preal)
    assert int(ref[3]) == int(got[3])
    assert _pairs(*ref, outer=outer) == _pairs(*got, outer=outer)
    # semi/anti derive from counts alone: dead probe rows never match
    ref_has = (np.asarray(ref[2]) > 0) & preal
    got_has = (np.asarray(got[2]) > 0) & preal
    assert np.array_equal(ref_has, got_has)


def test_emit_pairs_int64_offsets():
    # three probe rows each claiming 2^30 matches: int32 cumsum wraps
    # negative at the third prefix (3*2^30 > 2^31), scrambling every
    # lane's probe_idx; int64 offsets keep the mapping exact
    counts = jnp.asarray(np.full(3, 2**30, np.int32))
    lo = jnp.zeros(3, jnp.int32)
    build_order = jnp.zeros(8, jnp.int32)
    pi, bi, m, v = join_ops.emit_pairs(build_order, lo, counts, 16)
    assert np.asarray(pi).tolist() == [0] * 16  # all lanes in row 0's run
    assert bool(np.asarray(m).all()) and bool(np.asarray(v).all())


# ---------------------------------------------------------------------------
# Pallas MXU bucket probe (interpreter mode)
# ---------------------------------------------------------------------------


def test_pallas_probe_matches_xla_probe():
    from opentenbase_tpu.ops import pallas_join as pj

    rng = np.random.default_rng(7)
    nb, npr = 1500, 4000
    bk = (rng.permutation(np.arange(5000))[:nb] * 9 - 10**10).astype(
        np.int64
    )
    breal = rng.random(nb) > 0.1
    pk = np.concatenate([
        bk[rng.integers(0, nb, npr - 300)],
        rng.integers(-(10**14), 10**14, 300),
    ]).astype(np.int64)
    preal = rng.random(npr) > 0.1
    plan = batchplan.plan_radix_join(
        nb, npr, batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    assert pj.eligible(nb, plan.partitions, plan.bucket)
    tk, tv, ti, dup, ovf = join_ops.build_radix_table(
        jnp.asarray(bk), jnp.asarray(breal), plan.partitions, plan.bucket
    )
    assert not bool(dup) and not bool(ovf)
    m_x, b_x = join_ops.probe_radix_first(
        tk, tv, ti, jnp.asarray(pk), jnp.asarray(preal),
        plan.partitions, plan.bucket,
    )
    m_p, b_p = pj.probe_radix_pallas(
        tk, tv, ti, jnp.asarray(pk), jnp.asarray(preal),
        plan.partitions, plan.bucket, interpret=True,
    )
    m_x = np.asarray(m_x)
    assert m_x.any(), "probe must actually hit"
    assert np.array_equal(m_x, np.asarray(m_p))
    assert np.array_equal(np.asarray(b_x)[m_x], np.asarray(b_p)[m_x])


# ---------------------------------------------------------------------------
# spill-aware batch planner
# ---------------------------------------------------------------------------


def test_batchplan_sizing_and_passes():
    p = batchplan.plan_radix_join(1_000_000, 10_000_000, 4_000_000_000)
    assert p.passes == 1 and p.partitions & (p.partitions - 1) == 0
    assert p.bucket % batchplan.RADIX_BUCKET_QUANTUM == 0
    # tighter budget: the SAME build side splits into multi-pass probes
    tight = batchplan.plan_radix_join(10_000_000, 50_000_000, 500_000_000)
    assert tight is not None and tight.passes > 1
    assert tight.table_bytes <= 500_000_000 // batchplan.RADIX_TABLE_FRACTION
    # hopeless budget: no plan — caller keeps sort-merge
    assert batchplan.plan_radix_join(10**9, 10**9, 1_000_000) is None
    assert batchplan.plan_radix_join(0, 100, 10**9) is None


def test_resolve_budget_precedence(monkeypatch):
    """The device_memory_limit GUC over the op's constant, and nothing
    else: a budget in the process environment changes no answer."""
    assert batchplan.resolve_budget(0, 42) == 42
    assert batchplan.resolve_budget(99, 42) == 99
    for name in ("EXCHANGE_HBM", "SCAN_HBM", "RADIX_HBM", "DAG_WINDOW"):
        monkeypatch.setenv(f"OTB_{name}_BUDGET", "77")
    assert batchplan.resolve_budget(0, 42) == 42
    assert batchplan.resolve_budget(99, 42) == 99


def test_multipass_lookup_radix_matches_single_table():
    from opentenbase_tpu.executor.fused_dag import _lookup, _lookup_radix

    rng = np.random.default_rng(3)
    nb, npr = 4000, 9000
    bk = (rng.permutation(np.arange(20000))[:nb]).astype(np.int64)
    pk = np.concatenate([
        bk[rng.integers(0, nb, npr - 500)],
        rng.integers(30000, 60000, 500),
    ]).astype(np.int64)
    bmask = jnp.asarray(rng.random(nb) > 0.2)
    pmask = jnp.asarray(rng.random(npr) > 0.2)
    bkp = (jnp.asarray(bk), None)
    pkp = (jnp.asarray(pk), None)
    want = _lookup(pkp, pmask, bkp, bmask, check_dup=True)
    # budget tiny enough to force several build chunks, big enough to
    # admit a plan
    plan = None
    budget = 37_500
    while plan is None:
        budget *= 2
        plan = batchplan.plan_radix_join(nb, npr, budget)
    assert plan.passes > 1, plan
    got = _lookup_radix(pkp, pmask, bkp, bmask, budget, _lookup)
    assert not bool(got[2]) and not bool(want[2])
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    m = np.asarray(want[0])
    assert np.array_equal(
        np.asarray(want[1])[m], np.asarray(got[1])[m]
    )


# ---------------------------------------------------------------------------
# the shape rule: a radix table only where it is dimension-sized
# ---------------------------------------------------------------------------

RADIX_BOUND = 65_536  # widest padded build pallas_join.eligible admits


def _shape_rule_case(nb: int, npr: int, seed: int = 32):
    """Sparse int64 keys with NULLs and dead rows on both sides and one
    live duplicate build key: (pk, pmask, bk, bmask, dup key)."""
    rng = np.random.default_rng(seed + nb)
    bk = rng.permutation(np.arange(-nb, 3 * nb, dtype=np.int64))[:nb]
    bvalid = rng.random(nb) > 0.05
    bmask = rng.random(nb) > 0.2
    bvalid[:2] = bmask[:2] = True
    bk[1] = bk[0]  # the duplicate: two live, non-NULL build rows
    pk = np.where(
        rng.random(npr) < 0.7, bk[rng.integers(0, nb, npr)],
        rng.integers(4 * nb, 8 * nb, npr),
    ).astype(np.int64)
    pvalid = rng.random(npr) > 0.05
    pmask = rng.random(npr) > 0.2
    return (
        (jnp.asarray(pk), jnp.asarray(pvalid)), jnp.asarray(pmask),
        (jnp.asarray(bk), jnp.asarray(bvalid)), jnp.asarray(bmask),
        int(bk[0]),
    )


def _lowered_radix(nb: int, npr: int, forced: bool):
    """(noted modes, StableHLO text) of ``_lookup_radix`` at the static
    widths, lowered and not run."""
    import jax

    from opentenbase_tpu.executor.fused_dag import _lookup, _lookup_radix

    noted: list = []

    def note(mode, sized_out=False):
        noted.append((mode, sized_out))

    def fn(pd, pv, pm, bd, bv, bm):
        return _lookup_radix(
            (pd, pv), pm, (bd, bv), bm,
            batchplan.DEFAULT_EXCHANGE_BUDGET, _lookup,
            note_mode=note, forced=forced,
        )

    def arg(n, dt):
        return jax.ShapeDtypeStruct((n,), dt)

    text = jax.jit(fn).lower(
        arg(npr, jnp.int64), arg(npr, jnp.bool_), arg(npr, jnp.bool_),
        arg(nb, jnp.int64), arg(nb, jnp.bool_), arg(nb, jnp.bool_),
    ).as_text()
    return noted, text


@pytest.mark.parametrize("nb", [4_096, 65_536, 131_072, 1 << 21])
def test_auto_builds_a_radix_table_only_where_dimension_sized(nb):
    """Under ``auto`` ``_lookup_radix`` answers as ``_lookup`` does at
    every width; at or under the bound it notes ``radix`` and lowers a
    table, above it it notes ``merge`` (sized out by the shape rule, not
    by the budget) and the lowered program holds no ``P*B+1`` operand."""
    from opentenbase_tpu.executor.fused_dag import _lookup, _lookup_radix

    npr = 4 * nb
    plan = batchplan.plan_radix_join(
        nb, npr, batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    assert plan is not None and plan.passes == 1
    table = f"tensor<{plan.partitions * plan.bucket + 1}x"
    noted, text = _lowered_radix(nb, npr, forced=False)
    if nb <= RADIX_BOUND:
        assert noted == [("radix", False)], noted
        assert table in text
    else:
        assert noted == [("merge", True)], noted
        assert table not in text
    pk, pmask, bk, bmask, dupkey = _shape_rule_case(nb, npr)
    want = _lookup(pk, pmask, bk, bmask, check_dup=True)
    got = _lookup_radix(
        pk, pmask, bk, bmask, batchplan.DEFAULT_EXCHANGE_BUDGET, _lookup
    )
    # the duplicate build key raises the flag in either formulation
    assert bool(want[2]) and bool(got[2])
    wm, gm = np.asarray(want[0]), np.asarray(got[0])
    assert wm.any() and np.array_equal(wm, gm)
    # which of the two duplicates a probe row names is the retry's to
    # settle: every other matched row names the same build row
    sure = wm & (np.asarray(pk[0]) != dupkey)
    assert np.array_equal(np.asarray(want[1])[sure], np.asarray(got[1])[sure])


@pytest.mark.parametrize("nb,npr", [(2, 5), (700, 300), (4_096, 16_384)])
@pytest.mark.parametrize("check_dup", [True, False])
def test_sortmerge_lookup_equals_sorted_lookup(nb, npr, check_dup):
    """The TPU's double-sort formulation (no gather: one ``cummax``
    carries the build row's run and position) answers as the
    ``searchsorted`` one does: NULLs and dead rows on both sides, the
    full int64 key range, with and without a duplicate build key."""
    from opentenbase_tpu.executor.fused_dag import (
        _lookup, _lookup_sortmerge,
    )

    pk, pmask, bk, bmask, dupkey = _shape_rule_case(nb, npr)
    if nb > 2:
        # keys at both ends of int64 on both sides
        ends = jnp.asarray([-(2**63), 2**63 - 1], jnp.int64)
        bk = (bk[0].at[-2:].set(ends), bk[1].at[-2:].set(True))
        bmask = bmask.at[-2:].set(True)
        pk = (pk[0].at[-2:].set(ends), pk[1].at[-2:].set(True))
        pmask = pmask.at[-2:].set(True)
    for dup in (True, False):
        if not dup:  # break the duplicate: a fresh key
            bk = (bk[0].at[1].set(2**40), bk[1])
        want = _lookup(pk, pmask, bk, bmask, check_dup=check_dup)
        got = _lookup_sortmerge(pk, pmask, bk, bmask, check_dup=check_dup)
        assert bool(got[2]) == bool(want[2]) == (dup and check_dup)
        wm, gm = np.asarray(want[0]), np.asarray(got[0])
        assert np.array_equal(wm, gm)
        assert wm.any() or nb == 2
        sure = wm & ((np.asarray(pk[0]) != dupkey) | (not dup))
        assert np.array_equal(
            np.asarray(want[1])[sure], np.asarray(got[1])[sure])
        assert int(np.asarray(got[1]).min()) >= 0
        assert int(np.asarray(got[1]).max()) < nb


def test_forced_radix_builds_the_table_above_the_bound():
    """``join_mode = radix`` keeps what it names: past the bound the
    table is still lowered (probed by the XLA probe), nothing is sized
    out."""
    nb, npr = 131_072, 524_288
    plan = batchplan.plan_radix_join(
        nb, npr, batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    noted, text = _lowered_radix(nb, npr, forced=True)
    assert noted == [("radix", False)], noted
    assert f"tensor<{plan.partitions * plan.bucket + 1}x" in text


def test_the_bound_is_the_pallas_kernels_reach():
    """The rule's bound is ``pallas_join.eligible`` on the planner's
    table: flight 1's 2,556 dates and a 65,536-row build are
    dimension-sized, 131,072 rows and SF30's 2^21 customers a chip are
    not."""
    from opentenbase_tpu.ops import pallas_join as pj

    def sized(nb):
        p = batchplan.plan_radix_join(
            nb, 4 * nb, batchplan.DEFAULT_EXCHANGE_BUDGET
        )
        return pj.eligible(-(-nb // p.passes), p.partitions, p.bucket)

    assert [sized(n) for n in (2_556, RADIX_BOUND, 131_072, 1 << 21)] == [
        True, True, False, False]


# ---------------------------------------------------------------------------
# SQL-level parity: host executor + fused DAG, all four join types
# ---------------------------------------------------------------------------


QUERIES = [
    # inner with duplicates on the probe side + NULL keys
    "select d.name, sum(f.v) from f, d where f.k = d.k "
    "group by d.name order by d.name",
    # left outer with NULL-extended rows
    "select d.k, f.v from d left join f on d.k = f.k "
    "order by d.k, f.v",
    # semi
    "select count(*) from f where f.k in (select k from d)",
    # anti
    "select count(*) from f where not exists "
    "(select 1 from d where d.k = f.k)",
]


@pytest.fixture(scope="module")
def join_cluster():
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute(
        "create table d (k bigint, name int) distribute by roundrobin"
    )
    s.execute(
        "create table f (k bigint, v bigint) distribute by roundrobin"
    )
    rng = np.random.default_rng(11)
    dvals = []
    for i in range(60):
        k = "null" if i % 13 == 0 else i * 7 + 3  # sparse, some NULLs
        dvals.append(f"({k}, {i})")
    s.execute("insert into d values " + ",".join(dvals))
    fvals = []
    for i in range(2500):
        k = "null" if i % 17 == 0 else int(rng.integers(0, 75)) * 7 + 3
        fvals.append(f"({k}, {i})")
    s.execute("insert into f values " + ",".join(fvals))
    s.execute("analyze")
    yield c
    for sess in list(c.sessions):
        sess.close()


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_sql_parity_host_and_fused(join_cluster, qi, monkeypatch):
    q = QUERIES[qi]
    s = join_cluster.session()
    results = {}
    # host executor, both formulations forced via the env knob
    s.execute("set enable_fused_execution = off")
    for mode in ("radix", "sortmerge"):
        monkeypatch.setenv("OTB_JOIN_MODE", mode)
        results[f"host:{mode}"] = s.query(q)
    monkeypatch.delenv("OTB_JOIN_MODE", raising=False)
    # fused DAG, both formulations forced via the GUC
    s.execute("set enable_fused_execution = on")
    for mode in ("radix", "sortmerge"):
        s.execute(f"set join_mode = {mode}")
        results[f"fused:{mode}"] = s.query(q)
    want = results["host:sortmerge"]
    for label, got in results.items():
        assert got == want, (label, got[:5], want[:5])
    s.close()


def test_explain_shows_join_mode(join_cluster):
    s = join_cluster.session()
    q = QUERIES[0]
    s.execute("set join_mode = radix")
    s.execute("set enable_fused_execution = on")
    s.query(q)  # ensure compiled
    lines = [r[0] for r in s.query(f"explain analyze {q}")]
    fused = [ln for ln in lines if "Fused join modes:" in ln]
    if fused:  # device DAG answered
        assert "radix" in fused[0], lines
    s.execute("set enable_fused_execution = off")
    os.environ["OTB_JOIN_MODE"] = "radix"
    try:
        lines = [r[0] for r in s.query(f"explain analyze {q}")]
    finally:
        os.environ.pop("OTB_JOIN_MODE", None)
    joins = [
        ln for ln in lines
        if ln.strip().startswith("Join") and "rows=" in ln
    ]
    assert joins and any("(radix)" in ln for ln in joins), lines
    s.close()


def test_fused_radix_flag_degrades_to_sortmerge(join_cluster):
    """Duplicate build keys under forced radix: the flag machinery must
    disable the radix table for that join and re-answer via sort-merge
    (then flip orientation if needed) — never a wrong result."""
    c = join_cluster
    s = c.session()
    s.execute(
        "create table dupd (k bigint, g int) distribute by roundrobin"
    )
    s.execute("insert into dupd values " + ",".join(
        f"({i % 8}, {i})" for i in range(64)  # every key duplicated
    ))
    s.execute("analyze")
    q = ("select count(*) from f, dupd where f.k = dupd.k")
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    s.execute("set join_mode = radix")
    assert s.query(q) == want
    s.close()


def test_cached_radix_program_keeps_its_flag_handling(join_cluster):
    """A radix program found in the program cache still answers a
    raised flag as a radix table's (table off, sort-merge re-derives the
    verdict): the record of which joins it built a table for is the
    cached program's, not the fresh closure's."""
    c = join_cluster
    s = c.session()
    s.execute(
        "create table latedup (k bigint, g int) distribute by roundrobin"
    )
    s.execute("insert into latedup values " + ",".join(
        f"({i * 7 + 3}, {i})" for i in range(40)
    ))
    s.execute("analyze")
    q = "select count(*), max(latedup.g) from f, latedup where f.k = latedup.k"
    s.execute("set enable_fused_execution = on")
    s.execute("set join_mode = radix")
    s.query(q)  # the radix program is compiled and cached
    s.execute("insert into latedup values (10, 100), (10, 101)")
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    s.execute("set trace_queries = on")
    assert s.query(q) == want
    s.execute("set trace_queries = off")
    tr = next(t for t in reversed(c.tracer.last(4)) if t.query == q)
    final = [
        sp for sp in tr.spans
        if sp.name in ("fused.bind", "fused.launch")
        and sp.args["frag"] == "final"
    ]
    assert final[0].args["cache"] == "hit"
    assert final[0].args["joins"].startswith("join0=radix:")
    assert [sp.args.get("reason") for sp in final
            if sp.name == "fused.launch"][:2] == [
        None, "join0 radix overflow or dup: radix off"]
    s.close()


def test_sized_out_join_flag_flips_sides_without_a_radix_retry(
    join_cluster, monkeypatch
):
    """Duplicate build keys on a join the shape rule sent to sort-merge:
    the flag is a sort-merge join's (flip the build side), not a radix
    table's — no retry is spent disabling a table that never was."""
    from opentenbase_tpu.ops import pallas_join

    c = join_cluster
    s = c.session()
    s.execute(
        "create table dupw (k bigint, g int) distribute by roundrobin"
    )
    s.execute("insert into dupw values " + ",".join(
        f"({i % 8}, {i})" for i in range(64)  # every key duplicated
    ))
    s.execute("analyze")
    # (a statement shape of its own: what the runner remembers of a
    # join's failed formulations is keyed by the plan's structure)
    q = "select count(*), min(dupw.g) from f, dupw where f.k = dupw.k"
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    s.execute("set join_mode = auto")
    fx = c.fused_executor()
    # every table is past a bound of no partitions at all
    monkeypatch.setattr(pallas_join, "MAX_PARTITIONS", 0)
    sized_out = fx.radix_sized_out
    radix_off = dict(fx._dag._radix_off) if fx._dag is not None else {}
    s.execute("set trace_queries = on")
    assert s.query(q) == want
    s.execute("set trace_queries = off")
    tr = next(t for t in reversed(c.tracer.last(4)) if t.query == q)
    reasons = [
        sp.args["reason"] for sp in tr.spans
        if sp.name == "fused.launch" and "reason" in sp.args
    ]
    assert fx.radix_sized_out == sized_out + 1, reasons
    assert reasons == [
        "join0 fold density flag: fold off",
        "join0 duplicate build keys: flip sides",
    ]
    assert fx._dag._radix_off == radix_off
    s.close()


# ---------------------------------------------------------------------------
# demotion observability (logs + exporter)
# ---------------------------------------------------------------------------


def test_pallas_demotion_is_loud(tmp_path):
    import socket

    from opentenbase_tpu.obs.exporter import scrape

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    mport = probe.getsockname()[1]
    probe.close()
    d = tmp_path / "cn"
    d.mkdir()
    # the exporter listener opens from the conf file at cluster start
    (d / "opentenbase.conf").write_text(f"metrics_port = {mport}\n")
    c = Cluster(num_datanodes=1, shard_groups=16, data_dir=str(d))
    s = c.session()
    fx = c.fused_executor()
    assert fx is not None

    def counter(body, name):
        for ln in body.splitlines():
            if ln.startswith(name) and not ln.startswith("#"):
                return float(ln.rpartition(" ")[2])
        return None

    b1 = scrape("127.0.0.1", mport)
    assert "otb_device_platform" in b1
    c1 = counter(b1, "otb_pallas_demotions_total")
    assert c1 is not None
    try:
        raise RuntimeError("synthetic mosaic lowering failure")
    except RuntimeError:
        fx._note_pallas_failure(("pallas", "test-kernel"))
    b2 = scrape("127.0.0.1", mport)
    assert counter(b2, "otb_pallas_demotions_total") == c1 + 1
    logs = s.query("select pg_cluster_logs('warning')")
    msgs = [r[4] for r in logs if r[3] == "device"]
    assert any("demoted to XLA" in m for m in msgs), logs[-5:]
    s.close()
