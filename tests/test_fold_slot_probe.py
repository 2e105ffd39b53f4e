"""The dimension fold's probe by the slot itself (PR 36), its match bit
riding in a gathered build column (PR 38).

``fused_dag._lookup_dense`` hands back the build side IN KEY ORDER and
the slot a probe row computes (key - base) as that row's index, so a
fold costs a probe-width gather a gathered word. Where the plan reads
an integer column of the dimension after the lookup (a *carrier*), the
match bit rides in that word: a build row that fails the dimension's
filter is marked with the dtype's minimum at the build's width, and the
fold costs ``W`` probe-width gathers; without one (a dimension the plan
only filters by, or one with float columns alone) the bit has a gather
of its own, ``1 + W``. The contract is held against numpy case by case,
and the lowered text of a one-fold program is held to those counts: the
indirection through the sort permutation (``take(sidx, slot)``, 578 ms
at 67.1M rows on the chip) and the bit's own gather beside a carrier
(648 ms) cannot creep back."""

from __future__ import annotations

import re

import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.executor import fused
from opentenbase_tpu.executor.fused_dag import (
    _carrier, _lookup_dense, _take_rows,
)

SENT32, SENT64 = np.iinfo(np.int32).min, np.iinfo(np.int64).min


def _case(name: str) -> dict:
    """One fold's inputs: probe keys ``pd`` (validity ``pv``, liveness
    ``pmask``), build keys ``bd`` (``bv``, storage visibility ``bvis``,
    slot validity ``bfull``), two build columns, one with NULLs, and
    ``read``: the positions of those the plan reads after the lookup
    (none in the cases PR 36 brought: the bit is gathered on its own)."""
    rng = np.random.default_rng(sum(name.encode()))
    nb, npr, base = 96, 700, 40
    cnt = 80  # visible build rows; the rest is padding past ``cnt``
    order = rng.permutation(nb)  # storage order
    bd = np.empty(nb, np.int64)
    bvis = np.zeros(nb, bool)
    bd[order[:cnt]] = base + np.arange(cnt)
    bvis[order[:cnt]] = True
    bd[order[cnt:]] = rng.integers(-5, base + cnt + 5, nb - cnt)  # garbage
    bv = None
    bfull = bvis.copy()
    pd = rng.integers(base, base + cnt, npr).astype(np.int64)
    pv = None
    pmask = np.ones(npr, bool)
    presorted = False
    read: tuple = ()
    f32 = False
    if name in ("presorted", "presorted_carried"):
        # a fold-prep program's output: key order, dead rows last
        bd = np.concatenate([base + np.arange(cnt), bd[order[cnt:]]])
        bvis = np.arange(nb) < cnt
        bfull = bvis.copy()
        presorted = True
        if name == "presorted_carried":
            bfull = bvis & (rng.random(nb) < 0.5)
            read = (0, 1)
    elif name in ("filtered_dimension", "filtered_dimension_carried",
                  "nullable_carrier", "float_only_dimension",
                  "carrier_holds_sent", "sent_on_a_filtered_row"):
        bfull = bvis & (rng.random(nb) < 0.5)
        # (the 64-bit column alone is read: the carrier has NULLs)
        read = {"filtered_dimension": (),
                "nullable_carrier": (1,)}.get(name, (0, 1))
        f32 = name == "float_only_dimension"
    elif name == "dead_and_null_probe_keys":
        pmask = rng.random(npr) < 0.6
        pv = rng.random(npr) < 0.7
    elif name == "probe_keys_out_of_range":
        pd = rng.integers(base - 30, base + cnt + 30, npr).astype(np.int64)
        pd[:4] = [base - 1, base + cnt, -(2**40), 2**40]
    elif name == "null_build_key":
        # the NULL holds the range's last key: the rest is still dense
        bv = np.ones(nb, bool)
        bv[order[cnt - 1]] = False
    elif name == "gap":
        bd[order[cnt // 2]] = base + cnt  # one key moved past the end
    elif name == "duplicate":
        bd[order[3]] = bd[order[4]]
    elif name == "no_visible_build_row":
        bvis[:] = False
        bfull[:] = False
    elif name == "empty_build":
        bd, bvis, bfull = bd[:0], bvis[:0], bfull[:0]
    else:
        assert name == "storage_order", name
    nb = bd.shape[0]
    c0 = rng.integers(-1000, 1000, nb).astype(np.int32)
    c1 = rng.integers(-(2**40), 2**40, nb).astype(np.int64)
    c1v = rng.random(nb) < 0.8
    if f32:  # no integer column: nothing can carry the bit
        c0 = c0.astype(np.float32)
        c1 = c1.astype(np.float32)
    if name == "nullable_carrier":
        # NULLs under rows that pass the filter, their don't-care data
        # words the mark itself: they match all the same
        c1v = np.where(bfull, np.arange(nb) % 3 != 0, c1v)
        c1[~c1v] = SENT64
        assert (bfull & ~c1v).sum() > 5
    elif name == "carrier_holds_sent":
        c0[np.flatnonzero(bfull)[7]] = SENT32
    elif name == "sent_on_a_filtered_row":
        c0[np.flatnonzero(bvis & ~bfull)[3]] = SENT32
    return dict(pd=pd, pv=pv, pmask=pmask, bd=bd, bv=bv, bvis=bvis,
                bfull=bfull, presorted=presorted, c0=c0, c1=c1, c1v=c1v,
                read=read)


CASES = (
    "storage_order", "presorted", "filtered_dimension",
    "dead_and_null_probe_keys", "probe_keys_out_of_range", "null_build_key",
    "gap", "duplicate", "no_visible_build_row", "empty_build",
    # PR 38: the plan reads a column of the dimension after the lookup
    "filtered_dimension_carried", "presorted_carried", "nullable_carrier",
    "float_only_dimension", "carrier_holds_sent", "sent_on_a_filtered_row",
)
# the flag hands the join to another formulation: the keys are no dense
# range, or a row that passes the filter holds the carrier's mark
FLAGGED = {"gap", "duplicate", "carrier_holds_sent"}
# case -> the build column the bit rides in (the others: its own gather)
CARRIER = {
    "filtered_dimension_carried": 0, "presorted_carried": 0,
    "nullable_carrier": 1, "carrier_holds_sent": 0,
    "sent_on_a_filtered_row": 0,
}


def _dev(x):
    return None if x is None else jnp.asarray(x)


@pytest.mark.parametrize("name", CASES)
def test_lookup_dense_against_numpy(name):
    c = _case(name)
    cols = [(_dev(c["c0"]), None), (_dev(c["c1"]), _dev(c["c1v"]))]
    npr, nb = c["pd"].shape[0], c["bd"].shape[0]
    ci = _carrier(cols, c["read"], nb)
    assert ci == CARRIER.get(name)
    matched, slot, flag, benv, word = jax.device_get(_lookup_dense(
        (_dev(c["pd"]), _dev(c["pv"])), _dev(c["pmask"]),
        (_dev(c["bd"]), _dev(c["bv"])), _dev(c["bvis"]), _dev(c["bfull"]),
        cols, presorted=c["presorted"], carrier=ci,
    ))
    assert matched.shape == slot.shape == (npr,) and slot.dtype == np.int32
    assert bool(flag) == (name in FLAGGED)
    if name in FLAGGED:
        return  # the flag hands the join to another formulation
    (o0, o0v), (o1, o1v) = benv
    assert o0v is None and len(o0) == len(o1) == len(o1v) == nb
    breal = c["bvis"] if c["bv"] is None else (c["bvis"] & c["bv"])
    row_of = {int(k): i for i, k in enumerate(c["bd"]) if breal[i]}
    assert len(row_of) == breal.sum()
    live = c["pmask"] if c["pv"] is None else (c["pmask"] & c["pv"])
    want = np.array([
        bool(live[j]) and int(k) in row_of and bool(c["bfull"][row_of[int(k)]])
        for j, k in enumerate(c["pd"])
    ], bool)
    assert (matched == want).all()
    if nb:
        assert ((slot >= 0) & (slot < nb)).all()
    # the slot is the build row's index INTO the returned columns
    for j in np.flatnonzero(want):
        i = row_of[int(c["pd"][j])]
        assert o0[slot[j]] == c["c0"][i]
        assert o1v[slot[j]] == c["c1v"][i]
        # (a NULL's data word is don't-care: the carrier's is zeroed)
        assert o1[slot[j]] == c["c1"][i] or not c["c1v"][i]
    if name == "presorted":
        assert (o0 == c["c0"]).all() and (o1 == c["c1"]).all()
    if name in ("filtered_dimension", "dead_and_null_probe_keys",
                "probe_keys_out_of_range") or ci is not None:
        assert 0 < want.sum() < npr
    if ci is None:
        assert word is None
        return
    # the carrier's word as the probe gathered it IS the joined row's
    # column, and says by itself which rows matched
    data, sent = (c["c0"], SENT32) if ci == 0 else (c["c1"], SENT64)
    assert word.shape == (npr,) and word.dtype == data.dtype
    live = c["pmask"] if c["pv"] is None else (c["pmask"] & c["pv"])
    inr = np.array([int(k) in row_of for k in c["pd"]])
    assert ((word != sent) & live & inr == want).all()
    ok = want if ci == 0 else want & c["c1v"][
        [row_of.get(int(k), 0) for k in c["pd"]]]
    assert (word[ok] == [data[row_of[int(k)]] for k in c["pd"][ok]]).all()
    # the mark is made at the build's width, on the rows the filter drops
    ordered = benv[ci][0]
    dropped = np.array([not c["bfull"][row_of[base_k]] for base_k in
                        sorted(row_of)])
    assert ((ordered[:len(dropped)] == sent) == dropped).all()
    if name == "nullable_carrier":
        assert (want & ~c["c1v"][[row_of.get(int(k), 0)
                                   for k in c["pd"]]]).sum() > 20


def _gather_widths(text: str) -> list:
    """Rows of every gather's result as a lowered program runs them:
    ``jnp.take`` lowers to one private function a signature, so a
    function's gathers count once a call, from ``@main`` down."""
    funcs: dict = {}
    for body in re.split(r"\n\s*func\.func ", text)[1:]:
        name = re.match(r"(?:public |private )?@([\w.]+)", body).group(1)
        funcs[name] = (
            [int(w) for w in re.findall(
                r'"stablehlo\.gather"\(.*?-> tensor<(\d+)x', body)],
            re.findall(r"call @([\w.]+)\(", body),
        )

    def widths(name: str) -> list:
        own, callees = funcs[name]
        return own + [w for c in callees for w in widths(c)]

    return widths("main")


# (words, what carries the bit): the bit's own gather where nothing the
# plan reads can carry it (PR 36's six cases, and float columns), else
# the first 32-bit integer column read, else the first 64-bit one
WORDS_AND_CARRIERS = [
    (0, "own"), (1, "own"), (3, "own"),
    (1, "int32"), (3, "int32"), (1, "int64"), (3, "int64"),
    (3, "int64_then_int32"), (2, "float32"),
]


@pytest.mark.parametrize("presorted", [False, True])
@pytest.mark.parametrize("words,carrier", WORDS_AND_CARRIERS)
def test_fold_gathers_once_a_word(words, carrier, presorted):
    """``max(W, 1)`` gathers at the probe's width where one of the W
    gathered words carries the match bit, ``1 + W`` where the bit has a
    gather of its own; as many at the build's to put it in key order
    (``bfull`` itself is ordered only where it is gathered), none where
    a fold-prep program ordered it already."""
    nb, npr = 512, 8192
    dtypes = {
        "own": [jnp.int32] * words, "int32": [jnp.int32] * words,
        "int64": [jnp.int64] * words, "float32": [jnp.float32] * words,
        "int64_then_int32": [jnp.int64, jnp.float32, jnp.int32],
    }[carrier]
    read = () if carrier == "own" else tuple(range(words))
    chosen = []

    def fold(pd, pmask, bd, bvis, bfull, *cols):
        benv = [(c, None) for c in cols]
        ci = _carrier(benv, read, nb)
        chosen.append(ci)
        matched, slot, flag, benv, word = _lookup_dense(
            (pd, None), pmask, (bd, None), bvis, bfull, benv,
            presorted=presorted, carrier=ci,
        )
        rows = _take_rows(
            [c for i, c in enumerate(benv) if i != ci], slot, "clip")
        return matched, flag, rows, word

    i64, bool_ = jnp.int64, jnp.bool_
    S = jax.ShapeDtypeStruct
    text = jax.jit(fold).lower(
        S((npr,), i64), S((npr,), bool_), S((nb,), i64), S((nb,), bool_),
        S((nb,), bool_), *[S((nb,), dt) for dt in dtypes],
    ).as_text(debug_info=False)
    carried = carrier not in ("own", "float32")
    assert chosen == [
        (2 if carrier == "int64_then_int32" else 0) if carried else None
    ]
    widths = _gather_widths(text)
    bits = 0 if carried else 1
    assert widths.count(npr) == bits + words
    assert widths.count(nb) == (0 if presorted else bits + words)
    assert len(widths) == widths.count(npr) + widths.count(nb)


@pytest.fixture(scope="module")
def star():
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(36)
    s.execute(
        "create table dim (d_key bigint, d_cat int, d_name int, "
        "d_weight float) distribute by replication"
    )
    s.execute(
        "create table fact (f_key bigint, f_val bigint) "
        "distribute by roundrobin"
    )
    s.execute("insert into dim values " + ",".join(
        f"({k},{k % 4},{k % 7},{k % 5}.5)" for k in range(10, 110)
    ))
    s.execute("insert into fact values " + ",".join(
        f"({k},{v})" for k, v in zip(
            rng.integers(0, 130, 1200), rng.integers(1, 100, 1200))
    ))
    return s


def _fused_stat(s, name):
    return int(s.query(
        f"select detail from pg_stat_fused where event = '{name}'"
    )[-1][0])


@pytest.mark.parametrize("keys,where,gathers,bit", [
    ("d_cat", "", 1, "d_cat"), ("d_cat, d_name", "", 2, "d_cat"),
    # a filter on the dimension: the mark is what drops its rows
    ("d_name", " and d_cat <> 1", 1, "d_name"),
    # nothing of the dimension is read after the lookup, or only a
    # float: the bit is gathered on its own, as before PR 38
    ("", " and d_cat = 1", 1, "own"), ("sum(d_weight)", " and d_cat > 0", 2, "own"),
])
def test_one_fold_program_gathers_once_a_word(
        star, keys, where, gathers, bit, monkeypatch):
    """A statement with one folded dimension, as the engine lowers it:
    each attribute read after the join is ONE gather of the probe's
    width and the match bit rides in the first integer one (no gather of
    its own unless there is none); the build side (1,024 padded rows
    under 2,048 probe slots) is ordered by as many of its own. The
    join's record says where the bit came from, and ``pg_stat_fused``
    counts the fold by it, once a traced program."""
    seen = []
    real = fused.Launcher.__call__

    def call(self_, prog, build_args, late=None, **args):
        built = build_args()
        seen.append((prog, built))
        return real(self_, prog, lambda: built, late=late, **args)

    q = (
        f"select {keys}, count(*), sum(f_val) from fact, dim "
        f"where f_key = d_key{where} group by {keys} order by {keys}"
    ) if keys and "(" not in keys else (
        f"select count(*), sum(f_val){keys and ', ' + keys} from fact, dim "
        f"where f_key = d_key{where}"
    )
    star.execute("set enable_fused_execution = off")
    host = star.query(q)
    star.execute("set enable_fused_execution = on")
    star.query("select count(*) from fact, dim where f_key = d_key")
    counts = [_fused_stat(star, "fold_bits_carried"),
              _fused_stat(star, "fold_bits_own")]
    monkeypatch.setattr(fused.Launcher, "__call__", call)
    dev = star.query(q)
    monkeypatch.setattr(fused.Launcher, "__call__", real)
    assert dev == host and len(dev) > (3 if "group by" in q else 0)
    runner = star.cluster.fused_executor()._dag
    assert runner.last_join_modes == ("fold",) and len(seen) == 1
    prog, built = seen[0]
    assert prog.joins == {"join0": f"fold:1024x2048 bit={bit}"}
    counts[bit == "own"] += 1
    assert [_fused_stat(star, "fold_bits_carried"),
            _fused_stat(star, "fold_bits_own")] == counts
    widths = _gather_widths(prog.lower(*built).as_text(debug_info=False))
    nb, npr = min(widths), max(widths)
    assert nb < npr
    assert widths.count(npr) == gathers
    assert widths.count(nb) == gathers
    assert len(widths) == 2 * gathers
    star.query(q)  # a re-bind traces nothing and counts nothing
    assert [_fused_stat(star, "fold_bits_carried"),
            _fused_stat(star, "fold_bits_own")] == counts


def test_a_build_row_holding_the_mark_retries_with_the_fold_off(star):
    """A row that passes the dimension's filter and holds the carrier's
    mark (the dtype's minimum) would read as unmatched: the fold's data
    flag is raised in the program, the runner turns the fold off for
    that join as for a build that is not dense, and the next
    formulation answers exactly what the host executor does."""
    star.execute(
        "create table dim_m (m_key bigint, m_cat int) "
        "distribute by replication"
    )
    star.execute("insert into dim_m values " + ",".join(
        f"({k},{SENT32 if k == 42 else k % 4})" for k in range(10, 110)
    ))
    q = ("select m_cat, count(*), sum(f_val) from fact, dim_m "
         "where f_key = m_key group by m_cat order by m_cat")
    star.execute("set enable_fused_execution = off")
    host = star.query(q)
    star.execute("set enable_fused_execution = on")
    n0 = _fused_stat(star, "fused_statements")
    dev = star.query(q)
    assert dev == host and dev[0][0] == SENT32 and len(dev) == 5
    assert _fused_stat(star, "fused_statements") == n0 + 1
    runner = star.cluster.fused_executor()._dag
    assert "fold" not in runner.last_join_modes
    assert any(0 in off for off in runner._fold_off.values())
    # without that row the same statement folds, its bit in ``m_cat``
    star.execute("delete from dim_m where m_key = 42")
    star.execute("set enable_fused_execution = off")
    host = star.query(q)
    star.execute("set enable_fused_execution = on")
    assert star.query(q) == host and len(host) == 4
