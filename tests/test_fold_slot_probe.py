"""The dimension fold's probe by the slot itself (PR 36).

``fused_dag._lookup_dense`` hands back the build side IN KEY ORDER and
the slot a probe row computes (key - base) as that row's index, so a
fold costs one probe-width gather for its match bit and one a gathered
word. Its contract is held against numpy case by case, and the lowered
text of a one-fold program is held to ``1 + W`` probe-width gathers: the
indirection through the sort permutation (``take(sidx, slot)``, a
second probe-width gather a fold, 578 ms at 67.1M rows on the chip)
cannot creep back."""

from __future__ import annotations

import re

import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.executor import fused
from opentenbase_tpu.executor.fused_dag import _lookup_dense, _take_rows


def _case(name: str) -> dict:
    """One fold's inputs: probe keys ``pd`` (validity ``pv``, liveness
    ``pmask``), build keys ``bd`` (``bv``, storage visibility ``bvis``,
    slot validity ``bfull``) and two build columns, one with NULLs."""
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
    if name == "presorted":
        # a fold-prep program's output: key order, dead rows last
        bd = np.concatenate([base + np.arange(cnt), bd[order[cnt:]]])
        bvis = np.arange(nb) < cnt
        bfull = bvis.copy()
        presorted = True
    elif name == "filtered_dimension":
        bfull = bvis & (rng.random(nb) < 0.5)
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
    return dict(pd=pd, pv=pv, pmask=pmask, bd=bd, bv=bv, bvis=bvis,
                bfull=bfull, presorted=presorted, c0=c0, c1=c1, c1v=c1v)


CASES = (
    "storage_order", "presorted", "filtered_dimension",
    "dead_and_null_probe_keys", "probe_keys_out_of_range", "null_build_key",
    "gap", "duplicate", "no_visible_build_row", "empty_build",
)
NOT_DENSE = {"gap", "duplicate"}


def _dev(x):
    return None if x is None else jnp.asarray(x)


@pytest.mark.parametrize("name", CASES)
def test_lookup_dense_against_numpy(name):
    c = _case(name)
    matched, slot, notdense, benv = jax.device_get(_lookup_dense(
        (_dev(c["pd"]), _dev(c["pv"])), _dev(c["pmask"]),
        (_dev(c["bd"]), _dev(c["bv"])), _dev(c["bvis"]), _dev(c["bfull"]),
        [(_dev(c["c0"]), None), (_dev(c["c1"]), _dev(c["c1v"]))],
        presorted=c["presorted"],
    ))
    npr, nb = c["pd"].shape[0], c["bd"].shape[0]
    assert matched.shape == slot.shape == (npr,) and slot.dtype == np.int32
    assert bool(notdense) == (name in NOT_DENSE)
    if name in NOT_DENSE:
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
        assert o1[slot[j]] == c["c1"][i] and o1v[slot[j]] == c["c1v"][i]
    if name == "presorted":
        assert (o0 == c["c0"]).all() and (o1 == c["c1"]).all()
    if name in ("filtered_dimension", "dead_and_null_probe_keys",
                "probe_keys_out_of_range"):
        assert 0 < want.sum() < npr


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


@pytest.mark.parametrize("presorted", [False, True])
@pytest.mark.parametrize("words", [0, 1, 3])
def test_fold_gathers_once_a_word(words, presorted):
    """``1 + W`` gathers at the probe's width, ``1 + W`` at the build's
    to order it, none where a fold-prep program ordered it already."""
    nb, npr = 512, 8192

    def fold(pd, pmask, bd, bvis, bfull, *cols):
        matched, slot, notdense, benv = _lookup_dense(
            (pd, None), pmask, (bd, None), bvis, bfull,
            [(c, None) for c in cols], presorted=presorted,
        )
        return matched, notdense, _take_rows(benv, slot)

    i64, bool_ = jnp.int64, jnp.bool_
    S = jax.ShapeDtypeStruct
    text = jax.jit(fold).lower(
        S((npr,), i64), S((npr,), bool_), S((nb,), i64), S((nb,), bool_),
        S((nb,), bool_), *[S((nb,), jnp.int32)] * words,
    ).as_text(debug_info=False)
    widths = _gather_widths(text)
    assert widths.count(npr) == 1 + words
    assert widths.count(nb) == (0 if presorted else 1 + words)
    assert len(widths) == widths.count(npr) + widths.count(nb)


@pytest.fixture(scope="module")
def star():
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(36)
    s.execute(
        "create table dim (d_key bigint, d_cat int, d_name int) "
        "distribute by replication"
    )
    s.execute(
        "create table fact (f_key bigint, f_val bigint) "
        "distribute by roundrobin"
    )
    s.execute("insert into dim values " + ",".join(
        f"({k},{k % 4},{k % 7})" for k in range(10, 110)
    ))
    s.execute("insert into fact values " + ",".join(
        f"({k},{v})" for k, v in zip(
            rng.integers(0, 130, 1200), rng.integers(1, 100, 1200))
    ))
    return s


@pytest.mark.parametrize("keys,words", [
    ("d_cat", 1), ("d_cat, d_name", 2),
])
def test_one_fold_program_gathers_once_a_word(star, keys, words, monkeypatch):
    """A statement with one folded dimension, as the engine lowers it:
    the match bit and each attribute read after the join are ONE gather
    of the probe's width each; the build side (1,024 padded rows under
    2,048 probe slots) is ordered by as many of its own."""
    seen = []
    real = fused.Launcher.__call__

    def call(self_, prog, build_args, late=None, **args):
        built = build_args()
        seen.append((prog, built))
        return real(self_, prog, lambda: built, late=late, **args)

    q = (
        f"select {keys}, count(*), sum(f_val) from fact, dim "
        f"where f_key = d_key group by {keys} order by {keys}"
    )
    star.execute("set enable_fused_execution = off")
    host = star.query(q)
    star.execute("set enable_fused_execution = on")
    monkeypatch.setattr(fused.Launcher, "__call__", call)
    dev = star.query(q)
    monkeypatch.setattr(fused.Launcher, "__call__", real)
    assert dev == host and len(dev) > 3
    runner = star.cluster.fused_executor()._dag
    assert runner.last_join_modes == ("fold",) and len(seen) == 1
    prog, built = seen[0]
    widths = _gather_widths(prog.lower(*built).as_text(debug_info=False))
    nb, npr = min(widths), max(widths)
    assert nb < npr
    assert widths.count(npr) == 1 + words
    assert widths.count(nb) == 1 + words
    assert len(widths) == 2 * (1 + words)
