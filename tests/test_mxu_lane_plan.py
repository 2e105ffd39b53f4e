"""The MXU group reduce's lane plan (ops/agg.py): limbs sized from the
certified value bounds, only lanes that are read, each distinct argument
once — and the reduction it drives against a numpy int64 group-by."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
from opentenbase_tpu.ops import agg

I32, I64 = np.dtype(np.int32), np.dtype(np.int64)


@pytest.mark.parametrize("bound, nonneg, want", [
    (0, False, (1, True)),
    (2**7 - 1, False, (1, True)),
    (2**7, False, (2, True)),
    (2**15 - 1, False, (2, True)),
    (2**15, False, (3, True)),
    (2**31 - 1, False, (4, True)),
    (2**31, False, (5, True)),
    (2**52 - 1, False, (7, True)),
    (2**52, False, None),  # float interval arithmetic stops being exact
    (None, False, None),
    (2**8 - 1, True, (1, False)),
    (2**8, True, (2, False)),
    (2**16 - 1, True, (2, False)),
    (2**32 - 1, True, (4, False)),
    (2**32, True, (5, False)),
    (10_495_000.0, False, (4, True)),  # a float, as pallas_scan.bound gives
    (10_495_000.0, True, (3, False)),
])
def test_limbs_for_bound(bound, nonneg, want):
    assert agg.limbs_for_bound(bound, nonneg) == want


@pytest.mark.parametrize("rng, want", [
    (None, None),
    ((0, 2), (1, False)),
    ((0, 255), (1, False)),
    ((-1, 255), (2, True)),
    ((-5, 100), (1, True)),
    ((-128, -1), (2, True)),  # sized by |v|: 128 may be +128
    ((-(2**31), 2**31 - 1), (5, True)),
    ((-(2**60), 0), None),
])
def test_limbs_for_range(rng, want):
    assert agg.limbs_for_range(rng) == want


# TPC-H Q1's partial aggregate: two dictionary-coded keys, four sums,
# three averages as (sum, count), count(*)
Q1_SPECS = (
    "sum", "sum", "sum", "sum",
    "sum", "count", "sum", "count", "sum", "count", "count_star",
)
Q1_ARG_IDS = (0, 1, 2, 3, 0, 0, 1, 1, 4, 4, None)
Q1_KEYS = [(I32, False), (I32, False)]
Q1_ARGS = [(I64, False)] * 10 + [None]
EXT = 10_495_000  # SF10's largest l_extendedprice, in cents


def _q1_bounds(ext_nonneg):
    return agg.MxuBounds(
        (agg.limbs_for_range((0, 2)), agg.limbs_for_range((0, 1))),
        Q1_ARG_IDS,
        (
            agg.limbs_for_bound(5000),
            agg.limbs_for_bound(EXT, ext_nonneg),
            agg.limbs_for_bound(EXT * 110.0),
            agg.limbs_for_bound(EXT * 110.0 * 108.0),
            agg.limbs_for_bound(10),
        ),
    )


@pytest.mark.parametrize("bounds, lanes", [
    (_q1_bounds(False), 19),
    (_q1_bounds(True), 18),
    # every bound unknown: two 4-limb keys, five distinct arguments at
    # the dtype's eight limbs, ones — the unread count lanes and the two
    # repeated arguments gone, every limb of every value kept
    (agg.MxuBounds((None, None), Q1_ARG_IDS, (None,) * 5), 49),
    # nothing known, not even which arguments repeat: seven sums
    (None, 65),
], ids=["sf10", "sf10_nonneg_price", "unknown_bounds", "no_bounds"])
def test_q1_lane_count(bounds, lanes):
    plan = agg.mxu_lane_plan(Q1_KEYS, Q1_SPECS, Q1_ARGS, bounds)
    assert len(plan.lanes) == lanes
    assert plan.lanes[plan.ones] == ("ones",)
    assert agg.mxu_lanes_dtype_wide(Q1_KEYS, Q1_SPECS, Q1_ARGS) == 89


def test_count_reads_validity_or_ones_never_limbs():
    plan = agg.mxu_lane_plan(
        [(I32, True)], ("count", "count", "sum"),
        [(I64, True), (I64, False), (I64, True)],
        agg.MxuBounds((None,), (0, 1, 0), (None, None)),
    )
    # key: 4 limbs + validity; x: validity + 8 limbs (its sum); y: none
    assert len(plan.lanes) == 4 + 1 + 1 + 8 + 1
    assert plan.arg_valid[0] is not None and plan.arg_slices[0][1] == 8
    assert plan.arg_valid[1] is None and plan.arg_slices[1] is None


def _reference(keys, vals, specs, mask):
    """numpy int64 group-by: {key tuple: [aggregate per spec]}; a NULL
    key groups as (0, NULL)."""
    kcols = [
        (np.where(v, d, 0) if v is not None else d, v) for d, v in keys
    ]
    out: dict = {}
    for i in np.nonzero(mask)[0]:
        k = tuple(
            (int(d[i]), True if v is None else bool(v[i])) for d, v in kcols
        )
        row = out.setdefault(k, [0] * len(specs))
        for j, (spec, val) in enumerate(zip(specs, vals)):
            if spec == "count_star":
                row[j] += 1
                continue
            d, v = val
            if v is not None and not v[i]:
                continue
            row[j] += int(d[i]) if spec == "sum" else 1
    return out


def _case(name):
    """(keys, vals, specs, bounds) on seeded data; every bounded value
    sits exactly on its bound in some visible row."""
    rng = np.random.default_rng(28)
    n = 9000  # three blocks: the last one padded

    def col(lo, hi, dtype=np.int64):
        x = rng.integers(lo, hi + 1, n).astype(dtype)
        x[:4] = [lo, hi, hi, lo]
        return x

    def key(values, dtype=np.int32):  # few groups, the extremes among them
        return rng.choice(np.asarray(values, dtype=dtype), n)

    valid = rng.random(n) > 0.15
    if name == "signed_with_nulls":
        keys = [(key([-3, 3]), None), (key([0, 1]), valid)]
        x, y = col(-5000, 5000), col(-(2**31 - 1), 2**31 - 1)
        vals = [(x, valid), (y, None), (x, valid), (x, valid), None,
                (y, None)]
        specs = ("sum", "sum", "sum", "count", "count_star", "count")
        bounds = agg.MxuBounds(
            (agg.limbs_for_range((-3, 3)), agg.limbs_for_range((0, 1))),
            (0, 1, 0, 0, None, 1),
            (agg.limbs_for_bound(5000), agg.limbs_for_bound(2**31 - 1)),
        )
    elif name == "nonneg_edges":
        keys = [(key([0, 17, 255]), None)]
        a, b, c = col(0, 255), col(0, 2**16 - 1), col(0, 2**32 - 1)
        vals = [(a, None), (b, None), (c, None), (c, None)]
        specs = ("sum", "sum", "sum", "count")
        bounds = agg.MxuBounds(
            (agg.limbs_for_range((0, 255)),), (0, 1, 2, 2),
            tuple(agg.limbs_for_range((0, h))
                  for h in (255, 2**16 - 1, 2**32 - 1)),
        )
    elif name == "wide_int64_carry":
        keys = [(key([-(2**40), -1, 2**40 - 7], np.int64), None)]
        w = col(-(2**45), 2**45)
        vals = [(w, valid), (w, valid)]
        specs = ("sum", "count")
        bounds = agg.MxuBounds(
            (agg.limbs_for_bound(2**40),), (0, 0),
            (agg.limbs_for_bound(2**45),),
        )
    elif name == "unknown_bounds":
        keys = [(key([-(2**31), 0, 2**31 - 1]), valid)]
        x = col(-(2**48), 2**48)
        z = col(-(2**31), 2**31 - 1, np.int32)
        vals = [(x, None), (z, valid), (z, valid), None]
        specs = ("sum", "sum", "count", "count_star")
        bounds = agg.MxuBounds((None,), (0, 1, 1, None), (None, None))
    elif name == "no_bounds":
        keys = [(key([False, True], np.bool_), None)]
        x = col(-(2**40), 2**40)
        vals = [(x, valid), (x, valid)]
        specs = ("sum", "count")
        bounds = None
    else:
        raise AssertionError(name)
    return keys, vals, specs, bounds, rng.random(n) > 0.2


@pytest.mark.parametrize("name", [
    "signed_with_nulls", "nonneg_edges", "wide_int64_carry",
    "unknown_bounds", "no_bounds",
])
def test_group_reduce_equals_numpy(name):
    keys, vals, specs, bounds, mask = _case(name)
    cap = 64
    to_dev = lambda kv: None if kv is None else (  # noqa: E731
        jnp.asarray(kv[0]), None if kv[1] is None else jnp.asarray(kv[1])
    )

    @jax.jit
    def run(keys, vals, mask):
        slot, _p64, _vis = agg._hash_slot_ids(keys, mask, cap)
        return agg._mxu_group_reduce_impl(
            keys, vals, slot, cap, specs, bounds
        )

    out_keys, out_vals, got, ngroups, collision = jax.device_get(run(
        [to_dev(k) for k in keys], [to_dev(v) for v in vals],
        jnp.asarray(mask),
    ))
    want = _reference(keys, vals, specs, mask)
    assert not bool(collision)
    assert int(ngroups) == len(want)
    have = {}
    for g in np.nonzero(got)[0]:
        k = tuple((int(d[g]), bool(v[g])) for d, v in out_keys)
        have[k] = [int(d[g]) for d, _v in out_vals]
        for (d, v), spec, val, total in zip(
            out_vals, specs, vals, want[k]
        ):
            if spec == "sum" and val[1] is not None:
                # a sum is NULL exactly when no non-NULL value fed it
                nonnull = want[k][specs.index("count")]
                assert bool(v[g]) == (nonnull > 0)
            else:
                assert bool(v[g])
    assert have == want
