"""Pallas TPU kernel: fused scan -> filter -> scalar aggregation.

The hot inner loop of the analytic path (TPC-H Q6 shape): stream columns
HBM -> VMEM in row blocks, evaluate the WHERE predicate, and accumulate
masked SUM/COUNT partials across grid steps into a revisited output
block — one pass over memory with the grid pipeline doing the HBM->VMEM
prefetch. This is the per-DN fragment executor's innermost pass (the
reference's seqscan -> qual -> agg tuple pipeline, nodeSeqscan.c ->
execQual -> nodeAgg.c, recast as a blocked single-pass device kernel).

Numerics. Store columns are int64-scaled decimals, but Pallas TPU compute
is 32-bit. Exactness is kept by CERTIFIED LIMB ACCUMULATION:

- the planner-side certifier (``certify``) walks the typed expression
  tree with per-column |max| statistics and admits a query only when
  every comparison operand and every aggregated value is an
  integer-valued quantity with |v| < 2^24 — exactly representable in
  f32, so predicate evaluation is exact;
- each aggregated value splits into hi/lo limbs (v = 4096*hi + lo);
- a 4096-row block sums each limb exactly in f32 (block total <= 2^24);
- block totals accumulate across grid steps into double-float (hi/lo
  f32) running sums via error-free TwoSum — exact for integer totals to
  ~2^47, beyond any TPC-H aggregate; the engine already plays this
  double-float trick for f64 sort keys (ops/agg.py float_key_parts).

Anything the certifier rejects falls back to the XLA-fused path, so
results are never approximate.

Tested in interpreter mode on CPU (tests/test_pallas_scan.py); on the
chip the benchmark's scan cell runs Q6 through this kernel and Q1, which
the certifier rejects, through the XLA-fused path (PERF.md).
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from opentenbase_tpu import types as t
from opentenbase_tpu.obs.trace import scope
from opentenbase_tpu.plan import texpr as E

BLOCK = 4096  # rows per grid step: limb block sums stay exact (< 2^24)
LIMB = 4096.0  # limb radix: v = hi*LIMB + lo
EXACT = float(1 << 24)  # f32-exact integer bound


class PallasUnsupported(Exception):
    pass


# ---------------------------------------------------------------------------
# Certification: is this expression exactly computable in f32?
# ---------------------------------------------------------------------------

_CMP = {"=", "<>", "!=", "<", "<=", ">", ">="}
_BOOL = {"and", "or"}


def _is_int_type(ty: t.SqlType) -> bool:
    # decimal/date/timestamp are scaled/epoch integers in physical form
    return ty.id in (
        t.TypeId.INT4, t.TypeId.INT8, t.TypeId.BOOL,
        t.TypeId.DECIMAL, t.TypeId.DATE,
    )


def bound(e: E.TExpr, col_bounds: list) -> Optional[float]:
    """Max |value| of an integer-valued numeric expression, or None when
    the expression leaves the certifiable subset (floats, division,
    strings, NULL-able columns are handled by the caller's column gate).
    """
    if isinstance(e, E.Col):
        if not _is_int_type(e.type):
            return None
        return col_bounds[e.index]
    if isinstance(e, E.Const):
        if e.value is None or not _is_int_type(e.type):
            return None
        return abs(float(e.value))
    if isinstance(e, E.CastE):
        # only a cast that keeps the physical value: one between decimal
        # scales (or decimal and integer) multiplies or divides it
        # (ops/expr._cast_data), and compile_f32 passes casts through
        src, dst = e.operand.type, e.type
        if not _is_int_type(dst) or src.decimal_factor != dst.decimal_factor:
            return None
        return bound(e.operand, col_bounds)
    if isinstance(e, E.UnaryE) and e.op == "-":
        return bound(e.operand, col_bounds)
    if isinstance(e, E.BinE) and e.op in ("+", "-", "*"):
        lb = bound(e.left, col_bounds)
        rb = bound(e.right, col_bounds)
        if lb is None or rb is None:
            return None
        return lb * rb if e.op == "*" else lb + rb
    return None


def certify_predicate(e: Optional[E.TExpr], col_bounds: list) -> bool:
    """Predicate certifiable: boolean combinations of comparisons (and
    BETWEEN lowerings) whose operands are bounded integer expressions."""
    if e is None:
        return True
    if isinstance(e, E.BinE):
        if e.op in _BOOL:
            return certify_predicate(e.left, col_bounds) and (
                certify_predicate(e.right, col_bounds)
            )
        if e.op in _CMP:
            lb = bound(e.left, col_bounds)
            rb = bound(e.right, col_bounds)
            return (
                lb is not None and rb is not None
                and lb < EXACT and rb < EXACT
            )
        return False
    if isinstance(e, E.UnaryE) and e.op == "not":
        return certify_predicate(e.operand, col_bounds)
    if isinstance(e, E.InListE):
        lb = bound(e.operand, col_bounds)
        if lb is None or lb >= EXACT:
            return False
        return all(
            isinstance(i, E.Const)
            and i.value is not None and abs(float(i.value)) < EXACT
            for i in e.items
        )
    return False


def decompose_value(e: E.TExpr, col_bounds: list):
    """Split an aggregated value into f32-exact sub-values with host-side
    recombination scales: returns [(fn(blk)->f32, scale)] with every
    sub-value bounded < 2^24, or None when not certifiable.

    The interesting case is a product that overflows 2^24 (TPC-H's
    extendedprice * discount at scaled-decimal precision ~1e8): the wide
    operand X (< 2^24) splits into radix-4096 limbs, giving
    X*Y = 4096*(X_hi*Y) + X_lo*Y with both terms < 2^24 when the narrow
    operand Y is bounded by 4096."""
    b = bound(e, col_bounds)
    if b is not None and b < EXACT:
        return [(compile_f32(e), 1.0)]
    if isinstance(e, E.BinE) and e.op == "*":
        for x, y in ((e.left, e.right), (e.right, e.left)):
            bx, by = bound(x, col_bounds), bound(y, col_bounds)
            if (
                bx is not None and by is not None
                and bx < EXACT and by <= LIMB
            ):
                fx, fy = compile_f32(x), compile_f32(y)

                def hi_term(blk, fx=fx, fy=fy):
                    return jnp.floor(fx(blk) / LIMB) * fy(blk)

                def lo_term(blk, fx=fx, fy=fy):
                    xv = fx(blk)
                    return (xv - jnp.floor(xv / LIMB) * LIMB) * fy(blk)

                return [(hi_term, LIMB), (lo_term, 1.0)]
    return None


# ---------------------------------------------------------------------------
# f32 block compiler for the certified subset
# ---------------------------------------------------------------------------


def compile_f32(e: E.TExpr) -> Callable:
    """TExpr -> fn(blk: list of f32 arrays) for the certified subset.
    Comparisons return bool blocks; arithmetic returns f32 blocks."""
    if isinstance(e, E.Col):
        i = e.index
        return lambda blk: blk[i]
    if isinstance(e, E.Const):
        # plain python float: closing over a jnp array would make the
        # pallas kernel capture a traced constant (disallowed)
        v = float(e.value)
        return lambda blk: jnp.float32(v)
    if isinstance(e, E.CastE):
        return compile_f32(e.operand)
    if isinstance(e, E.UnaryE):
        f = compile_f32(e.operand)
        if e.op == "-":
            return lambda blk: -f(blk)
        if e.op == "not":
            return lambda blk: ~f(blk)
        raise PallasUnsupported(e.op)
    if isinstance(e, E.InListE):
        f = compile_f32(e.operand)
        vals = [float(i.value) for i in e.items]

        def in_list(blk):
            x = f(blk)
            m = x == jnp.float32(vals[0])
            for v in vals[1:]:
                m = m | (x == jnp.float32(v))
            return ~m if e.negated else m

        return in_list
    if isinstance(e, E.BinE):
        lf, rf = compile_f32(e.left), compile_f32(e.right)
        op = e.op
        ops = {
            "+": lambda a, b: a + b,
            "-": lambda a, b: a - b,
            "*": lambda a, b: a * b,
            "=": lambda a, b: a == b,
            "<>": lambda a, b: a != b,
            "!=": lambda a, b: a != b,
            "<": lambda a, b: a < b,
            "<=": lambda a, b: a <= b,
            ">": lambda a, b: a > b,
            ">=": lambda a, b: a >= b,
            "and": lambda a, b: a & b,
            "or": lambda a, b: a | b,
        }
        if op not in ops:
            raise PallasUnsupported(op)
        fn = ops[op]
        return lambda blk: fn(lf(blk), rf(blk))
    raise PallasUnsupported(type(e).__name__)


def inline_projects(e: E.TExpr, project_chain: list) -> E.TExpr:
    """Rewrite an expression over a projected schema into one over the
    scan schema by substituting each Project step's expressions
    bottom-up. ``project_chain``: list of expr tuples, scan-side first."""
    for exprs in reversed(project_chain):
        e = _subst(e, exprs)
    return e


def _subst(e, exprs):
    """``e`` with every Col replaced by ``exprs[index]``; tuples (a
    function's args, a CASE's (cond, value) pairs) are rewritten
    element-wise. What holds no Col comes back as the same object."""
    import dataclasses

    if isinstance(e, E.Col):
        return exprs[e.index]
    if isinstance(e, tuple):
        out = tuple(_subst(x, exprs) for x in e)
        return e if all(a is b for a, b in zip(out, e)) else out
    if isinstance(e, E.TExpr) and dataclasses.is_dataclass(e):
        changes = {}
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, (E.TExpr, tuple)):
                nv = _subst(v, exprs)
                if nv is not v:
                    changes[f.name] = nv
        if changes:
            return dataclasses.replace(e, **changes)
    return e


# ---------------------------------------------------------------------------
# Group-key planning for the grouped kernel
# ---------------------------------------------------------------------------

GROUP_DOMAIN_CAP = 16  # max joint key domain the grouped kernel accepts


def plan_group_keys(
    key_exprs: list, col_ranges: list, cap: int = GROUP_DOMAIN_CAP
):
    """Admit GROUP BY keys into the grouped kernel when every key (after
    project inlining) is a bare column with a small host-known value
    range — TPC-H Q1's (returnflag, linestatus) shape. Returns
    (key_fn, decoders, n_groups):

    - ``key_fn(blk) -> f32`` the joint dense group index in [0, D);
    - ``decoders``: per key, (col_index, min, domain, stride) so the host
      recovers each key value from a joint index (g // stride) % domain;
    - ``n_groups``: the static joint domain D <= cap.

    Raises PallasUnsupported outside this subset (the XLA path handles
    computed keys and large/unknown domains)."""
    decoders = []
    stride = 1
    for e in key_exprs:
        if not isinstance(e, E.Col):
            raise PallasUnsupported("computed group key")
        rng = col_ranges[e.index]
        if rng is None:
            raise PallasUnsupported("unbounded group key")
        lo, hi = rng
        if abs(lo) >= EXACT or abs(hi) >= EXACT:
            # key values themselves must be f32-exact: 2^24 and 2^24+1
            # would collapse to one f32 value and merge two groups
            raise PallasUnsupported("group key beyond f32-exact bound")
        domain = hi - lo + 1
        decoders.append((e.index, lo, domain, stride))
        stride *= domain
        if stride > cap:
            raise PallasUnsupported("group domain too large")
    return key_fn_from_decoders(decoders), decoders, stride


def key_fn_from_decoders(decoders) -> Callable:
    """fn(blk) -> f32 dense joint group index from (col, min, domain,
    stride) decoders (see plan_group_keys)."""

    def key_fn(blk):
        joint = jnp.float32(0.0)
        for idx, lo, _domain, st in decoders:
            joint = joint + (blk[idx] - jnp.float32(lo)) * jnp.float32(st)
        return joint

    return key_fn


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def build_partials(
    n_cols: int,
    mask_fn: Callable,
    val_fns: list,
    block: int = BLOCK,
    interpret: bool = False,
    key_fn: Optional[Callable] = None,
    n_groups: int = 1,
):
    """Build fn(cols: [n] f32 each) -> f32[2, G*Q] device partials, where
    Q = 2*len(val_fns) + 1 accumulated lanes per group: per value its
    hi/lo limb block sums, then the count. Ungrouped aggregation is the
    G=1 case (key_fn None). Row 0 holds the double-float hi parts, row 1
    the lo parts — the whole accumulator updates as one vector
    read-modify-write (Mosaic disallows scalar VMEM stores). The LAST
    input column is the visibility mask (1.0/0.0); padding rows carry 0
    there, so the predicate never sees them.

    Grouped mode: ``key_fn(blk)`` yields the dense joint group index; a
    row outside [0, n_groups) contributes to no group (its equality mask
    never fires) — the planner guarantees in-range keys for live rows."""
    from jax.experimental import pallas as pl

    q_lanes = (2 * len(val_fns) + 1) * n_groups

    def kernel(*refs):
        (*col_refs, acc_ref) = refs
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        blk = [r[...] for r in col_refs]
        live = blk[-1] > 0.5
        m = mask_fn(blk) & live
        vs = []
        if key_fn is None:
            mf = m.astype(jnp.float32)
            for fn in val_fns:
                v = fn(blk) * mf
                v_hi = jnp.floor(v / LIMB)
                vs.append(v_hi)
                vs.append(v - v_hi * LIMB)
            vs.append(mf)
        else:
            key = key_fn(blk)
            vals = [fn(blk) for fn in val_fns]
            for g in range(n_groups):
                mg = (m & (key == jnp.float32(g))).astype(jnp.float32)
                for v in vals:
                    vg = v * mg
                    v_hi = jnp.floor(vg / LIMB)
                    vs.append(v_hi)
                    vs.append(vg - v_hi * LIMB)
                vs.append(mg)
        # (Q, block) -> exact per-lane block totals (each < 2^24)
        b = jnp.sum(jnp.stack(vs), axis=1, dtype=jnp.float32)
        acc = acc_ref[...]
        a_hi, a_lo = acc[0], acc[1]
        # vectorized error-free TwoSum accumulate + renormalize
        s = a_hi + b
        bb = s - a_hi
        err = (a_hi - (s - bb)) + (b - bb)
        lo = a_lo + err
        hi = s + lo
        lo = lo - (hi - s)
        acc_ref[...] = jnp.stack([hi, lo])

    def run(cols):
        n = cols[0].shape[0]
        grid = max((n + block - 1) // block, 1)
        padded = grid * block
        cols_p = [
            jnp.pad(c, (0, padded - n)) if padded != n else c
            for c in cols
        ]
        # the engine runs in global x64 mode, but Mosaic cannot legalize
        # the i64 grid/index scalars that mode produces — this kernel is
        # pure f32/i32, so trace it with x64 off
        with jax.enable_x64(False), scope("scan/kernel"):
            return pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[
                    pl.BlockSpec((block,), lambda i: (i,))
                    for _ in range(n_cols)
                ],
                out_specs=pl.BlockSpec((2, q_lanes), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((2, q_lanes), jnp.float32),
                interpret=interpret,
            )(*cols_p)

    return run


def combine_partials(
    partials: np.ndarray, layout, n_exprs: int, n_groups: int = 1
):
    """[S, 2, G*Q] f32 device partials -> per-shard exact
    (sums int64 [S, G, n_exprs], counts int64 [S, G]); the ungrouped
    G=1 caller squeezes the group axis away.

    ``layout``: per decomposed sub-value, its (expr_index, scale) —
    limb-split products contribute several scaled sub-values to one
    expression's sum. Lane order matches build_partials: per group, per
    sub-value its hi then lo limb lane, then the group's count."""
    p = np.asarray(partials, dtype=np.float64)
    totals = p[:, 0, :] + p[:, 1, :]  # double-float pair -> exact f64
    S = p.shape[0]
    totals = totals.reshape(S, n_groups, -1)  # [S, G, Q]
    sums = np.zeros((S, n_groups, n_exprs), dtype=np.int64)
    for q, (e, scale) in enumerate(layout):
        v = totals[:, :, 2 * q] * LIMB + totals[:, :, 2 * q + 1]
        sums[:, :, e] += np.round(scale * v).astype(np.int64)
    counts = np.round(totals[:, :, -1]).astype(np.int64)
    return sums, counts
