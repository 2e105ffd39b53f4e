"""Price the DAG's direct-addressed grouped reduce on the real chip.

Times ``ops/agg._direct_group_reduce_impl`` alone (one sum and one
count(*) over ``--rows`` rows, most of them dead as after a star
join's filters) for each slot capacity, low-slot width L (1 is the
flat one-hot) and sum dtype asked for, after checking one small case
against numpy on the same device. One process: it owns the chip for
its lifetime and starts no child; bound it from outside.

Writes JSON lines to stdout: ``sixteenth_s`` (a sixteenth of the rows,
run first: a formulation the chip dislikes shows there for a sixteenth
of the price, and its full size is skipped), then ``best_s`` of two
warm calls, each ended by a blocking fetch, and the first call's
``compile_s``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from opentenbase_tpu.ops import agg  # noqa: E402

SPECS = ("sum", "count_star")


def reduce_fn(cap: int, low):
    def fn(v, slot):
        out, got, n = agg._direct_group_reduce_impl(
            [(v, None), None], slot, cap, SPECS, low
        )
        return out[0][0], out[1][0], n

    return jax.jit(fn)


def check(dev) -> None:
    """Full-range int64 sums on this device against numpy."""
    rng = np.random.default_rng(7)
    n, cap = 1 << 20, 4096
    slot = rng.integers(0, cap + 1, n).astype(np.int32)
    v = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    s, c, ng = jax.device_get(
        reduce_fn(cap, None)(*jax.device_put((v, slot), dev))
    )
    live = slot < cap
    want = np.zeros(cap, np.int64)
    np.add.at(want, slot[live], v[live])
    cnt = np.bincount(slot[live], minlength=cap)
    ok = bool((s == want).all() and (c == cnt).all() and ng == (cnt > 0).sum())
    print(json.dumps({"name": "check_int64_4096", "exact": ok}), flush=True)
    if not ok:
        raise SystemExit(1)


def timed(fn, *args) -> tuple:
    """(first call's seconds, best of two warm calls)."""
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    # importing opentenbase_tpu.ops makes the CPU backend the default
    # device: inputs that are not committed to the chip are computed on
    # its host, at the host's speed (PR 30 and PR 34 each lost calls)
    assert {d.platform for d in out[0].devices()} == {
        jax.devices()[0].platform}, "timed on the host's CPU"
    best = float("inf")
    for _ in range(2):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return round(first, 2), round(best, 4)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=67_108_864)
    ap.add_argument("--caps", default="256,4096,8192,65536")
    ap.add_argument("--lows", default="1,32,128")
    ap.add_argument("--dtypes", default="int32")
    ap.add_argument("--give-up-s", type=float, default=4.0,
                    help="skip the full size where a sixteenth of the "
                    "rows says it would take longer than this")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    check(dev)
    key = jax.random.PRNGKey(0)
    with jax.default_device(dev):
        live = jax.random.uniform(key, (args.rows,)) < 0.02
        base = jax.random.randint(key, (args.rows,), 0, 1 << 30, jnp.int32)
    part = args.rows // 16
    for cap, dtype, low in itertools.product(
        map(int, args.caps.split(",")), args.dtypes.split(","),
        map(int, args.lows.split(",")),
    ):
        if low > cap:
            continue
        slot, v = jax.device_put((
            jnp.where(live, base & (cap - 1), cap),
            (base - (1 << 29)).astype(dtype),
        ), dev)
        rec = {"cap": cap, "low": low, "dtype": dtype, "rows": args.rows}
        try:
            fn = reduce_fn(cap, low)
            _first, small = timed(
                fn, *jax.device_put((v[:part], slot[:part]), dev)
            )
            rec["sixteenth_s"] = small
            if small * 16 <= args.give_up_s:
                rec["compile_s"], rec["best_s"] = timed(fn, v, slot)
        except Exception as e:  # keep pricing the rest
            rec["error"] = repr(e)[:300]
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
