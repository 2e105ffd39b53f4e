"""Measure TPU primitive costs on the real chip (round-4 design input).

Times the primitives that decide the SSB/ClickBench/Q3 engine designs:
gather throughput as a function of table size, narrow-vs-wide sorts,
scatter-add, cumsum, nonzero-compaction, and host->device transfer.

Each timing warms once (compile) then takes best-of-2 with a blocking
fetch, so the dispatch + fetch cost a real query pays is included
exactly once per sample.

One process: it owns the chip for its lifetime and starts no child.
Bound its run time from outside (the chip tool's --timeout).

Writes JSON lines to stdout and a summary dict at the end.

``--only fold_probe`` prices a dimension fold's probe alone, three ways
(PRs 36 and 38): the build side left in storage order and reached
through the sort permutation a probe row (``take(sidx, slot)``, then
the match bit and W columns through it), against the build side put in
key order once at its own width and reached by the slot itself (form
``slot``: 1 + W probe-width gathers), against the same with the match
bit riding in the first gathered word (form ``carried``, W >= 1: rows
that fail the filter marked with INT32_MIN at the build's width, W
gathers at either width); and the ordering as W + 1 gathers by the
permutation against W + 1 payload operands of the build sort. Same
inputs and widths for every form, committed to the chip; a sixteenth
of the probe rows runs first.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def bench(name, fn, *args, reps=2, check=None, **rec):
    """Time ``fn(*args)``; ``check``: called with the first call's
    result; ``rec``: further fields of the printed line."""
    try:
        t_c0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t_c0
        if check is not None:
            check(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        rec = {**rec, "name": name, "best_s": round(best, 5),
               "compile_s": round(compile_s, 1)}
    except Exception as e:  # keep measuring the rest
        rec = {**rec, "name": name, "error": repr(e)[:200]}
    print(json.dumps(rec), flush=True)
    return rec


def fold_probe(dev, probe_rows: int, builds, words) -> None:
    """The fold's probe by permutation and by slot, and the build
    side's ordering by gather and by sort payload."""
    jax.config.update("jax_enable_x64", True)  # the build key is int64
    key = jax.random.PRNGKey(36)

    def total(matched, cols):
        return [jnp.sum(matched, dtype=jnp.int32)] + [
            jnp.sum(jnp.where(matched, c, 0), dtype=jnp.int32) for c in cols
        ]

    def by_permutation(slot, sidx, bfull, cols):
        bidx = jnp.take(sidx, slot)
        return total(jnp.take(bfull, bidx), [jnp.take(c, bidx) for c in cols])

    def by_slot(slot, sidx, bfull, cols):
        # (as fused_dag._lookup_dense gathers: indices known in range)
        bfull = jnp.take(bfull, sidx, mode="clip")
        cols = [jnp.take(c, sidx, mode="clip") for c in cols]
        return total(jnp.take(bfull, slot, mode="clip"),
                     [jnp.take(c, slot, mode="clip") for c in cols])

    def carried(slot, sidx, bfull, cols):
        # (as _lookup_dense with a carrier: the mark before the order
        # gather, the match read off the word the probe gathers anyway)
        sent = jnp.iinfo(jnp.int32).min
        cols = [jnp.where(bfull, cols[0], sent)] + list(cols[1:])
        cols = [jnp.take(c, sidx, mode="clip") for c in cols]
        got = [jnp.take(c, slot, mode="clip") for c in cols]
        return total(got[0] != sent, got)

    def build_sort(bkey, bfull, cols):
        return jax.lax.sort(
            (bkey, jnp.arange(bkey.shape[0], dtype=jnp.int32)), num_keys=1,
            is_stable=False,
        )

    def order_take(bkey, bfull, cols):
        sk, sidx = build_sort(bkey, bfull, cols)
        return [sk, jnp.take(bfull, sidx, mode="clip")] + [
            jnp.take(c, sidx, mode="clip") for c in cols]

    def order_sort(bkey, bfull, cols):
        return jax.lax.sort((bkey, bfull) + tuple(cols), num_keys=1,
                            is_stable=False)

    def on_chip(out):
        # an input that is not committed to the chip is computed on its
        # host, at the host's speed (PRs 30 and 34 each lost calls)
        if {d.platform for d in out[0].devices()} != {dev.platform}:
            raise SystemExit("timed on the host's CPU, not on the chip")

    def timed(name, fn, *args, **rec):
        return bench(name, jax.jit(fn), *args, check=on_chip, **rec).get(
            "best_s", float("inf"))

    for nb in builds:
        perm = jax.random.permutation(key, nb).astype(jnp.int32)
        bkey = perm.astype(jnp.int64) + 1  # dense keys in storage order
        sidx = jnp.argsort(perm).astype(jnp.int32)
        bfull = (perm % 3) != 0
        slot = jax.random.randint(key, (probe_rows,), 0, nb, jnp.int32)
        bkey, sidx, bfull, slot = jax.device_put(
            (bkey, sidx, bfull, slot), dev)
        part = slot[: probe_rows // 16]
        rec = {"case": "fold_probe", "build": nb, "probe": probe_rows}
        timed(f"fold_order_sort_alone_{nb}", build_sort, bkey, bfull, [],
              **rec)
        for w in words:
            cols = jax.device_put(
                [perm * (i + 3) for i in range(w)], dev)
            rec["words"] = w
            for form, fn in (("permutation", by_permutation),
                             ("slot", by_slot), ("carried", carried)):
                if form == "carried" and not w:
                    continue  # no word to ride in
                small = timed(f"fold_probe_{form}_16th_{nb}_w{w}", fn,
                              part, sidx, bfull, cols, **rec)
                if small * 16 <= 8.0:
                    timed(f"fold_probe_{form}_{nb}_w{w}", fn,
                          slot, sidx, bfull, cols, **rec)
            for form, fn in (("take", order_take), ("sort", order_sort)):
                timed(f"fold_order_{form}_{nb}_w{w}", fn, bkey, bfull,
                      cols, **rec)
        del slot, part


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["fold_probe"])
    ap.add_argument("--probe-rows", type=int, default=67_108_864)
    ap.add_argument("--builds", default="32768,524288,1048576,2097152")
    ap.add_argument("--words", default="0,1,2")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    if args.only == "fold_probe":
        fold_probe(dev, args.probe_rows,
                   [int(x) for x in args.builds.split(",")],
                   [int(x) for x in args.words.split(",")])
        print(json.dumps({"name": "done"}), flush=True)
        return
    key = jax.random.PRNGKey(0)

    # --- transfer speed re-check (100MB) ---
    host = np.random.default_rng(0).integers(0, 1 << 30, 25_000_000,
                                             dtype=np.int32)
    t0 = time.perf_counter()
    d = jax.device_put(host, dev)
    jax.block_until_ready(d)
    dt = time.perf_counter() - t0
    print(json.dumps({"name": "transfer_100MB", "best_s": round(dt, 3),
                      "MBps": round(100 / dt, 1)}), flush=True)
    del d, host

    N30, N60 = 30_000_000, 60_000_000

    # --- gather: 30M i32 indices from tables of varying size ---
    for tab in (2_556, 16_384, 200_000, 1_500_000, 15_000_000):
        idx = jax.random.randint(key, (N30,), 0, tab, dtype=jnp.int32)
        table = jnp.arange(tab, dtype=jnp.int32)
        idx, table = jax.device_put((idx, table), dev)
        f = jax.jit(lambda t, i: jnp.sum(t[i], dtype=jnp.int64))
        bench(f"gather_30M_from_{tab}", f, table, idx)
        del idx, table

    # gather 60M from 15M (the Q3 okmask shape)
    idx = jax.random.randint(key, (N60,), 0, 15_000_000, dtype=jnp.int32)
    table = jnp.arange(15_000_000, dtype=jnp.int32)
    f = jax.jit(lambda t, i: jnp.sum(t[i], dtype=jnp.int64))
    bench("gather_60M_from_15M", f, table, idx)
    # gather i8 table (okmask as bytes)
    table8 = (jnp.arange(15_000_000) % 2).astype(jnp.int8)
    f8 = jax.jit(lambda t, i: jnp.sum(t[i].astype(jnp.int32)))
    bench("gather_i8_60M_from_15M", f8, table8, idx)
    del idx, table, table8

    # --- sorts ---
    k32 = jax.random.randint(key, (N30,), 0, 1 << 30, dtype=jnp.int32)
    bench("sort_i32_30M_1op", jax.jit(lambda x: jnp.sort(x)[-1]), k32)
    v32 = jnp.arange(N30, dtype=jnp.int32)
    f2 = jax.jit(lambda k, v: jax.lax.sort((k, v), num_keys=1)[1][-1])
    bench("sort_i32i32_30M", f2, k32, v32)
    v64 = jnp.arange(N30, dtype=jnp.int64)
    f3 = jax.jit(lambda k, v: jax.lax.sort((k, v), num_keys=1)[1][-1])
    bench("sort_i32i64_30M", f3, k32, v64)
    k64 = k32.astype(jnp.int64)
    bench("sort_i64_30M_1op", jax.jit(lambda x: jnp.sort(x)[-1]), k64)
    del k32, v32, v64, k64

    k32 = jax.random.randint(key, (N60,), 0, 1 << 30, dtype=jnp.int32)
    bench("sort_i32_60M_1op", jax.jit(lambda x: jnp.sort(x)[-1]), k32)
    k1 = jax.random.randint(key, (100_000_000,), 0, 1 << 30,
                            dtype=jnp.int32)
    bench("sort_i32_100M_1op", jax.jit(lambda x: jnp.sort(x)[-1]), k1)
    del k1

    # --- scans on 60M ---
    bench("cumsum_i64_60M",
          jax.jit(lambda x: jnp.cumsum(x.astype(jnp.int64))[-1]), k32)
    bench("diff_boundary_60M",
          jax.jit(lambda x: jnp.sum((x[1:] != x[:-1]).astype(jnp.int32))),
          k32)

    # --- scatter-add 30M -> 8k and -> 16M (confirm dead) ---
    idx = jax.random.randint(key, (N30,), 0, 8_000, dtype=jnp.int32)
    w = jnp.ones((N30,), dtype=jnp.int32)

    def scat(i, w):
        return jnp.zeros((8_000,), jnp.int32).at[i].add(w)[0]

    bench("scatter_add_30M_to_8k", jax.jit(scat), idx, w)
    del idx

    # --- one-hot VPU histogram, G=8k, chunked scan (SSB final agg) ---
    keys8k = jax.random.randint(key, (N30,), 0, 8_000, dtype=jnp.int32)
    wts = jax.random.randint(key, (N30,), 0, 10_000, dtype=jnp.int32)

    def onehot_hist(k, w):
        G = 8_192
        CH = 8_192
        iota = jnp.arange(G, dtype=jnp.int32)

        def body(acc, kw):
            kk, ww = kw
            m = (kk[:, None] == iota[None, :])
            return acc + jnp.sum(
                jnp.where(m, ww[:, None], 0).astype(jnp.int64), axis=0
            ), None

        acc0 = jnp.zeros((G,), jnp.int64)
        acc, _ = jax.lax.scan(
            body, acc0,
            (k.reshape(-1, CH), w.reshape(-1, CH)),
        )
        return acc[0]

    bench("onehot_hist_8k_30M", jax.jit(onehot_hist), keys8k, wts)

    # --- nonzero compaction, 30M -> ~4% kept ---
    mask_src = jax.random.randint(key, (N30,), 0, 25, dtype=jnp.int32)

    def compact(m):
        idx = jnp.nonzero(m == 0, size=1_500_000, fill_value=0)[0]
        return idx[-1]

    bench("nonzero_size_30M_4pct", jax.jit(compact), mask_src)

    # --- top_k on 16M (group-capacity topk) ---
    bench("topk10_16M",
          jax.jit(lambda x: jax.lax.top_k(x, 10)[0][0]),
          jax.random.randint(key, (16_000_000,), 0, 1 << 30,
                             dtype=jnp.int32))

    print(json.dumps({"name": "done"}), flush=True)


if __name__ == "__main__":
    main()
