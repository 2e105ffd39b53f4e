#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, from the client's side of
the wire.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip and starts no child. It builds the deployment
the cell's configuration file describes (durable ``Cluster`` behind a
``ClusterServer``, as ``cli/otb_server.py``), generates the data from
``--seed``, loads it, warms every program the cell's traffic uses, drives
the window with a ``net/client.connect_tcp`` client, frees the engine,
compares the timed statements' rows with the data
set's plain reference, prints one JSON line last and exits.

It prints no result when there is no TPU (or fewer chips than the cell
asks for), when a timed statement was answered by the host executor, or
when anything compiled inside the window.

``--rehearse <rows>`` runs the whole command at ``rows`` lineitem-scale
rows on whatever backend JAX has (the CPU here), to find wrong paths.
Its line is labelled ``rehearsal``, carries no metric and never says
``correct: true``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import (  # noqa: E402
    client_loop, compare, layer_metrics, loader, stats, trace_reduce,
    traffic, views, work,
)

TIME_LIMIT_S = 1150.0
REFERENCE_THREADS = 6
ROWS_PER_SF = 6_000_000  # both sources scale their fact table so


class RunFailure(Exception):
    """The run may not report: no chip, host answered, compiled in window."""


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def read_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(entries: list, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


class CompileWatch:
    """Counts what JAX compiles, loads from its cache or traces while
    ``armed``: inside the window all three must stay at nought."""

    def __init__(self, jax):
        import jax.monitoring as monitoring

        self.armed = False
        self.window: dict = {}
        self.total: dict = {}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _note(self, name: str) -> None:
        if "compil" not in name:
            return
        self.total[name] = self.total.get(name, 0) + 1
        if self.armed:
            self.window[name] = self.window.get(name, 0) + 1

    def _event(self, name, **_kw):
        self._note(name)

    def _duration(self, name, _secs, **_kw):
        self._note(name)


def tree_bytes(path: str) -> int:
    """Bytes of the files under ``path`` (what a run left on disk)."""
    return sum(
        os.path.getsize(os.path.join(d, f))
        for d, _dirs, files in os.walk(path) for f in files
    )


def device_record(devices) -> dict:
    peaks = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def check_rows(done: list, data, exact: bool = True) -> list:
    """Compare every timed statement with the reference, computed once
    per distinct (kind, parameters)."""
    refs: dict = {}
    out = []
    with ThreadPoolExecutor(REFERENCE_THREADS) as pool:
        for s in done:
            if s["error"]:
                out.append({"wrong": s["error"], "sum_gap": 0.0,
                            "avg_gap": 0.0})
                continue
            key = (s["kind"], json.dumps(s["params"], sort_keys=True))
            if key not in refs:
                refs[key] = data.module.reference(
                    s["kind"], s["params"], data.blocks, data.glob,
                    exact=exact, pool=pool,
                )
            out.append(compare.compare_statement(s["rows"], refs[key]))
    return out


def run(args, jax, devices, cell: dict, bench: dict) -> dict:
    cfg = loader.read_config(cell["config"])
    mix = traffic.read_mix(cell["traffic"])
    rehearsal = args.rehearse > 0
    scale = (
        args.rehearse / ROWS_PER_SF if rehearsal else cfg["scale_factor"]
    )
    platform = devices[0].platform
    watch = CompileWatch(jax)
    phases = loader.Phases()

    from opentenbase_tpu.executor.fused import enable_compile_cache

    cache_dir = enable_compile_cache()
    data = loader.generate(cfg, args.seed, scale)
    phases.mark("generate_s")
    dep = loader.Deployment(cfg)
    try:
        dep.create_tables()
        phases.mark("build_s")
        dep.load(data)
        phases.mark("load_s")
        log(f"loaded {dep.shard_rows()}")
        dep.sql(f"set expected_device_platform = {platform}")
        v0 = views.read(dep.sql)
        warm_s: dict = {}
        for kind, text, _params in traffic.warm_up(mix, args.seed):
            t = time.perf_counter()
            dep.sql(text)
            warm_s.setdefault(kind, []).append(time.perf_counter() - t)
            log(f"warm {kind}: {warm_s[kind][-1]:.3f}s")
        phases.mark("warm_s")
        # a kind's first warm-up pays its uploads and its program; every
        # further one is what a new set of literals alone costs
        further = [t for ts in warm_s.values() for t in ts[1:]]
        before = views.read(dep.sql)
        warm_delta = views.statement_delta(v0, before)
        f = before["fused"]
        paths = {
            "last_mode": views.last(f, "last_mode"),
            "last_join_modes": views.last(f, "last_join_modes"),
            "pallas": before["pallas"],
        }
        split = {
            **{k: round(v, 3) for k, v in phases.t.items()},
            "warm_s_by_kind": {
                k: [round(t, 3) for t in ts] for k, ts in warm_s.items()
            },
            "warm_s_per_further_set": (
                sum(further) / len(further) if further else None
            ),
            "compile_s": round(warm_delta["compile_ms"] / 1000.0, 3),
            "upload_bytes": int(warm_delta["h2d_bytes"]),
            "data_dir_bytes": tree_bytes(dep.data_dir),
            "cache_dir": os.path.relpath(cache_dir, ROOT),
            "jax_compile_events": dict(watch.total),
            **paths,
        }
        print(json.dumps({"setup_split": split}), flush=True)

        trace_dir = os.path.join(ROOT, ".benchtmp", "trace")
        span = None
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

            def span(kind):
                return jax.profiler.TraceAnnotation(f"bench:stmt:{kind}")

        gc.collect()
        setup_s = time.perf_counter() - T0
        watch.armed = True
        window = (
            jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            if args.trace else contextlib.nullcontext()
        )
        with window:
            res = client_loop.closed_loop(
                dep.client, traffic.stream(mix, args.seed), args.seconds,
                len(mix["rotation"]), span,
            )
        if args.trace:
            jax.profiler.stop_trace()
        watch.armed = False
        done = res["statements"]
        log(f"window: {len(done)} statements in {res['window_s']:.3f}s")
        after = views.read(dep.sql)
        device = device_record(devices)
        delta = views.statement_delta(before, after)
        errors = [s for s in done if s["error"]]
        if not errors:  # a failed statement is reported, not hidden
            views.check_window(before, after, len(done), platform)
        if watch.window or delta["compile_ms"] > 0:
            raise RunFailure(
                f"compiled inside the window: {watch.window}, "
                f"compile_ms {delta['compile_ms']}"
            )
    finally:
        dep.close()
    del dep
    gc.collect()

    t = time.perf_counter()
    results = check_rows(done, data)
    verdict = compare.judge(results, compare.read_limits())
    log(f"compared {len(done)} statements in {time.perf_counter() - t:.1f}s")
    if args.control:
        control = compare.judge(
            check_rows(done, data, exact=False), compare.read_limits()
        )
        print(json.dumps({"control": control}), flush=True)

    lat = [s["ms"] for s in done if not s["error"]]
    values = {
        "setup_s": setup_s,
        "stmt_per_s": len(lat) / res["window_s"],
        "stmt_p50_ms": stats.percentile(lat, 50) if lat else None,
        "stmt_p95_ms": stats.percentile(lat, 95) if lat else None,
    }
    line: dict = {
        "correct": verdict["correct"] and not rehearsal,
        "attempted": len(done),
        "failed": sum(1 for r in results if r["wrong"]),
    }
    metrics: dict = {}
    if args.trace and not rehearsal:  # the CPU's trace has no device plane
        tr = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        )
        rows = {t: data.rows(t) for t in cfg["tables"]
                if cfg["tables"][t].get("loaded", True)}
        ctx = {
            "delta": delta, "trace": tr, "client_ms": lat, "setup": split,
            "peaks": work.peaks(devices[0].device_kind),
            "work_bytes": sum(
                work.statement_bytes(
                    cfg, rows, mix["statements"][s["kind"]]["reads"]
                ) for s in done
            ),
        }
        for m in metrics_of(bench["per_layer"], cell["name"]):
            v = layer_metrics.evaluate(
                layer_metrics.read_metric(m["name"]), ctx
            )
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace on disk: {tree_bytes(trace_dir)} bytes")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {
            "device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"],
        }
        line["programs"] = tr["programs"]
    else:
        for m in metrics_of(bench["end_to_end"], cell["name"]):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"],
                }
    if rehearsal:
        line["rehearsal"] = True
        line["rehearsal_agrees"] = verdict["correct"]
        line["rehearsal_counts"] = {
            "statements": len(done), "by_kind": {
                k: sum(1 for s in done if s["kind"] == k)
                for k in mix["statements"]
            },
            "paths": paths,
        }
        metrics = {}
    line["metrics"] = metrics
    line["device"] = device
    if not rehearsal:  # no timing of a CPU run goes into a record
        line["by_kind"] = {
            k: {"n": len(v), "p50_ms": stats.percentile(v, 50),
                "max_ms": max(v)}
            for k in mix["statements"]
            if (v := [s["ms"] for s in done
                      if s["kind"] == k and not s["error"]])
        }
    line["reasons"] = verdict["reasons"]
    line["compared"] = verdict["compared"]
    for name, c in verdict["compared"].items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="ROWS")
    ap.add_argument("--control", action="store_true",
                    help="also judge the control (the reference in lower "
                         "precision) against the same rows; for "
                         "benchmarks/tests, never for a measured run")
    args = ap.parse_args(argv)
    bench = read_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (
        platform != "tpu" or len(devices) < cell["chips"]
    ):
        print(f"benchmarks/run.py needs {cell['chips']} TPU chip(s): JAX "
              f"found {len(devices)} {platform!r} device(s)",
              file=sys.stderr)
        return 2
    try:
        import opentenbase_tpu.ops  # noqa: F401  (x64 + host placement)
    except ImportError:
        print("benchmarks/run.py must run from a checkout of the "
              "repository (opentenbase_tpu/ not importable)",
              file=sys.stderr)
        return 2

    out: list = []

    def work_thread():
        try:
            out.append(run(args, jax, devices, cell, bench))
        except BaseException as e:  # reported by the main thread
            import traceback

            traceback.print_exc()
            out.append(e)

    th = threading.Thread(target=work_thread, name="bench", daemon=True)
    th.start()
    th.join(TIME_LIMIT_S)
    if th.is_alive():
        print(f"benchmarks/run.py exceeded {TIME_LIMIT_S}s",
              file=sys.stderr, flush=True)
        os._exit(3)  # a wedged device call cannot be unwound
    if isinstance(out[0], BaseException):
        print(f"benchmarks/run.py FAILED: {out[0]}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(out[0]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
