"""Perf-regression gate: per-query rows/sec floors + demotion checks.

The bench trajectory showed two silent failure classes survive whole
PRs: a leg regressing (Q3 dipped from 2.6x to 0.1x baseline) and the
platform demoting (runs r04/r05 executed on ``platform: cpu`` and
nobody noticed until the JSON was read).
This module makes both LOUD:

- ``BENCH_FLOORS.json`` (repo root) persists per-metric rows/sec floors
  from the best green run; ``bench.py`` evaluates its final record
  against them and exits nonzero on any violation;
- a platform demotion (a record whose ``platform`` is not an
  accelerator, pallas->XLA kernel demotions) is itself a violation — device floors are then
  skipped (they would all fail redundantly), the demotion line is the
  verdict.

Floors file schema::

    {
      "_meta": {
        "source_run": "r03",          # the green run the floors came from
        "note": "...",                # how to re-baseline (see README)
        "default_tolerance": 0.75     # optional; per-metric overrides win
      },
      "floors": {
        "<record metric name>": {
          "floor": 37174305,          # rows/sec of the source run
          "tolerance": 0.7,           # pass while value >= floor*tolerance
          "platform": "device",       # 'device' (default): only checked
                                      # on a real accelerator; 'any':
                                      # checked on every platform
          "required": true            # optional (default true): a record
                                      # MISSING this metric on a healthy
                                      # device run is a lost leg -> fail
        }, ...
      }
    }

Re-baselining after a legitimate win or an accepted regression is an
explicit act: edit the floor value and ``_meta.source_run`` in the same
commit that changes the performance, so the diff review sees both.

``BENCH_GATE=0`` in the environment skips the exit-code enforcement
(the gate still prints its verdict line) — for local smoke runs of
bench.py on laptops where no accelerator is expected.
"""

from __future__ import annotations

import json
import os
from typing import Optional

DEFAULT_TOLERANCE = 0.75
GATE_EXIT_CODE = 4


def floors_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_FLOORS.json",
    )


def validate_floors(doc) -> list[str]:
    """Schema errors ([] = valid). Checked by tier-1 so a malformed
    floors file fails CI, not the next TPU bench."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["floors document must be a JSON object"]
    meta = doc.get("_meta")
    if not isinstance(meta, dict) or not meta.get("source_run"):
        errs.append("_meta.source_run: required (which green run)")
    elif "default_tolerance" in meta and not (
        isinstance(meta["default_tolerance"], (int, float))
        and 0 < meta["default_tolerance"] <= 1
    ):
        errs.append("_meta.default_tolerance: number in (0, 1] required")
    floors = doc.get("floors")
    if not isinstance(floors, dict) or not floors:
        errs.append("floors: non-empty object required")
        return errs
    for name, spec in floors.items():
        if not isinstance(spec, dict):
            errs.append(f"floors.{name}: object required")
            continue
        fl = spec.get("floor")
        if not isinstance(fl, (int, float)) or isinstance(fl, bool) \
                or fl <= 0:
            errs.append(f"floors.{name}.floor: positive number required")
        tol = spec.get("tolerance")
        if tol is not None and not (
            isinstance(tol, (int, float)) and not isinstance(tol, bool)
            and 0 < tol <= 1
        ):
            errs.append(f"floors.{name}.tolerance: number in (0, 1]")
        if spec.get("platform", "device") not in ("device", "any"):
            errs.append(f"floors.{name}.platform: 'device' or 'any'")
        if not isinstance(spec.get("required", True), bool):
            errs.append(f"floors.{name}.required: boolean")
        unknown = set(spec) - {"floor", "tolerance", "platform",
                               "required", "unit", "note"}
        if unknown:
            errs.append(f"floors.{name}: unknown keys {sorted(unknown)}")
    return errs


def load_floors(path: Optional[str] = None) -> dict:
    with open(path or floors_path()) as f:
        doc = json.load(f)
    errs = validate_floors(doc)
    if errs:
        raise ValueError("invalid BENCH_FLOORS.json: " + "; ".join(errs))
    return doc


def platform_demoted(record: dict) -> Optional[str]:
    """The demotion reason, or None on a healthy device run."""
    plat = record.get("platform")
    if plat not in (None, "tpu"):
        return f"platform demoted to '{plat}'"
    return None


def check_record(record: dict, doc: dict) -> list[str]:
    """Gate verdict: list of violations ([] = green).

    Demotions are violations in their own right; device floors are then
    skipped (a CPU run failing every device floor would bury the one
    line that matters). Pallas->XLA kernel demotions count even on a
    healthy platform — PR 3 shipped one for two whole rounds."""
    # the headline leg stores its value under 'value' with its name in
    # 'metric' (the driver-facing record shape) — alias it so the floor
    # keyed by the metric NAME finds it
    headline = record.get("metric")
    if headline and headline not in record and "value" in record:
        record = dict(record)
        record[headline] = record["value"]
    violations: list[str] = []
    demoted = platform_demoted(record)
    if demoted:
        violations.append(f"platform demotion: {demoted}")
    pallas = int(record.get("pallas_demotions", 0) or 0)
    if pallas:
        violations.append(
            f"pallas demotions during run: {pallas} "
            "(kernel silently fell back to XLA)"
        )
    default_tol = doc.get("_meta", {}).get(
        "default_tolerance", DEFAULT_TOLERANCE
    )
    for metric, spec in sorted(doc.get("floors", {}).items()):
        if spec.get("platform", "device") == "device" and demoted:
            continue
        value = record.get(metric)
        if value is None:
            if spec.get("required", True) and not demoted:
                violations.append(
                    f"{metric}: missing from the record "
                    "(leg did not run/complete)"
                )
            continue
        tol = spec.get("tolerance", default_tol)
        floor = spec["floor"] * tol
        if value < floor:
            violations.append(
                f"{metric}: {value:.0f} < {spec['floor']:.0f} x {tol} "
                f"= {floor:.0f} (source run {doc['_meta']['source_run']})"
            )
    return violations


def gate_enabled() -> bool:
    return os.environ.get("BENCH_GATE", "1") != "0"
