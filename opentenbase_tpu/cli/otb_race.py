"""otb_race — lockset-based static race detection with a baseline
ratchet (the otb_lint shape, second instance).

    python -m opentenbase_tpu.cli.otb_race --check
    python -m opentenbase_tpu.cli.otb_race --update-baseline
    python -m opentenbase_tpu.cli.otb_race --list-rules
    python -m opentenbase_tpu.cli.otb_race --format json
    python -m opentenbase_tpu.cli.otb_race --bless-dynamic KEY --reason WHY

``--check`` is the ratchet (tests/test_race_analysis.py runs it on the
shipped tree): it diffs the tree's STATIC findings
(``race-guard-mismatch`` / ``race-check-then-act`` /
``lock-release-path``) against ``tools/race_baseline.json`` and exits
nonzero only on findings absent from it.  The baseline is SHARED with
the dynamic half: ``race-dynamic::*`` keys are recorded by the
racewatch chaos gate and are preserved verbatim across
``--update-baseline`` (a static regeneration must never silently drop
a reviewed dynamic suppression — and vice versa, the gate never
touches static keys).  ``--bless-dynamic`` adds one dynamic key
deliberately and REFUSES to do it without ``--reason``: dynamic
findings have no source line to hang a pragma on, so the reason lives
in the baseline entry instead.

The final line of ``--check`` is a one-line JSON verdict:

    {"race_gate": "ok", "findings": N, "new": 0, "fixed": 0, ...}

Exit codes: 0 green; 1 new findings; 2 usage/baseline errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_BASELINE = os.path.join("tools", "race_baseline.json")


def _repo_root() -> str:
    import opentenbase_tpu

    if os.path.isdir(os.path.join(os.getcwd(), "opentenbase_tpu")):
        return os.getcwd()
    return os.path.dirname(os.path.dirname(
        os.path.abspath(opentenbase_tpu.__file__)
    ))


def _save_merged(path: str, static_findings, keep: dict) -> dict:
    """Write the baseline from ``static_findings`` plus the preserved
    (dynamic) entries in ``keep`` — atomic, sorted, versioned like
    analysis.baseline.save."""
    from opentenbase_tpu.analysis.baseline import BASELINE_VERSION
    from opentenbase_tpu.analysis.core import NEVER_BASELINE

    findings = dict(keep)
    for f in static_findings:
        if f.rule not in NEVER_BASELINE:
            findings[f.key] = {"line": f.line, "message": f.message}
    doc = {"version": BASELINE_VERSION, "findings": findings}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as out:
        json.dump(doc, out, indent=1, sort_keys=True)
        out.write("\n")
    os.replace(tmp, path)
    return doc


def _dynamic_entries(doc: dict) -> dict:
    return {
        k: v for k, v in doc.get("findings", {}).items()
        if k.startswith("race-dynamic::")
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="otb_race",
        description="lockset-based static race detection (ratcheted)",
    )
    ap.add_argument("--root", default=None, help="repo root to analyze")
    ap.add_argument(
        "--baseline", default=None,
        help="baseline path (default tools/race_baseline.json)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="fail only on findings NOT in the baseline (the ratchet)",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="regenerate the static entries (dynamic keys preserved)",
    )
    ap.add_argument(
        "--list-rules", action="store_true",
        help="print every rule (both halves) with its description",
    )
    ap.add_argument(
        "--show-suppressed", action="store_true",
        help="also print pragma-suppressed findings (with reasons)",
    )
    ap.add_argument(
        "--bless-dynamic", metavar="KEY", default=None,
        help="baseline one race-dynamic::<path>::<Class>.<field> key",
    )
    ap.add_argument(
        "--reason", default=None,
        help="why the blessed dynamic race is acceptable (REQUIRED "
             "with --bless-dynamic)",
    )
    ap.add_argument(
        "--format", choices=("text", "json"), default="text",
    )
    args = ap.parse_args(argv)

    from opentenbase_tpu.analysis import (
        Project, race_checkers, run_checkers,
    )
    from opentenbase_tpu.analysis import baseline as bl

    if args.list_rules:
        from opentenbase_tpu.analysis.checkers import race_rules

        for rule, desc in race_rules():
            print(f"{rule:24s} {desc}")
        return 0

    root = args.root or _repo_root()
    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)

    if args.bless_dynamic:
        if not args.bless_dynamic.startswith("race-dynamic::"):
            print("otb_race: --bless-dynamic takes a race-dynamic:: "
                  "key (static findings are baselined by "
                  "--update-baseline or fixed)", file=sys.stderr)
            return 2
        if not (args.reason or "").strip():
            print("otb_race: a dynamic bless REQUIRES --reason — the "
                  "baseline entry is where the why lives", file=sys.stderr)
            return 2
        try:
            doc = bl.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"otb_race: {e}", file=sys.stderr)
            return 2
        doc["findings"][args.bless_dynamic] = {
            "line": 1, "message": args.reason.strip(),
        }
        tmp = baseline_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as out:
            json.dump(doc, out, indent=1, sort_keys=True)
            out.write("\n")
        os.replace(tmp, baseline_path)
        print(f"otb_race: blessed {args.bless_dynamic}")
        return 0

    project = Project(root)
    if not project.files:
        print(f"otb_race: no package files under {root}", file=sys.stderr)
        return 2
    active, suppressed = run_checkers(
        project, race_checkers(), tool="race",
    )
    for err in project.parse_errors:
        print(f"otb_race: parse error (compileall owns this): {err}",
              file=sys.stderr)

    if args.update_baseline:
        try:
            old = bl.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"otb_race: {e}", file=sys.stderr)
            return 2
        doc = _save_merged(
            baseline_path, active, _dynamic_entries(old),
        )
        n_dyn = len(_dynamic_entries(doc))
        print(
            f"otb_race: baseline written: {baseline_path} "
            f"({len(doc['findings'])} findings, {n_dyn} dynamic "
            f"preserved)"
        )
        return 0

    if args.check:
        try:
            doc = bl.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"otb_race: {e}", file=sys.stderr)
            return 2
        new, fixed = bl.diff(active, doc)
        # dynamic keys belong to the racewatch gate, not this static
        # diff: never report them as burned-down here
        fixed = [k for k in fixed if not k.startswith("race-dynamic::")]
        for f in new:
            print(f"NEW {f.render()}")
        if fixed:
            print(
                f"otb_race: {len(fixed)} baselined finding(s) no longer "
                f"present — burn them down with --update-baseline:"
            )
            for k in fixed:
                print(f"  fixed {k}")
        verdict = {
            "race_gate": "ok" if not new else "fail",
            "findings": len(active),
            "baselined": len(doc["findings"]),
            "new": len(new),
            "fixed": len(fixed),
            "suppressed": len(suppressed),
        }
        print(json.dumps(verdict))
        return 1 if new else 0

    if args.format == "json":
        print(json.dumps({
            "findings": [
                {
                    "rule": f.rule, "path": f.path, "line": f.line,
                    "message": f.message, "key": f.key,
                }
                for f in active
            ],
            "suppressed": len(suppressed),
        }, indent=1))
    else:
        for f in active:
            print(f.render())
        if args.show_suppressed:
            for f in suppressed:
                print(f"suppressed {f.render()}")
        print(
            f"otb_race: {len(active)} finding(s), "
            f"{len(suppressed)} suppressed"
        )
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
