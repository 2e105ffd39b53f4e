"""otb_lint — project-invariant static analysis with a baseline ratchet.

    python -m opentenbase_tpu.cli.otb_lint --check
    python -m opentenbase_tpu.cli.otb_lint --update-baseline
    python -m opentenbase_tpu.cli.otb_lint --list-rules
    python -m opentenbase_tpu.cli.otb_lint            # full report

``--check`` is the ratchet (tests/test_static_analysis.py runs it on
the shipped tree): it diffs the tree's findings against
``tools/lint_baseline.json`` and exits nonzero ONLY on findings absent
from the baseline (new debt). Burned-down entries print as a hint;
``--update-baseline`` harvests them (and blesses reviewed additions)
by regenerating the file. The final line of ``--check`` is a one-line
JSON verdict, so CI logs grep clean:

    {"lint_gate": "ok", "findings": 41, "new": 0, "fixed": 0, ...}

Exit codes: 0 green; 1 new findings (or, with no baseline flags, any
finding); 2 usage/baseline errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _repo_root() -> str:
    """The directory holding the opentenbase_tpu package (cwd when it
    looks right, else the package's parent)."""
    import opentenbase_tpu

    if os.path.isdir(os.path.join(os.getcwd(), "opentenbase_tpu")):
        return os.getcwd()
    return os.path.dirname(os.path.dirname(
        os.path.abspath(opentenbase_tpu.__file__)
    ))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="otb_lint",
        description="project-invariant static analysis (ratcheted)",
    )
    ap.add_argument("--root", default=None, help="repo root to analyze")
    ap.add_argument(
        "--baseline", default=None,
        help="baseline path (default tools/lint_baseline.json)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="fail only on findings NOT in the baseline (the ratchet)",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="regenerate the baseline from the current tree",
    )
    ap.add_argument(
        "--list-rules", action="store_true",
        help="print every rule with its one-line description",
    )
    ap.add_argument(
        "--show-suppressed", action="store_true",
        help="also print pragma-suppressed findings (with reasons)",
    )
    ap.add_argument(
        "--format", choices=("text", "json"), default="text",
    )
    args = ap.parse_args(argv)

    from opentenbase_tpu.analysis import (
        Project, all_checkers, run_checkers,
    )
    from opentenbase_tpu.analysis import baseline as bl

    if args.list_rules:
        from opentenbase_tpu.analysis.checkers import all_rules

        for rule, desc in all_rules():
            print(f"{rule:24s} {desc}")
        return 0

    root = args.root or _repo_root()
    baseline_path = args.baseline or os.path.join(
        root, bl.DEFAULT_BASELINE
    )
    project = Project(root)
    if not project.files:
        print(f"otb_lint: no package files under {root}", file=sys.stderr)
        return 2
    active, suppressed = run_checkers(project, all_checkers())
    for err in project.parse_errors:
        print(f"otb_lint: parse error (compileall owns this): {err}",
              file=sys.stderr)

    if args.update_baseline:
        doc = bl.save(baseline_path, active)
        print(
            f"otb_lint: baseline written: {baseline_path} "
            f"({len(doc['findings'])} findings)"
        )
        return 0

    if args.check:
        try:
            doc = bl.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"otb_lint: {e}", file=sys.stderr)
            return 2
        new, fixed = bl.diff(active, doc)
        for f in new:
            print(f"NEW {f.render()}")
        if fixed:
            print(
                f"otb_lint: {len(fixed)} baselined finding(s) no longer "
                f"present — burn them down with --update-baseline:"
            )
            for k in fixed:
                print(f"  fixed {k}")
        verdict = {
            "lint_gate": "ok" if not new else "fail",
            "findings": len(active),
            "baselined": len(doc["findings"]),
            "new": len(new),
            "fixed": len(fixed),
            "suppressed": len(suppressed),
        }
        print(json.dumps(verdict))
        return 1 if new else 0

    # plain report: everything active (and optionally suppressed)
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
            f"otb_lint: {len(active)} finding(s), "
            f"{len(suppressed)} suppressed"
        )
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
