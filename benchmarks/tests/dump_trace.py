"""Turn the .xplane.pb a traced run left under .benchtmp/trace into the
plain JSON ``harness/trace_reduce.py`` reduces (optionally cut to the
first ``--events`` events of each line, to keep a small recorded trace
for the tests).

    python benchmarks/tests/dump_trace.py .benchtmp/trace out.json [--events N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--events", type=int, default=0)
    args = ap.parse_args()
    trace = trace_reduce.load(trace_reduce.find_xplane(args.trace_dir))
    summary = {
        p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]}
        for p in trace["planes"]
    }
    print(json.dumps(summary, indent=1))
    if args.events:
        for p in trace["planes"]:
            for ln in p["lines"]:
                ln["events"] = ln["events"][: args.events]
    with open(args.out, "w") as f:
        json.dump(trace, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
