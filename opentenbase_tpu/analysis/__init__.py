"""Project-invariant static analysis — the src/tools lint lineage.

The reference enforces hygiene over 1.5M LoC of C with compiler
warnings promoted to errors and a family of src/tools passes
(pgindent, cpluspluscheck, the perl validators over gram.y and the
catalogs). This reproduction kept paying for the absence of that
layer: an unread GUC shipped for four PRs (``log_min_messages``), a
removed jax API silently demoted every Pallas kernel to XLA for two
(the ``enable_x64`` context manager), 31 socket ``close()``s without ``shutdown()``
cost ~155 s of every run, an int32 cumsum wrapped past 2^31 pairs.
Each of those is mechanically detectable — so this package detects
them.

Layout:

- ``core``      — the AST framework: one parse per file, pragma
                  suppression (``# otb_lint: ignore[rule] -- reason``),
                  checker registry and runner;
- ``checkers``  — one module per invariant family (GUC lifecycle,
                  deprecated APIs, socket hygiene, failpoint coverage,
                  exception hygiene, numeric width, wire protocol);
- ``baseline``  — the ratchet: findings diff against a checked-in
                  ``tools/lint_baseline.json``; pre-existing violations
                  are burned down over time, NEW ones fail tier-1;
- ``lockwatch`` — the runtime half: an opt-in (``OTB_LOCKWATCH=1``)
                  lock-acquisition-order watchdog that reports cycles
                  (potential deadlocks) at process exit;
- ``racewatch`` — otb_race's runtime half: an opt-in
                  (``OTB_RACEWATCH=1``) TSan-lite sanitizer — classes
                  annotated ``@shared_state("_mu")`` record every
                  (thread, lockset, access) tuple, and disjoint-lockset
                  pairs with a write are reported with both stacks.

The race family (``checkers/races.py`` static lockset inference +
``racewatch``) shares this framework but ratchets against its own
``tools/race_baseline.json`` via ``cli/otb_race.py``.

CLIs: ``python -m opentenbase_tpu.cli.otb_lint [--check|--update-baseline]``,
``python -m opentenbase_tpu.cli.otb_race [--check|--update-baseline]``.
"""

from opentenbase_tpu.analysis.core import (  # noqa: F401
    Finding,
    Project,
    run_checkers,
)
from opentenbase_tpu.analysis.checkers import (  # noqa: F401
    all_checkers,
    race_checkers,
)
