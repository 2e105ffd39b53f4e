"""What crosses each join of a compiled DAG program at the join's own
probe width: the check that a build column a final left for later (a
``gagg``'s dropped group keys, read at its output rows) is not gathered
at any join's width on the way."""

import re


def crossings(text: str, joins: str) -> dict:
    """join -> the stages, sorted, of the gathers in a compiled program
    ``text`` at that join's probe width (its record in ``joins``) that
    move build columns across it: ``<formulation>/gather``, and a fold's
    ``fold/probe`` (its carrier's word)."""
    probe = {
        rec.split("=")[0]: int(rec.split(":")[1].split()[0].split("x")[1])
        for rec in joins.split(";")
    }
    out: dict = {}
    for w, join, stage in re.findall(
            r"= \w+\[(\d+)[^ ]* gather\(.*?op_name=\"[^\"]*?otb/"
            r"(join\d+)/(\w+/\w+)/", text):
        if int(w) == probe.get(join) and (
                stage.endswith("/gather") or stage == "fold/probe"):
            out.setdefault(join, []).append(stage)
    return {j: sorted(st) for j, st in out.items()}
