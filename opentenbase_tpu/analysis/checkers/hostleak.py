"""Device→host leak detection — the static half of the r04/r05
silent-CPU class.

PR 11's watchdog catches a fused program that RAN on the wrong
platform; this checker catches the code shape that CAUSES silent host
round-trips: a host-sync call on a traced value inside the device
subsystems (``ops/``, ``executor/fused*``).  ``np.anything(jnp_array)``
forces a device→host transfer and blocks on the device; ``.item()``,
``float()`` / ``int()`` / ``bool()`` coercions do the same one scalar
at a time — inside a per-batch loop that is the whole r04 regression.

Rule ``device-host-leak``: within a scoped function, a name assigned
from a ``jnp.`` / ``lax.`` expression (or from another traced name) is
TRACED; flagged are ``np.*(traced)``, ``traced.item()``, and
``float/int/bool(traced)``.  A statement that says ``device_get`` or
``block_until_ready`` is an EXPLICIT sync point — deliberate
transfers are the fix, not the bug, so they pass.  Existing findings
are baselined; genuinely-host merge helpers get pragmas naming why the
value is already host-side.
"""

from __future__ import annotations

import ast
from typing import Iterable

from opentenbase_tpu.analysis.core import (
    Finding,
    Project,
    dotted_name,
    iter_functions,
    walk_shallow,
)

_SCOPED_PREFIXES = ("opentenbase_tpu/ops/",)
_SCOPED_GLOBS = (
    "opentenbase_tpu/executor/fused.py",
    "opentenbase_tpu/executor/fused_dag.py",
)
_TRACED_ROOTS = {"jnp", "lax"}
_COERCIONS = {"float", "int", "bool"}
# spelled in the statement = the sync is explicit and intended
_EXPLICIT_SYNC = ("device_get", "block_until_ready")


def _in_scope(rel: str) -> bool:
    return rel.startswith(_SCOPED_PREFIXES) or rel in _SCOPED_GLOBS


def _mentions(node: ast.AST, names: set) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return True
    return False


def _target_names(tgt: ast.AST):
    if isinstance(tgt, ast.Name):
        yield tgt.id
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for elt in tgt.elts:
            yield from _target_names(elt)
    elif isinstance(tgt, ast.Starred):
        yield from _target_names(tgt.value)


def _traced_names(fn: ast.AST) -> set:
    """Names assigned (transitively) from jnp/lax expressions inside
    ``fn``.  Two passes close simple forward/backward chains; deeper
    fixpoints aren't worth the cost at this file count."""
    traced: set = set()
    for _ in range(2):
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.AugAssign):
                value, targets = node.value, [node.target]
            else:
                continue
            if any(
                isinstance(s, ast.Attribute) and s.attr in _EXPLICIT_SYNC
                for s in ast.walk(value)
            ):
                continue  # device_get(...) lands host-side: taint ends
            if _mentions(value, _TRACED_ROOTS | traced):
                for tgt in targets:
                    traced.update(_target_names(tgt))
    return traced


class HostLeakChecker:
    rules = (
        ("device-host-leak",
         "host-sync call on a traced value in device code"),
    )

    def run(self, project: Project) -> Iterable[Finding]:
        for rel, sf in sorted(project.files.items()):
            if not _in_scope(rel):
                continue
            for qualname, fn in iter_functions(sf.tree):
                # no early-out on an empty traced set: a direct
                # `float(jnp.vdot(a, b))` leaks without any assignment
                traced = _traced_names(fn)
                seq: dict = {}
                for stmt in walk_shallow(fn):
                    if not isinstance(stmt, (
                        ast.Assign, ast.AugAssign, ast.AnnAssign,
                        ast.Expr, ast.Return, ast.If, ast.While,
                    )):
                        continue
                    root = (
                        stmt.test if isinstance(stmt, (ast.If, ast.While))
                        else stmt
                    )
                    if any(
                        isinstance(s, ast.Attribute)
                        and s.attr in _EXPLICIT_SYNC
                        for s in ast.walk(root)
                    ):
                        continue  # explicit, deliberate sync point
                    yield from self._flag_calls(
                        rel, qualname, root, traced, seq
                    )

    def _flag_calls(self, rel, qualname, root, traced, seq):
        for node in ast.walk(root):
            if not isinstance(node, ast.Call):
                continue
            label = self._leak_label(node, traced)
            if label is None:
                continue
            n = seq[label] = seq.get(label, 0) + 1
            yield Finding(
                rule="device-host-leak",
                path=rel,
                line=node.lineno,
                message=(
                    f"{qualname}: {label} on a traced (jnp-derived) "
                    f"value forces a device->host sync inside device "
                    f"code — the r04/r05 silent-CPU class; keep the "
                    f"computation in jnp, or make the transfer "
                    f"explicit with jax.device_get / pragma with why "
                    f"the value is already host-side"
                ),
                ident=f"{qualname}:{label}:{n}",
            )

    @staticmethod
    def _leak_label(node: ast.Call, traced: set):
        f = node.func
        args = list(node.args) + [kw.value for kw in node.keywords]
        touches = any(
            _mentions(a, traced | _TRACED_ROOTS) for a in args
        )
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not args and _mentions(
                f.value, traced | _TRACED_ROOTS
            ):
                return ".item()"
            name = dotted_name(f)
            if name is not None and name.startswith("np.") and touches:
                return name
        elif isinstance(f, ast.Name):
            if f.id in _COERCIONS and args and _mentions(
                args[0], traced | _TRACED_ROOTS
            ):
                return f"{f.id}()"
        return None


def checkers() -> list:
    return [HostLeakChecker()]
