"""Deprecated / removed-API denylist — the ``enable_x64`` class.

PR 3's post-mortem: the x64 context manager moved between the jax and
jax.experimental namespaces across releases, the AttributeError was
swallowed by a broad guard, and every Pallas kernel silently demoted
to XLA for two whole PRs — the bench ran 7x slower and nothing failed.
The denylist tracks the INSTALLED jax (0.9: ``jax.enable_x64`` is the
one name) and names the allowed replacement in the message so the fix
is in the finding.

Matches dotted attribute chains (``jax.experimental.enable_x64``) and the
string-knob form (``jax.config.update("enable_x64", ...)`` — the knob
is ``jax_enable_x64``; the unprefixed name raises nothing and sets
nothing on old jax versions).
"""

from __future__ import annotations

import ast
from typing import Iterable

from opentenbase_tpu.analysis.core import Finding, Project, dotted_name

# dotted path -> replacement named in the message
DENYLIST: dict[str, str] = {
    "jax.experimental.enable_x64": (
        "removed in jax 0.9; use jax.enable_x64 (context manager) or "
        "jax.config.update('jax_enable_x64', ...)"
    ),
    "jax.experimental.host_callback": (
        "deprecated and removed; use jax.experimental.io_callback / "
        "jax.debug.callback"
    ),
    "jax.tree_map": "moved in jax 0.4.26; use jax.tree.map",
    "jax.tree_util.tree_multimap": "removed; use jax.tree.map",
    "jnp.DeviceArray": "removed; use jax.Array",
    "jax.xla_computation": "removed in jax 0.5; use jax.jit(...).lower()",
    "np.float": "removed in numpy 1.24; use float or np.float64",
    "np.int": "removed in numpy 1.24; use int or np.int64",
    "np.bool": "removed in numpy 1.24; use bool or np.bool_",
    "np.object": "removed in numpy 1.24; use object",
    "numpy.float": "removed in numpy 1.24; use float or np.float64",
    "numpy.int": "removed in numpy 1.24; use int or np.int64",
}

# first argument of jax.config.update that silently does nothing
_BAD_CONFIG_KNOBS: dict[str, str] = {
    "enable_x64": "the knob is 'jax_enable_x64' (jax_ prefix required)",
    "x64_enabled": "the knob is 'jax_enable_x64'",
}


class DeprecatedApiChecker:
    rules = (
        ("deprecated-api", "removed/deprecated API with named replacement"),
    )

    def run(self, project: Project) -> Iterable[Finding]:
        for rel, sf in sorted(project.files.items()):
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Attribute):
                    dotted = dotted_name(node)
                    repl = DENYLIST.get(dotted) if dotted else None
                    if repl is not None:
                        yield Finding(
                            rule="deprecated-api",
                            path=rel,
                            line=node.lineno,
                            message=f"{dotted}: {repl}",
                            ident=dotted,
                        )
                elif isinstance(node, ast.Call):
                    knob = _config_update_knob(node)
                    note = (
                        _BAD_CONFIG_KNOBS.get(knob) if knob else None
                    )
                    if note is not None:
                        yield Finding(
                            rule="deprecated-api",
                            path=rel,
                            line=node.lineno,
                            message=(
                                f"jax.config.update({knob!r}, ...): {note}"
                            ),
                            ident=f"config.update:{knob}",
                        )


def _config_update_knob(call: ast.Call):
    """The knob string of a ``*.config.update("knob", ...)`` call."""
    f = call.func
    if not (
        isinstance(f, ast.Attribute)
        and f.attr == "update"
        and isinstance(f.value, ast.Attribute)
        and f.value.attr == "config"
    ):
        return None
    if call.args and isinstance(call.args[0], ast.Constant) and isinstance(
        call.args[0].value, str
    ):
        return call.args[0].value
    return None


def checkers() -> list:
    return [DeprecatedApiChecker()]
