"""CLNT007 env-knob registry: every ``COMETBFT_*`` environment variable
read anywhere must be declared in ``config.py``'s ``ENV_KNOBS``.

Undocumented knobs are how the round-5 backend-gate bug happened: an
environment pin of the verify kernel changed dispatch behavior that no
config surface admitted existed. The registry is the single catalog an
operator (and the docs) can trust; reading a knob that isn't in it is a
lint failure, so adding the env read and documenting it become one
change.

Recognized read forms (with ``os`` import aliases and knob names held
in module-level string constants resolved)::

    os.environ.get("COMETBFT_X")     os.environ["COMETBFT_X"]
    os.getenv("COMETBFT_X")          environ.get(KNOB_CONST)
"""

from __future__ import annotations

import ast

from ..engine import Checker, FileContext, Finding


class EnvKnobChecker(Checker):
    codes = ("CLNT007",)
    name = "env-knob-registry"
    description = (
        "COMETBFT_* environment reads must be declared in "
        "config.py ENV_KNOBS"
    )

    def check(self, ctx: FileContext) -> list[Finding]:
        declared = ctx.declared_knobs or frozenset()
        os_aliases: set[str] = set()
        environ_aliases: set[str] = set()
        constants: dict[str, str] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "os":
                        os_aliases.add(a.asname or "os")
            elif isinstance(node, ast.ImportFrom) and node.module == "os":
                for a in node.names:
                    if a.name == "environ":
                        environ_aliases.add(a.asname or "environ")
                    if a.name == "getenv":
                        environ_aliases.add(a.asname or "getenv")
            elif isinstance(node, ast.Assign):
                if (
                    len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)
                ):
                    constants[node.targets[0].id] = node.value.value
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            knob = self._read_knob(node, os_aliases, environ_aliases, constants)
            if knob is None or knob in declared:
                continue
            if ctx.suppressed(node, "CLNT007"):
                continue
            findings.append(
                ctx.finding(
                    node,
                    "CLNT007",
                    f"env knob '{knob}' is read here but not declared "
                    "in config.py ENV_KNOBS — undocumented knobs are "
                    "invisible to operators (round-5 backend-gate bug)",
                )
            )
        return findings

    def _read_knob(
        self, node, os_aliases, environ_aliases, constants
    ) -> str | None:
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute):
                if fn.attr == "get" and self._is_environ(
                    fn.value, os_aliases, environ_aliases
                ):
                    return self._knob_name(node.args, constants)
                if (
                    fn.attr == "getenv"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in os_aliases
                ):
                    return self._knob_name(node.args, constants)
            elif isinstance(fn, ast.Name) and fn.id in environ_aliases:
                # bare getenv(...) via `from os import getenv`
                return self._knob_name(node.args, constants)
        elif isinstance(node, ast.Subscript) and self._is_environ(
            node.value, os_aliases, environ_aliases
        ):
            return self._knob_name([node.slice], constants)
        return None

    @staticmethod
    def _is_environ(expr, os_aliases, environ_aliases) -> bool:
        if (
            isinstance(expr, ast.Attribute)
            and expr.attr == "environ"
            and isinstance(expr.value, ast.Name)
            and expr.value.id in os_aliases
        ):
            return True
        return isinstance(expr, ast.Name) and expr.id in environ_aliases

    @staticmethod
    def _knob_name(args, constants) -> str | None:
        if not args:
            return None
        a = args[0]
        value = None
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            value = a.value
        elif isinstance(a, ast.Name):
            value = constants.get(a.id)
        if value is not None and value.startswith("COMETBFT_"):
            return value
        return None
