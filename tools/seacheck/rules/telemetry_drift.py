"""Rule ``telemetry-drift``: the ``COUNTERS`` registry, the ``Telemetry``
dataclass fields, and the increments must all agree.

Generalizes the ``tests/test_docs.py`` config-drift gate to counters
(ISSUE 9 satellite): ``core/telemetry.py`` carries one canonical table —
``COUNTERS: {name: description}`` — that ``snapshot()`` iterates and this
rule cross-checks, so a counter can no longer be added, renamed, or
dropped in one place only.

Checks inside ``telemetry.py`` (all purely lexical — the CI lint job needs
no imports):

* every ``COUNTERS`` key is a ``Telemetry`` dataclass field;
* every public scalar (int/float) ``Telemetry`` field is in ``COUNTERS``;
* every ``self.<name> += ...`` inside ``Telemetry`` methods targets a
  registered counter;
* ``snapshot`` actually consumes ``COUNTERS`` (the registry must drive the
  export, not decorate it).

Check everywhere else: counters are mutated only through
``Telemetry.record_*`` methods — a ``<x>.telemetry.<counter> += ...`` spot
increment bypasses the lock and the registry and is flagged.

Across files (``check_tree``, where the run lints ``telemetry.py``): every
literal span name passed to ``<x>.span(...)`` or ``<x>.record_span(...)``
in a ``repro`` module is a key of the ``SPANS`` registry, and every
``SPANS`` key is passed somewhere.
"""

from __future__ import annotations

import ast

from ..astutil import qualname
from ..violations import SourceFile, Violation

RULE_ID = "telemetry-drift"
RULE_DOC = (
    "every incremented Telemetry counter must be registered in COUNTERS "
    "and vice versa; every span name in SPANS and vice versa"
)

SPAN_CALLS = ("span", "record_span")

TELEMETRY_SUFFIX = "repro/core/telemetry.py"


def _find_class(tree: ast.AST, name: str) -> ast.ClassDef | None:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == name:
            return node
    return None


def _counters_table(tree: ast.AST, table: str = "COUNTERS") -> tuple[dict[str, int], int]:
    """``{name: lineno}`` from the module-level ``table`` dict literal
    (COUNTERS or SPANS), plus the table's own line (0 when absent)."""
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            target = node.target.id
            value = node.value
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 and (
            isinstance(node.targets[0], ast.Name)
        ):
            target = node.targets[0].id
            value = node.value
        if target != table:
            continue
        if not isinstance(value, ast.Dict):
            return {}, node.lineno
        out = {}
        for k in value.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                out[k.value] = k.lineno
        return out, node.lineno
    return {}, 0


def _scalar_fields(cls: ast.ClassDef) -> dict[str, int]:
    """Public dataclass fields annotated int/float -> lineno."""
    out = {}
    for stmt in cls.body:
        if not isinstance(stmt, ast.AnnAssign):
            continue
        if not isinstance(stmt.target, ast.Name):
            continue
        name = stmt.target.id
        if name.startswith("_"):
            continue
        ann = stmt.annotation
        if isinstance(ann, ast.Name) and ann.id in ("int", "float"):
            out[name] = stmt.lineno
    return out


def _self_increments(cls: ast.ClassDef) -> list[tuple[str, int]]:
    out = []
    for node in ast.walk(cls):
        if not isinstance(node, ast.AugAssign):
            continue
        t = node.target
        if (
            isinstance(t, ast.Attribute)
            and isinstance(t.value, ast.Name)
            and t.value.id == "self"
        ):
            out.append((t.attr, node.lineno))
    return out


def _check_telemetry_module(sf: SourceFile, tree: ast.AST) -> list[Violation]:
    out: list[Violation] = []

    def flag(line: int, sym: str, msg: str) -> None:
        if not sf.suppressed(line, RULE_ID):
            out.append(Violation(RULE_ID, sf.path, line, sym, msg))

    counters, table_line = _counters_table(tree)
    cls = _find_class(tree, "Telemetry")
    if table_line == 0:
        flag(1, "<module>", "no COUNTERS registry table found")
        return out
    if cls is None:  # pragma: no cover - telemetry.py always has the class
        return out
    fields = _scalar_fields(cls)
    for name, line in counters.items():
        if name not in fields:
            flag(
                line,
                "COUNTERS",
                f"registered counter {name!r} is not a Telemetry field",
            )
    for name, line in fields.items():
        if name not in counters:
            flag(
                line,
                f"Telemetry.{name}",
                f"Telemetry field {name!r} is not registered in COUNTERS",
            )
    for name, line in _self_increments(cls):
        if not name.startswith("_") and name not in counters:
            flag(
                line,
                f"Telemetry.{name}",
                f"increment of unregistered counter {name!r}",
            )
    snapshot = next(
        (
            n
            for n in cls.body
            if isinstance(n, ast.FunctionDef) and n.name == "snapshot"
        ),
        None,
    )
    if snapshot is not None and not any(
        isinstance(n, ast.Name) and n.id == "COUNTERS"
        for n in ast.walk(snapshot)
    ):
        flag(
            snapshot.lineno,
            "Telemetry.snapshot",
            "snapshot() does not iterate the COUNTERS registry",
        )
    return out


def _check_other_module(sf: SourceFile, tree: ast.AST) -> list[Violation]:
    out: list[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.AugAssign):
            continue
        t = node.target
        if not isinstance(t, ast.Attribute):
            continue
        recv = t.value
        is_telemetry = (
            isinstance(recv, ast.Name) and recv.id == "telemetry"
        ) or (isinstance(recv, ast.Attribute) and recv.attr == "telemetry")
        if is_telemetry and not sf.suppressed(node.lineno, RULE_ID):
            out.append(
                Violation(
                    RULE_ID,
                    sf.path,
                    node.lineno,
                    qualname(node),
                    f"ad-hoc increment of telemetry.{t.attr}; add a "
                    "Telemetry.record_* method (lock + registry) instead",
                )
            )
    return out


def check(sf: SourceFile, tree: ast.AST) -> list[Violation]:
    if sf.path.endswith(TELEMETRY_SUFFIX):
        return _check_telemetry_module(sf, tree)
    return _check_other_module(sf, tree)


def _span_names(tree: ast.AST) -> list[tuple[str, int]]:
    """``(name, lineno)`` of every string literal in the first argument of
    a ``.span(...)`` or ``.record_span(...)`` call (both arms of a
    conditional name count)."""
    out = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in SPAN_CALLS
            and node.args
        ):
            out.extend(
                (n.value, n.lineno)
                for n in ast.walk(node.args[0])
                if isinstance(n, ast.Constant) and isinstance(n.value, str)
            )
    return out


def check_tree(files: list[tuple[SourceFile, ast.AST]]) -> list[Violation]:
    registry = next(
        ((sf, tree) for sf, tree in files if sf.path.endswith(TELEMETRY_SUFFIX)),
        None,
    )
    if registry is None:
        return []  # the run does not lint telemetry.py: nothing to hold to
    reg_sf, reg_tree = registry
    spans, _ = _counters_table(reg_tree, "SPANS")
    out: list[Violation] = []
    used: set[str] = set()
    for sf, tree in files:
        if "repro/" not in sf.path:
            continue
        for name, line in _span_names(tree):
            used.add(name)
            if name not in spans and not sf.suppressed(line, RULE_ID):
                out.append(Violation(RULE_ID, sf.path, line, "<span>",
                                     f"span {name!r} is not registered in SPANS"))
    for name, line in spans.items():
        if name not in used and not reg_sf.suppressed(line, RULE_ID):
            out.append(Violation(RULE_ID, reg_sf.path, line, "SPANS",
                                 f"registered span {name!r} is never recorded"))
    return out
