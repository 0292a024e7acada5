"""seacheck rule registry.

Each rule module exposes ``RULE_ID`` (kebab-case), ``RULE_DOC`` (one-line
summary) and ``check(sf, tree) -> list[Violation]``. The engine parses each
file once, annotates parent links, and hands the tree to every rule. A rule
that checks across files also exposes ``check_tree(files)``, called once
with every ``(sf, tree)`` of the run.
"""

from __future__ import annotations

from .. import violations as _v
from . import (
    atomic_commit,
    invalidation,
    lock_discipline,
    reservation,
    telemetry_drift,
)

ALL_RULES = (
    reservation,
    atomic_commit,
    invalidation,
    telemetry_drift,
    lock_discipline,
)

for _mod in ALL_RULES:
    _v.RULES[_mod.RULE_ID] = _mod.RULE_DOC
