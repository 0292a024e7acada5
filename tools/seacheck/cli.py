"""``seacheck lint`` — run the invariant rules over a source tree.

Pure stdlib (``ast`` + ``json``): the CI lint job needs no third-party
installs and never imports the checked code.

Usage::

    PYTHONPATH=src:tools python -m seacheck lint src/repro
    python -m seacheck lint --update-baseline src/repro   # accept findings
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys

from .rules import ALL_RULES
from .violations import (
    RULES,
    SourceFile,
    Violation,
    filter_baselined,
    load_baseline,
)
from .astutil import annotate_parents

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")


def iter_py_files(paths: list[str]):
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [
                d for d in dirnames if d not in ("__pycache__", ".git")
            ]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def relpath(path: str, root: str) -> str:
    rel = os.path.relpath(os.path.abspath(path), root)
    return rel.replace(os.sep, "/")


def lint_paths(
    paths: list[str], *, root: str | None = None, rules=ALL_RULES
) -> list[Violation]:
    """All unsuppressed violations over ``paths`` (baseline NOT applied)."""
    root = root or os.getcwd()
    out: list[Violation] = []
    parsed: list = []
    for path in iter_py_files(paths):
        out.extend(lint_file(path, root=root, rules=rules, parsed=parsed))
    for rule in rules:
        check_tree = getattr(rule, "check_tree", None)
        if check_tree is not None:
            out.extend(check_tree(parsed))
    return out


def lint_file(
    path: str, *, root: str, rules=ALL_RULES, parsed: list | None = None
) -> list[Violation]:
    """One file's violations; appends ``(SourceFile, tree)`` to ``parsed``
    (when given) for the rules that check across files."""
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except OSError as e:
        print(f"seacheck: cannot read {path}: {e}", file=sys.stderr)
        return []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            Violation(
                "parse-error",
                relpath(path, root),
                e.lineno or 1,
                "<module>",
                f"syntax error: {e.msg}",
            )
        ]
    annotate_parents(tree)
    sf = SourceFile(path=relpath(path, root), source=source)
    if parsed is not None:
        parsed.append((sf, tree))
    out: list[Violation] = []
    for rule in rules:
        out.extend(rule.check(sf, tree))
    return out


def _cmd_lint(args: argparse.Namespace) -> int:
    violations = lint_paths(args.paths, root=args.root)
    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    fresh, stale = filter_baselined(violations, baseline)
    if args.update_baseline:
        entries = [
            {
                "rule": v.rule,
                "path": v.path,
                "symbol": v.symbol,
                "reason": "TODO: justify or fix",
            }
            for v in sorted(fresh, key=lambda v: v.key())
        ]
        entries.extend(
            {"rule": r, "path": p, "symbol": s, "reason": baseline[(r, p, s)]}
            for (r, p, s) in sorted(baseline)
            if (r, p, s) not in stale
        )
        with open(args.baseline, "w") as f:
            json.dump(sorted(entries, key=lambda e: (e["path"], e["rule"])), f,
                      indent=2)
            f.write("\n")
        print(f"seacheck: baseline updated ({len(entries)} entries)")
        return 0
    for key in stale:
        print(
            "seacheck: warning: stale baseline entry "
            f"{key[0]} {key[1]} {key[2]} (fixed? prune it)",
            file=sys.stderr,
        )
    for v in sorted(fresh, key=lambda v: (v.path, v.line)):
        print(v.render())
    n_base = len(violations) - len(fresh)
    if fresh:
        print(
            f"seacheck: {len(fresh)} violation(s) "
            f"({n_base} baselined, {len(RULES)} rules)"
        )
        return 1
    print(
        f"seacheck: clean ({n_base} baselined accepted violation(s), "
        f"{len(RULES)} rules)"
    )
    return 0


def _cmd_rules(_args: argparse.Namespace) -> int:
    for rule_id, doc in sorted(RULES.items()):
        print(f"{rule_id}: {doc}")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="seacheck")
    sub = parser.add_subparsers(dest="cmd", required=True)
    lint = sub.add_parser("lint", help="run the invariant rules")
    lint.add_argument("paths", nargs="+")
    lint.add_argument("--root", default=os.getcwd())
    lint.add_argument("--baseline", default=DEFAULT_BASELINE)
    lint.add_argument("--no-baseline", action="store_true")
    lint.add_argument(
        "--update-baseline",
        action="store_true",
        help="accept current findings into the baseline (reasons: TODO)",
    )
    lint.set_defaults(func=_cmd_lint)
    rules = sub.add_parser("rules", help="list rules")
    rules.set_defaults(func=_cmd_rules)
    args = parser.parse_args(argv)
    return args.func(args)
