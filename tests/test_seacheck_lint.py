"""seacheck layer 1 (AST invariant linter) — rule behaviour on the
known-bad fixtures, suppression + baseline mechanics, and the
acceptance-criteria demos: deliberately introducing each violation class
turns the CI gate (exit code) red, while the real tree lints clean."""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from seacheck import cli  # noqa: E402
from seacheck.astutil import annotate_parents  # noqa: E402
from seacheck.rules import (  # noqa: E402
    atomic_commit,
    invalidation,
    lock_discipline,
    reservation,
    telemetry_drift,
)
from seacheck.violations import (  # noqa: E402
    SourceFile,
    Violation,
    filter_baselined,
)

FIXTURES = os.path.join(REPO, "tools", "seacheck", "fixtures")


def run_rule(rule, fixture, fake_path):
    """Lint a fixture as if it lived at ``fake_path`` (the rules are
    path-scoped to the data plane)."""
    with open(os.path.join(FIXTURES, fixture)) as f:
        src = f.read()
    tree = ast.parse(src)
    annotate_parents(tree)
    return rule.check(SourceFile(path=fake_path, source=src), tree)


def symbols(violations):
    return {v.symbol for v in violations}


# ---------------------------------------------------------------- rule (a)
def test_reservation_pairing_rule():
    out = run_rule(
        reservation, "bad_reservation.py", "src/repro/core/fixture.py"
    )
    assert symbols(out) == {"leaked_forever", "leaks_on_exception"}
    assert all(v.rule == "reservation-pairing" for v in out)
    # paired_correctly / escapes_to_caller comply; suppressed_leak is
    # silenced by its inline `# seacheck: ignore[...]`


# ---------------------------------------------------------------- rule (b)
def test_atomic_commit_rule():
    out = run_rule(
        atomic_commit, "bad_atomic_commit.py", "src/repro/core/fixture.py"
    )
    assert symbols(out) == {
        "bare_write_to_tier_path",
        "shutil_copy_bypasses_engine",
        "np_save_in_place",
    }
    # tmp+os.replace, the mount API, and reads are all sanctioned


def test_atomic_commit_rule_is_scoped_to_core():
    out = run_rule(
        atomic_commit, "bad_atomic_commit.py", "src/repro/train/feed.py"
    )
    assert out == []


def test_atomic_commit_tmp_destination_is_sanctioned():
    src = "import shutil\ndef stage(src, dst):\n    shutil.copyfile(src, dst + '.sea_tmp')\n"
    tree = ast.parse(src)
    annotate_parents(tree)
    sf = SourceFile(path="src/repro/core/x.py", source=src)
    assert atomic_commit.check(sf, tree) == []


# ---------------------------------------------------------------- rule (c)
def test_invalidation_completeness_rule():
    out = run_rule(
        invalidation, "bad_invalidation.py", "src/repro/core/seafs.py"
    )
    assert symbols(out) == {
        "BadFS.evict_without_invalidation",
        "BadFS.evict_without_fed",
    }
    msgs = {v.symbol: v.message for v in out}
    assert "resolver" in msgs["BadFS.evict_without_invalidation"]


# ---------------------------------------------------------------- rule (d)
def test_telemetry_drift_rule():
    out = run_rule(
        telemetry_drift, "bad_telemetry.py", "src/repro/core/telemetry.py"
    )
    blob = " ".join(v.message for v in out)
    assert "ghost_counter" in blob  # registered but not a field
    assert "unregistered_field" in blob  # field but not registered
    assert "sneaky_counter" in blob  # increments an unregistered name
    assert any("snapshot" in v.message or "snapshot" in v.symbol for v in out)


def test_telemetry_drift_flags_ad_hoc_increments():
    out = run_rule(
        telemetry_drift, "bad_ad_hoc_counter.py", "src/repro/core/engine.py"
    )
    assert len(out) == 1 and "flushed_bytes" in out[0].message


def test_telemetry_drift_spans_registry_and_sites():
    with open(os.path.join(FIXTURES, "bad_spans.py")) as f:
        src = f.read()
    tree = ast.parse(src)
    sf = SourceFile(path="src/repro/core/telemetry.py", source=src)
    out = telemetry_drift.check_tree([(sf, tree)])
    msgs = sorted(v.message for v in out)
    assert len(out) == 2, msgs
    assert "ghost.span" in msgs[0] and "never recorded" in msgs[0]
    assert "stray.span" in msgs[1] and "not registered" in msgs[1]
    # without telemetry.py in the run there is no registry to hold sites to
    other = SourceFile(path="src/repro/core/seafs.py", source=src)
    assert telemetry_drift.check_tree([(other, tree)]) == []


def test_gate_reddens_on_unregistered_span(tmp_path, capsys):
    (tmp_path / "src" / "repro" / "core").mkdir(parents=True)
    (tmp_path / "src" / "repro" / "core" / "telemetry.py").write_text(
        'COUNTERS = {}\nSPANS = {"sea.read": (None, "x")}\n'
        "class Telemetry:\n    def snapshot(self):\n        return COUNTERS\n"
    )
    rc = _gate(
        tmp_path,
        "src/repro/core/fs.py",
        "def f(tel):\n"
        "    with tel.span('sea.read'):\n"
        "        pass\n"
        "    tel.record_span('sea.typo', 1.0)\n",
    )
    assert rc == 1
    assert "'sea.typo' is not registered in SPANS" in capsys.readouterr().out


def test_real_counters_registry_matches_fields():
    """The live COUNTERS table and the Telemetry dataclass agree (the
    lint rule checks this lexically; this checks it at runtime)."""
    import dataclasses

    from repro.core.telemetry import COUNTERS, Telemetry

    scalar = {
        f.name
        for f in dataclasses.fields(Telemetry)
        if not f.name.startswith("_") and f.type in ("int", "float", int, float)
    }
    assert set(COUNTERS) == scalar
    snap = Telemetry().snapshot()
    for name in COUNTERS:
        assert name in snap


# ---------------------------------------------------------------- rule (e)
def test_lock_discipline_rule():
    out = run_rule(
        lock_discipline, "bad_lock_discipline.py", "src/repro/core/seafs.py"
    )
    assert symbols(out) == {
        "BadFS.unlocked_mutation",
        "BadFS.unlocked_method_mutation",
    }
    # locked_mutation is under `with self._lock`; _locked_helper carries
    # `# seacheck: holds-lock`; reads are never checked


# ------------------------------------------------------- baseline mechanics
def test_baseline_filtering_and_staleness():
    v1 = Violation("atomic-commit", "src/a.py", 10, "f", "m")
    v2 = Violation("atomic-commit", "src/b.py", 20, "g", "m")
    baseline = {
        ("atomic-commit", "src/a.py", "f"): "justified",
        ("atomic-commit", "src/gone.py", "h"): "stale entry",
    }
    fresh, stale = filter_baselined([v1, v2], baseline)
    assert fresh == [v2]
    assert stale == [("atomic-commit", "src/gone.py", "h")]


def test_baseline_survives_line_drift():
    # baseline keys are (rule, path, symbol) — moving the code around a
    # file must not resurrect an accepted violation
    v = Violation("atomic-commit", "src/a.py", 999, "f", "m")
    fresh, _ = filter_baselined(
        [v], {("atomic-commit", "src/a.py", "f"): "ok"}
    )
    assert fresh == []


# ------------------------------------------------------------ the CI gate
def test_real_tree_lints_clean():
    rc = cli.main(["lint", "--root", REPO, os.path.join(REPO, "src", "repro")])
    assert rc == 0


def _gate(tmp_path, rel, source):
    """Exit code of the lint gate over a tree containing one bad file
    planted at a data-plane path."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(source)
    return cli.main(
        ["lint", "--root", str(tmp_path), "--no-baseline", str(tmp_path)]
    )


def test_gate_reddens_on_unreleased_reservation(tmp_path, capsys):
    rc = _gate(
        tmp_path,
        "src/repro/core/bad.py",
        "def f(ledger, root, n):\n"
        "    res = ledger.try_reserve(root, n, capacity=10, required=1)\n"
        "    do_write(root)\n"
        "    return True\n"
        "def do_write(root): ...\n",
    )
    assert rc == 1
    assert "reservation-pairing" in capsys.readouterr().out


def test_gate_reddens_on_bare_write(tmp_path, capsys):
    rc = _gate(
        tmp_path,
        "src/repro/core/bad.py",
        "def f(real, data):\n"
        "    with open(real, 'w') as fh:\n"
        "        fh.write(data)\n",
    )
    assert rc == 1
    assert "atomic-commit" in capsys.readouterr().out


def test_gate_green_on_clean_file(tmp_path):
    rc = _gate(
        tmp_path,
        "src/repro/core/fine.py",
        "import os\n"
        "def f(real, data):\n"
        "    tmp = real + '.sea_tmp'\n"
        "    with open(tmp, 'wb') as fh:\n"
        "        fh.write(data)\n"
        "    os.replace(tmp, real)\n",
    )
    assert rc == 0


def test_gate_reddens_on_syntax_error(tmp_path, capsys):
    rc = _gate(tmp_path, "src/repro/core/broken.py", "def f(:\n")
    assert rc == 1
    assert "parse-error" in capsys.readouterr().out


def test_cli_entrypoint_runs_from_scratch():
    """The CI invocation exactly: stdlib-only module run, clean tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "seacheck", "lint", "src/repro"],
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": "src" + os.pathsep + "tools"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_rules_subcommand_lists_all_five(capsys):
    assert cli.main(["rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in (
        "reservation-pairing",
        "atomic-commit",
        "invalidation-completeness",
        "telemetry-drift",
        "lock-discipline",
    ):
        assert rule_id in out


def test_update_baseline_roundtrip(tmp_path, capsys):
    p = tmp_path / "src" / "repro" / "core" / "bad.py"
    p.parent.mkdir(parents=True)
    p.write_text("def f(r, d):\n    with open(r, 'w') as fh:\n        fh.write(d)\n")
    bl = tmp_path / "baseline.json"
    rc = cli.main(
        [
            "lint",
            "--root",
            str(tmp_path),
            "--baseline",
            str(bl),
            "--update-baseline",
            str(tmp_path),
        ]
    )
    assert rc == 0
    entries = json.loads(bl.read_text())
    assert len(entries) == 1 and entries[0]["rule"] == "atomic-commit"
    # with the finding accepted, the gate is green
    rc = cli.main(
        ["lint", "--root", str(tmp_path), "--baseline", str(bl), str(tmp_path)]
    )
    assert rc == 0
