"""The project lint pass gates the repo itself.

Every pre-existing violation is either fixed or carries an auditable
``# wql: allow(<rule>)`` pragma, so the package must lint clean — this
test keeps it that way between CI runs (the workflow's lint job runs
the same command).
"""

import ast
from pathlib import Path

import pytest

from tools.check import check_paths

REPO = Path(__file__).resolve().parent.parent


def test_package_is_lint_clean():
    violations = check_paths([str(REPO / "worldql_server_tpu")])
    assert violations == [], "\n" + "\n".join(v.render() for v in violations)


def test_tooling_is_lint_clean():
    violations = check_paths([str(REPO / "tools")])
    assert violations == [], "\n" + "\n".join(v.render() for v in violations)


@pytest.mark.parametrize("where", [
    "worldql_server_tpu", "tests", "tools", "chip_smoke.py",
])
def test_no_zmq_context_is_termed_bare(where):
    """A zmq context ends by ``destroy(linger=0)``, which closes its
    sockets first. ``term()`` waits, with no limit, for every socket of
    the context to be closed by someone: one left open (a failed test,
    a cancelled handshake) and a server does not come down on SIGTERM;
    tier-1 lost 1,250 s to one such call (PERF.md, PR 43). Nothing else
    here has a ``term`` method, so the name alone is the rule.
    (``benchmark/worker.py`` keeps one: not every PR may edit it.)"""
    root = REPO / where
    found = [
        f"{path.relative_to(REPO)}:{node.lineno}"
        for path in ([root] if root.is_file() else sorted(root.rglob("*.py")))
        for node in ast.walk(ast.parse(path.read_text()))
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute) and node.func.attr == "term"
    ]
    assert found == [], f"bare term(): {found}; use destroy(linger=0)"


def test_no_runtime_artifacts_committed():
    """Runtime artifacts must never be committed: a stray ``worldql.db``
    (the default sqlite store, created by any server run in the repo
    root) has slipped into the tree twice now, and a committed WAL
    segment would replay into someone else's store at boot. Guard the
    tracked file list itself — .gitignore only helps before the fact."""
    import subprocess

    try:
        tracked = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True,
            text=True, timeout=30, check=True,
        ).stdout.splitlines()
    except Exception:
        pytest.skip("not a git checkout")
    offenders = [
        f for f in tracked
        if f.endswith((".db", ".sqlite", ".db-journal"))
        or f.rsplit("/", 1)[-1].startswith("wal-") and f.endswith(".log")
    ]
    assert offenders == [], (
        f"runtime artifacts committed: {offenders} — delete them and "
        "keep .gitignore covering *.db / wal-*.log"
    )


def test_package_is_domain_clean():
    """The interprocedural tier (rules 21-24) gates the repo too: the
    whole package goes into ONE call graph and must come back clean —
    every finding either fixed (plane.py's control-socket retry) or
    carrying an auditable happens-before pragma (the WAL writer's
    single-owner handoff)."""
    import os

    from tools.check.domains import check_program_paths

    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        violations = check_program_paths(
            [str(REPO / "worldql_server_tpu")], cache=False,
        )
    finally:
        os.chdir(cwd)
    assert violations == [], "\n" + "\n".join(
        v.render() for v in violations
    )
