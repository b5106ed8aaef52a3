"""graftlint (tools/analysis) — rule self-tests, pragma semantics, the
repo-wide zero-findings gate, and the flags-registry contract."""

import pathlib
import re
import subprocess
import sys

import pytest

from racon_tpu import flags

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_selftest_fixtures():
    """Every rule fires on its seeded fixture and stays quiet on the
    clean twin (exact counts — see tools/analysis/selftest.py)."""
    sys.path.insert(0, str(REPO))
    try:
        from tools.analysis.selftest import run_selftest
        assert run_selftest(verbose=False) == 0
    finally:
        sys.path.remove(str(REPO))


def test_repo_is_clean():
    """The acceptance gate: zero unsuppressed findings over racon_tpu/
    (and the support trees CI lints)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--quiet",
         "racon_tpu", "tests", "tools"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_pragma_without_reason_does_not_suppress(tmp_path):
    sys.path.insert(0, str(REPO))
    try:
        from tools.analysis import run
        bad = tmp_path / "m.py"
        bad.write_text(
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:  # graftlint: disable=swallowed-exception\n"
            "        pass\n")
        reported, suppressed = run([str(bad)], scoped=False)
        assert len(reported) == 1 and not suppressed
        assert "missing its (reason)" in reported[0].message

        good = tmp_path / "ok.py"
        good.write_text(
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:"
            "  # graftlint: disable=swallowed-exception (why)\n"
            "        pass\n")
        reported, suppressed = run([str(good)], scoped=False)
        assert not reported and len(suppressed) == 1
    finally:
        sys.path.remove(str(REPO))


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "m.py"
    bad.write_text("import os\n"
                   "x = os.environ.get('RACON_TPU_BOGUS', '')\n")
    rc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--quiet", str(bad)],
        cwd=REPO, capture_output=True, text=True)
    assert rc.returncode == 1
    assert "env-flag-registry" in rc.stdout


def test_json_output(tmp_path):
    """--json emits one machine-readable record per finding (rule,
    path, line, message, pragma state) for CI annotation."""
    import json

    src = tmp_path / "m.py"
    src.write_text(
        "import os\n"
        "x = os.environ.get('RACON_TPU_BOGUS', '')\n"
        "y = os.environ.get('RACON_TPU_ALSO', '')"
        "  # graftlint: disable=env-flag-registry (json fixture)\n")
    rc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--json", "--quiet",
         str(src)],
        cwd=REPO, capture_output=True, text=True)
    assert rc.returncode == 1
    data = json.loads(rc.stdout)
    assert len(data["findings"]) == 1
    f = data["findings"][0]
    assert f["rule"] == "env-flag-registry" and f["line"] == 2
    assert f["path"].endswith("m.py") and f["pragma"] is None
    assert "RACON_TPU_BOGUS" in f["message"]
    sup = data["suppressed"]
    assert len(sup) == 1 and sup[0]["pragma"] == "json fixture"


# ------------------------------------------------------- concurrency layer

def test_thread_entry_point_discovery():
    """Regression: the analyzer's thread discovery must see the repo's
    real concurrent surface — the chip-worker drain closure, the serve
    connection/worker/heartbeat threads, the lease keeper, and the
    pipelined polisher's producer."""
    sys.path.insert(0, str(REPO))
    try:
        from tools.analysis import load_project
        project = load_project([str(REPO / "racon_tpu")])
        roots = {fi.qualname for fi in project.thread_roots()}
    finally:
        sys.path.remove(str(REPO))
    expected = {
        "ShardRunner._drain.body",        # in-process chip workers
        "PolishServer._handle_conn",      # serve connection handlers
        "PolishServer._worker_loop",      # serve job workers
        "PolishServer._heartbeat_loop",
        "LeaseKeeper._run",               # lease mtime keeper
        "Heartbeat._tick",
        "QueueWatchdog._watch",
        "Polisher.run.produce",           # pipelined layer producer
    }
    assert expected <= roots, f"missing thread roots: {expected - roots}"


def test_exec_contexts_see_chip_worker_and_main():
    """The drain loop runs both on the main thread (single-slot) and on
    chip-worker threads — the context propagation must see both, which
    is exactly what arms lock-discipline over the shared manifest."""
    sys.path.insert(0, str(REPO))
    try:
        from tools.analysis import load_project
        from tools.analysis.astutil import MAIN_CONTEXT
        project = load_project([str(REPO / "racon_tpu")])
        ctx = project.exec_contexts()
        by_qual = {fi.qualname: ctx[id(fi)] for fi in project.functions}
    finally:
        sys.path.remove(str(REPO))
    drain_ctx = by_qual["ShardRunner._drain_loop_inner"]
    assert MAIN_CONTEXT in drain_ctx
    assert "thread:ShardRunner._drain.body" in drain_ctx


def test_every_pragma_carries_a_reason():
    """Repo-wide audit: a pragma without a (reason) does not suppress,
    so any reasonless pragma is dead weight that silently stops
    documenting its escape — fail it here, at the source."""
    sys.path.insert(0, str(REPO))
    try:
        from tools.analysis import EXCLUDE_PARTS, pragma_rules
    finally:
        sys.path.remove(str(REPO))
    bad = []
    for path in sorted(REPO.rglob("*.py")):
        # fixtures stay out: seeded-violation files deliberately carry
        # a reasonless pragma to prove it does NOT suppress
        if set(path.parts) & EXCLUDE_PARTS:
            continue
        for i, line in enumerate(
                path.read_text(encoding="utf-8").splitlines(), 1):
            if "graftlint" not in line or "disable=" not in line:
                continue
            parsed = pragma_rules(line)
            if parsed is not None and not parsed[1].strip():
                bad.append(f"{path.relative_to(REPO)}:{i}")
    assert not bad, f"pragmas without a reason: {bad}"


# ------------------------------------------------------------ flags registry

def test_undeclared_flag_raises():
    with pytest.raises(KeyError, match="not declared"):
        # graftlint: disable=env-flag-registry (negative test: must raise)
        flags.get_bool("RACON_TPU_NOT_A_FLAG")


def test_declared_flags_have_docs():
    for f in flags.REGISTRY.values():
        assert f.name.startswith("RACON_TPU_")
        assert f.help.strip()


def test_bool_semantics(monkeypatch):
    monkeypatch.setenv("RACON_TPU_SWAR", "0")
    assert not flags.get_bool("RACON_TPU_SWAR")
    monkeypatch.setenv("RACON_TPU_SWAR", "off")
    assert not flags.get_bool("RACON_TPU_SWAR")
    monkeypatch.setenv("RACON_TPU_SWAR", "1")
    assert flags.get_bool("RACON_TPU_SWAR")
    monkeypatch.delenv("RACON_TPU_SWAR")
    assert flags.get_bool("RACON_TPU_SWAR")  # registry default


def test_readme_table_is_current():
    """The README 'Environment flags' section must match the generated
    table exactly (regenerate with `python -m racon_tpu.flags`)."""
    assert flags.check_readme(str(REPO / "README.md")), \
        "stale README flags table — run `python -m racon_tpu.flags`"


_README_TREES = ("racon_tpu/", "tests/", "tools/", "benchmark/", "ci/")
_README_ROOT_SUFFIXES = (".py", ".json", ".jsonl", ".md")


def _readme_file_tokens(text):
    """Backticked README tokens that name a file of this repository: a
    path under one of the source trees, or a root-level name with a
    source/record suffix (a trailing ``:line`` is cut)."""
    out = []
    for tok in re.findall(r"`([^`\s]+)`", text):
        tok = re.sub(r":\d+(-\d+)?$", "", tok)
        if tok.startswith(_README_TREES) or (
                "/" not in tok and tok.endswith(_README_ROOT_SUFFIXES)):
            out.append(tok)
    return out


def test_readme_names_only_files_that_exist():
    """Every file the README names exists in the tree, so a deletion
    cannot leave the first document a reader opens pointing at it."""
    tokens = _readme_file_tokens((REPO / "README.md").read_text())
    assert len(tokens) >= 20, tokens  # the extractor still sees paths
    missing = sorted({t for t in tokens if not (REPO / t).exists()})
    assert not missing, missing
