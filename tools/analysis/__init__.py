"""graftlint — the repo's first-party JAX-hazard + concurrency linter.

AST-based and repo-aware: rules consult a project-wide function index,
jit-reachability with interprocedural taint, a logging-function
closure, (round 15) the concurrency layer — thread entry-point
discovery, per-function execution contexts, lock inventories, guard
regions and a blocking-call closure — and (round 18) the
compile-surface dataflow layer — shape/dtype-determining parameters
of every jit root propagated up the call graph, with bounded/unbounded
origin classification of the values reaching them (see
:mod:`tools.analysis.astutil` / :mod:`tools.analysis.rules` /
:mod:`tools.analysis.concurrency` /
:mod:`tools.analysis.compilesurface`).  Run it as::

    python -m tools.analysis racon_tpu tests tools
    python -m tools.analysis --selftest        # fixture-based rule tests
    python -m tools.analysis --list            # rule inventory
    python -m tools.analysis --json PATH       # machine JSON on stdout
    python -m tools.analysis --json out.json PATH   # ...to a CI artifact
    python -m tools.analysis --changed-only PATH    # git-diff set + import
                                               # neighbors (CI gate mode)
    python -m tools.analysis --timings PATH    # per-rule seconds to stderr
    python -m tools.analysis --rules-md        # README rule table (gated
                                               # by --check-readme README.md)

Suppression: a finding is silenced by a pragma **with a reason** on the
finding line or the line above::

    except Exception:  # graftlint: disable=swallowed-exception (probe)

A pragma without a reason does not suppress (the finding is reported
with a note), so every escape documents its justification.  Exit code 0
means zero unsuppressed findings.

The runtime half of the tool lives in ``racon_tpu/sanitize.py``
(``RACON_TPU_SANITIZE=1``): SWAR int32 shadow execution, kernel-output
canaries, the jit-retrace phase budget, the pipeline queue watchdog,
and the lock-order witness over the project's named locks (cycle =
potential deadlock, reported with the stack of every edge at process
exit).
"""

from __future__ import annotations

import pathlib
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .astutil import Module, Project, load_module
from .rules import ALL_RULES, RULES_BY_NAME, Finding, Rule

_PRAGMA = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_\-,\s]+?)"
    r"(?:\s*\((?P<reason>[^)]*)\))?\s*$")

EXCLUDE_PARTS = {"__pycache__", "fixtures", ".git"}


def pragma_rules(line: str) -> Optional[Tuple[List[str], str]]:
    """(rule names, reason) of a pragma on ``line``, else None."""
    m = _PRAGMA.search(line)
    if not m:
        return None
    rules = [r.strip() for r in m.group(1).split(",") if r.strip()]
    return rules, (m.group("reason") or "").strip()


def collect_files(paths: Sequence[str]) -> List[pathlib.Path]:
    files: List[pathlib.Path] = []
    for raw in paths:
        p = pathlib.Path(raw)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not (set(f.parts) & EXCLUDE_PARTS):
                    files.append(f)
        elif p.suffix == ".py":
            files.append(p)
        else:
            raise FileNotFoundError(f"not a Python file or directory: {raw}")
    return files


def _rel(path: pathlib.Path) -> str:
    try:
        return path.resolve().relative_to(
            pathlib.Path.cwd().resolve()).as_posix()
    except ValueError:
        return path.as_posix()


def load_project(paths: Sequence[str]) -> Project:
    return Project([load_module(f, _rel(f)) for f in collect_files(paths)])


def apply_pragmas(module: Module,
                  findings: Iterable[Finding]) -> Tuple[List[Finding],
                                                        List[Finding]]:
    """Split findings into (reported, suppressed) per the module's
    pragmas. Unknown rule names in pragmas and missing reasons become
    extra findings — the pragma escape polices itself."""
    reported: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        verdict = None
        for line_no in (f.line, f.line - 1):
            parsed = pragma_rules(module.line(line_no))
            if parsed is None:
                continue
            rules, reason = parsed
            if f.rule in rules:
                verdict = (line_no, reason)
                break
        if verdict is None:
            reported.append(f)
        elif not verdict[1]:
            f.message += " [pragma present but missing its (reason)]"
            reported.append(f)
        else:
            f.pragma = verdict[1]
            suppressed.append(f)
    return reported, suppressed


def check_pragma_hygiene(module: Module) -> List[Finding]:
    """Pragmas naming unknown rules are themselves findings (a typo'd
    pragma silently suppresses nothing — surface it)."""
    out: List[Finding] = []
    for i, line in enumerate(module.lines, 1):
        parsed = pragma_rules(line)
        if parsed is None:
            continue
        for rule in parsed[0]:
            if rule not in RULES_BY_NAME:
                out.append(Finding(
                    "pragma", module.rel, i,
                    f"pragma names unknown rule {rule!r} (known: "
                    f"{', '.join(sorted(RULES_BY_NAME))})"))
    return out


# ------------------------------------------------------- incremental mode

# a change to the analyzer itself or to a registry EVERY rule reads
# invalidates any incremental skip: fall back to the full run
_FULL_RUN_TRIGGERS = ("tools/analysis/", "racon_tpu/contracts.py",
                      "racon_tpu/flags.py")


def changed_rels() -> Optional[set]:
    """Repo-relative ``.py`` files changed vs HEAD (worktree diff +
    untracked), per git.  None = incremental mode unavailable (no git,
    or the analyzer/registries themselves changed) — callers fall back
    to the full run.  Paths come from git, so the caller must run from
    the repo root (CI does)."""
    import subprocess
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            capture_output=True, text=True, timeout=30)
        extra = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return None
    if diff.returncode != 0 or extra.returncode != 0:
        return None
    rels = {line.strip()
            for line in (diff.stdout + extra.stdout).splitlines()
            if line.strip().endswith(".py")}
    if any(r.startswith(_FULL_RUN_TRIGGERS) for r in rels):
        return None
    return rels


def expand_changed(project: Project, changed: set) -> set:
    """The changed set plus its import neighbors in BOTH directions:
    modules a changed module imports (its contracts may have moved)
    and modules importing a changed one (their use sites may have
    broken).  One hop — the project index the rules consult is still
    built over the WHOLE tree, so deeper effects (jit taint, lock
    closures) stay correct; the hop only widens which modules get
    re-checked."""
    prov = project.provenance()
    dotted_to_rel = {d: m.rel for d, m in prov._by_dotted.items()}
    imports_of = {}
    for m in project.modules:
        cands = set()
        for (mod, member) in prov.imports(m).values():
            cands.add(mod)
            if member:
                cands.add(f"{mod}.{member}")
        imports_of[m.rel] = cands
    changed_dotted = {d for d, r in dotted_to_rel.items() if r in changed}
    out = set(changed)
    for m in project.modules:
        if imports_of[m.rel] & changed_dotted:
            out.add(m.rel)
    for r in changed:
        for cand in imports_of.get(r, ()):
            if cand in dotted_to_rel:
                out.add(dotted_to_rel[cand])
    return out


def run(paths: Sequence[str],
        rules: Optional[Sequence[Rule]] = None,
        scoped: bool = True,
        only: Optional[set] = None,
        timings: Optional[Dict[str, float]] = None,
        ) -> Tuple[List[Finding], List[Finding]]:
    """Lint ``paths``; returns (reported, suppressed). ``scoped=False``
    disables per-rule path scoping (the selftest fixtures live outside
    the rules' production scopes).  ``only`` restricts which modules'
    findings are computed (the full project is still parsed and
    indexed — incremental mode narrows checking, never the rules'
    view).  ``timings`` accumulates per-rule wall seconds in place."""
    import time
    project = load_project(paths)
    if only is not None:
        only = expand_changed(project, only)
    rules = list(rules if rules is not None else ALL_RULES)
    reported: List[Finding] = []
    suppressed: List[Finding] = []
    for module in project.modules:
        if only is not None and module.rel not in only:
            continue
        found: List[Finding] = []
        for rule in rules:
            if scoped and not rule.applies(module.rel):
                continue
            if timings is None:
                found.extend(rule.check(project, module))
            else:
                t0 = time.perf_counter()
                found.extend(rule.check(project, module))
                timings[rule.name] = (timings.get(rule.name, 0.0)
                                      + time.perf_counter() - t0)
        rep, sup = apply_pragmas(module, found)
        reported.extend(rep)
        suppressed.extend(sup)
        reported.extend(check_pragma_hygiene(module))
    reported.sort(key=lambda f: (f.rel, f.line, f.rule))
    return reported, suppressed


# ------------------------------------------------------- README generation

_TABLE_NOTE = ("<!-- generated by `python -m tools.analysis --rules-md` "
               "from tools/analysis — do not edit by hand -->")


def rules_md() -> str:
    """The README "Static analysis" rule table, generated from the live
    rule registry (one row per rule, registration order) — the same
    generate-and-gate mechanism as the flags table."""
    lines = [_TABLE_NOTE, "",
             "| rule | catches |",
             "| --- | --- |"]
    for rule in ALL_RULES:
        lines.append(f"| `{rule.name}` | {rule.blurb} |")
    return "\n".join(lines) + "\n"


def check_readme(path: str) -> bool:
    """True when ``path`` contains the current generated rule table
    verbatim (the lint shard runs this so the README cannot drift)."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return rules_md() in fh.read()
    except OSError:
        return False


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list" in argv:
        for rule in ALL_RULES:
            doc = (rule.__doc__ or "").strip().splitlines()[0]
            print(f"{rule.name}: {doc}")
        return 0
    if "--selftest" in argv:
        from .selftest import run_selftest
        return run_selftest()
    if "--rules-md" in argv:
        print(rules_md(), end="")
        return 0
    if "--check-readme" in argv:
        i = argv.index("--check-readme")
        target = (argv[i + 1] if i + 1 < len(argv) else "README.md")
        if check_readme(target):
            return 0
        print("README static-analysis rule table is stale — regenerate "
              "with `python -m tools.analysis --rules-md` and paste the "
              "output", file=sys.stderr)
        return 1
    quiet = "--quiet" in argv
    changed_only = "--changed-only" in argv
    want_timings = "--timings" in argv
    as_json = "--json" in argv
    json_path: Optional[str] = None
    if as_json:
        # `--json FILE.json` writes the machine-readable record to a CI
        # artifact file (diffable across runs) while the human findings
        # keep printing; bare `--json` prints the JSON to stdout.  The
        # artifact slot is STRICTLY `.json`-suffixed: any other token
        # stays a scan path, so a mistyped tree fails the run loudly
        # instead of being silently consumed as the output file.
        i = argv.index("--json")
        if i + 1 < len(argv) and argv[i + 1].endswith(".json"):
            json_path = argv.pop(i + 1)
    paths = [a for a in argv if not a.startswith("--")]
    if not paths:
        print("usage: python -m tools.analysis [--selftest|--list|"
              "--rules-md|--check-readme [README]|--changed-only|"
              "--timings|--json [FILE.json]] PATH [PATH...]",
              file=sys.stderr)
        return 2
    only: Optional[set] = None
    if changed_only:
        only = changed_rels()
        if only is None:
            print("graftlint: --changed-only unavailable (no git, or "
                  "the analyzer/registries changed) — full run",
                  file=sys.stderr)
        elif not quiet:
            print(f"graftlint: --changed-only over {len(only)} changed "
                  f"file(s) + import neighbors", file=sys.stderr)
    timings: Optional[Dict[str, float]] = {} if want_timings else None
    try:
        reported, suppressed = run(paths, only=only, timings=timings)
    except (FileNotFoundError, SyntaxError) as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2
    if timings is not None:
        for name, secs in sorted(timings.items(),
                                 key=lambda kv: -kv[1]):
            print(f"graftlint timing: {name} {secs:.2f}s",
                  file=sys.stderr)
    if as_json:
        # machine-readable output for CI annotation/aggregation: every
        # finding (reported AND pragma-suppressed, distinguished by the
        # pragma field) as one JSON object
        import json
        blob = json.dumps({
            "findings": [f.as_dict() for f in reported],
            "suppressed": [f.as_dict() for f in suppressed],
        }, indent=1)
        if json_path is not None:
            with open(json_path, "w", encoding="utf-8") as fh:
                fh.write(blob + "\n")
            for f in reported:
                print(f)
        else:
            print(blob)
    else:
        for f in reported:
            print(f)
    if not quiet:
        print(f"graftlint: {len(reported)} finding(s), "
              f"{len(suppressed)} suppressed by pragma", file=sys.stderr)
    return 1 if reported else 0
