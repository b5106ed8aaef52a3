"""The contract pack: string-keyed registries and lifecycle machines
become statically checked invariants.

Five rules, all checking emission/consumption sites against the ONE
declarative registry ``racon_tpu/contracts.py`` (stdlib-only, imported
by the rules the same way env-flag-registry loads the flag registry):

| rule                | catches                                        |
| ------------------- | ---------------------------------------------- |
| metric-registry     | metrics.inc/set_gauge/add_time names that      |
|                     | break the grammar, are unregistered, or carry  |
|                     | an unregistered dynamic (f-string) prefix      |
| span-registry       | obs.span names not declared in SPANS (a silent |
|                     | rename orphans the report's span-timer reads)  |
| fault-site-registry | FAULT_SITES entries with no faults.check site  |
|                     | or no test that injects them                   |
| schema-coherence    | report-section emitters whose dict keys drift  |
|                     | from the schema key sets — both directions     |
| state-transition    | journal appends / job+shard state writes that  |
|                     | mint undeclared states or encode undeclared    |
|                     | machine edges (e.g. collected->running)        |

String names are resolved through project-wide constant provenance
(:class:`tools.analysis.astutil.StringProvenance`): a literal, a
module constant, a cross-module ``alias.NAME`` chain, or an f-string's
literal prefix.  Unresolvable names are skipped, never guessed.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from .astutil import (Module, Project, dotted, fstring_prefix,
                      last_segment)
from .rules import Finding, Rule


def _contracts():
    """The live registry (racon_tpu.contracts is stdlib-only, so this
    is safe anywhere the linter runs); None disables the pack."""
    try:
        import racon_tpu.contracts as c
        return c
    # graftlint: disable=swallowed-exception (lint must run without the repo importable)
    except Exception:
        return None


# ---------------------------------------------------------- metric-registry

class MetricRegistryRule(Rule):
    """Every ``metrics.inc/set_gauge/add_time`` name must parse under
    the metric grammar and land in the registry: static names in
    ``contracts.METRICS``, dynamic (f-string) names under a registered
    ``contracts.DYNAMIC_METRIC_PREFIXES`` prefix.  Names the resolver
    cannot prove (a plain variable, e.g. the span exit's
    ``self.name``) are skipped — the span-registry rule closes that
    hole at the point the name is minted."""

    name = "metric-registry"
    blurb = ("`metrics.inc/set_gauge/add_time` names that break the metric grammar, are unregistered, or carry an unregistered dynamic prefix (`racon_tpu/contracts.py`)")
    EMITTERS = {"inc", "set_gauge", "add_time"}

    def applies(self, rel: str) -> bool:
        return (rel.startswith("racon_tpu/")
                and rel != "racon_tpu/obs/metrics.py"
                and rel.endswith(".py"))

    def check(self, project: Project, module: Module) -> List[Finding]:
        c = _contracts()
        if c is None:
            return []
        prov = project.provenance()
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = dotted(node.func)
            if last_segment(fn) not in self.EMITTERS:
                continue
            if fn not in self.EMITTERS \
                    and not fn.endswith(tuple("metrics." + e
                                              for e in self.EMITTERS)):
                continue
            arg0 = node.args[0]
            if isinstance(arg0, ast.JoinedStr):
                prefix = fstring_prefix(arg0)
                if not prefix:
                    out.append(self.finding(
                        module, node,
                        f"dynamic metric name passed to `{fn}` has no "
                        f"literal prefix — nothing to check against "
                        f"contracts.DYNAMIC_METRIC_PREFIXES"))
                elif not prefix.startswith(
                        tuple(c.DYNAMIC_METRIC_PREFIXES)):
                    out.append(self.finding(
                        module, node,
                        f"dynamic metric prefix {prefix!r} is not "
                        f"registered in contracts."
                        f"DYNAMIC_METRIC_PREFIXES"))
                continue
            name = prov.resolve_str(module, arg0)
            if name is None:
                continue
            if not c.METRIC_NAME_RE.match(name):
                out.append(self.finding(
                    module, node,
                    f"metric name {name!r} violates the name grammar "
                    f"(lowercase dotted segments, contracts."
                    f"METRIC_NAME_RE)"))
            elif name not in c.METRICS:
                out.append(self.finding(
                    module, node,
                    f"metric {name!r} is not registered in "
                    f"racon_tpu/contracts.py METRICS"))
        return out


# ------------------------------------------------------------ span-registry

class SpanRegistryRule(Rule):
    """Every ``obs.span`` name must be declared in ``contracts.SPANS``.
    Span exits land in the metrics timers keyed by the span name and
    the run report's dispatch-vs-fetch splits read those timers BY
    NAME — so a silently renamed span zeroes a report column without
    failing anything.  Now the rename fails here."""

    name = "span-registry"
    blurb = ("`obs.span` names not declared in `contracts.SPANS` — a silent span rename orphans the report's span-timer reads")
    SPAN_CALLS = {"obs.span", "span", "trace.span", "obs.trace.span"}

    def applies(self, rel: str) -> bool:
        return (rel.startswith("racon_tpu/") and rel.endswith(".py")
                and not rel.startswith("racon_tpu/obs/"))

    def check(self, project: Project, module: Module) -> List[Finding]:
        c = _contracts()
        if c is None:
            return []
        prov = project.provenance()
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if dotted(node.func) not in self.SPAN_CALLS:
                continue
            name = prov.resolve_str(module, node.args[0])
            if name is not None and name not in c.SPANS:
                out.append(self.finding(
                    module, node,
                    f"span {name!r} is not declared in "
                    f"racon_tpu/contracts.py SPANS — the report's "
                    f"span-timer reads would silently miss it"))
        return out


# ----------------------------------------------------- fault-site-registry

class FaultSiteRegistryRule(Rule):
    """Every declared fault site must have BOTH halves of its chaos
    contract: a ``faults.check("<site>")`` injection point somewhere in
    the tree, and at least one test that actually injects it (a
    ``"<site>:"`` spec literal in tests/).  A site with no check call
    is dead registry; a site no test injects is an untested failure
    path — the kind that works until the one production day it
    matters.  Anchored to the FAULT_SITES declaration so each site's
    finding lands on its own tuple element line."""

    name = "fault-site-registry"
    blurb = ("a declared fault site with no `faults.check` injection point, or one no test injects")

    def applies(self, rel: str) -> bool:
        return rel == "racon_tpu/contracts.py"

    def check(self, project: Project, module: Module) -> List[Finding]:
        prov = project.provenance()
        assign = None
        for node in module.tree.body:
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target]
                       if isinstance(node, ast.AnnAssign) else [])
            if any(isinstance(t, ast.Name) and t.id == "FAULT_SITES"
                   for t in targets):
                assign = node
                break
        if assign is None or not isinstance(assign.value,
                                            (ast.Tuple, ast.List)):
            return []
        sites: List[Tuple[str, ast.AST]] = []
        for elt in assign.value.elts:
            v = prov.resolve_str(module, elt)
            if v is not None:
                sites.append((v, elt))
        checked = set()
        for m in project.modules:
            for node in ast.walk(m.tree):
                if isinstance(node, ast.Call) and node.args:
                    fn = dotted(node.func)
                    if fn and (fn == "check"
                               or fn.endswith("faults.check")):
                        v = prov.resolve_str(m, node.args[0])
                        if v is not None:
                            checked.add(v)
        # injection specs live in tests; a single-file selftest project
        # has no tests/ modules, so the fixture itself is scanned
        test_mods = [m for m in project.modules
                     if m.rel.startswith("tests/")]
        if not test_mods:
            test_mods = list(project.modules)
        injected = set()
        for m in test_mods:
            for node in ast.walk(m.tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    for site, _ in sites:
                        if site + ":" in node.value:
                            injected.add(site)
        out: List[Finding] = []
        for site, elt in sites:
            if site not in checked:
                out.append(self.finding(
                    module, elt,
                    f"fault site {site!r} is declared but has no "
                    f"faults.check({site!r}) injection point"))
            elif site not in injected:
                out.append(self.finding(
                    module, elt,
                    f"fault site {site!r} has an injection point but "
                    f"no test injects '{site}:<kind>' — the failure "
                    f"path is untested"))
        return out


# ------------------------------------------------------- schema-coherence

class SchemaCoherenceRule(Rule):
    """Both directions of the report-schema contract: every key a
    section emitter's returned dict literal carries must be schema-
    known (``contracts.SECTION_KEYS`` / ``TOP_KEYS``), and every
    schema-required key must be emitted.  A key someone forgot to
    retire after a schema bump (stale v<=N emission) fails the first
    direction; a schema bump without its emitter fails the second —
    both used to be grep-and-pray."""

    name = "schema-coherence"
    blurb = ("report-section emitters whose dict keys drift from the schema key sets — both directions, stale retired keys included")

    def applies(self, rel: str) -> bool:
        c = _contracts()
        if c is None:
            return False
        return rel in {r for r, _ in c.SECTION_EMITTERS.values()}

    def check(self, project: Project, module: Module) -> List[Finding]:
        c = _contracts()
        if c is None:
            return []
        known = c.schema_keys()
        funcs = {node.name: node for node in module.tree.body
                 if isinstance(node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))}
        out: List[Finding] = []
        for section, (_, fname) in sorted(c.SECTION_EMITTERS.items()):
            fn = funcs.get(fname)
            if fn is None:
                continue
            if section == "top":
                emitted = self._top_keys(fn)
            elif section == "dispatch_fetch":
                emitted = self._nested_keys(fn, "dispatch_fetch")
            else:
                emitted = self._return_keys(fn)
            if emitted is None:
                continue
            for key, node in sorted(emitted.items()):
                if key not in known[section]:
                    removed = c.REMOVED_KEYS.get(key)
                    why = (f"retired in schema v{removed[1]}"
                           if removed and removed[0] == section
                           else f"not a schema-v{c.SCHEMA_VERSION} key")
                    out.append(self.finding(
                        module, node,
                        f"`{fname}` emits {section!r} key {key!r} — "
                        f"{why} (racon_tpu/contracts.py)"))
            for key in sorted(known[section] - set(emitted)):
                out.append(self.finding(
                    module, fn,
                    f"schema v{c.SCHEMA_VERSION} requires {section!r} "
                    f"key {key!r} but `{fname}` never emits it"))
        return out

    @staticmethod
    def _dict_keys(d: ast.Dict) -> Dict[str, ast.AST]:
        return {k.value: k for k in d.keys
                if isinstance(k, ast.Constant)
                and isinstance(k.value, str)}

    def _return_keys(self, fn) -> Optional[Dict[str, ast.AST]]:
        """Union of string keys over every returned dict literal (None
        when the function never returns one — nothing checkable)."""
        found = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) \
                    and isinstance(node.value, ast.Dict):
                found = {**(found or {}),
                         **self._dict_keys(node.value)}
        return found

    def _report_dict(self, fn) -> Optional[ast.Dict]:
        """build_report's assembled ``rep`` literal — the dict that
        carries "schema_version"."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Dict) \
                    and "schema_version" in self._dict_keys(node):
                return node
        return None

    def _top_keys(self, fn) -> Optional[Dict[str, ast.AST]]:
        rep = self._report_dict(fn)
        if rep is None:
            return None
        keys = self._dict_keys(rep)
        # conditional sections land via rep["<key>"] = ... assignments
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.slice, ast.Constant) \
                            and isinstance(t.slice.value, str):
                        keys.setdefault(t.slice.value, t)
        return keys

    def _nested_keys(self, fn,
                     section: str) -> Optional[Dict[str, ast.AST]]:
        rep = self._report_dict(fn)
        if rep is None:
            return None
        for k, v in zip(rep.keys, rep.values):
            if isinstance(k, ast.Constant) and k.value == section \
                    and isinstance(v, ast.Dict):
                return self._dict_keys(v)
        return None


# ------------------------------------------------------- state-transition

class StateTransitionRule(Rule):
    """Lifecycle writes must stay inside the declared machines: a
    journal append's ``"rec"`` must be a declared record type, a
    ``job.state = X`` / ``entry["status"] = X`` /
    ``entry.update(status=X)`` target must be a declared state, and a
    write lexically guarded by an equality test of the SAME object's
    state field must encode a declared edge (``collected -> running``
    is a finding).  Unresolvable values and non-equality guards are
    skipped — the rule reports only what it can prove."""

    name = "state-transition"
    blurb = ("journal appends / job+shard state writes minting undeclared states or encoding undeclared lifecycle edges (e.g. `collected->running`)")

    def applies(self, rel: str) -> bool:
        return rel.startswith("racon_tpu/") and rel.endswith(".py")

    def check(self, project: Project, module: Module) -> List[Finding]:
        c = _contracts()
        if c is None:
            return []
        self._c = c
        self._prov = project.provenance()
        self._module = module
        out: List[Finding] = []
        self._visit(module.tree.body, {}, out)
        return out

    # -- machine plumbing ------------------------------------------------

    def _machine(self, kind: str):
        return (self._c.JOB_MACHINE if kind == "job"
                else self._c.SHARD_MACHINE)

    def _field_of(self, expr) -> Optional[Tuple[str, Optional[str]]]:
        """(kind, receiver) when ``expr`` reads a lifecycle field:
        ``<recv>.state`` -> job, ``<recv>["status"]`` /
        ``<recv>.get("status")`` -> shard."""
        if isinstance(expr, ast.Attribute) and expr.attr == "state":
            return "job", dotted(expr.value)
        if isinstance(expr, ast.Subscript) \
                and isinstance(expr.slice, ast.Constant) \
                and expr.slice.value == "status":
            return "shard", dotted(expr.value)
        if isinstance(expr, ast.Call) \
                and isinstance(expr.func, ast.Attribute) \
                and expr.func.attr == "get" and expr.args \
                and isinstance(expr.args[0], ast.Constant) \
                and expr.args[0].value == "status":
            return "shard", dotted(expr.func.value)
        return None

    def _guards_from_test(self, test) -> Dict[Tuple[str, Optional[str]],
                                              str]:
        """Equality guards a test establishes: {(kind, receiver):
        state}.  Only single ``==`` comparisons bind (an ``in``/``!=``
        narrows nothing usable for one edge)."""
        guards: Dict[Tuple[str, Optional[str]], str] = {}
        tests = (test.values if isinstance(test, ast.BoolOp)
                 and isinstance(test.op, ast.And) else [test])
        for t in tests:
            if not (isinstance(t, ast.Compare) and len(t.ops) == 1
                    and isinstance(t.ops[0], ast.Eq)):
                continue
            for field_expr, value_expr in ((t.left, t.comparators[0]),
                                           (t.comparators[0], t.left)):
                field = self._field_of(field_expr)
                if field is None:
                    continue
                state = self._prov.resolve_str(self._module, value_expr)
                if state is not None:
                    guards[field] = state
        return guards

    # -- statement walk --------------------------------------------------

    _COMPOUND = (ast.If, ast.For, ast.AsyncFor, ast.While, ast.With,
                 ast.AsyncWith, ast.Try)

    def _visit(self, stmts, guards, out) -> None:
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                self._visit(node.body, {}, out)
                continue
            # simple statements only — a compound statement's nested
            # writes are reached by the recursion below (walking the
            # whole subtree here would double-count them)
            if not isinstance(node, self._COMPOUND):
                self._check_exprs(node, guards, out)
            if isinstance(node, ast.If):
                new = self._guards_from_test(node.test)
                self._visit(node.body, {**guards, **new}, out)
                self._visit(node.orelse, guards, out)
            elif isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                self._visit(node.body + node.orelse, guards, out)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                self._visit(node.body, guards, out)
            elif isinstance(node, ast.Try):
                self._visit(node.body, guards, out)
                for h in node.handlers:
                    self._visit(h.body, guards, out)
                self._visit(node.orelse + node.finalbody, guards, out)

    def _check_exprs(self, stmt, guards, out) -> None:
        """Lifecycle writes inside one (simple or header) statement."""
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                field = self._field_of(t)
                if field is not None:
                    self._check_write(field, stmt.value, stmt, guards,
                                      out)
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "update":
                for kw in node.keywords:
                    if kw.arg == "status":
                        field = ("shard", dotted(node.func.value))
                        self._check_write(field, kw.value, node,
                                          guards, out)
            elif isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if not (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        continue
                    if k.value == "rec":
                        rec = self._prov.resolve_str(self._module, v)
                        if rec is not None \
                                and rec not in self._c.JOURNAL_RECORDS:
                            out.append(self.finding(
                                self._module, v,
                                f"journal record type {rec!r} is not "
                                f"declared in contracts."
                                f"JOURNAL_RECORDS"))
                    elif k.value == "status":
                        state = self._prov.resolve_str(self._module, v)
                        if state is not None and \
                                state not in self._c.SHARD_MACHINE:
                            out.append(self.finding(
                                self._module, v,
                                f"shard entry minted with undeclared "
                                f"status {state!r} (contracts."
                                f"SHARD_MACHINE)"))

    def _check_write(self, field, value_expr, node, guards, out) -> None:
        kind, _recv = field
        state = self._prov.resolve_str(self._module, value_expr)
        if state is None:
            return
        machine = self._machine(kind)
        if state not in machine:
            out.append(self.finding(
                self._module, node,
                f"writes undeclared {machine.name} state {state!r} "
                f"(contracts.{machine.name.upper()}_MACHINE states: "
                f"{', '.join(machine.states)})"))
            return
        src = guards.get(field)
        if src is not None and not machine.has_edge(src, state):
            out.append(self.finding(
                self._module, node,
                f"encodes undeclared {machine.name} transition "
                f"{src!r} -> {state!r} — declare the edge in "
                f"racon_tpu/contracts.py or fix the write"))


CONTRACT_RULES = [MetricRegistryRule(), SpanRegistryRule(),
                  FaultSiteRegistryRule(), SchemaCoherenceRule(),
                  StateTransitionRule()]
