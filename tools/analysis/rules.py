"""The graftlint rule set.

Each rule is a callable object: ``rule.check(project, module) ->
[Finding]``; the driver applies path scoping (``rule.applies(rel)``)
and pragma suppression.  Rules are repo-aware — they consult the
project-wide function index, jit-reachability/taint, and the logging
closure built in :mod:`tools.analysis.astutil`.

| rule                  | catches                                        |
| --------------------- | ---------------------------------------------- |
| tracer-leak           | Python control flow / int() / bool() / .item() |
|                       | on traced values in jit-reachable kernels      |
| swar-guard            | packed int16 entry points not dominated by a   |
|                       | swar_fits-family overflow guard                |
| swallowed-exception   | except Exception that neither re-raises nor    |
|                       | logs (directly or via a repo logging function) |
| env-flag-registry     | RACON_TPU_* env reads outside racon_tpu/flags  |
|                       | and reads of undeclared flag names             |
| host-sync-in-hot-loop | device->host pulls / block_until_ready inside  |
|                       | the per-chunk loops of the engines             |
| span-discipline       | obs.span(...) used any way other than directly |
|                       | as a `with` item (manual spans leak open)      |

The concurrency & durability pack (round 15) lives in
:mod:`tools.analysis.concurrency` and registers below: lock-discipline,
blocking-under-lock, atomic-write-discipline, thread-lifecycle and
scope-discipline.  The compile-surface pack (round 18) lives in
:mod:`tools.analysis.compilesurface` and registers below too:
jit-shape-hazard, dtype-drift, jit-in-loop, warmup-coverage and
host-transfer-in-jit.  The contract pack (round 22) lives in
:mod:`tools.analysis.contracts`: metric-registry, span-registry,
fault-site-registry, schema-coherence and state-transition —
21 rules total.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import List, Optional, Set

from .astutil import (Module, Project, dotted, iter_own_calls,
                      iter_own_nodes, last_segment, map_call_args)


@dataclass
class Finding:
    rule: str
    rel: str
    line: int
    message: str
    # pragma state, filled by the driver: None = no pragma applied;
    # a string = the reason of the pragma that suppressed this finding
    pragma: Optional[str] = None

    def __str__(self) -> str:
        return f"{self.rel}:{self.line}: {self.rule}: {self.message}"

    def as_dict(self) -> dict:
        """The ``--json`` record: rule, path, line, message, pragma
        state."""
        return {"rule": self.rule, "path": self.rel, "line": self.line,
                "message": self.message, "pragma": self.pragma}


class Rule:
    name = "?"

    def applies(self, rel: str) -> bool:
        return rel.endswith(".py")

    def check(self, project: Project, module: Module) -> List[Finding]:
        raise NotImplementedError

    def finding(self, module: Module, node: ast.AST,
                message: str) -> Finding:
        return Finding(self.name, module.rel,
                       getattr(node, "lineno", 1), message)


# ------------------------------------------------------------ tracer-leak

class TracerLeakRule(Rule):
    """Python-level branching or concretization of traced values inside
    jit-reachable functions: ``if``/``while``/``for``/``assert`` on a
    traced expression, ``int()``/``bool()``/``float()`` of a traced
    value, ``.item()``/``.tolist()`` on a traced value. All of these
    either fail at trace time on real tracers or — worse — silently
    bake one traced batch's concrete value into the compiled program."""

    name = "tracer-leak"
    blurb = ("Python control flow / `int()` / `.item()` on traced values in jit-reachable kernels")
    CASTS = {"int", "bool", "float", "complex"}
    PULL_METHODS = {"item", "tolist"}

    def applies(self, rel: str) -> bool:
        return rel.startswith("racon_tpu/ops/") and rel.endswith(".py")

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        taints = project.taints()
        for fi in project.functions:
            if fi.module is not module or id(fi) not in taints:
                continue
            tainted = taints[id(fi)]
            for node in iter_own_nodes(fi.node):
                out.extend(self._check_node(project, module, fi.qualname,
                                            node, tainted))
        return out

    def _check_node(self, project, module, qual, node, tainted):
        t = lambda e: project.expr_tainted(e, tainted)
        if isinstance(node, (ast.If, ast.While)) and t(node.test):
            kind = "if" if isinstance(node, ast.If) else "while"
            yield self.finding(
                module, node,
                f"Python `{kind}` on a traced value in jit-reachable "
                f"`{qual}` — use jnp.where/lax.cond (or mark the "
                f"argument static)")
        elif isinstance(node, ast.IfExp) and t(node.test):
            yield self.finding(
                module, node,
                f"conditional expression on a traced value in "
                f"jit-reachable `{qual}` — use jnp.where")
        elif isinstance(node, (ast.For, ast.AsyncFor)) and t(node.iter):
            yield self.finding(
                module, node,
                f"Python `for` over a traced value in jit-reachable "
                f"`{qual}` — use lax.scan/fori_loop")
        elif isinstance(node, ast.Assert) and t(node.test):
            yield self.finding(
                module, node,
                f"assert on a traced value in jit-reachable `{qual}` — "
                f"use checkify or a host-side canary")
        elif isinstance(node, ast.Call):
            fn = dotted(node.func)
            if fn in self.CASTS and any(t(a) for a in node.args):
                yield self.finding(
                    module, node,
                    f"`{fn}()` concretizes a traced value in "
                    f"jit-reachable `{qual}`")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in self.PULL_METHODS
                  and t(node.func.value)):
                yield self.finding(
                    module, node,
                    f"`.{node.func.attr}()` pulls a traced value to "
                    f"host in jit-reachable `{qual}`")


# ------------------------------------------------------------- swar-guard

class SwarGuardRule(Rule):
    """Every call that turns the packed int16 path on (a truthy
    ``swar=`` / ``use_swar=`` argument) must be *dominated* by the
    overflow guard: the flag value must derive — through local
    assignments — from a ``swar_fits``-family call, or be a forwarded
    parameter of the enclosing function (checked at its callers). A
    bare ``swar=True`` (probes, tests-in-ops) needs a pragma stating
    why the geometry cannot overflow."""

    name = "swar-guard"
    blurb = ("packed-int16 entry points not dominated by a `swar_fits`-family overflow guard")
    FLAG_PARAMS = {"swar", "use_swar"}
    GUARDS = {"swar_fits", "_swar_choice", "swar_ok", "pallas_swar_ok"}

    def applies(self, rel: str) -> bool:
        return rel.startswith("racon_tpu/ops/") and rel.endswith(".py")

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for fi in project.functions:
            if fi.module is not module:
                continue
            for call in iter_own_calls(fi.node):
                out.extend(self._check_call(project, module, fi, call))
        return out

    def _flag_args(self, project: Project, call: ast.Call):
        """(param_name, value_expr) for every packed-path flag this call
        passes — by keyword, or positionally via the resolved callee
        signature."""
        for kw in call.keywords:
            if kw.arg in self.FLAG_PARAMS:
                yield kw.arg, kw.value
        for callee in project.resolve(call):
            if not (set(callee.all_params()) & self.FLAG_PARAMS):
                continue
            mapped = map_call_args(call, callee)
            for p in self.FLAG_PARAMS:
                v = mapped.get(p)
                if v is not None and not any(kw.arg == p
                                             for kw in call.keywords):
                    yield p, v
            break

    def _check_call(self, project, module, fi, call):
        for pname, value in self._flag_args(project, call):
            if isinstance(value, ast.Constant):
                if not value.value:
                    continue  # literal off-switch
                yield self.finding(
                    module, call,
                    f"`{pname}={value.value!r}` enables the packed "
                    f"int16 path unguarded — derive it from "
                    f"swar_fits()/swar_ok() (or pragma with the "
                    f"geometry argument)")
            elif not self._guard_derived(project, fi, value):
                yield self.finding(
                    module, call,
                    f"`{pname}` value does not derive from a "
                    f"swar_fits()/swar_ok() guard on any assignment "
                    f"path — packed int16 scores can overflow "
                    f"silently")

    def _guard_derived(self, project: Project, fi, expr: ast.AST,
                       depth: int = 0) -> bool:
        """Does ``expr`` derive from a guard call through assignments in
        the lexical function chain (or forward a parameter)?"""
        if depth > 8:
            return False
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) \
                    and last_segment(dotted(node.func)) in self.GUARDS:
                return True
        names = {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}
        if not names:
            return False
        chain = [fi] + project.enclosing(fi)
        for name in names:
            if name in self.FLAG_PARAMS and any(
                    name in f.all_params() for f in chain):
                return True  # conventional pass-through: callers checked
            for f in chain:
                for node in iter_own_nodes(f.node):
                    value = None
                    if isinstance(node, ast.Assign) and any(
                            isinstance(t, ast.Name) and t.id == name
                            for t in node.targets):
                        value = node.value
                    elif isinstance(node, ast.NamedExpr) and isinstance(
                            node.target, ast.Name) \
                            and node.target.id == name:
                        value = node.value
                    if value is not None and self._guard_derived(
                            project, f, value, depth + 1):
                        return True
        return False


# ---------------------------------------------------- swallowed-exception

class SwallowedExceptionRule(Rule):
    """``except Exception`` (or bare / BaseException) handlers must
    re-raise, log through the sanctioned sinks (``utils.logger.warn`` /
    ``log_swallowed`` / ``warnings.warn`` / a repo function that
    transitively does), or carry a pragma with the reason the fault is
    safe to swallow."""

    name = "swallowed-exception"
    blurb = ("broad `except` that neither re-raises nor logs")
    BROAD = {"Exception", "BaseException"}
    # calls that transfer control out of the handler like a raise does
    TERMINAL_CALLS = {"pytest.skip", "pytest.fail", "pytest.xfail",
                      "pytest.exit", "sys.exit", "os.abort"}

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node.type):
                continue
            if self._handled(project, node):
                continue
            out.append(self.finding(
                module, node,
                "broad `except` neither re-raises nor logs — route "
                "through utils.logger (log_swallowed/warn) or pragma "
                "with the reason"))
        return out

    def _is_broad(self, type_node) -> bool:
        if type_node is None:
            return True  # bare except
        names = ([dotted(type_node)] if not isinstance(type_node, ast.Tuple)
                 else [dotted(e) for e in type_node.elts])
        return any(last_segment(n) in self.BROAD for n in names if n)

    def _handled(self, project: Project, handler: ast.ExceptHandler) -> bool:
        # own nodes only: a raise/log inside a nested def the handler
        # merely *defines* (a callback that may never run) handles nothing
        for node in iter_own_nodes(handler):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                if project.call_is_logging(node):
                    return True
                if dotted(node.func) in self.TERMINAL_CALLS:
                    return True
        return False


# ------------------------------------------------------ env-flag-registry

class EnvFlagRegistryRule(Rule):
    """All ``RACON_TPU_*`` environment reads go through
    ``racon_tpu/flags.py``; names read through the registry must be
    declared there. The registry itself is loaded (it is import-safe:
    stdlib only) so declarations are checked for real, not by regex."""

    name = "env-flag-registry"
    blurb = ("`RACON_TPU_*` env reads outside `racon_tpu/flags.py`, or of undeclared names")
    ENV_GETTERS = {"os.environ.get", "environ.get", "os.getenv", "getenv"}
    REGISTRY_GETTERS = {"raw", "get_bool", "get_int", "get_float",
                        "get_str"}
    PREFIX = "RACON_TPU_"

    def __init__(self):
        self._registry: Optional[Set[str]] = None

    def _declared(self) -> Optional[Set[str]]:
        if self._registry is None:
            try:
                from racon_tpu.flags import REGISTRY
                self._registry = set(REGISTRY)
            # graftlint: disable=swallowed-exception (lint must run without the repo importable)
            except Exception:
                self._registry = set()  # unknown: skip declaration checks
        return self._registry

    def applies(self, rel: str) -> bool:
        return rel.endswith(".py") and rel != "racon_tpu/flags.py"

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                out.extend(self._check_call(module, node))
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Load) \
                    and dotted(node.value) in ("os.environ", "environ"):
                key = node.slice
                if isinstance(key, ast.Constant) \
                        and isinstance(key.value, str) \
                        and key.value.startswith(self.PREFIX):
                    out.append(self.finding(
                        module, node,
                        f"direct os.environ[{key.value!r}] read — go "
                        f"through racon_tpu.flags"))
        return out

    def _check_call(self, module, call):
        fn = dotted(call.func)
        arg0 = call.args[0] if call.args else None
        is_str = (isinstance(arg0, ast.Constant)
                  and isinstance(arg0.value, str))
        if fn in self.ENV_GETTERS and is_str \
                and arg0.value.startswith(self.PREFIX):
            yield self.finding(
                module, call,
                f"direct environment read of {arg0.value!r} — declare "
                f"it in racon_tpu/flags.py and use flags.get_*")
        elif last_segment(fn) in self.REGISTRY_GETTERS and is_str \
                and arg0.value.startswith(self.PREFIX):
            declared = self._declared()
            if declared and arg0.value not in declared:
                yield self.finding(
                    module, call,
                    f"flag {arg0.value!r} is not declared in "
                    f"racon_tpu/flags.py REGISTRY")


# ------------------------------------------------- host-sync-in-hot-loop

class HostSyncRule(Rule):
    """No device->host pulls inside per-chunk loops: a
    ``block_until_ready``/``jax.device_get``/``np.asarray``-of-a-device-
    value inside a ``for``/``while`` serializes the async dispatch
    pipeline once per iteration (every sync stalls the host on the device).
    ``fetch_global``/``to_global`` are the sanctioned transfer
    primitives — their bodies are exempt, and values they return are
    host-side."""

    name = "host-sync-in-hot-loop"
    blurb = ("device->host pulls inside per-chunk loops")
    EXEMPT_FUNCS = {"fetch_global", "to_global"}
    # calls whose results live on device (host pulls of these are syncs)
    DEVICE_PRODUCERS = {"_dispatch", "align_chain", "sharded_align",
                        "sharded_refine_loop"}
    PULLERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
    CASTS = {"int", "float", "bool"}

    def applies(self, rel: str) -> bool:
        return rel.startswith("racon_tpu/") and rel.endswith(".py")

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        jit_names = {fi.name for fi in project.functions
                     if fi.is_jit_root}
        for fi in project.functions:
            if fi.module is not module or fi.name in self.EXEMPT_FUNCS:
                continue
            device = self._device_names(fi, jit_names)
            for loop in iter_own_nodes(fi.node):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                for node in ast.walk(loop):
                    if not isinstance(node, ast.Call):
                        continue
                    f = self._sync_finding(module, fi, node, device)
                    if f is not None:
                        out.append(f)
        return out

    def _device_names(self, fi, jit_names) -> Set[str]:
        """Names in ``fi`` assigned from device-producing calls (jitted
        repo kernels, the dispatch seams, jnp/lax ops)."""
        device: Set[str] = set()
        for node in iter_own_nodes(fi.node):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            if not isinstance(v, ast.Call):
                continue
            fn = dotted(v.func)
            seg = last_segment(fn)
            if seg in self.EXEMPT_FUNCS:
                continue  # sanctioned transfer: results are host-side
            if (seg in jit_names or seg in self.DEVICE_PRODUCERS
                    or (fn or "").startswith(("jnp.", "lax."))):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            device.add(n.id)
        return device

    def _sync_finding(self, module, fi, call, device):
        fn = dotted(call.func)
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr == "block_until_ready":
            return self.finding(
                module, call,
                f"`.block_until_ready()` inside a loop in "
                f"`{fi.qualname}` serializes the dispatch pipeline "
                f"per iteration")
        if fn in ("jax.device_get", "jax.block_until_ready"):
            return self.finding(
                module, call,
                f"`{fn}` inside a loop in `{fi.qualname}` — fetch once "
                f"per chunk through fetch_global")
        tainted = lambda e: any(
            isinstance(n, ast.Name) and n.id in device
            for n in ast.walk(e))
        if fn in self.PULLERS and call.args and tainted(call.args[0]):
            return self.finding(
                module, call,
                f"`{fn}` of a device value inside a loop in "
                f"`{fi.qualname}` — a hidden device->host pull per "
                f"iteration")
        if fn in self.CASTS and call.args and tainted(call.args[0]):
            return self.finding(
                module, call,
                f"`{fn}()` of a device value inside a loop in "
                f"`{fi.qualname}` — a hidden sync per iteration")
        return None


# -------------------------------------------------------- span-discipline

class SpanDisciplineRule(Rule):
    """Observability spans only via ``with obs.span(...):`` — every
    ``obs.span(...)`` call must appear *directly* as a ``with`` item
    (``with obs.span(...):`` / ``with obs.span(...) as s:``, including
    multi-item withs).  Assigning a span to a name, calling
    ``__enter__``/``__exit__`` by hand, or passing a fresh span into a
    helper builds a manual begin/end pair that leaks the span open when
    an exception unwinds between the calls — the exact failure mode the
    context-manager protocol exists to close.  The tracer internals
    (``racon_tpu/obs/``) are exempt; a deliberate exception (e.g. an
    identity probe in a test) takes a reasoned pragma."""

    name = "span-discipline"
    blurb = ("`obs.span(...)` used any way other than directly as a `with` item")
    # dotted call names that create a span (obs.span is the repo idiom;
    # the bare name covers `from racon_tpu.obs import span`)
    SPAN_CALLS = {"obs.span", "span", "trace.span", "obs.trace.span"}

    def applies(self, rel: str) -> bool:
        return (rel.startswith("racon_tpu/") and rel.endswith(".py")
                and not rel.startswith("racon_tpu/obs/"))

    def check(self, project: Project, module: Module) -> List[Finding]:
        out: List[Finding] = []
        with_items: Set[int] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_items.add(id(item.context_expr))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted(node.func)
            if fn not in self.SPAN_CALLS or id(node) in with_items:
                continue
            out.append(self.finding(
                module, node,
                f"`{fn}(...)` used outside a `with` item — a span held "
                f"by hand leaks open when an exception unwinds; write "
                f"`with {fn}(...):` (or pragma with the reason)"))
        return out


# imported at the bottom so the concurrency and compile-surface packs
# can subclass Rule / build Findings without a circular import (both
# names are bound above by the time these lines run)
from .compilesurface import COMPILE_SURFACE_RULES  # noqa: E402
from .concurrency import CONCURRENCY_RULES  # noqa: E402
from .contracts import CONTRACT_RULES  # noqa: E402

ALL_RULES = [TracerLeakRule(), SwarGuardRule(), SwallowedExceptionRule(),
             EnvFlagRegistryRule(), HostSyncRule(), SpanDisciplineRule(),
             *CONCURRENCY_RULES, *COMPILE_SURFACE_RULES, *CONTRACT_RULES]
RULES_BY_NAME = {r.name: r for r in ALL_RULES}
