"""The JAX-specific rule catalogue behind ``ptpu check``.

This module holds the six JAX rules and assembles the full registry
(:data:`RULES`), which also includes the concurrency rule family from
:mod:`.concurrency` (``unguarded-shared-state``,
``lock-order-inversion``, ``blocking-under-lock``,
``callback-under-lock``) and the Pallas kernel-safety family from
:mod:`.kernels` (``vmem-overbudget``, ``dma-unwaited``,
``low-precision-accumulator``, ``missing-interpret-fallback``).

``host-sync-in-hot-path`` and ``materialized-gather`` are
project-scoped: beyond their direct per-module passes they consult the
interprocedural effect summaries (:class:`~.core.ProjectIndex`) so a
violation hidden inside a helper — any number of calls away — is
reported at the hot-path call site with the call chain in the message.

The JAX rules:

- ``host-sync-in-hot-path`` — device→host landings (``np.asarray``,
  ``.item()``, ``.tolist()``, ``jax.device_get``,
  ``.block_until_ready()``, ``float(jnp...)``) inside functions of the
  hot packages (``server/``, ``ops/``). Each is a synchronous transfer
  that stalls the dispatch pipeline; on the query path one stray sync
  caps throughput at the host↔device round-trip rate.
- ``recompile-hazard`` — jit call sites that re-trace or re-compile
  silently: unhashable values passed for static args, jitted closures
  capturing ``jnp`` arrays built in an enclosing scope (the captured
  array is baked into the trace — a new array means a new program),
  and Python ``if``/``while`` on traced arguments (data-dependent
  control flow re-traces per branch or just fails late).
- ``missing-donation`` — ``x = step(x, …)`` update patterns calling a
  jitted function that does not donate the re-bound buffer: the old
  ``x`` stays alive across the step, doubling peak HBM for large
  factor/accumulator arrays.
- ``sharding-mismatch`` — ``PartitionSpec`` axis-name literals (wherever
  they appear: ``NamedSharding(mesh, P(...))`` annotations on entry
  points, ``shard_map`` in/out specs, jit ``out_shardings``) and axis
  names passed to ``lax`` collectives (``psum``/``all_gather``/
  ``ppermute``/``axis_index``/…) that no mesh builder in
  ``parallel/mesh.py`` declares; XLA only reports these at trace time
  on a real mesh, usually mid-deploy.
- ``materialized-gather`` — ``table[indices]`` advanced-indexing and
  ``jnp.take``/``jnp.take_along_axis`` gathers by a caller-supplied
  index array inside ``models/``/``ops/``/``server/`` functions
  (directly, or through a helper the traced index flows into): XLA
  materializes the gathered rows as an HBM temp sized by the index
  shape (the ``[B, L, r]`` ALS gather temp); bound it, or pragma a
  size case.
- ``config-drift`` — ``jax.config.update`` outside
  ``utils/platform.py``: scattered config flips make process behavior
  depend on import order (exactly the class of bug
  ``force_cpu_if_requested`` exists to fix).

Every rule obeys the ``# ptpu: allow[rule] — justification`` pragma
(see :mod:`.core`). Rules are heuristics tuned for this codebase's
idioms; they prefer a pragma-able false positive on genuinely hot files
over silence.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from .core import (
    CheckContext,
    Finding,
    ModuleInfo,
    chain_related,
    chain_text,
    short_name,
)

RuleFn = Callable[[ModuleInfo, CheckContext], List[Finding]]


@dataclass(frozen=True)
class Rule:
    name: str
    description: str
    fn: RuleFn
    #: project-scoped rules run ONCE over the whole parsed module set
    #: (cross-file facts like the lock-order graph); their ``fn`` takes
    #: ``(mods: List[ModuleInfo], ctx)`` instead of one module
    project: bool = False


# ---------------------------------------------------------------------------
# rule 1: host-sync-in-hot-path
# ---------------------------------------------------------------------------

#: directories whose function bodies are considered hot (serving/query
#: and device-op code; module level runs once at import and is exempt)
HOT_DIR_PARTS = {"server", "ops"}

HOST_SYNC_CALLS = {
    "numpy.asarray": "np.asarray on a device value copies device→host "
                     "synchronously",
    "numpy.ascontiguousarray": "np.ascontiguousarray forces a host "
                               "copy (and a second one if the first "
                               "landing was non-contiguous)",
    "jax.device_get": "jax.device_get blocks until the transfer "
                      "completes",
}

HOST_SYNC_METHODS = {
    "item": ".item() synchronously pulls a scalar off the device",
    "tolist": ".tolist() copies the whole array to host Python objects",
    "block_until_ready": ".block_until_ready() stalls the caller on "
                         "device completion",
}


def _in_hot_path(path: str) -> bool:
    parts = path.split("/")
    return bool(set(parts[:-1]) & HOT_DIR_PARTS)


def host_sync_reason(mod: ModuleInfo, node: ast.Call) -> Optional[str]:
    """Why this call is a device→host sync, or None — the shared
    predicate behind the direct rule and the interprocedural effect
    summaries (:class:`~.core.ProjectIndex`)."""
    name = mod.resolve(node.func)
    if name in HOST_SYNC_CALLS:
        return HOST_SYNC_CALLS[name]
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in HOST_SYNC_METHODS \
            and not node.args and not node.keywords:
        return HOST_SYNC_METHODS[node.func.attr]
    if name in ("float", "int") and len(node.args) == 1 \
            and isinstance(node.args[0], ast.Call):
        inner = mod.resolve(node.args[0].func)
        if inner and inner.startswith("jax.numpy."):
            return (f"{name}() on a jnp result forces a blocking "
                    f"device→host scalar read")
    return None


def rule_host_sync(mods: Sequence[ModuleInfo],
                   ctx: CheckContext) -> List[Finding]:
    """Project-scoped: direct syncs inside hot-package functions, plus
    — through the call graph — hot-path calls into helpers (anywhere
    in the project) that transitively sync, reported at the hot call
    site with the chain down to the direct site. Helpers living in hot
    packages are skipped here: their bodies already get the direct
    finding."""
    findings: List[Finding] = []
    for mod in mods:
        if not _in_hot_path(mod.path):
            continue
        seen: Set[int] = set()
        funcs = [n for n in ast.walk(mod.tree)
                 if isinstance(n, (ast.FunctionDef,
                                   ast.AsyncFunctionDef))]
        for fn in funcs:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                why = host_sync_reason(mod, node)
                if why is not None:
                    findings.append(Finding(
                        "host-sync-in-hot-path", mod.path, node.lineno,
                        node.col_offset,
                        f"{why} (in hot function `{fn.name}`); keep "
                        f"the hot path device-resident or pragma with "
                        f"justification"))
    proj = ctx.project
    if proj is None:
        return findings
    for fninfo in proj.functions.values():
        if not fninfo.hot(HOT_DIR_PARTS):
            continue
        for call in fninfo.calls:
            callee = proj.functions.get(call.callee or "")
            if callee is None or callee.hot(HOT_DIR_PARTS):
                continue
            if callee.effects["host_sync"] is None:
                continue
            hops = proj.chain(callee, "host_sync")
            if not hops:
                continue
            findings.append(Finding(
                "host-sync-in-hot-path", fninfo.mod.path, call.line,
                call.col,
                f"calling `{short_name(callee.qname)}` from hot "
                f"function `{short_name(fninfo.qname)}` transitively "
                f"syncs device→host: {chain_text(hops)}; keep the hot "
                f"path device-resident, or pragma the blessed helper "
                f"at its direct site",
                related=chain_related(hops)))
    return findings


# ---------------------------------------------------------------------------
# shared jit-site discovery (rules 2 and 3)
# ---------------------------------------------------------------------------

#: constructors whose results are device arrays — a jitted closure
#: capturing one re-traces whenever the captured array changes identity
ARRAY_BUILDERS_PREFIX = "jax.numpy."
ARRAY_BUILDERS_EXACT = {"jax.device_put"}


@dataclass
class JitSite:
    """One jit wrapping: decorator or ``jax.jit(fn, …)`` call."""

    fn: Optional[ast.AST]           # FunctionDef/Lambda being wrapped
    call: Optional[ast.Call]        # the jax.jit(...) call node, if any
    lineno: int
    col: int
    bound_name: Optional[str]       # name the jitted callable binds to
    static_names: Set[str]
    donate_nums: Set[int]
    donate_names: Set[str]
    scope_stack: Tuple[ast.AST, ...]  # enclosing function defs, outer→inner


def _param_names(fn: ast.AST) -> List[str]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
        return []
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _const_strs(node: ast.AST) -> List[str]:
    """String literals in a str/tuple/list constant expression."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str)]
    return []


def _const_ints(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, int)]
    return []


def _jit_kwargs(call: ast.Call) -> Dict[str, ast.AST]:
    return {kw.arg: kw.value for kw in call.keywords if kw.arg}


def _statics_and_donations(kwargs: Dict[str, ast.AST],
                           params: Sequence[str]
                           ) -> Tuple[Set[str], Set[int], Set[str]]:
    static_names: Set[str] = set()
    if "static_argnames" in kwargs:
        static_names |= set(_const_strs(kwargs["static_argnames"]))
    if "static_argnums" in kwargs:
        for i in _const_ints(kwargs["static_argnums"]):
            if 0 <= i < len(params):
                static_names.add(params[i])
    donate_nums = set(_const_ints(kwargs["donate_argnums"])) \
        if "donate_argnums" in kwargs else set()
    donate_names = set(_const_strs(kwargs["donate_argnames"])) \
        if "donate_argnames" in kwargs else set()
    return static_names, donate_nums, donate_names


class _JitCollector(ast.NodeVisitor):
    """Find every jit wrapping in a module, with its enclosing function
    scopes and the per-scope simple ``name = <expr>`` assignments (for
    the closure-capture check)."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.sites: List[JitSite] = []
        self.scope: List[ast.AST] = []
        #: id(scope fn) or None → {name: value expr}
        self.assigns: Dict[Optional[int], Dict[str, ast.AST]] = {None: {}}
        #: function defs by name, outermost first (jax.jit(Name) lookup)
        self.defs_by_name: Dict[str, ast.AST] = {}

    def _scope_key(self) -> Optional[int]:
        return id(self.scope[-1]) if self.scope else None

    def visit_FunctionDef(self, node):  # noqa: N802 — ast API
        self._handle_def(node)

    def visit_AsyncFunctionDef(self, node):  # noqa: N802 — ast API
        self._handle_def(node)

    def _handle_def(self, node) -> None:
        self.defs_by_name.setdefault(node.name, node)
        params = _param_names(node)
        for dec in node.decorator_list:
            site = self._site_from_decorator(dec, node, params)
            if site is not None:
                self.sites.append(site)
        self.scope.append(node)
        self.assigns.setdefault(id(node), {})
        self.generic_visit(node)
        self.scope.pop()

    def _site_from_decorator(self, dec: ast.AST, node, params
                             ) -> Optional[JitSite]:
        name = self.mod.resolve(dec)
        if name == "jax.jit":
            return JitSite(node, None, node.lineno, node.col_offset,
                           node.name, set(), set(), set(),
                           tuple(self.scope))
        if isinstance(dec, ast.Call):
            callee = self.mod.resolve(dec.func)
            if callee == "jax.jit":
                s, dn, dnm = _statics_and_donations(_jit_kwargs(dec),
                                                    params)
                return JitSite(node, dec, node.lineno, node.col_offset,
                               node.name, s, dn, dnm, tuple(self.scope))
            if callee == "functools.partial" and dec.args \
                    and self.mod.resolve(dec.args[0]) == "jax.jit":
                s, dn, dnm = _statics_and_donations(_jit_kwargs(dec),
                                                    params)
                return JitSite(node, dec, node.lineno, node.col_offset,
                               node.name, s, dn, dnm, tuple(self.scope))
        return None

    def visit_Assign(self, node):  # noqa: N802 — ast API
        if len(node.targets) == 1 and isinstance(node.targets[0],
                                                 ast.Name):
            self.assigns[self._scope_key()][node.targets[0].id] = \
                node.value
        self.generic_visit(node)

    def visit_Call(self, node):  # noqa: N802 — ast API
        if self.mod.resolve(node.func) == "jax.jit" and node.args:
            wrapped = node.args[0]
            target: Optional[ast.AST] = None
            if isinstance(wrapped, ast.Lambda):
                target = wrapped
            elif isinstance(wrapped, ast.Name):
                target = self.defs_by_name.get(wrapped.id)
            elif isinstance(wrapped, ast.Attribute) \
                    and wrapped.attr == "__wrapped__" \
                    and isinstance(wrapped.value, ast.Name):
                # jax.jit(f.__wrapped__, ...) re-wraps a decorated def
                target = self.defs_by_name.get(wrapped.value.id)
            params = _param_names(target) if target is not None else []
            s, dn, dnm = _statics_and_donations(_jit_kwargs(node), params)
            bound = None
            site = JitSite(target, node, node.lineno, node.col_offset,
                           bound, s, dn, dnm, tuple(self.scope))
            self.sites.append(site)
        self.generic_visit(node)


def _collect_jit(mod: ModuleInfo) -> _JitCollector:
    collector = _JitCollector(mod)
    collector.visit(mod.tree)
    # bind `X = jax.jit(f, …)` sites to their assigned name so call
    # sites of X resolve to the wrapped function's params/donations
    for scope_assigns in collector.assigns.values():
        for name, value in scope_assigns.items():
            for site in collector.sites:
                if site.call is value:
                    site.bound_name = name
    return collector


def _free_loads(fn: ast.AST) -> Set[str]:
    """Names a function/lambda loads but neither binds as a param nor
    assigns locally — its closure candidates."""
    params = set(_param_names(fn))
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    local: Set[str] = set()
    loads: Set[str] = set()
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    local.add(node.id)
                elif isinstance(node.ctx, ast.Load):
                    loads.add(node.id)
    return loads - params - local


def rule_recompile_hazard(mod: ModuleInfo,
                          ctx: CheckContext) -> List[Finding]:
    collector = _collect_jit(mod)
    findings: List[Finding] = []

    # (a) unhashable values passed for declared static args
    statics_by_name: Dict[str, Set[str]] = {}
    for site in collector.sites:
        if site.bound_name and site.static_names:
            statics_by_name[site.bound_name] = site.static_names
    unhashable = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                  ast.DictComp, ast.SetComp)
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)):
            continue
        statics = statics_by_name.get(node.func.id)
        if not statics:
            continue
        for kw in node.keywords:
            if kw.arg in statics and isinstance(kw.value, unhashable):
                findings.append(Finding(
                    "recompile-hazard", mod.path, node.lineno,
                    node.col_offset,
                    f"static arg `{kw.arg}` of `{node.func.id}` gets an "
                    f"unhashable {type(kw.value).__name__.lower()}; "
                    f"jit static args must hash — pass a tuple or "
                    f"hashable config object"))

    # (b) jitted closures over enclosing-scope jnp arrays
    for site in collector.sites:
        if site.fn is None or not site.scope_stack:
            continue
        free = _free_loads(site.fn)
        for scope in reversed(site.scope_stack):
            scope_assigns = collector.assigns.get(id(scope), {})
            for name in sorted(free & set(scope_assigns)):
                value = scope_assigns[name]
                built = mod.resolve(value.func) \
                    if isinstance(value, ast.Call) else None
                if built and (built.startswith(ARRAY_BUILDERS_PREFIX)
                              or built in ARRAY_BUILDERS_EXACT):
                    findings.append(Finding(
                        "recompile-hazard", mod.path, site.lineno,
                        site.col,
                        f"jitted function closes over device array "
                        f"`{name}` (built by `{built}` in an enclosing "
                        f"scope); captured arrays are baked into the "
                        f"trace — a fresh array means a fresh compile. "
                        f"Pass it as an argument instead"))

    # (c) Python control flow on traced arguments inside jitted bodies
    flagged: Set[int] = set()
    for site in collector.sites:
        fn = site.fn
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        traced = set(_param_names(fn)) - site.static_names
        if not traced:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                continue
            if id(node) in flagged:
                continue
            test_loads: Dict[str, int] = {}
            for n in ast.walk(node.test):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                    test_loads[n.id] = test_loads.get(n.id, 0) + 1
            # `x is None` / `x is not None` resolves by pytree STRUCTURE
            # at trace time (None is a static empty pytree): a bounded
            # Optional specialization, not a value-dependent retrace.
            # Exempt names used ONLY that way in this test.
            structural: Dict[str, int] = {}
            for c in ast.walk(node.test):
                if (isinstance(c, ast.Compare) and len(c.ops) == 1
                        and isinstance(c.ops[0], (ast.Is, ast.IsNot))
                        and isinstance(c.left, ast.Name)
                        and isinstance(c.comparators[0], ast.Constant)
                        and c.comparators[0].value is None):
                    structural[c.left.id] = \
                        structural.get(c.left.id, 0) + 1
            bad = sorted(name for name, cnt in test_loads.items()
                         if name in traced
                         and structural.get(name) != cnt)
            if bad:
                flagged.add(id(node))
                kind = {ast.If: "if", ast.While: "while",
                        ast.IfExp: "conditional expression"}[type(node)]
                findings.append(Finding(
                    "recompile-hazard", mod.path, node.lineno,
                    node.col_offset,
                    f"Python `{kind}` on traced argument(s) "
                    f"{', '.join(bad)} inside jitted `{fn.name}`; "
                    f"mark them static, or branch with "
                    f"jnp.where/lax.cond"))
    return findings


# ---------------------------------------------------------------------------
# rule 3: missing-donation
# ---------------------------------------------------------------------------

def rule_missing_donation(mod: ModuleInfo,
                          ctx: CheckContext) -> List[Finding]:
    collector = _collect_jit(mod)
    jitted: Dict[str, JitSite] = {}
    for site in collector.sites:
        if site.bound_name:
            jitted.setdefault(site.bound_name, site)

    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not (isinstance(call, ast.Call)
                and isinstance(call.func, ast.Name)
                and call.func.id in jitted):
            continue
        site = jitted[call.func.id]
        params = _param_names(site.fn) if site.fn is not None else []
        targets: Set[str] = set()
        for t in node.targets:
            if isinstance(t, ast.Name):
                targets.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                targets |= {e.id for e in t.elts
                            if isinstance(e, ast.Name)}
        if not targets:
            continue
        rebound: List[Tuple[int, str]] = []
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Name) and arg.id in targets:
                pname = params[i] if i < len(params) else ""
                if i not in site.donate_nums \
                        and pname not in site.donate_names:
                    rebound.append((i, arg.id))
        for kw in call.keywords:
            if isinstance(kw.value, ast.Name) and kw.arg \
                    and kw.value.id in targets \
                    and kw.arg not in site.donate_names \
                    and (kw.arg not in params
                         or params.index(kw.arg)
                         not in site.donate_nums):
                rebound.append((-1, kw.value.id))
        for _, name in rebound:
            findings.append(Finding(
                "missing-donation", mod.path, node.lineno,
                node.col_offset,
                f"`{name}` is re-bound to an output of jitted "
                f"`{call.func.id}` but not donated; the old buffer "
                f"stays live across the step (2x peak HBM for large "
                f"arrays) — add it to donate_argnums"))
    return findings


# ---------------------------------------------------------------------------
# rule 4: sharding-mismatch
# ---------------------------------------------------------------------------

def _axis_literals(node: ast.AST) -> List[str]:
    """Axis-name string literals in one PartitionSpec argument: a bare
    string, or a tuple/list of strings (multi-axis sharding)."""
    out: List[str] = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
    return out


#: ``lax`` collectives whose axis-name argument must name a declared
#: mesh axis — a typo'd axis here fails exactly like a bad
#: PartitionSpec, at trace time on a real mesh. Maps dotted name →
#: positional index of the axis argument.
_COLLECTIVE_AXIS_ARG = {
    "jax.lax.psum": 1,
    "jax.lax.pmean": 1,
    "jax.lax.pmax": 1,
    "jax.lax.pmin": 1,
    "jax.lax.all_gather": 1,
    "jax.lax.psum_scatter": 1,
    "jax.lax.ppermute": 1,
    "jax.lax.all_to_all": 1,
    "jax.lax.axis_index": 0,
    "jax.lax.pvary": 1,
}


def rule_sharding_mismatch(mod: ModuleInfo,
                           ctx: CheckContext) -> List[Finding]:
    from .sharding import _is_pspec_call, _is_shard_map_call

    axes = ctx.declared_axes
    if not axes:
        return []
    findings: List[Finding] = []
    flagged: Set[Tuple[int, str]] = set()

    def check(node: ast.AST, arg: ast.AST, what: str) -> None:
        for name in _axis_literals(arg):
            if name not in axes and (id(node), name) not in flagged:
                flagged.add((id(node), name))
                findings.append(Finding(
                    "sharding-mismatch", mod.path, node.lineno,
                    node.col_offset,
                    f"{what} axis {name!r} is not declared by "
                    f"parallel/mesh.py (declared: {sorted(axes)}); "
                    f"XLA will reject it at trace time on a real "
                    f"mesh"))

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = mod.resolve(node.func)
        if _is_pspec_call(mod, node):
            # covers every NamedSharding-annotated entry point too:
            # NamedSharding(mesh, P(...)), shard_map in/out specs, jit
            # out_shardings — the axis names always ride a
            # PartitionSpec call, however P was imported (the alias
            # table, OR a bare `P`/`PartitionSpec` name the aliases
            # cannot resolve: star imports, `jax.P`)
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                check(node, arg, "PartitionSpec")
            continue
        if _is_shard_map_call(mod, node):
            # keyword-form in_specs=/out_specs= of a shard_map
            # boundary: axis literals OUTSIDE a P(...) call (those are
            # caught above) — bare tuple/string forms a compat wrapper
            # might accept
            for kw in node.keywords:
                if kw.arg not in ("in_specs", "out_specs"):
                    continue
                covered: Set[int] = set()
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Call) \
                            and _is_pspec_call(mod, sub):
                        covered |= {id(d) for d in ast.walk(sub)}
                for sub in ast.walk(kw.value):
                    if id(sub) not in covered \
                            and isinstance(sub, (ast.Tuple, ast.List,
                                                 ast.Constant)):
                        check(node, sub, f"shard_map {kw.arg}")
            continue
        pos = _COLLECTIVE_AXIS_ARG.get(resolved or "")
        if pos is None:
            continue
        short = (resolved or "").rsplit(".", 1)[-1]
        if pos < len(node.args):
            check(node, node.args[pos], f"lax.{short}")
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis"):
                check(node, kw.value, f"lax.{short}")
    return findings


# ---------------------------------------------------------------------------
# rule 5: materialized-gather
# ---------------------------------------------------------------------------

#: directories whose functions sit on the train/serve hot paths — the
#: places where an advanced-indexing gather's HBM temp scales with the
#: problem, not with a constant
MATGATHER_DIR_PARTS = {"models", "ops", "server"}

#: gather-by-call forms that materialize exactly like advanced
#: indexing (``jnp.take(table, idx)`` lowers to the same XLA gather);
#: maps dotted name → positional index of the ``indices`` argument
GATHER_CALLS = {
    "jax.numpy.take": 1,
    "jax.numpy.take_along_axis": 1,
}


def _gather_finding(mod: ModuleInfo, node: ast.AST, desc: str,
                    fname: str, idx_name: str) -> Finding:
    return Finding(
        "materialized-gather", mod.path, node.lineno, node.col_offset,
        f"{desc} by the index array `{idx_name}` in hot function "
        f"`{fname}` materializes the gathered rows as an HBM temp of "
        f"unbounded size; bound it (row blocks), or pragma with a "
        f"size justification")


def _module_materialized_gather(mod: ModuleInfo,
                                ctx: CheckContext) -> List[Finding]:
    """``table[indices]`` advanced indexing by an index ARRAY inside
    train/serve hot-path functions.

    XLA materializes the gathered rows as an HBM temp whose size is the
    full index shape times the row width — ``fixed[indices]`` in the
    ALS half-step is ``[B, L, r]``, written once and read back at
    least once. Bound the gather (row blocks), or pragma it with
    a size justification (a ``[B, r]`` serving row-fetch is fine; an
    unbounded ``[B, L, r]`` training temp is not).

    Heuristic scope: inside a JITTED function (decorator, wrapped def,
    or ``jax.jit(lambda …)``) whose subscripted value and index are
    both bare names, with the index a TRACED parameter of that jit
    site — a traced scalar would be a data-dependent-shape error, so a
    traced parameter used as a subscript is an index array and the
    result is a device gather sized by the caller. ``jnp.take`` /
    ``jnp.take_along_axis`` on a traced-parameter index are the same
    gather spelled as a call and are flagged identically. ``x.at[i]``
    scatter/update builders and tuple-literal subscripts (host
    dispatch tables) are excluded; host-side helpers are out of scope
    (their gathers are numpy, paid once, not per dispatch).

    The project pass (:func:`rule_materialized_gather`) additionally
    flags a jitted function PASSING a traced parameter into a helper
    that (transitively) uses that parameter position as a gather
    index — the helper hides the subscript, the call site pays the
    HBM temp."""
    parts = set(mod.path.split("/")[:-1])
    if not (parts & MATGATHER_DIR_PARTS):
        return []
    findings: List[Finding] = []
    seen: Set[int] = set()
    proj = ctx.project
    collector = _collect_jit(mod)
    for site in collector.sites:
        fn = site.fn
        if fn is None:
            continue
        params = set(_param_names(fn)) - site.static_names
        if not params:
            continue
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        fname = getattr(fn, "name", "<lambda>")
        for stmt in body:
            for node in ast.walk(stmt):
                if id(node) in seen:
                    continue
                if isinstance(node, ast.Subscript) \
                        and isinstance(node.ctx, ast.Load):
                    idx = node.slice
                    if not (isinstance(idx, ast.Name)
                            and idx.id in params):
                        continue
                    val = node.value
                    if not isinstance(val, (ast.Name, ast.Attribute)):
                        continue  # (a, b)[i] host dispatch
                    if isinstance(val, ast.Attribute) \
                            and val.attr == "at":
                        continue  # x.at[ids] is a scatter builder
                    seen.add(id(node))
                    vname = mod.resolve(val) or "<expr>"
                    findings.append(_gather_finding(
                        mod, node,
                        f"advanced indexing `{vname}[{idx.id}]`",
                        fname, idx.id))
                    continue
                if not isinstance(node, ast.Call):
                    continue
                resolved = mod.resolve(node.func)
                pos = GATHER_CALLS.get(resolved or "")
                if pos is not None:
                    idx_arg = node.args[pos] \
                        if len(node.args) > pos else None
                    for kw in node.keywords:
                        if kw.arg == "indices":
                            idx_arg = kw.value
                    if isinstance(idx_arg, ast.Name) \
                            and idx_arg.id in params:
                        seen.add(id(node))
                        short = (resolved or "").rsplit(".", 1)[-1]
                        findings.append(_gather_finding(
                            mod, node, f"`jnp.{short}(…)`", fname,
                            idx_arg.id))
                    continue
                # interprocedural: traced param flows into a helper's
                # gather-index position
                if proj is None or id(node) in seen:
                    continue
                qname, bound = proj.resolve_call(mod, None, node.func)
                callee = proj.functions.get(qname or "")
                if callee is None or not callee.index_sinks:
                    continue
                off = 1 if bound else 0
                flow = None
                for i, a in enumerate(node.args):
                    if isinstance(a, ast.Name) and a.id in params \
                            and (i + off) in callee.index_sinks:
                        flow = (a.id, i + off)
                        break
                if flow is None:
                    for kw in node.keywords:
                        if kw.arg and kw.arg in callee.params \
                                and isinstance(kw.value, ast.Name) \
                                and kw.value.id in params:
                            p = callee.params.index(kw.arg)
                            if p in callee.index_sinks:
                                flow = (kw.value.id, p)
                                break
                if flow is None:
                    continue
                seen.add(id(node))
                idx_name, p = flow
                hops = proj.sink_chain(callee, "index", p)
                findings.append(Finding(
                    "materialized-gather", mod.path, node.lineno,
                    node.col_offset,
                    f"traced index `{idx_name}` of jitted `{fname}` "
                    f"flows into a gather one call away: "
                    f"{chain_text(hops)} — the helper hides the "
                    f"subscript but the call site pays the HBM temp; "
                    f"bound it, or "
                    f"pragma the helper's gather with a size "
                    f"justification",
                    related=chain_related(hops)))
    return findings


def rule_materialized_gather(mods: Sequence[ModuleInfo],
                             ctx: CheckContext) -> List[Finding]:
    findings: List[Finding] = []
    for mod in mods:
        findings.extend(_module_materialized_gather(mod, ctx))
    return findings


# ---------------------------------------------------------------------------
# rule 6: config-drift
# ---------------------------------------------------------------------------

#: the one module allowed to flip global jax config (platform policy)
CONFIG_HOME_SUFFIX = "utils/platform.py"


def rule_config_drift(mod: ModuleInfo, ctx: CheckContext) -> List[Finding]:
    if mod.path.endswith(CONFIG_HOME_SUFFIX):
        return []
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and mod.resolve(node.func) == "jax.config.update":
            key = ""
            if node.args and isinstance(node.args[0], ast.Constant):
                key = f" ({node.args[0].value!r})"
            findings.append(Finding(
                "config-drift", mod.path, node.lineno, node.col_offset,
                f"jax.config.update{key} outside utils/platform.py; "
                f"global config flips scattered across modules make "
                f"behavior depend on import order — route it through "
                f"the platform module"))
    return findings


# ---------------------------------------------------------------------------
# rule: unbounded-retry
# ---------------------------------------------------------------------------

#: directories whose loops talk to failable dependencies (ISSUE 11):
#: a swallow-and-continue loop here is a wedged-daemon generator
RETRY_SCOPE_PARTS = {"server", "streaming", "storage"}

#: attribute calls that pace (block/sleep) or bound a loop iteration —
#: their presence anywhere in the loop body means the retry is not a
#: hot spin; ``*_nowait`` variants deliberately do NOT count
_PACING_ATTRS = {"sleep", "wait", "get", "join", "acquire", "select",
                 "accept", "recv", "poll"}
_PACING_NAMES = {"time.sleep", "select.select"}
#: the shared bounded-backoff helpers (utils/retrying.py)
_PACING_SUFFIXES = ("retry_call", "backoff_delays")


def _walk_same_scope(node):
    """Walk a loop body without descending into nested function
    definitions (their loops are judged where they are defined)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            continue
        yield child
        yield from _walk_same_scope(child)


def _loop_unbounded(mod: ModuleInfo, node: ast.AST) -> bool:
    if isinstance(node, ast.While):
        t = node.test
        return isinstance(t, ast.Constant) and bool(t.value)
    if isinstance(node, ast.For):
        it = node.iter
        return isinstance(it, ast.Call) \
            and mod.resolve(it.func) == "itertools.count"
    return False


def _is_pacing_call(mod: ModuleInfo, call: ast.Call) -> bool:
    name = mod.resolve(call.func) or ""
    if name in _PACING_NAMES or name.endswith(_PACING_SUFFIXES):
        return True
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        return attr in _PACING_ATTRS and not attr.endswith("_nowait")
    return False


def rule_unbounded_retry(mod: ModuleInfo,
                         ctx: CheckContext) -> List[Finding]:
    """``while True`` (or ``itertools.count``) loops in server/,
    streaming/, or storage/ code that swallow exceptions and loop again
    with NO max-attempts bound and NO pacing call (sleep / blocking
    wait / the shared retry helpers): a failing dependency turns such a
    loop into a hot spin or a silently wedged daemon. Bound it with
    ``utils.retrying.retry_call`` (bounded exponential backoff) or add
    explicit pacing."""
    parts = set(mod.path.split("/")[:-1])
    if not parts & RETRY_SCOPE_PARTS:
        return []
    findings: List[Finding] = []
    for loop in ast.walk(mod.tree):
        if not isinstance(loop, (ast.While, ast.For)) \
                or not _loop_unbounded(mod, loop):
            continue
        body_nodes = [n for stmt in loop.body
                      for n in [stmt, *_walk_same_scope(stmt)]]
        swallows = None
        for n in body_nodes:
            if not isinstance(n, ast.Try):
                continue
            for handler in n.handlers:
                escapes = any(isinstance(h, (ast.Raise, ast.Return,
                                             ast.Break))
                              for stmt in handler.body
                              for h in [stmt, *_walk_same_scope(stmt)])
                if not escapes:
                    swallows = handler
                    break
            if swallows is not None:
                break
        if swallows is None:
            continue
        paced = any(isinstance(n, ast.Call) and _is_pacing_call(mod, n)
                    for n in body_nodes)
        if paced:
            continue
        findings.append(Finding(
            "unbounded-retry", mod.path, swallows.lineno,
            swallows.col_offset,
            "unbounded retry: this loop swallows the exception and "
            "re-runs with no max-attempts bound and no backoff/pacing "
            "— a failing dependency becomes a hot spin or a wedged "
            "daemon; bound it with utils.retrying.retry_call (bounded "
            "exponential backoff) or add explicit pacing"))
    return findings


# ---------------------------------------------------------------------------
# registry (JAX rules here; concurrency rule family in .concurrency)
# ---------------------------------------------------------------------------

from .concurrency import (  # noqa: E402 — registry assembly
    rule_blocking_under_lock,
    rule_callback_under_lock,
    rule_lock_order_inversion,
    rule_unguarded_shared_state,
)
from .kernels import (  # noqa: E402 — registry assembly
    rule_dma_unwaited,
    rule_low_precision_accumulator,
    rule_missing_interpret_fallback,
    rule_vmem_overbudget,
)
from .lifecycle import (  # noqa: E402 — registry assembly
    rule_hot_spin_loop,
    rule_leaked_thread,
    rule_missing_timeout,
    rule_non_atomic_persist,
    rule_unbounded_queue,
)
from .metrics_catalog import (  # noqa: E402 — registry assembly
    rule_metric_catalog_drift,
)
from .numerics import (  # noqa: E402 — registry assembly
    rule_dequant_outside_funnel,
    rule_low_precision_reduction,
    rule_quantize_without_parity_gate,
    rule_requant_torn_pair,
    rule_unguarded_domain,
)
from .sharding import (  # noqa: E402 — registry assembly
    rule_implicit_reshard,
    rule_missing_donation_sharded,
    rule_shard_map_spec_mismatch,
    rule_unsharded_capture,
)

RULES: Dict[str, Rule] = {r.name: r for r in (
    Rule("host-sync-in-hot-path",
         "device→host sync (np.asarray/.item()/.tolist()/device_get/"
         "block_until_ready) inside server/ or ops/ functions, "
         "directly or through any helper call chain",
         rule_host_sync, project=True),
    Rule("recompile-hazard",
         "jit sites that silently re-trace: unhashable statics, "
         "closures over jnp arrays, Python control flow on traced args",
         rule_recompile_hazard),
    Rule("missing-donation",
         "x = jitted(x, …) update steps without donate_argnums on the "
         "re-bound buffer",
         rule_missing_donation),
    Rule("sharding-mismatch",
         "PartitionSpec / NamedSharding / lax-collective / shard_map "
         "spec axis names (bare P() literals included) not declared "
         "by parallel/mesh.py",
         rule_sharding_mismatch),
    Rule("implicit-reshard",
         "a value with a known sharding passed — directly or through "
         "any helper chain — where a shard_map boundary pins a "
         "different spec: a silent all-gather/all-to-all per dispatch",
         rule_implicit_reshard, project=True),
    Rule("shard-map-spec-mismatch",
         "shard_map in_specs/out_specs arity disagreeing with the "
         "wrapped function, or axis names mixing different declared "
         "meshes (parallel/mesh.py groups)",
         rule_shard_map_spec_mismatch),
    Rule("unsharded-capture",
         "a shard_map'd/jitted closure capturing an array the "
         "enclosing scope shards — the capture enters replicated "
         "(implicit all-gather of the whole table)",
         rule_unsharded_capture),
    Rule("missing-donation-sharded",
         "x = step(x, …) re-binding a SHARDED buffer through a "
         "cross-module jitted step that does not donate the slot "
         "(2x peak HBM at exactly the scale that forced sharding)",
         rule_missing_donation_sharded, project=True),
    Rule("materialized-gather",
         "table[indices] / jnp.take gathers by traced params in "
         "models/, ops/, or server/ functions — directly or through "
         "a helper — unbounded HBM temps on train/serve hot paths "
         "(fuse or bound, or pragma with a size case)",
         rule_materialized_gather, project=True),
    Rule("config-drift",
         "jax.config.update outside utils/platform.py",
         rule_config_drift),
    Rule("unbounded-retry",
         "swallow-and-continue retry loops in server/, streaming/, or "
         "storage/ code with no max-attempts bound and no "
         "backoff/pacing (route through utils/retrying.py)",
         rule_unbounded_retry),
    Rule("vmem-overbudget",
         "pallas_call whose statically-evaluated VMEM working set "
         "(BlockSpec tiles double-buffered + scratch) exceeds the "
         "~16 MiB/core budget for the autotune rank/chunk grid",
         rule_vmem_overbudget),
    Rule("dma-unwaited",
         "make_async_copy .start() without a matching .wait() (by "
         "variable or semaphore slot), or a slot restarted before "
         "its wait",
         rule_dma_unwaited),
    Rule("low-precision-accumulator",
         "+=/dot accumulation into bf16/f16 Pallas scratch refs — "
         "kernel accumulators must be f32",
         rule_low_precision_accumulator),
    Rule("missing-interpret-fallback",
         "pallas_call hard-wired to compiled mode (no interpret= "
         "escape) instead of riding a support-gated dispatcher",
         rule_missing_interpret_fallback),
    Rule("unguarded-shared-state",
         "reads/writes of a class's lock-guarded attributes outside "
         "the lock (honors # ptpu: guarded-by[lock])",
         rule_unguarded_shared_state),
    Rule("lock-order-inversion",
         "cycles in the cross-file static lock-acquisition graph "
         "built from nested with-lock scopes",
         rule_lock_order_inversion, project=True),
    Rule("blocking-under-lock",
         "device dispatch, HTTP/storage I/O, sleep, join/wait/result "
         "inside a held-lock region in server/, cache/, or rollout/",
         rule_blocking_under_lock),
    Rule("callback-under-lock",
         "bus/plugin callbacks invoked while holding the publisher's "
         "lock (re-entrancy deadlock)",
         rule_callback_under_lock),
    Rule("low-precision-reduction",
         "sum/mean/dot/einsum/@ over bf16/f16 operands accumulating "
         "at operand precision (no f32 preferred_element_type or "
         "upcast) in models/ops/streaming — directly or through any "
         "helper chain",
         rule_low_precision_reduction, project=True),
    Rule("dequant-outside-funnel",
         "f32 materialization of quantized table data outside the "
         "blessed dequantize_table/table_host_f32/_host_row_f32 "
         "funnels — the silent HBM-win defeat",
         rule_dequant_outside_funnel),
    Rule("quantize-without-parity-gate",
         "QuantizedFactors/_quantize_rows construction bypassing "
         "quantize_serving_model's NDCG@10 parity probe and "
         "auto-fallback path",
         rule_quantize_without_parity_gate),
    Rule("unguarded-domain",
         "log/sqrt/rsqrt/division over traced or accumulated values "
         "with no epsilon/clip guard (drift.py's max(x, 1e-9) is the "
         "blessed idiom)",
         rule_unguarded_domain),
    Rule("requant-torn-pair",
         "QuantizedFactors.data written (assignment or "
         "dataclasses.replace) without the paired scale update across "
         "the fold-in/hot-swap seam",
         rule_requant_torn_pair),
    Rule("metric-catalog-drift",
         "pio_* families registered in code but missing from the "
         "docs/observability.md catalog, or documented but never "
         "emitted (both directions)",
         rule_metric_catalog_drift, project=True),
    Rule("leaked-thread",
         "threading.Thread with a looping target started in server/, "
         "fleet/, router/, streaming/, or rollout/ code whose handle "
         "is never joined — in the spawning function, the owning "
         "class, or through a call-graph join helper",
         rule_leaked_thread, project=True),
    Rule("missing-timeout",
         "urlopen/HTTPConnection/create_connection with no explicit "
         "timeout reachable from fleet/, router/, data/, or storage/ "
         "code — directly or through any helper chain (a wedged peer "
         "freezes the scrape/control tick forever)",
         rule_missing_timeout, project=True),
    Rule("non-atomic-persist",
         "durable state (baselines, gates, registries, artifacts) "
         "written with a plain open(path, 'w') outside the temp-file+"
         "fsync+os.replace funnel — a crash mid-write tears the file",
         rule_non_atomic_persist),
    Rule("unbounded-queue",
         "queue.Queue()/collections.deque() constructed without a "
         "bound on serving/streaming paths — backlog becomes an OOM "
         "instead of backpressure under overload",
         rule_unbounded_queue),
    Rule("hot-spin-loop",
         "while-True daemon loops in server/, streaming/, fleet/, "
         "router/, rollout/, or slo/ code with neither a stop-event "
         "check nor a pacing/blocking call — pins a core and ignores "
         "shutdown (complements unbounded-retry)",
         rule_hot_spin_loop),
)}
