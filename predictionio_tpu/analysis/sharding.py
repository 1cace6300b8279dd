"""The SPMD sharding-flow rule family behind ``ptpu check``.

PRs 6/7/13 put every hot path through GSPMD — replicated/sharded
serving, shard_map'd fused kernels, mesh-wide training — and the
failure mode that taxes a mesh hardest is *silent*: when the
PartitionSpec a value carries disagrees with the spec its consumer
constrains, XLA does not raise — it inserts an all-gather or
all-to-all at the jit/shard_map boundary and the program quietly pays
ICI bandwidth for every dispatch (the dominant scaling tax of both the
ALX sharded layout, arXiv 2112.02194, and Google's ads-infra fleet
paper, arXiv 2501.10546). Four rules, pure AST like the rest of this
package; their runtime complement is ``ptpu audit-hlo``
(:mod:`.hlo_audit`), which compiles the registered entry points on a
forced 8-device mesh and diffs the *actual* collectives against a
committed golden manifest.

- ``implicit-reshard`` — a value with a known sharding (built by
  ``jax.device_put(x, NamedSharding(mesh, spec))`` or a
  ``*shard*``-named helper taking a spec argument) is passed where the
  callee — directly, or any number of helper calls away — feeds that
  parameter position into a ``shard_map`` whose ``in_specs`` pins a
  *different* spec. The boundary is a hidden collective; the finding
  carries the interprocedural chain down to the shard_map site.
  Constraints are collected as per-function **spec sinks**
  (:class:`~.core.ProjectIndex` effect summaries) so a pragma at the
  shard_map boundary blesses every caller at once.
- ``shard-map-spec-mismatch`` — ``shard_map`` / ``shard_map_compat`` /
  ``sharded`` sites whose ``in_specs`` arity disagrees with the wrapped
  function's parameter count, whose ``out_specs`` arity disagrees with
  the function's returned tuple, or whose literal axis names (specs +
  the body's lax collectives) mix axes of *different* declared meshes
  (``parallel/mesh.py`` declares the groups — ``(data, model)`` and
  ``(batch, model)``; a site using ``data`` with ``batch`` can run on
  no mesh this framework builds). Undeclared axis names are the
  (generalized) ``sharding-mismatch`` rule's job.
- ``unsharded-capture`` — a shard_map'd (or nested-jitted) function
  **closing over** an array the enclosing scope knows to be sharded:
  a closure capture enters the program replicated, i.e. an implicit
  all-gather of the full table on every dispatch, exactly when a
  row-sharded spec already exists for it. Pass it as an argument with
  a matching in_spec.
- ``missing-donation-sharded`` — ``x = step(x, …)`` where ``x`` is
  known sharded and ``step`` resolves (cross-module, through the
  project index) to a jit-decorated function that does not donate that
  slot: the un-donated buffer doubles peak HBM at exactly the scale
  where the table was sharded because it did not fit. The same-module
  case is ``missing-donation``'s job; this rule covers the boundary
  the per-module pass cannot see.

All four honor ``# ptpu: allow[rule] — justification`` pragmas and ride
``--format sarif`` and the baseline ratchet like every other rule.
``docs/static-analysis.md`` is the operator-facing reference;
``docs/parallelism.md`` documents how to read an ``audit-hlo`` diff.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import (
    PRAGMA_RE,
    CheckContext,
    Finding,
    ModuleInfo,
    Witness,
    chain_related,
    chain_text,
    short_name,
)

#: canonical symbol for :func:`parallel.mesh.rows_spec` — the leading
#: axis sharded over EVERY axis of whichever mesh is in scope
ROWS_SPEC = "rows(*)"

#: canonical replicated spec
REPLICATED = "P()"

#: callables that wrap a function with pinned in/out specs
_SHARD_MAP_NAMES = {"shard_map", "shard_map_compat", "sharded"}

#: the sharding rule family (the ``pio_sharding_findings`` gauge and
#: the docs catalogue both key off this tuple)
SHARDING_RULES = (
    "implicit-reshard",
    "shard-map-spec-mismatch",
    "unsharded-capture",
    "missing-donation-sharded",
    "sharding-mismatch",
)


# ---------------------------------------------------------------------------
# PartitionSpec expression parsing → canonical spec strings
# ---------------------------------------------------------------------------

def _is_pspec_call(mod: ModuleInfo, node: ast.AST) -> bool:
    """A ``PartitionSpec(...)`` literal however it is spelled: the
    resolved dotted name, or — when the alias table cannot resolve it
    (star imports, ``jax.P``) — a bare ``P`` / ``PartitionSpec``
    callee name."""
    if not isinstance(node, ast.Call):
        return False
    resolved = mod.resolve(node.func) or ""
    if resolved == "jax.sharding.PartitionSpec":
        return True
    last = resolved.rsplit(".", 1)[-1] if resolved else ""
    if last in ("P", "PartitionSpec"):
        return True
    return isinstance(node.func, ast.Attribute) \
        and node.func.attr in ("P", "PartitionSpec")


def _is_rows_spec_call(mod: ModuleInfo, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    resolved = mod.resolve(node.func) or ""
    return resolved.rsplit(".", 1)[-1] == "rows_spec"


class _Assigns:
    """Name → value-expression chains over (module constants, one
    function's simple assignments) — the same best-effort resolution
    the kernel rules use, for following ``spec = rows_spec(mesh)``
    into ``in_specs=(P(), spec, …)``."""

    def __init__(self, mod: ModuleInfo, fn: Optional[ast.AST] = None):
        self.table: Dict[str, ast.AST] = {}
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self.table[node.targets[0].id] = node.value
        if fn is not None:
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    self.table[node.targets[0].id] = node.value

    def follow(self, node: ast.AST, depth: int = 0) -> ast.AST:
        while isinstance(node, ast.Name) and depth < 8:
            tgt = self.table.get(node.id)
            if tgt is None or tgt is node:
                break
            node = tgt
            depth += 1
        return node


def parse_spec(mod: ModuleInfo, assigns: _Assigns,
               node: Optional[ast.AST]) -> Optional[str]:
    """Canonical string for one PartitionSpec expression, or None when
    it cannot be pinned down. ``P()``/``P(None)`` → ``"P()"``;
    ``P("x")`` → ``"P(x)"``; ``P(("a","b"))`` → ``"P((a,b))"``;
    ``rows_spec(mesh)`` → :data:`ROWS_SPEC`. Trailing ``None`` entries
    drop (they shard nothing)."""
    if node is None:
        return None
    node = assigns.follow(node)
    if _is_rows_spec_call(mod, node):
        return ROWS_SPEC
    if not _is_pspec_call(mod, node):
        return None
    entries: List[str] = []
    for arg in node.args:
        arg = assigns.follow(arg)
        if isinstance(arg, ast.Constant) and arg.value is None:
            entries.append("None")
        elif isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            entries.append(arg.value)
        elif isinstance(arg, (ast.Tuple, ast.List)):
            names: List[str] = []
            for e in arg.elts:
                e = assigns.follow(e)
                if isinstance(e, ast.Constant) \
                        and isinstance(e.value, str):
                    names.append(e.value)
                else:
                    return None
            entries.append("(" + ",".join(names) + ")")
        else:
            return None
    if node.keywords:
        return None
    while entries and entries[-1] == "None":
        entries.pop()
    return "P(" + ",".join(entries) + ")"


def spec_axes(spec: str) -> Set[str]:
    """Axis names a canonical spec string shards over (empty for
    replicated / rows-symbolic)."""
    if spec in (ROWS_SPEC, REPLICATED):
        return set()
    inner = spec[2:-1] if spec.startswith("P(") else spec
    return {a for a in re.split(r"[(),]", inner)
            if a and a != "None"}


def normalize_spec(spec: str,
                   groups: Set[Tuple[str, ...]]) -> str:
    """Fold a literal spec that row-shards over a FULL declared mesh
    group (``P((data,model))``) into :data:`ROWS_SPEC` — that is
    exactly what ``rows_spec`` evaluates to on that mesh, and the two
    spellings must not count as a reshard."""
    if spec == ROWS_SPEC or not groups:
        return spec
    m = re.fullmatch(r"P\(\(([^()]+)\)\)", spec)
    if m:
        axes = frozenset(a.strip() for a in m.group(1).split(","))
        if any(axes == frozenset(g) for g in groups):
            return ROWS_SPEC
    return spec


def specs_conflict(a: str, b: str,
                   groups: Set[Tuple[str, ...]]) -> bool:
    return normalize_spec(a, groups) != normalize_spec(b, groups)


def _named_sharding_spec(mod: ModuleInfo, assigns: _Assigns,
                         node: ast.AST) -> Optional[str]:
    """Canonical spec of a ``NamedSharding(mesh, spec)`` expression
    (followed through simple assignments)."""
    node = assigns.follow(node)
    if not (isinstance(node, ast.Call)
            and (mod.resolve(node.func) or "").rsplit(".", 1)[-1]
            == "NamedSharding"):
        return None
    spec_node = node.args[1] if len(node.args) > 1 else None
    for kw in node.keywords:
        if kw.arg == "spec":
            spec_node = kw.value
    return parse_spec(mod, assigns, spec_node)


# ---------------------------------------------------------------------------
# shard_map site model
# ---------------------------------------------------------------------------

def _is_shard_map_call(mod: ModuleInfo, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    resolved = mod.resolve(node.func) or ""
    if resolved.rsplit(".", 1)[-1] in _SHARD_MAP_NAMES:
        return True
    return isinstance(node.func, ast.Attribute) \
        and node.func.attr in _SHARD_MAP_NAMES


class ShardMapSite:
    """One ``shard_map(fn, mesh, in_specs, out_specs)`` /
    ``shard_map_compat(…)`` call or ``@sharded(mesh, in_specs,
    out_specs)`` decoration, with its specs parsed to canonical
    strings (None where unparseable)."""

    def __init__(self, mod: ModuleInfo, assigns: _Assigns,
                 call: ast.Call, wrapped: Optional[ast.AST]):
        self.call = call
        self.mod = mod
        resolved = mod.resolve(call.func) or ""
        is_deco = resolved.rsplit(".", 1)[-1] == "sharded" or (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "sharded")
        # sharded(mesh, in, out) decorates; shard_map(fn, mesh, in, out)
        pos = list(call.args)
        if is_deco:
            pos = [None] + pos
        self.wrapped: Optional[ast.AST] = wrapped
        if self.wrapped is None and pos and pos[0] is not None:
            self.wrapped = assigns.follow(pos[0])
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        self.in_specs_node = kw.get("in_specs", pos[2]
                                    if len(pos) > 2 else None)
        self.out_specs_node = kw.get("out_specs", pos[3]
                                     if len(pos) > 3 else None)
        self.in_specs, self.in_specs_is_seq = self._parse_side(
            mod, assigns, self.in_specs_node)
        self.out_specs, self.out_specs_is_seq = self._parse_side(
            mod, assigns, self.out_specs_node)

    @staticmethod
    def _parse_side(mod: ModuleInfo, assigns: _Assigns,
                    node: Optional[ast.AST]
                    ) -> Tuple[Optional[List[Optional[str]]], bool]:
        """(per-leaf canonical specs, was-a-tuple) — None list when the
        expression is absent or unfollowable."""
        if node is None:
            return None, False
        node = assigns.follow(node)
        if isinstance(node, (ast.Tuple, ast.List)):
            return [parse_spec(mod, assigns, e)
                    for e in node.elts], True
        one = parse_spec(mod, assigns, node)
        return ([one], False) if one is not None else (None, False)

    def spec_for_arg(self, i: int) -> Optional[str]:
        if self.in_specs is None:
            return None
        if not self.in_specs_is_seq:
            return self.in_specs[0]
        return self.in_specs[i] if i < len(self.in_specs) else None


def _local_def(fn_scope: Optional[ast.AST], mod: ModuleInfo,
               expr: Optional[ast.AST]) -> Optional[ast.AST]:
    """Resolve a shard_map's wrapped expression to a FunctionDef /
    Lambda: direct, or a Name bound to a def in the enclosing function
    or at module level."""
    if expr is None:
        return None
    if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.Lambda)):
        return expr
    if not isinstance(expr, ast.Name):
        return None
    scopes: List[ast.AST] = []
    if fn_scope is not None:
        scopes.append(fn_scope)
    scopes.append(mod.tree)
    for scope in scopes:
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == expr.id:
                return node
    return None


def _shard_map_sites(mod: ModuleInfo, scope: ast.AST,
                     assigns: _Assigns) -> List[ShardMapSite]:
    """Every shard_map-family call within ``scope``, plus ``@sharded``
    decorations (their wrapped fn is the decorated def)."""
    sites: List[ShardMapSite] = []
    for node in ast.walk(scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_shard_map_call(mod, dec):
                    sites.append(ShardMapSite(mod, assigns, dec, node))
        if _is_shard_map_call(mod, node):
            sites.append(ShardMapSite(mod, assigns, node, None))
    return sites


# ---------------------------------------------------------------------------
# known-sharding local dataflow
# ---------------------------------------------------------------------------

def _mentions_sharding(mod: ModuleInfo) -> bool:
    """Cheap text gate: a module that never says ``shard`` or
    ``device_put`` can hold no shard_map boundary and no placed array
    — every rule in this family early-outs on it (the scan is
    O(repo), the AST passes are not)."""
    cached = getattr(mod, "_sharding_hint", None)
    if cached is None:
        cached = ("shard" in mod.source
                  or "device_put" in mod.source)
        mod._sharding_hint = cached
    return cached


def local_spec_map(mod: ModuleInfo, fn: ast.AST,
                   assigns: Optional[_Assigns] = None
                   ) -> Dict[str, Tuple[str, int]]:
    """Variable → (canonical spec, line) facts inside one function:
    ``x = jax.device_put(y, NamedSharding(mesh, spec))`` (sharding
    followed through assignment), and ``x = helper(…, spec, …)`` where
    the helper's name contains ``shard`` and some argument parses as a
    spec (the ``_shard`` / ``_zeros_sharded`` idiom — the framework
    funnels every explicit placement through such helpers)."""
    memo = getattr(mod, "_spec_maps", None)
    if memo is None:
        memo = mod._spec_maps = {}
    cached = memo.get(id(fn))
    if cached is not None:
        return cached
    if not _mentions_sharding(mod):
        memo[id(fn)] = {}
        return {}
    assigns = assigns or _Assigns(mod, fn)
    out: Dict[str, Tuple[str, int]] = {}

    def record(targets: List[ast.expr], spec: str, line: int) -> None:
        for t in targets:
            if isinstance(t, ast.Name):
                out[t.id] = (spec, line)

    for node in ast.walk(fn):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        resolved = mod.resolve(call.func) or ""
        last = resolved.rsplit(".", 1)[-1]
        spec: Optional[str] = None
        if last == "device_put" and len(call.args) >= 2:
            spec = _named_sharding_spec(mod, assigns, call.args[1])
        elif "shard" in last.lower() \
                and not _is_shard_map_call(mod, call):
            for arg in list(call.args) + [k.value for k in
                                          call.keywords]:
                spec = parse_spec(mod, assigns, arg)
                if spec is not None:
                    break
        if spec is not None:
            record(node.targets, spec, node.lineno)
    memo[id(fn)] = out
    return out


# ---------------------------------------------------------------------------
# spec sinks: the interprocedural constraint summaries
# (collected by core.ProjectIndex._collect_direct)
# ---------------------------------------------------------------------------

def collect_spec_sinks(fn_info) -> Dict[int, Tuple[str, Witness]]:
    """Parameter position → (canonical in_spec, witness) for params
    this function feeds into a shard_map boundary: the direct sites of
    ``implicit-reshard``. A ``# ptpu: allow[implicit-reshard]`` pragma
    at the boundary kills the sink — blessing the one documented
    boundary blesses every caller."""
    mod: ModuleInfo = fn_info.mod
    fn = fn_info.node
    params: List[str] = fn_info.params
    if not params or not _mentions_sharding(mod) \
            or "shard_map" not in mod.source \
            and "sharded" not in mod.source:
        return {}
    assigns = _Assigns(mod, fn)
    sites_by_name: Dict[str, ShardMapSite] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_shard_map_call(mod, node.value):
            sites_by_name[node.targets[0].id] = ShardMapSite(
                mod, assigns, node.value, None)
    out: Dict[int, Tuple[str, Witness]] = {}

    def consume(call: ast.Call, site: ShardMapSite) -> None:
        for i, a in enumerate(call.args):
            if not (isinstance(a, ast.Name) and a.id in params):
                continue
            spec = site.spec_for_arg(i)
            if spec is None:
                continue
            pos = params.index(a.id)
            if pos in out:
                continue
            probe = Finding("implicit-reshard", mod.path,
                            call.lineno, 0, "")
            if mod.suppressed(probe):
                continue
            out[pos] = (spec, Witness(
                "implicit-reshard", mod.path, call.lineno,
                call.col_offset,
                f"`{a.id}` enters a shard_map boundary with "
                f"in_spec {spec}"))

    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name) \
                and node.func.id in sites_by_name:
            consume(node, sites_by_name[node.func.id])
        elif isinstance(node.func, ast.Call) \
                and _is_shard_map_call(mod, node.func):
            consume(node, ShardMapSite(mod, assigns, node.func, None))
    return out


# ---------------------------------------------------------------------------
# rule: implicit-reshard (project-scoped)
# ---------------------------------------------------------------------------

def _function_nodes(mod: ModuleInfo
                    ) -> List[Tuple[Optional[str], ast.AST]]:
    out: List[Tuple[Optional[str], ast.AST]] = []
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append((None, node))
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    out.append((node.name, sub))
    return out


def rule_implicit_reshard(mods: Sequence[ModuleInfo],
                          ctx: CheckContext) -> List[Finding]:
    """A value with a known sharding passed — directly or through any
    helper chain — into a shard_map boundary whose ``in_specs`` pins a
    different spec: XLA inserts the collective silently. Reported at
    the call site that owns the sharded value, with the chain down to
    the boundary."""
    proj = ctx.project
    if proj is None:
        return []
    groups = ctx.declared_groups
    findings: List[Finding] = []
    for mod in mods:
        if not _mentions_sharding(mod):
            continue
        for cls, fn in _function_nodes(mod):
            specmap = local_spec_map(mod, fn)
            if not specmap:
                continue
            assigns = _Assigns(mod, fn)
            sites_by_name: Dict[str, ShardMapSite] = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and _is_shard_map_call(mod, node.value):
                    sites_by_name[node.targets[0].id] = ShardMapSite(
                        mod, assigns, node.value, None)
            seen: Set[int] = set()
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) \
                        or id(node) in seen:
                    continue
                # direct: calling a shard_map'd local with a var whose
                # known spec disagrees with that position's in_spec
                site = None
                if isinstance(node.func, ast.Name) \
                        and node.func.id in sites_by_name:
                    site = sites_by_name[node.func.id]
                elif isinstance(node.func, ast.Call) \
                        and _is_shard_map_call(mod, node.func):
                    site = ShardMapSite(mod, assigns, node.func, None)
                if site is not None:
                    for i, a in enumerate(node.args):
                        if not (isinstance(a, ast.Name)
                                and a.id in specmap):
                            continue
                        want = site.spec_for_arg(i)
                        have = specmap[a.id][0]
                        if want is None \
                                or not specs_conflict(have, want,
                                                      groups):
                            continue
                        seen.add(id(node))
                        findings.append(Finding(
                            "implicit-reshard", mod.path, node.lineno,
                            node.col_offset,
                            f"`{a.id}` carries sharding {have} but "
                            f"this shard_map boundary consumes it "
                            f"with in_spec {want}; XLA inserts a "
                            f"silent collective (all-gather / "
                            f"all-to-all) on every dispatch — align "
                            f"the specs, reshard explicitly, or "
                            f"pragma the boundary with a "
                            f"justification"))
                    continue
                # interprocedural: the callee (transitively) pins a
                # conflicting spec on this parameter position
                qname, bound = proj.resolve_call(mod, cls, node.func)
                callee = proj.functions.get(qname or "")
                if callee is None or not callee.spec_constraints:
                    continue
                off = 1 if bound else 0
                for i, a in enumerate(node.args):
                    if not (isinstance(a, ast.Name)
                            and a.id in specmap):
                        continue
                    want = callee.spec_constraints.get(i + off)
                    have = specmap[a.id][0]
                    if want is None \
                            or not specs_conflict(have, want, groups):
                        continue
                    seen.add(id(node))
                    hops = proj.sink_chain(callee, "spec", i + off)
                    findings.append(Finding(
                        "implicit-reshard", mod.path, node.lineno,
                        node.col_offset,
                        f"`{a.id}` carries sharding {have} but "
                        f"`{short_name(callee.qname)}` consumes it "
                        f"with spec {want} at a shard_map boundary: "
                        f"{chain_text(hops)} — XLA inserts a silent "
                        f"collective at that boundary on every "
                        f"dispatch; align the specs, reshard "
                        f"explicitly, or pragma the boundary (its "
                        f"direct site blesses all callers)",
                        related=chain_related(hops)))
                    break
    return findings


# ---------------------------------------------------------------------------
# rule: shard-map-spec-mismatch
# ---------------------------------------------------------------------------

def _return_tuple_lengths(fn: ast.AST) -> Optional[Set[int]]:
    """Lengths of the tuple literals this function returns — None when
    any return is a non-tuple expression (single output or opaque
    call: not statically checkable)."""
    lengths: Set[int] = set()
    stack = list(fn.body) if isinstance(fn.body, list) else [fn.body]
    returns: List[ast.Return] = []
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Return) and node.value is not None:
            returns.append(node)
        stack.extend(ast.iter_child_nodes(node))
    if isinstance(fn, ast.Lambda):
        returns = []
        if isinstance(fn.body, ast.Tuple):
            lengths.add(len(fn.body.elts))
            return lengths
        return None
    for r in returns:
        if isinstance(r.value, ast.Tuple):
            lengths.add(len(r.value.elts))
        else:
            return None
    return lengths or None


def _collective_axis_literals(mod: ModuleInfo,
                              fn: ast.AST) -> List[Tuple[str, ast.AST]]:
    """(axis name, node) for literal axis arguments of lax collectives
    inside ``fn``."""
    from .rules import _COLLECTIVE_AXIS_ARG, _axis_literals

    out: List[Tuple[str, ast.AST]] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        pos = _COLLECTIVE_AXIS_ARG.get(mod.resolve(node.func) or "")
        if pos is None:
            continue
        args = []
        if pos < len(node.args):
            args.append(node.args[pos])
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis"):
                args.append(kw.value)
        for a in args:
            for name in _axis_literals(a):
                out.append((name, node))
    return out


def rule_shard_map_spec_mismatch(mod: ModuleInfo,
                                 ctx: CheckContext) -> List[Finding]:
    """shard_map sites whose specs cannot agree with the function they
    wrap: in_specs arity ≠ parameter count, out_specs arity ≠ returned
    tuple length, or axis names (specs + body collectives) drawn from
    *different* declared meshes — generalizing the positional-only
    PR 6 ``sharding-mismatch`` collective check to the whole
    boundary."""
    if "shard" not in mod.source:
        return []
    findings: List[Finding] = []
    assigns = _Assigns(mod)
    groups = ctx.declared_groups
    for site in _shard_map_sites(mod, mod.tree, assigns):
        call = site.call
        fn = _local_def(None, mod, site.wrapped)
        # (a) in_specs arity vs wrapped parameter count
        if fn is not None and site.in_specs is not None \
                and site.in_specs_is_seq \
                and all(s is not None for s in site.in_specs):
            a = fn.args
            n_params = len(a.posonlyargs) + len(a.args)
            has_var = a.vararg is not None
            n_required = n_params - len(a.defaults)
            n = len(site.in_specs)
            if not has_var and (n > n_params or n < n_required):
                fname = getattr(fn, "name", "<lambda>")
                findings.append(Finding(
                    "shard-map-spec-mismatch", mod.path, call.lineno,
                    call.col_offset,
                    f"in_specs carries {n} spec(s) but the wrapped "
                    f"`{fname}` takes "
                    f"{n_required if n_required == n_params else f'{n_required}..{n_params}'} "
                    f"argument(s); shard_map will reject the call at "
                    f"trace time on a real mesh — align the spec "
                    f"tuple with the signature"))
        # (b) out_specs arity vs returned tuple length
        if fn is not None and site.out_specs_node is not None:
            lengths = _return_tuple_lengths(fn)
            if lengths is not None and len(lengths) == 1:
                m = next(iter(lengths))
                if site.out_specs is not None \
                        and all(s is not None
                                for s in site.out_specs):
                    n = len(site.out_specs)
                    mismatch = (site.out_specs_is_seq and n != m) or \
                        (not site.out_specs_is_seq and m > 1)
                    if mismatch:
                        fname = getattr(fn, "name", "<lambda>")
                        findings.append(Finding(
                            "shard-map-spec-mismatch", mod.path,
                            call.lineno, call.col_offset,
                            f"out_specs carries "
                            f"{n if site.out_specs_is_seq else 'one'} "
                            f"spec(s) but `{fname}` returns a "
                            f"{m}-tuple; shard_map will reject the "
                            f"output pytree at trace time — one spec "
                            f"per returned leaf"))
        # (c) axis coherence: every literal axis this boundary touches
        # must fit on ONE declared mesh
        if groups:
            axes_used: Dict[str, ast.AST] = {}
            for side in (site.in_specs, site.out_specs):
                for s in side or []:
                    if s is not None:
                        for name in spec_axes(s):
                            axes_used.setdefault(name, call)
            if fn is not None:
                for name, node in _collective_axis_literals(mod, fn):
                    axes_used.setdefault(name, node)
            declared = {a for g in groups for a in g}
            known = {a for a in axes_used if a in declared}
            if known and not any(known <= set(g) for g in groups):
                findings.append(Finding(
                    "shard-map-spec-mismatch", mod.path, call.lineno,
                    call.col_offset,
                    f"this shard_map boundary mixes axes "
                    f"{sorted(known)} that belong to different "
                    f"declared meshes "
                    f"({sorted(tuple(g) for g in ctx.declared_groups)} "
                    f"in parallel/mesh.py); no single mesh carries "
                    f"them all — derive the specs from the mesh "
                    f"(rows_spec) or split the boundary"))
    return findings


# ---------------------------------------------------------------------------
# rule: unsharded-capture
# ---------------------------------------------------------------------------

def rule_unsharded_capture(mod: ModuleInfo,
                           ctx: CheckContext) -> List[Finding]:
    """A shard_map'd (or nested-jitted) function closing over an array
    the enclosing scope placed with a non-replicated NamedSharding:
    the capture enters the program replicated — an implicit
    all-gather of the whole table per dispatch — precisely when a
    sharded spec already exists for it. Pass it as an argument with a
    matching in_spec instead."""
    if not _mentions_sharding(mod):
        return []
    from .rules import _collect_jit, _free_loads

    findings: List[Finding] = []
    flagged: Set[Tuple[int, str]] = set()

    def check_capture(inner: ast.AST, anchor: ast.AST, kind: str,
                      specmap: Dict[str, Tuple[str, int]]) -> None:
        free = _free_loads(inner)
        for name in sorted(free & set(specmap)):
            spec, _line = specmap[name]
            if spec == REPLICATED:
                continue
            key = (id(anchor), name)
            if key in flagged:
                continue
            flagged.add(key)
            iname = getattr(inner, "name", "<lambda>")
            findings.append(Finding(
                "unsharded-capture", mod.path, anchor.lineno,
                anchor.col_offset,
                f"`{iname}` closes over `{name}`, which the enclosing "
                f"scope shards as {spec}; a closure capture enters "
                f"the {kind} replicated — an implicit all-gather of "
                f"the whole array per dispatch. Pass it as an "
                f"argument with a matching spec, or pragma with the "
                f"sizing argument"))

    for _cls, fn in _function_nodes(mod):
        assigns = _Assigns(mod, fn)
        specmap = local_spec_map(mod, fn, assigns)
        if not specmap:
            continue
        for site in _shard_map_sites(mod, fn, assigns):
            inner = _local_def(fn, mod, site.wrapped)
            if inner is not None:
                check_capture(inner, site.call, "shard_map", specmap)
    collector = _collect_jit(mod)
    for site in collector.sites:
        if site.fn is None or not site.scope_stack:
            continue
        for scope in site.scope_stack:
            specmap = local_spec_map(mod, scope)
            if specmap:
                anchor = site.call if site.call is not None else site.fn
                check_capture(site.fn, anchor, "jit trace", specmap)
    return findings


# ---------------------------------------------------------------------------
# rule: missing-donation-sharded (project-scoped)
# ---------------------------------------------------------------------------

def _jit_donations(mod: ModuleInfo, fn: ast.AST
                   ) -> Optional[Tuple[Set[int], Set[str]]]:
    """(donate_argnums, donate_argnames) of a jit-decorated def, or
    None when the def carries no jit decoration."""
    from .rules import _jit_kwargs, _statics_and_donations, _param_names

    params = _param_names(fn)
    for dec in getattr(fn, "decorator_list", []):
        name = mod.resolve(dec)
        if name == "jax.jit":
            return set(), set()
        if isinstance(dec, ast.Call):
            callee = mod.resolve(dec.func)
            if callee == "jax.jit" or (
                    callee == "functools.partial" and dec.args
                    and mod.resolve(dec.args[0]) == "jax.jit"):
                _s, dn, dnm = _statics_and_donations(
                    _jit_kwargs(dec), params)
                return dn, dnm
    return None


def rule_missing_donation_sharded(mods: Sequence[ModuleInfo],
                                  ctx: CheckContext) -> List[Finding]:
    """``x = step(x, …)`` where ``x`` is known SHARDED and ``step``
    resolves cross-module (through the project index) to a
    jit-decorated function that does not donate that slot: the old
    sharded buffer stays live across the dispatch — 2× peak HBM at
    exactly the scale where the table was sharded because one HBM
    could not hold it. The same-module case is ``missing-donation``'s;
    this rule covers the import boundary the per-module pass cannot
    see."""
    proj = ctx.project
    if proj is None:
        return []
    from .rules import _param_names

    findings: List[Finding] = []
    donations_cache: Dict[str, Optional[Tuple[Set[int], Set[str]]]] = {}
    for mod in mods:
        if not _mentions_sharding(mod):
            continue
        for cls, fn in _function_nodes(mod):
            specmap = local_spec_map(mod, fn)
            if not specmap:
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    continue
                call = node.value
                targets: Set[str] = set()
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        targets.add(t.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        targets |= {e.id for e in t.elts
                                    if isinstance(e, ast.Name)}
                rebound = [(i, a.id) for i, a in enumerate(call.args)
                           if isinstance(a, ast.Name)
                           and a.id in targets and a.id in specmap]
                if not rebound:
                    continue
                qname, bound = proj.resolve_call(mod, cls, call.func)
                callee = proj.functions.get(qname or "")
                if callee is None or callee.mod is mod:
                    continue  # same module: missing-donation's job
                don = donations_cache.get(callee.qname)
                if callee.qname not in donations_cache:
                    don = _jit_donations(callee.mod, callee.node)
                    donations_cache[callee.qname] = don
                if don is None:
                    continue  # not a jit boundary
                dn, dnm = don
                cparams = _param_names(callee.node)
                off = 1 if bound else 0
                for i, name in rebound:
                    pos = i + off
                    pname = cparams[pos] if pos < len(cparams) else ""
                    if pos in dn or pname in dnm:
                        continue
                    spec = specmap[name][0]
                    findings.append(Finding(
                        "missing-donation-sharded", mod.path,
                        node.lineno, node.col_offset,
                        f"sharded buffer `{name}` ({spec}) is "
                        f"re-bound to an output of jitted "
                        f"`{short_name(callee.qname)}` "
                        f"({callee.mod.path}) without donation; the "
                        f"old shards stay live across the step — 2x "
                        f"peak HBM at exactly the scale that forced "
                        f"sharding — add position {pos} to its "
                        f"donate_argnums",
                        related=((callee.mod.path,
                                  callee.node.lineno,
                                  f"`{short_name(callee.qname)}` is "
                                  f"jitted here without donating "
                                  f"`{pname or pos}`"),)))
    return findings


# ---------------------------------------------------------------------------
# pragma census (the pio_sharding_findings info gauge)
# ---------------------------------------------------------------------------

def count_sharding_pragmas(root: Optional[str] = None
                           ) -> Dict[str, int]:
    """Per-rule count of ``# ptpu: allow[...]`` pragmas naming a
    sharding-family rule under ``root`` (default: this installed
    package) — the number of accepted-and-justified sharding findings
    baked into the deployed build, exported by the engine server as
    the ``pio_sharding_findings`` info gauge so a deploy that ships
    new suppressed sharding debt is visible on /metrics. Pure text
    scan: no jax, no AST, milliseconds."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    self_dir = os.path.dirname(os.path.abspath(__file__))
    counts: Dict[str, int] = {}
    for dirpath, dirnames, names in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if not d.startswith(".")
                             and d != "__pycache__")
        if os.path.abspath(dirpath) == self_dir:
            # the checker's own sources DESCRIBE the pragmas; they are
            # not suppressed findings
            continue
        for n in sorted(names):
            if not n.endswith(".py"):
                continue
            try:
                with open(os.path.join(dirpath, n), "r",
                          encoding="utf-8") as fh:
                    text = fh.read()
            except (OSError, UnicodeDecodeError):
                continue
            for m in PRAGMA_RE.finditer(text):
                named = {r.strip() for r in m.group(1).split(",")}
                for rule in SHARDING_RULES:
                    if rule in named:
                        counts[rule] = counts.get(rule, 0) + 1
    return counts


__all__ = (
    "ROWS_SPEC",
    "SHARDING_RULES",
    "collect_spec_sinks",
    "count_sharding_pragmas",
    "parse_spec",
    "rule_implicit_reshard",
    "rule_missing_donation_sharded",
    "rule_shard_map_spec_mismatch",
    "rule_unsharded_capture",
    "specs_conflict",
)
