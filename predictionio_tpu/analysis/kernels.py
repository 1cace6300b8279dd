"""The Pallas kernel-safety rule family behind ``ptpu check``.

Hand-written Pallas kernels sit on the training and serving hot paths
(``ops/solve.py`` and the generative engine's ``ops/``), and the
failure classes that silently corrupt or OOM a
kernel are invisible to both ``ruff`` and the JAX rules: a VMEM
working set that only blows up at rank 128, a DMA started and never
waited (reads garbage from the in-flight buffer), an accumulator that
quietly rounds in bf16, a ``pallas_call`` that hard-fails on every
backend whose Mosaic can't lower it. ALX (arXiv 2112.02194) and Tensor
Casting (arXiv 2010.13100) both live or die on exactly these
invariants — on-chip memory layout and mixed-precision accumulation —
so the checker enforces them before the hardware does. Four rules,
pure AST like everything else in this package:

- ``vmem-overbudget`` — statically evaluate every ``pallas_call``'s
  VMEM working set (BlockSpec tiles — doubled when a grid pipelines
  them — plus VMEM scratch) against the ~16 MiB/core budget, across
  the rank grid :data:`RANKS` and the module's own chunk constants.
  Symbolic dims resolve through
  local assignments, module constants, and parameter defaults; rank-
  like / chunk-like / history-like free names bind to the scenario
  grid; enclosing ``if``/``assert`` bounds (``if rp <= _RP_SCRATCH:``)
  make infeasible scenarios skip instead of lying. Dims that still
  can't be evaluated drop out of the sum (under-counting never
  over-reports).
- ``dma-unwaited`` — a ``make_async_copy`` ``.start()`` with no
  matching ``.wait()`` anywhere in the kernel (matched by copy
  variable or by semaphore expression, so a split
  issue-in-one-helper / drain-in-another pipeline matches), or the
  same semaphore slot restarted
  within a straight-line block before its wait.
- ``low-precision-accumulator`` — ``+=`` / read-modify-write / dot
  results accumulated into bf16/f16 VMEM scratch refs. Accumulators
  must be f32 (``preferred_element_type`` upcasting exists precisely
  so the wire can be bf16 while the sum is not).
- ``missing-interpret-fallback`` — a ``pallas_call`` with no
  ``interpret=`` escape hatch: every kernel must be routable through
  a support-gated dispatcher (``ops/solve.py::solve_spd_batch``)
  so CPU hosts and Mosaic versions that can't lower it degrade
  instead of raising mid-train.

All four honor ``# ptpu: allow[rule] — justification`` pragmas and
flow through ``--format sarif`` and the baseline gate like every other
rule. See docs/static-analysis.md (rules) and docs/kernels.md (the
budget math the first rule encodes).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import CheckContext, Finding, ModuleInfo

#: per-core VMEM (the guide's ~16 MB; Mosaic's scoped limit)
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: history-axis bound for L-like free dims: the bucketed ALS layouts
#: reach L=8192 (docs/kernels.md) — a kernel whose working set scales
#: with L must survive the largest bucket
MAX_HISTORY_L = 8192

#: scenario fallback bindings for names that never resolve statically
_RANK_NAME = re.compile(r"^(r|rank)$")
_CHUNK_NAME = re.compile(r"^(chunk|chunks|lc)$", re.IGNORECASE)
_HIST_NAME = re.compile(r"^(l|lp|seq_len|slen)$", re.IGNORECASE)

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
}

_LOW_PRECISION = {"bfloat16", "float16"}

_DOT_CALLS = {"jax.lax.dot_general", "jax.lax.dot", "jax.numpy.dot",
              "jax.numpy.matmul", "jax.numpy.einsum"}


def _uses_pallas(mod: ModuleInfo) -> bool:
    return any(v.startswith("jax.experimental.pallas")
               for v in mod.aliases.values())


def _dtype_bytes(mod: ModuleInfo, node: Optional[ast.AST]
                 ) -> Optional[int]:
    """Bytes/element for a dtype expression, or None when unknown
    (callers treat unknown as 4 — worst-case f32 wire)."""
    if node is None:
        return None
    name = mod.resolve(node)
    if name:
        return _DTYPE_BYTES.get(name.rsplit(".", 1)[-1])
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return _DTYPE_BYTES.get(node.value)
    return None


def _dtype_name(mod: ModuleInfo, node: Optional[ast.AST]
                ) -> Optional[str]:
    if node is None:
        return None
    name = mod.resolve(node)
    if name:
        return name.rsplit(".", 1)[-1]
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---------------------------------------------------------------------------
# symbolic integer evaluation over one function scope
# ---------------------------------------------------------------------------

class _Scope:
    """Evaluation environment for one function: module-level int
    constants, the function's simple local assignments, parameter
    defaults, and the per-scenario bindings for rank/chunk/history
    names that cannot resolve any other way."""

    def __init__(self, mod: ModuleInfo, fn: Optional[ast.AST]):
        self.mod = mod
        self.consts: Dict[str, ast.AST] = {}
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self.consts[node.targets[0].id] = node.value
        self.assigns: Dict[str, ast.AST] = {}
        if fn is not None:
            a = fn.args
            defaults = list(a.defaults)
            pos = list(a.posonlyargs) + list(a.args)
            for p, d in zip(pos[len(pos) - len(defaults):], defaults):
                self.assigns[p.arg] = d
            for p, d in zip(a.kwonlyargs, a.kw_defaults):
                if d is not None:
                    self.assigns[p.arg] = d
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    self.assigns[node.targets[0].id] = node.value
        self.scenario: Dict[str, int] = {}

    def bind(self, rank: int, chunk: int) -> None:
        self.scenario = {"__rank__": rank, "__chunk__": chunk}

    def _fallback(self, name: str) -> Optional[int]:
        if _RANK_NAME.match(name):
            return self.scenario.get("__rank__")
        if _CHUNK_NAME.match(name):
            return self.scenario.get("__chunk__")
        if _HIST_NAME.match(name):
            return MAX_HISTORY_L
        return None

    def eval(self, node: Optional[ast.AST],
             depth: int = 0) -> Optional[int]:
        """Best-effort integer value of an expression; None when it
        cannot be pinned down (the caller drops the term)."""
        if node is None or depth > 24:
            return None
        if isinstance(node, ast.Constant):
            return node.value if isinstance(node.value, int) \
                and not isinstance(node.value, bool) else None
        if isinstance(node, ast.Name):
            tgt = self.assigns.get(node.id)
            if tgt is not None and tgt is not node:
                v = self.eval(tgt, depth + 1)
                if v is not None:
                    return v
            tgt = self.consts.get(node.id)
            if tgt is not None:
                v = self.eval(tgt, depth + 1)
                if v is not None:
                    return v
            return self._fallback(node.id)
        if isinstance(node, ast.UnaryOp) \
                and isinstance(node.op, ast.USub):
            v = self.eval(node.operand, depth + 1)
            return -v if v is not None else None
        if isinstance(node, ast.BinOp):
            a = self.eval(node.left, depth + 1)
            b = self.eval(node.right, depth + 1)
            if a is None or b is None:
                return None
            try:
                if isinstance(node.op, ast.Add):
                    return a + b
                if isinstance(node.op, ast.Sub):
                    return a - b
                if isinstance(node.op, ast.Mult):
                    return a * b
                if isinstance(node.op, ast.FloorDiv):
                    return a // b
                if isinstance(node.op, ast.Mod):
                    return a % b
                if isinstance(node.op, ast.Div):
                    return a // b if a % b == 0 else None
            except (ZeroDivisionError, ValueError):
                return None
            return None
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Name) \
                and node.func.id in ("min", "max") and node.args \
                and not node.keywords:
            vals = [self.eval(a, depth + 1) for a in node.args]
            if any(v is None for v in vals):
                return None
            return min(vals) if node.func.id == "min" else max(vals)
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.Or):
            # `chunk or _L_CHUNK` with chunk defaulting to None — take
            # the first operand that pins down
            for operand in node.values:
                v = self.eval(operand, depth + 1)
                if v is not None:
                    return v
            return None
        return None

    def feasible(self, constraints: Sequence[ast.AST]) -> bool:
        """True unless some enclosing ``if``/``assert`` comparison
        provably fails under the current scenario (unknowns pass)."""
        for test in constraints:
            if not isinstance(test, ast.Compare) \
                    or len(test.ops) != 1:
                continue
            a = self.eval(test.left)
            b = self.eval(test.comparators[0])
            if a is None or b is None:
                continue
            op = test.ops[0]
            ok = {ast.Lt: a < b, ast.LtE: a <= b, ast.Gt: a > b,
                  ast.GtE: a >= b, ast.Eq: a == b,
                  ast.NotEq: a != b}.get(type(op), True)
            if not ok:
                return False
        return True


# ---------------------------------------------------------------------------
# shared pallas_call site discovery
# ---------------------------------------------------------------------------

class _PallasSite:
    def __init__(self, call: ast.Call, fn: Optional[ast.AST],
                 constraints: Tuple[ast.AST, ...]):
        self.call = call
        self.fn = fn
        self.constraints = constraints
        self.kwargs = {kw.arg: kw.value for kw in call.keywords
                       if kw.arg}


def _is_pallas_call(mod: ModuleInfo, node: ast.Call) -> bool:
    resolved = mod.resolve(node.func)
    if resolved and (resolved.endswith(".pallas_call")
                     or resolved == "pallas_call"):
        return True
    return isinstance(node.func, ast.Attribute) \
        and node.func.attr == "pallas_call"


def _pallas_sites(mod: ModuleInfo) -> List[_PallasSite]:
    """Every ``pallas_call`` with its enclosing function and the
    comparison constraints in force there (enclosing ``if`` tests on
    the taken branch; the function's ``assert``s)."""
    sites: List[_PallasSite] = []

    def visit(node: ast.AST, fn: Optional[ast.AST],
              constraints: Tuple[ast.AST, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            asserts = tuple(
                n.test for n in ast.walk(node)
                if isinstance(n, ast.Assert))
            for child in ast.iter_child_nodes(node):
                visit(child, node, asserts)
            return
        if isinstance(node, ast.If):
            for child in node.body:
                visit(child, fn, constraints + (node.test,))
            for child in node.orelse:
                visit(child, fn, constraints)
            visit(node.test, fn, constraints)
            return
        if isinstance(node, ast.Call) and _is_pallas_call(mod, node):
            sites.append(_PallasSite(node, fn, constraints))
        for child in ast.iter_child_nodes(node):
            visit(child, fn, constraints)

    visit(mod.tree, None, ())
    return sites


def _resolve_local(scope: _Scope, node: ast.AST,
                   depth: int = 0) -> ast.AST:
    """Follow simple Name → local-assignment chains (``mat_spec =
    pl.BlockSpec(…)`` then ``in_specs=[mat_spec]``)."""
    while isinstance(node, ast.Name) and depth < 8:
        tgt = scope.assigns.get(node.id) or scope.consts.get(node.id)
        if tgt is None or tgt is node:
            break
        node = tgt
        depth += 1
    return node


def _spec_list(scope: _Scope, node: Optional[ast.AST]
               ) -> List[ast.AST]:
    if node is None:
        return []
    node = _resolve_local(scope, node)
    if isinstance(node, (ast.List, ast.Tuple)):
        return [_resolve_local(scope, e) for e in node.elts]
    return [node]


def _memory_space_of(mod: ModuleInfo, call: ast.Call) -> Optional[str]:
    for kw in call.keywords:
        if kw.arg == "memory_space":
            name = mod.resolve(kw.value) or ""
            return name.rsplit(".", 1)[-1]
    return None


# ---------------------------------------------------------------------------
# rule: vmem-overbudget
# ---------------------------------------------------------------------------

#: the rank grid ``vmem-overbudget`` evaluates a kernel's free
#: rank-like names at
RANKS: Tuple[int, ...] = (32, 64, 128)


def _module_chunks(scope: _Scope) -> Tuple[int, ...]:
    """Chunk-size scenario values: every module constant whose name
    contains CHUNK (``_L_CHUNK = 512``), else 512."""
    out: Set[int] = set()
    for name, value in scope.consts.items():
        if "CHUNK" in name.upper():
            v = scope.eval(value)
            if v is not None and v > 0:
                out.add(v)
    return tuple(sorted(out)) or (512,)


def _block_bytes(mod: ModuleInfo, scope: _Scope, spec: ast.AST,
                 dtype_bytes: int, pipelined: bool
                 ) -> Tuple[Optional[int], Optional[str]]:
    """(bytes, label) for one BlockSpec — None bytes when the spec
    carries no static shape (HBM/ANY residents, whole-operand blocks)
    or a dim can't be evaluated."""
    if not (isinstance(spec, ast.Call)
            and (mod.resolve(spec.func) or "").endswith("BlockSpec")):
        return None, None
    space = _memory_space_of(mod, spec)
    if space in ("ANY", "HBM", "SMEM"):
        return None, None       # not VMEM-resident (SMEM is scalar mem)
    shape = spec.args[0] if spec.args else None
    for kw in spec.keywords:
        if kw.arg == "block_shape":
            shape = kw.value
    if not isinstance(shape, (ast.Tuple, ast.List)):
        return None, "?"        # whole-operand block: size unknown
    total = dtype_bytes
    dims: List[str] = []
    for e in shape.elts:
        v = scope.eval(e)
        if v is None:
            return None, "?"
        total *= v
        dims.append(str(v))
    if pipelined:
        total *= 2              # Mosaic double-buffers gridded blocks
    return total, "×".join(dims)


def rule_vmem_overbudget(mod: ModuleInfo,
                         ctx: CheckContext) -> List[Finding]:
    if not _uses_pallas(mod):
        return []
    findings: List[Finding] = []
    for site in _pallas_sites(mod):
        scope = _Scope(mod, site.fn)
        chunks = _module_chunks(scope)
        pipelined = "grid" in site.kwargs
        worst: Optional[Tuple[int, int, int, List[str]]] = None
        for rank in RANKS:
            for chunk in chunks:
                scope.bind(rank, chunk)
                if not scope.feasible(site.constraints):
                    continue
                total = 0
                parts: List[str] = []
                skipped = 0
                out_shapes = _spec_list(
                    scope, site.kwargs.get("out_shape"))
                for kind in ("in_specs", "out_specs"):
                    specs = _spec_list(scope, site.kwargs.get(kind))
                    for i, spec in enumerate(specs):
                        dt = 4
                        if kind == "out_specs" and i < len(out_shapes):
                            os_call = out_shapes[i]
                            if isinstance(os_call, ast.Call) \
                                    and len(os_call.args) > 1:
                                dt = _dtype_bytes(
                                    mod, os_call.args[1]) or 4
                        nbytes, label = _block_bytes(
                            mod, scope, spec, dt, pipelined)
                        if nbytes is None:
                            skipped += label is not None
                            continue
                        total += nbytes
                        parts.append(
                            f"{kind[:-1]}[{i}] {label}·{dt}B"
                            f"{'·2buf' if pipelined else ''}")
                for i, sc in enumerate(_spec_list(
                        scope, site.kwargs.get("scratch_shapes"))):
                    if not isinstance(sc, ast.Call):
                        continue
                    sname = (mod.resolve(sc.func) or "")
                    if not sname.endswith(".VMEM"):
                        continue   # SMEM / semaphores are not VMEM
                    shape = sc.args[0] if sc.args else None
                    dt = _dtype_bytes(
                        mod, sc.args[1] if len(sc.args) > 1
                        else None) or 4
                    if not isinstance(shape, (ast.Tuple, ast.List)):
                        skipped += 1
                        continue
                    n = dt
                    dims = []
                    bad = False
                    for e in shape.elts:
                        v = scope.eval(e)
                        if v is None:
                            bad = True
                            break
                        n *= v
                        dims.append(str(v))
                    if bad:
                        skipped += 1
                        continue
                    total += n
                    parts.append(f"scratch[{i}] {'×'.join(dims)}·{dt}B")
                if total > VMEM_BUDGET_BYTES \
                        and (worst is None or total > worst[0]):
                    worst = (total, rank, chunk, parts)
        if worst is not None:
            total, rank, chunk, parts = worst
            findings.append(Finding(
                "vmem-overbudget", mod.path, site.call.lineno,
                site.call.col_offset,
                f"pallas_call VMEM working set ≈ "
                f"{total / (1 << 20):.1f} MiB at rank {rank} / chunk "
                f"{chunk} exceeds the ~16 MiB/core budget "
                f"({' + '.join(parts)}); shrink the block/scratch "
                f"tiles, stream via ANY+DMA, or "
                f"pragma with the measured budget argument "
                f"(docs/kernels.md)"))
    return findings


# ---------------------------------------------------------------------------
# rule: dma-unwaited
# ---------------------------------------------------------------------------

def _is_make_async_copy(mod: ModuleInfo, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    resolved = mod.resolve(node.func) or ""
    return resolved.endswith("make_async_copy") \
        or (isinstance(node.func, ast.Attribute)
            and node.func.attr == "make_async_copy")


def _sem_key(copy_call: ast.Call) -> Optional[str]:
    sem = copy_call.args[2] if len(copy_call.args) > 2 else None
    for kw in copy_call.keywords:
        if kw.arg in ("sem", "sems", "semaphore"):
            sem = kw.value
    if sem is None:
        return None
    try:
        return ast.unparse(sem).replace(" ", "")
    except Exception:  # noqa: BLE001 — unparse is best-effort
        return None


class _DmaEvent:
    __slots__ = ("kind", "key", "var", "node")

    def __init__(self, kind: str, key: Optional[str],
                 var: Optional[str], node: ast.AST):
        self.kind = kind     # "start" | "wait"
        self.key = key       # normalized semaphore expression
        self.var = var       # copy variable, for var.start()/var.wait()
        self.node = node


def _dma_events(mod: ModuleInfo, fn: ast.AST) -> List[_DmaEvent]:
    """start/wait events anywhere in ``fn`` (nested helper defs
    included — the issue-in-one-helper/drain-in-another pipeline split
    is the idiom the matching must span)."""
    copies: Dict[str, Optional[str]] = {}   # var → sem key
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_make_async_copy(mod, node.value):
            copies[node.targets[0].id] = _sem_key(node.value)
    events: List[_DmaEvent] = []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("start", "wait")):
            continue
        recv = node.func.value
        if _is_make_async_copy(mod, recv):
            events.append(_DmaEvent(node.func.attr, _sem_key(recv),
                                    None, node))
        elif isinstance(recv, ast.Name) and recv.id in copies:
            events.append(_DmaEvent(node.func.attr, copies[recv.id],
                                    recv.id, node))
    return events


def rule_dma_unwaited(mod: ModuleInfo,
                      ctx: CheckContext) -> List[Finding]:
    if not _uses_pallas(mod):
        return []
    findings: List[Finding] = []
    for fn in mod.tree.body:
        stack = [fn]
        tops: List[ast.AST] = []
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                tops.append(n)
            elif isinstance(n, ast.ClassDef):
                stack.extend(n.body)
        for top in tops:
            events = _dma_events(mod, top)
            if not events:
                continue
            waited_keys = {e.key for e in events
                           if e.kind == "wait" and e.key}
            waited_vars = {e.var for e in events
                           if e.kind == "wait" and e.var}
            for e in events:
                if e.kind != "start":
                    continue
                if (e.var and e.var in waited_vars) \
                        or (e.key and e.key in waited_keys):
                    continue
                what = f"`{e.var}.start()`" if e.var else \
                    "`make_async_copy(…).start()`"
                findings.append(Finding(
                    "dma-unwaited", mod.path, e.node.lineno,
                    e.node.col_offset,
                    f"{what} has no matching .wait() in "
                    f"`{top.name}` (matched by copy variable and by "
                    f"semaphore slot); an unwaited DMA races the "
                    f"compute reading its destination buffer — pair "
                    f"every start with a wait before the data is "
                    f"consumed"))
            # same-slot restart before its wait, per straight-line
            # statement block: only simple statements participate —
            # events under a nested compound (loop/branch/def) have
            # ordering the block can't see statically, and the
            # double-buffer slot rotation idiom lives exactly there
            for node in ast.walk(top):
                bodies = []
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.With,
                                     ast.If, ast.For, ast.While)):
                    bodies = [node.body, getattr(node, "orelse", [])]
                for body in bodies:
                    started: Set[str] = set()
                    for stmt in body:
                        if not isinstance(stmt, (ast.Expr, ast.Assign,
                                                 ast.AugAssign)):
                            started.clear()
                            continue
                        for e in events:
                            if not (stmt.lineno <= e.node.lineno
                                    <= (stmt.end_lineno
                                        or stmt.lineno)) \
                                    or e.key is None:
                                continue
                            if e.kind == "wait":
                                started.discard(e.key)
                            elif e.key in started:
                                findings.append(Finding(
                                    "dma-unwaited", mod.path,
                                    e.node.lineno, e.node.col_offset,
                                    f"semaphore slot `{e.key}` "
                                    f"restarted before its wait in "
                                    f"`{top.name}`; the second DMA "
                                    f"overwrites the in-flight "
                                    f"buffer — wait (or rotate "
                                    f"slots) first"))
                            else:
                                started.add(e.key)
    return findings


# ---------------------------------------------------------------------------
# rule: low-precision-accumulator
# ---------------------------------------------------------------------------

def _kernel_fn_and_bound(mod: ModuleInfo, scope: _Scope,
                         site: _PallasSite
                         ) -> Tuple[Optional[ast.AST], int]:
    """The kernel FunctionDef a pallas_call dispatches to, plus the
    number of leading params pre-bound by functools.partial."""
    if not site.call.args:
        return None, 0
    target = _resolve_local(scope, site.call.args[0])
    bound = 0
    if isinstance(target, ast.Call) \
            and (mod.resolve(target.func) or "").endswith("partial") \
            and target.args:
        bound = len(target.args) - 1
        target = _resolve_local(scope, target.args[0])
    if isinstance(target, ast.Name):
        target = _resolve_local(scope, target)
    if isinstance(target, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return target, bound
    if isinstance(target, ast.Name):
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)) \
                    and node.name == target.id:
                return node, bound
    return None, bound


def _function_by_name(mod: ModuleInfo, name: str
                      ) -> Optional[ast.AST]:
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == name:
            return node
    return None


def rule_low_precision_accumulator(mod: ModuleInfo,
                                   ctx: CheckContext) -> List[Finding]:
    if not _uses_pallas(mod):
        return []
    findings: List[Finding] = []
    flagged: Set[int] = set()
    for site in _pallas_sites(mod):
        scope = _Scope(mod, site.fn)
        kernel, bound = _kernel_fn_and_bound(mod, scope, site)
        if isinstance(kernel, ast.Name):
            kernel = _function_by_name(mod, kernel.id)
        if kernel is None:
            continue
        in_specs = _spec_list(scope, site.kwargs.get("in_specs"))
        out_specs = _spec_list(scope, site.kwargs.get("out_specs"))
        scratch = _spec_list(scope, site.kwargs.get("scratch_shapes"))
        a = kernel.args
        params = [p.arg for p in (*a.posonlyargs, *a.args)]
        expect = bound + len(in_specs) + len(out_specs) + len(scratch)
        if not scratch or len(params) != expect:
            continue    # can't map refs to scratch slots — stay quiet
        low: Dict[str, str] = {}
        base = bound + len(in_specs) + len(out_specs)
        for i, sc in enumerate(scratch):
            if not (isinstance(sc, ast.Call)
                    and (mod.resolve(sc.func) or "")
                    .endswith(".VMEM")):
                continue
            dt = _dtype_name(mod, sc.args[1]
                             if len(sc.args) > 1 else None)
            if dt in _LOW_PRECISION:
                low[params[base + i]] = dt
        if not low:
            continue
        for node in ast.walk(kernel):
            tgt = None
            accum = False
            if isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Subscript) \
                    and isinstance(node.target.value, ast.Name):
                tgt = node.target.value.id
                accum = True
                rhs = node.value
            elif isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Subscript) \
                    and isinstance(node.targets[0].value, ast.Name):
                tgt = node.targets[0].value.id
                rhs = node.value
                accum = any(
                    isinstance(n, ast.Name) and n.id == tgt
                    for n in ast.walk(rhs)) or any(
                    isinstance(n, ast.Call)
                    and (mod.resolve(n.func) or "") in _DOT_CALLS
                    for n in ast.walk(rhs))
            if tgt in low and accum and id(node) not in flagged:
                flagged.add(id(node))
                findings.append(Finding(
                    "low-precision-accumulator", mod.path,
                    node.lineno, node.col_offset,
                    f"accumulation into {low[tgt]} scratch ref "
                    f"`{tgt}` — every partial sum rounds to "
                    f"{low[tgt]} and the Gramian drifts; declare the "
                    f"accumulator f32 (upcast after the wire, "
                    f"contract with preferred_element_type=f32)"))
    return findings


# ---------------------------------------------------------------------------
# rule: missing-interpret-fallback
# ---------------------------------------------------------------------------

def rule_missing_interpret_fallback(mod: ModuleInfo,
                                    ctx: CheckContext
                                    ) -> List[Finding]:
    if not _uses_pallas(mod):
        return []
    findings: List[Finding] = []
    for site in _pallas_sites(mod):
        interp = site.kwargs.get("interpret")
        hardwired = interp is None or (
            isinstance(interp, ast.Constant)
            and interp.value is False)
        if hardwired:
            findings.append(Finding(
                "missing-interpret-fallback", mod.path,
                site.call.lineno, site.call.col_offset,
                "pallas_call is hard-wired to compiled mode; thread "
                "an interpret= parameter through and route callers "
                "via a support-gated dispatcher (compiled kernel on "
                "TPU, interpret-mode or the XLA reference elsewhere, "
                "as ops/solve.py::solve_spd_batch does) so a CPU host "
                "or an older Mosaic degrades instead of raising "
                "mid-train"))
    return findings


# re-exported by .rules into the registry
__all__: Iterable[str] = (
    "VMEM_BUDGET_BYTES",
    "MAX_HISTORY_L",
    "RANKS",
    "rule_dma_unwaited",
    "rule_low_precision_accumulator",
    "rule_missing_interpret_fallback",
    "rule_vmem_overbudget",
)
