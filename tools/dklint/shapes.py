"""Interprocedural shape & sharding abstract interpretation for dklint
(DK123–DK126) — proving layouts off-device.

Device truth has been unreachable since BENCH r03: a wrong ``in_specs``
rank, a mesh axis that does not divide a dim, or a bad Pallas BlockSpec
costs a full (failed) device run to discover.  This module is the static
side of that feedback loop: a symbolic abstract interpreter over the
per-function CFG/reaching-definitions engine (:mod:`tools.dklint.dataflow`)
that the four shape rules are thin views over.

**Dim domain** — a dimension is an ``int``, a named symbol, or a product
``axis_size('dp') * k`` (:class:`Dim`: integer coefficient × a multiset of
symbols).  ``None`` means *unknown*; every judgement in the checkers is of
the form "provably wrong", so unknown always means *trusted* — the same
stance DK104/DK108 take on unresolvable axis expressions.

**Values** — :class:`ArrayVal` (shape/dtype/producer sharding),
:class:`MeshVal` (ordered ``(axis, size)`` pairs), :class:`SpecVal`
(``PartitionSpec`` entries, each a tuple of axis names), plus sharding /
ShapeDtypeStruct / BlockSpec / function values for the Pallas contract
checks.

**Evaluation** is demand-driven: a ``Name`` load resolves through
``FunctionFlow.reaching`` to its defining expression (exactly the v3
machinery — a name rebound on one arm only evaluates the defs that reach
*this* use), free variables resolve through module-level bindings and the
per-file import map (so ``P(PP_AXIS)`` with ``PP_AXIS`` imported from the
mesh module still resolves), and parameters resolve **interprocedurally**
through the same resolved-call-site discipline DK101/DK119 use: every
in-tree call site of the enclosing function is located project-wide, the
argument is evaluated in the *caller's* context, and the binding is used
only when all resolvable sites agree.

**Mesh model** — ``make_mesh``/``make_mesh_grid`` from
``distkeras_tpu/parallel/mesh.py``, raw ``jax.sharding.Mesh``
constructions (axis sizes recovered from literal dims or a
``.reshape(...)``), and ``compat.shard_map`` wrappers: a call that resolves
(directly or through the import map) to
``distkeras_tpu.utils.compat.shard_map`` is a shard_map site like a direct
``jax.shard_map`` call (tagged ``via='compat'`` in the layout report).

Adding an op evaluator: extend ``Evaluator._eval_call`` (dispatch on the
import-resolved dotted name, then the short name) — take resolved operand
values, return a new value or ``UNKNOWN``.  Never guess: returning
``UNKNOWN`` silences every downstream check for that value, returning a
wrong shape invents findings.  ``tests/test_shapes.py`` pins the domain.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.dklint import dataflow
from tools.dklint.core import FileInfo, Project, call_name, dotted_name


def _modules_match(target_mod: str, analyzed_mod: str) -> bool:
    """Same contract as host_sync's: a dotted import target plausibly
    denotes an analyzed file (suffix-tolerant both ways — the import was
    written against ``sys.path``, the analyzed name is root-relative).
    Redefined here because the checkers package imports this module."""
    if not target_mod or not analyzed_mod:
        return False
    return (
        target_mod == analyzed_mod
        or analyzed_mod.endswith("." + target_mod)
        or target_mod.endswith("." + analyzed_mod)
    )

FACTS_KEY = "DKSHAPE.facts"
BIND_KEY = "DKSHAPE.parambind"
MODMAP_KEY = "DKSHAPE.modmap"

_FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

_MAX_SITES = 8        # call sites examined per interprocedural binding
_MAX_DEPTH = 4        # caller-context evaluation depth


class _Unknown:
    """Singleton bottom element: nothing is provable about this value."""

    __slots__ = ()

    def __repr__(self) -> str:
        return "?"


UNKNOWN = _Unknown()


# --------------------------------------------------------------- dim domain

class Dim:
    """``coeff * sym1 * sym2 * ...`` — an int is a Dim with no syms."""

    __slots__ = ("coeff", "syms")

    def __init__(self, coeff: int, syms: Tuple[str, ...] = ()):
        self.coeff = coeff
        self.syms = tuple(sorted(syms))

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Dim)
            and self.coeff == other.coeff
            and self.syms == other.syms
        )

    def __hash__(self) -> int:
        return hash((self.coeff, self.syms))

    def __repr__(self) -> str:
        if not self.syms:
            return str(self.coeff)
        body = "*".join(self.syms)
        return body if self.coeff == 1 else f"{self.coeff}*{body}"

    @property
    def is_int(self) -> bool:
        return not self.syms

    def as_int(self) -> Optional[int]:
        return self.coeff if not self.syms else None


def dim_of(value) -> Optional[Dim]:
    """Lift an evaluator value into the dim domain (None = unknown)."""
    if isinstance(value, Dim):
        return value
    if isinstance(value, bool):  # bool is an int; shapes never want it
        return None
    if isinstance(value, int):
        return Dim(value)
    return None


def axis_sym(axis: str) -> Dim:
    return Dim(1, (f"ax${axis}",))


def dim_mul(a: Optional[Dim], b: Optional[Dim]) -> Optional[Dim]:
    if a is None or b is None:
        return None
    return Dim(a.coeff * b.coeff, a.syms + b.syms)


def dim_add(a: Optional[Dim], b: Optional[Dim]) -> Optional[Dim]:
    if a is None or b is None:
        return None
    if not a.syms and not b.syms:
        return Dim(a.coeff + b.coeff)
    if a.syms == b.syms:
        return Dim(a.coeff + b.coeff, a.syms)
    return None


def dim_sub(a: Optional[Dim], b: Optional[Dim]) -> Optional[Dim]:
    if b is None:
        return None
    return dim_add(a, Dim(-b.coeff, b.syms))


def dim_floordiv(a: Optional[Dim], b: Optional[Dim]) -> Optional[Dim]:
    """Exact division only — a lossy floordiv is an unknown, not a guess."""
    if a is None or b is None or b.coeff == 0:
        return None
    remaining = list(a.syms)
    for sym in b.syms:
        if sym in remaining:
            remaining.remove(sym)
        else:
            return None
    if a.coeff % b.coeff != 0:
        return None
    return Dim(a.coeff // b.coeff, tuple(remaining))


def provably_not_divides(k: int, d: Dim) -> bool:
    """True when ``k`` provably fails to divide ``d`` — only decidable for
    fully-concrete dims (a symbolic factor could absorb anything)."""
    return k > 0 and d.is_int and d.coeff % k != 0


# ------------------------------------------------------------------- values

class ArrayVal:
    __slots__ = ("shape", "dtype", "sharding")

    def __init__(self, shape, dtype=None, sharding=None):
        # shape: tuple[Dim|None, ...] (rank known) or None (rank unknown)
        self.shape = shape
        self.dtype = dtype          # str | None
        self.sharding = sharding    # ShardingVal | None

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, ArrayVal)
            and self.shape == other.shape
            and self.dtype == other.dtype
        )

    def __hash__(self) -> int:
        return hash((self.shape, self.dtype))

    @property
    def rank(self) -> Optional[int]:
        return len(self.shape) if self.shape is not None else None

    def __repr__(self) -> str:
        shape = "?" if self.shape is None else \
            "(" + ", ".join("?" if d is None else repr(d) for d in self.shape) + ")"
        return f"Array{shape}" + (f":{self.dtype}" if self.dtype else "")


class MeshVal:
    __slots__ = ("axes",)

    def __init__(self, axes: Sequence[Tuple[str, Optional[int]]]):
        self.axes = tuple(axes)

    def __eq__(self, other) -> bool:
        return isinstance(other, MeshVal) and self.axes == other.axes

    def __hash__(self) -> int:
        return hash(self.axes)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(name for name, _size in self.axes)

    def size_of(self, axis: str) -> Optional[int]:
        for name, size in self.axes:
            if name == axis:
                return size
        return None

    def __repr__(self) -> str:
        body = ", ".join(
            f"{n}:{'?' if s is None else s}" for n, s in self.axes
        )
        return "Mesh{" + body + "}"


class SpecVal:
    """A PartitionSpec: one entry per partitioned dim.  Each entry is a
    tuple of axis names (``P('a')`` → ``('a',)``, ``None`` → ``()``,
    ``P(('a','b'))`` → ``('a','b')``) or ``UNKNOWN`` for an unresolvable
    element (the entry still counts toward the spec's rank)."""

    __slots__ = ("entries",)

    def __init__(self, entries: Sequence):
        self.entries = tuple(entries)

    def __eq__(self, other) -> bool:
        return isinstance(other, SpecVal) and self.entries == other.entries

    def __hash__(self) -> int:
        return hash(tuple(
            e if isinstance(e, tuple) else "?" for e in self.entries
        ))

    @property
    def rank(self) -> int:
        return len(self.entries)

    def axis_names(self) -> Optional[Set[str]]:
        """The axis set this spec partitions over; None when any entry is
        unresolved (the set is not provable)."""
        out: Set[str] = set()
        for entry in self.entries:
            if entry is UNKNOWN:
                return None
            out.update(entry)
        return out

    def __repr__(self) -> str:
        def ent(e):
            if e is UNKNOWN:
                return "?"
            if not e:
                return "None"
            if len(e) == 1:
                return repr(e[0])
            return "(" + ", ".join(repr(n) for n in e) + ")"

        return "P(" + ", ".join(ent(e) for e in self.entries) + ")"


class ShardingVal:
    __slots__ = ("mesh", "spec")

    def __init__(self, mesh, spec):
        self.mesh = mesh    # MeshVal | UNKNOWN
        self.spec = spec    # SpecVal | UNKNOWN

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, ShardingVal)
            and self.mesh == other.mesh
            and self.spec == other.spec
        )

    def __hash__(self) -> int:
        return hash((repr(self.mesh), repr(self.spec)))

    def __repr__(self) -> str:
        return f"NamedSharding({self.mesh!r}, {self.spec!r})"


class ShapeDtypeVal:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = shape      # tuple[Dim|None,...] | None
        self.dtype = dtype      # str | None

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, ShapeDtypeVal)
            and self.shape == other.shape
            and self.dtype == other.dtype
        )

    def __hash__(self) -> int:
        return hash((self.shape, self.dtype))

    def __repr__(self) -> str:
        shape = "?" if self.shape is None else \
            "(" + ", ".join("?" if d is None else repr(d) for d in self.shape) + ")"
        return f"ShapeDtype{shape}:{self.dtype or '?'}"


class BlockSpecVal:
    __slots__ = ("block", "index_map")

    def __init__(self, block, index_map):
        self.block = block          # tuple[Dim|None,...] | None
        self.index_map = index_map  # ast.Lambda | None

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, BlockSpecVal)
            and self.block == other.block
            and self.index_map is other.index_map
        )

    def __hash__(self) -> int:
        return hash(self.block)

    def __repr__(self) -> str:
        block = "?" if self.block is None else \
            "(" + ", ".join("?" if d is None else repr(d) for d in self.block) + ")"
        suffix = "" if self.index_map is None else \
            f"@L{self.index_map.lineno}"
        return f"Block{block}{suffix}"


class FnVal:
    """A resolved function object, possibly through ``functools.partial``.
    ``bound_pos`` counts positionally-bound leading params."""

    __slots__ = ("node", "bound_pos")

    def __init__(self, node, bound_pos: int = 0):
        self.node = node
        self.bound_pos = bound_pos

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, FnVal)
            and self.node is other.node
            and self.bound_pos == other.bound_pos
        )

    def __hash__(self) -> int:
        return hash((id(self.node), self.bound_pos))

    def positional_arity(self) -> int:
        args = self.node.args
        n = len(args.posonlyargs) + len(args.args) - self.bound_pos
        return max(0, n)


# -------------------------------------------------------------- file facts

class _FileFacts:
    __slots__ = ("fi", "encl", "toplevel_fns", "methods", "class_of",
                 "module_assigns", "calls", "flows")

    def __init__(self, fi: FileInfo):
        self.fi = fi
        # id(node) -> nearest enclosing function node (None = module scope)
        self.encl: Dict[int, Optional[ast.AST]] = {}
        # top-level def name -> node
        self.toplevel_fns: Dict[str, ast.AST] = {}
        # method name -> [(class name, node)]
        self.methods: Dict[str, List[Tuple[str, ast.AST]]] = {}
        # id(fn node) -> class name ("" for free functions)
        self.class_of: Dict[int, str] = {}
        # module-level ``name = expr`` (last assignment wins)
        self.module_assigns: Dict[str, ast.AST] = {}
        # every Call node with its enclosing function
        self.calls: List[Tuple[ast.Call, Optional[ast.AST]]] = []
        # FunctionFlow cache (dataflow.function_flow's cache dict)
        self.flows: Dict[int, dataflow.FunctionFlow] = {}


def _build_facts(fi: FileInfo) -> _FileFacts:
    facts = _FileFacts(fi)

    def walk(node: ast.AST, fn: Optional[ast.AST], cls: str) -> None:
        for child in ast.iter_child_nodes(node):
            facts.encl[id(child)] = fn
            if isinstance(child, _FN_NODES):
                name = getattr(child, "name", "<lambda>")
                facts.class_of[id(child)] = cls if fn is None else ""
                if fn is None and not isinstance(child, ast.Lambda):
                    if cls:
                        facts.methods.setdefault(name, []).append((cls, child))
                    else:
                        facts.toplevel_fns.setdefault(name, child)
                walk(child, child, "")
            elif isinstance(child, ast.ClassDef):
                # methods keep fn=None (module-ish scope for resolution);
                # nested classes inherit the outer class name for methods
                walk(child, fn, child.name if fn is None else cls)
            else:
                if isinstance(child, ast.Call):
                    facts.calls.append((child, fn))
                walk(child, fn, cls)

    facts.encl[id(fi.tree)] = None
    walk(fi.tree, None, "")

    for node in fi.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            facts.module_assigns[node.targets[0].id] = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None and \
                isinstance(node.target, ast.Name):
            facts.module_assigns[node.target.id] = node.value
    return facts


def collect_facts(project: Project, fi: FileInfo) -> None:
    """Pass-1 hook shared by DK123–DK126: idempotent per file."""
    store = project.data.setdefault(FACTS_KEY, {})
    if fi.relpath not in store:
        store[fi.relpath] = _build_facts(fi)


def _facts_for(project: Project, fi: FileInfo) -> _FileFacts:
    store = project.data.setdefault(FACTS_KEY, {})
    if fi.relpath not in store:
        store[fi.relpath] = _build_facts(fi)
    return store[fi.relpath]


def _module_map(project: Project) -> Dict[str, FileInfo]:
    cached = project.data.get(MODMAP_KEY)
    if cached is None:
        cached = {f.module: f for f in project.files}
        project.data[MODMAP_KEY] = cached
    return cached


def resolved_call(fi: FileInfo, node: ast.Call) -> Tuple[Optional[str], str]:
    """(import-resolved dotted name | None, short name) of a call target.
    The short name comes from the *resolved* target, so ``from m import
    shard_map as sm`` still dispatches as ``shard_map``."""
    name = call_name(node)
    if name is None:
        return None, ""
    head, _, rest = name.partition(".")
    target = fi.imports.get(head)
    resolved = (target + ("." + rest if rest else "")) if target else name
    return resolved, resolved.rsplit(".", 1)[-1]


# ---------------------------------------------------------------- evaluator

_MESH_CTORS = {"Mesh"}
_SPEC_CTORS = {"PartitionSpec", "P"}
_ZEROS_LIKE = {"zeros", "ones", "empty", "full"}
_SAME_SHAPE_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "ppermute"}
_REDUCTIONS = {"sum", "mean", "max", "min", "prod", "any", "all"}

_DTYPE_NAMES = {
    "float32", "float16", "bfloat16", "float64", "int32", "int64", "int8",
    "int16", "uint8", "uint32", "bool_",
}


class Evaluator:
    """Demand-driven abstract evaluation of expressions in one function
    (or module) scope.  All resolution failures return :data:`UNKNOWN`."""

    def __init__(self, project: Project, fi: FileInfo,
                 fn: Optional[ast.AST] = None,
                 bindings: Optional[Dict[str, object]] = None,
                 depth: int = 0,
                 fn_stack: frozenset = frozenset()):
        self.project = project
        self.fi = fi
        self.fn = fn
        self.facts = _facts_for(project, fi)
        self.flow = (
            dataflow.function_flow(fn, self.facts.flows)
            if fn is not None else None
        )
        self.bindings = dict(bindings or {})
        self.depth = depth
        self.fn_stack = fn_stack
        self._memo: Dict[int, object] = {}
        self._busy: Set[int] = set()
        self._params_resolved = False

    # -------------------------------------------------------------- public

    def eval(self, node: Optional[ast.AST]):
        if node is None:
            return UNKNOWN
        key = id(node)
        if key in self._memo:
            return self._memo[key]
        if key in self._busy:
            return UNKNOWN
        self._busy.add(key)
        try:
            value = self._eval(node)
        finally:
            self._busy.discard(key)
        self._memo[key] = value
        return value

    # ------------------------------------------------------------ dispatch

    def _eval(self, node: ast.AST):
        if isinstance(node, ast.Constant):
            v = node.value
            if v is None or isinstance(v, (bool, str)):
                return v
            if isinstance(v, int):
                return v
            return UNKNOWN
        if isinstance(node, ast.Name):
            return self._eval_name(node)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(self.eval(el) for el in node.elts)
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, ast.Attribute):
            return self._eval_attribute(node)
        if isinstance(node, ast.Subscript):
            return self._eval_subscript(node)
        if isinstance(node, ast.BinOp):
            return self._eval_binop(node)
        if isinstance(node, ast.UnaryOp):
            operand = self.eval(node.operand)
            if isinstance(node.op, ast.USub):
                if isinstance(operand, int):
                    return -operand
                if isinstance(operand, Dim):
                    return Dim(-operand.coeff, operand.syms)
            return UNKNOWN
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        if isinstance(node, ast.IfExp):
            a, b = self.eval(node.body), self.eval(node.orelse)
            return a if _values_equal(a, b) else UNKNOWN
        if isinstance(node, ast.NamedExpr):
            return self.eval(node.value)
        if isinstance(node, ast.Dict):
            return UNKNOWN  # pytrees of specs stay trusted
        return UNKNOWN

    # --------------------------------------------------------------- names

    def _eval_name(self, node: ast.Name):
        if node.id in self.bindings:
            return self.bindings[node.id]
        if self.flow is not None and self.flow.is_use(node):
            defs = self.flow.reaching(node)
            if not defs:
                return self._module_name(node.id)
            values = []
            for d in defs:
                if d.kind == "param":
                    values.append(self._param_value(d.name))
                elif d.kind in ("assign", "walrus", "with") and d.value is not None:
                    values.append(self.eval(d.value))
                else:
                    values.append(UNKNOWN)
            first = values[0]
            if first is not UNKNOWN and all(
                _values_equal(first, v) for v in values[1:]
            ):
                return first
            return UNKNOWN
        return self._module_name(node.id)

    def _module_name(self, name: str):
        expr = self.facts.module_assigns.get(name)
        if expr is not None:
            mod_ev = self if self.fn is None else Evaluator(
                self.project, self.fi, None,
                depth=self.depth, fn_stack=self.fn_stack,
            )
            return mod_ev.eval(expr)
        fn = self.facts.toplevel_fns.get(name)
        if fn is not None:
            return FnVal(fn)
        target = self.fi.imports.get(name)
        if target is not None:
            return self._imported(target)
        return UNKNOWN

    def _imported(self, target: str):
        mod, _, name = target.rpartition(".")
        if not name:
            return UNKNOWN
        for module, other in sorted(_module_map(self.project).items()):
            if not _modules_match(mod, module):
                continue
            other_facts = _facts_for(self.project, other)
            expr = other_facts.module_assigns.get(name)
            if expr is not None:
                return Evaluator(
                    self.project, other, None,
                    depth=self.depth + 1, fn_stack=self.fn_stack,
                ).eval(expr) if self.depth < _MAX_DEPTH else UNKNOWN
            fn = other_facts.toplevel_fns.get(name)
            if fn is not None:
                return FnVal(fn)
        return UNKNOWN

    # ---------------------------------------------------- interprocedural

    def _param_value(self, name: str):
        """Resolve a parameter through the function's in-tree call sites:
        bound only when every resolvable site passes an equal value."""
        if name in self.bindings:
            return self.bindings[name]
        if not self._params_resolved:
            self._params_resolved = True
            self.bindings.update(param_bindings(
                self.project, self.fi, self.fn,
                depth=self.depth, fn_stack=self.fn_stack,
            ))
        return self.bindings.get(name, UNKNOWN)


def _values_equal(a, b) -> bool:
    if a is UNKNOWN or b is UNKNOWN:
        return False
    if isinstance(a, tuple) and isinstance(b, tuple):
        return len(a) == len(b) and all(
            _values_equal(x, y) for x, y in zip(a, b)
        )
    try:
        return bool(a == b)
    except Exception:
        return False


def _param_names(fn: ast.AST) -> List[str]:
    args = fn.args
    return [a.arg for a in args.posonlyargs + args.args]


def param_bindings(project: Project, fi: FileInfo, fn: ast.AST,
                   depth: int = 0,
                   fn_stack: frozenset = frozenset()) -> Dict[str, object]:
    """Interprocedural parameter bindings for ``fn``: evaluate each in-tree
    call site's arguments in the caller's context and keep the params on
    which every resolvable site agrees.  Memoized per function (top-level
    entry only — nested/depth>0 resolutions skip the cache so a recursion
    guard in ``fn_stack`` can't poison it)."""
    if isinstance(fn, ast.Lambda):
        return {}
    if id(fn) in fn_stack or depth >= _MAX_DEPTH:
        return {}
    memo: Dict[int, Dict[str, object]] = project.data.setdefault(BIND_KEY, {})
    if depth == 0 and id(fn) in memo:
        return memo[id(fn)]

    facts = _facts_for(project, fi)
    cls = facts.class_of.get(id(fn), "")
    names = _param_names(fn)
    is_method = bool(cls) and names[:1] in (["self"], ["cls"])

    sites = _call_sites(project, fi, fn, cls)
    bindings: Dict[str, object] = {}
    if sites and len(sites) <= _MAX_SITES:
        per_param: Dict[str, List[object]] = {}
        for site_fi, site_fn, call, via_self in sites:
            ev = Evaluator(
                project, site_fi, site_fn,
                depth=depth + 1, fn_stack=fn_stack | {id(fn)},
            )
            if any(isinstance(a, ast.Starred) for a in call.args) or any(
                kw.arg is None for kw in call.keywords
            ):
                per_param.setdefault("*", []).append(UNKNOWN)
                continue
            offset = 1 if (is_method and via_self) else 0
            positional = names[offset:]
            for i, arg in enumerate(call.args):
                if i < len(positional):
                    per_param.setdefault(positional[i], []).append(ev.eval(arg))
            for kw in call.keywords:
                if kw.arg in names:
                    per_param.setdefault(kw.arg, []).append(ev.eval(kw.value))
        if "*" not in per_param and len(sites) >= 1:
            n_sites = len(sites)
            for pname, values in per_param.items():
                if len(values) != n_sites:
                    continue  # a site omitted it (default) — don't guess
                first = values[0]
                if first is not UNKNOWN and all(
                    _values_equal(first, v) for v in values[1:]
                ):
                    bindings[pname] = first
    if depth == 0:
        memo[id(fn)] = bindings
    return bindings


def _call_sites(project: Project, fi: FileInfo, fn: ast.AST, cls: str):
    """(site_fi, site_fn, call, via_self) for every in-tree call that
    plausibly targets ``fn``.  More candidate *definitions* than one for a
    name means ambiguity — the caller gets no sites at all."""
    name = getattr(fn, "name", None)
    if not name:
        return []
    out = []
    for other in project.files:
        other_facts = _facts_for(project, other)
        for call, site_fn in other_facts.calls:
            func = call.func
            if isinstance(func, ast.Name) and func.id == name:
                if other is fi and name in other_facts.toplevel_fns:
                    out.append((other, site_fn, call, False))
                elif _modules_match(
                    other.imports.get(name, "").rpartition(".")[0], fi.module
                ) and other.imports.get(name, "").endswith("." + name):
                    out.append((other, site_fn, call, False))
            elif isinstance(func, ast.Attribute) and func.attr == name:
                base = func.value
                if isinstance(base, ast.Name) and base.id in ("self", "cls"):
                    if other is fi and cls and any(
                        c == cls for c, _n in other_facts.methods.get(name, ())
                    ):
                        out.append((other, site_fn, call, True))
                elif isinstance(base, ast.Name) and not cls:
                    target = other.imports.get(base.id)
                    if target is not None and _modules_match(target, fi.module):
                        out.append((other, site_fn, call, False))
            if len(out) > _MAX_SITES:
                return out
    return out


# ----------------------------------------------------- evaluator: calls &co

def _shape_tuple(value) -> Optional[Tuple[Optional[Dim], ...]]:
    """A shape argument (tuple/list of dims, or a single int) as dims."""
    if isinstance(value, tuple):
        return tuple(dim_of(v) for v in value)
    d = dim_of(value)
    if d is not None:
        return (d,)
    return None


def _dtype_str(value) -> Optional[str]:
    if isinstance(value, str):
        return value
    return None


def _broadcast(a: ArrayVal, b) -> object:
    if not isinstance(b, ArrayVal):
        if isinstance(b, (int, Dim)):
            return ArrayVal(a.shape, a.dtype)
        return UNKNOWN
    if a.shape is None or b.shape is None:
        return ArrayVal(None)
    out: List[Optional[Dim]] = []
    for x, y in zip(
        (None,) * (len(b.shape) - len(a.shape)) + tuple(a.shape),
        (None,) * (len(a.shape) - len(b.shape)) + tuple(b.shape),
    ):
        if x is None and y is None:
            out.append(None)
        elif x is None:
            out.append(y if y != Dim(1) else None)
        elif y is None:
            out.append(x if x != Dim(1) else None)
        elif x == Dim(1):
            out.append(y)
        elif y == Dim(1):
            out.append(x)
        elif x == y:
            out.append(x)
        else:
            out.append(None)  # can't prove; never invent a mismatch
    return ArrayVal(tuple(out), a.dtype or b.dtype)


def _matmul(a, b) -> object:
    if not (isinstance(a, ArrayVal) and isinstance(b, ArrayVal)):
        return UNKNOWN
    if a.shape is None or b.shape is None or len(a.shape) < 2 or len(b.shape) < 2:
        return ArrayVal(None)
    batch = max(len(a.shape), len(b.shape)) - 2
    lead_a = (None,) * (batch - (len(a.shape) - 2)) + tuple(a.shape[:-2])
    lead_b = (None,) * (batch - (len(b.shape) - 2)) + tuple(b.shape[:-2])
    lead = tuple(
        x if (y is None or x == y) else (y if x is None else None)
        for x, y in zip(lead_a, lead_b)
    )
    lead = tuple(x if x is not None else y for x, y in zip(lead, lead_b))
    return ArrayVal(lead + (a.shape[-2], b.shape[-1]), a.dtype or b.dtype)


def _einsum(spec: str, operands: List[object]) -> object:
    if "..." in spec or "->" not in spec:
        return UNKNOWN
    lhs, rhs = spec.replace(" ", "").split("->")
    terms = lhs.split(",")
    if len(terms) != len(operands):
        return UNKNOWN
    env: Dict[str, Optional[Dim]] = {}
    for term, op in zip(terms, operands):
        if not isinstance(op, ArrayVal) or op.shape is None:
            continue
        if len(term) != len(op.shape):
            return UNKNOWN
        for letter, d in zip(term, op.shape):
            if d is None:
                continue
            seen = env.get(letter)
            if seen is None:
                env[letter] = d
            elif seen != d:
                env[letter] = None
    return ArrayVal(tuple(env.get(letter) for letter in rhs))


class _CallEval:
    """Namespace of call evaluators, dispatched by short name."""


def _eval_mesh_ctor(ev: Evaluator, node: ast.Call) -> object:
    """``Mesh(devices, axis_names)`` — axis sizes recovered from a literal
    ``.reshape(dims)`` on the devices expression when present."""
    names_val = None
    for kw in node.keywords:
        if kw.arg in ("axis_names", "axis_name"):
            names_val = ev.eval(kw.value)
    if names_val is None and len(node.args) >= 2:
        names_val = ev.eval(node.args[1])
    if isinstance(names_val, str):
        names_val = (names_val,)
    if not isinstance(names_val, tuple) or not all(
        isinstance(n, str) for n in names_val
    ):
        return UNKNOWN
    sizes: List[Optional[int]] = [None] * len(names_val)
    if node.args:
        dev = node.args[0]
        if (
            isinstance(dev, ast.Call)
            and isinstance(dev.func, ast.Attribute)
            and dev.func.attr == "reshape"
        ):
            dims = [ev.eval(a) for a in dev.args]
            if len(dims) == 1 and isinstance(dims[0], tuple):
                dims = list(dims[0])
            if len(dims) == len(names_val):
                sizes = [d if isinstance(d, int) else None for d in dims]
    return MeshVal(tuple(zip(names_val, sizes)))


def _eval_make_mesh(ev: Evaluator, node: ast.Call) -> object:
    size = ev.eval(node.args[0]) if node.args else None
    axis = "workers"
    for kw in node.keywords:
        if kw.arg == "axis_name":
            got = ev.eval(kw.value)
            if isinstance(got, str):
                axis = got
            else:
                return UNKNOWN
    if len(node.args) >= 2:
        got = ev.eval(node.args[1])
        if isinstance(got, str):
            axis = got
        else:
            return UNKNOWN
    return MeshVal(((axis, size if isinstance(size, int) else None),))


def _eval_make_mesh_grid(ev: Evaluator, node: ast.Call) -> object:
    dims = [ev.eval(a) for a in node.args]
    if len(dims) == 1 and isinstance(dims[0], tuple):
        dims = list(dims[0])
    names: object = ("workers", "seq")
    for kw in node.keywords:
        if kw.arg == "axis_names":
            names = ev.eval(kw.value)
    if not isinstance(names, tuple) or not all(
        isinstance(n, str) for n in names
    ):
        return UNKNOWN
    if len(dims) != len(names):
        return UNKNOWN
    return MeshVal(tuple(
        (n, d if isinstance(d, int) else None) for n, d in zip(names, dims)
    ))


def _eval_spec_ctor(ev: Evaluator, node: ast.Call) -> object:
    entries: List[object] = []
    for arg in node.args:
        got = ev.eval(arg)
        if got is None:
            entries.append(())
        elif isinstance(got, str):
            entries.append((got,))
        elif isinstance(got, tuple) and all(isinstance(x, str) for x in got):
            entries.append(tuple(got))
        else:
            entries.append(UNKNOWN)
    return SpecVal(entries)


def _grid_tuple(value) -> Optional[Tuple[Optional[Dim], ...]]:
    return _shape_tuple(value)


# the dispatch table proper lives on Evaluator to keep `self` access simple
def _evaluator_eval_call(self: Evaluator, node: ast.Call):
    resolved, short = resolved_call(self.fi, node)
    resolved = resolved or ""

    # -- constructors the rules care about
    if short in _SPEC_CTORS and (
        "PartitionSpec" in resolved or short == "P"
    ):
        return _eval_spec_ctor(self, node)
    if short == "Mesh":
        return _eval_mesh_ctor(self, node)
    if short == "make_mesh":
        return _eval_make_mesh(self, node)
    if short == "make_mesh_grid":
        return _eval_make_mesh_grid(self, node)
    if short == "NamedSharding":
        if len(node.args) >= 2:
            mesh = self.eval(node.args[0])
            spec = self.eval(node.args[1])
            return ShardingVal(
                mesh if isinstance(mesh, MeshVal) else UNKNOWN,
                spec if isinstance(spec, SpecVal) else UNKNOWN,
            )
        return UNKNOWN
    if short in ("worker_sharding", "replicated_sharding"):
        mesh = self.eval(node.args[0]) if node.args else UNKNOWN
        if isinstance(mesh, MeshVal) and mesh.axes:
            spec = SpecVal(((mesh.axes[0][0],),)) if short == "worker_sharding" \
                else SpecVal(())
            return ShardingVal(mesh, spec)
        return UNKNOWN
    if short == "ShapeDtypeStruct":
        shape = _shape_tuple(self.eval(node.args[0])) if node.args else None
        dtype = None
        if len(node.args) >= 2:
            dtype = _dtype_str(self.eval(node.args[1]))
        for kw in node.keywords:
            if kw.arg == "shape":
                shape = _shape_tuple(self.eval(kw.value))
            elif kw.arg == "dtype":
                dtype = _dtype_str(self.eval(kw.value))
        return ShapeDtypeVal(shape, dtype)
    if short == "BlockSpec":
        block = _shape_tuple(self.eval(node.args[0])) if node.args else None
        index_map = None
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Lambda):
            index_map = node.args[1]
        for kw in node.keywords:
            if kw.arg == "block_shape":
                block = _shape_tuple(self.eval(kw.value))
            elif kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
                index_map = kw.value
        return BlockSpecVal(block, index_map)
    if short == "VMEM" or short == "SMEM":
        shape = _shape_tuple(self.eval(node.args[0])) if node.args else None
        dtype = _dtype_str(self.eval(node.args[1])) if len(node.args) >= 2 else None
        return ShapeDtypeVal(shape, dtype)
    if short == "partial" and node.args:
        target = self.eval(node.args[0])
        if isinstance(target, FnVal):
            return FnVal(target.node, target.bound_pos + len(node.args) - 1)
        return UNKNOWN

    # -- sharding producers (DK126 sources)
    if short == "device_put":
        arr = self.eval(node.args[0]) if node.args else UNKNOWN
        sharding = UNKNOWN
        if len(node.args) >= 2:
            sharding = self.eval(node.args[1])
        for kw in node.keywords:
            if kw.arg in ("device", "sharding"):
                sharding = self.eval(kw.value)
        sh = sharding if isinstance(sharding, ShardingVal) else None
        if isinstance(arr, ArrayVal):
            return ArrayVal(arr.shape, arr.dtype, sh or arr.sharding)
        return ArrayVal(None, None, sh)
    if short == "with_sharding_constraint":
        arr = self.eval(node.args[0]) if node.args else UNKNOWN
        sharding = self.eval(node.args[1]) if len(node.args) >= 2 else UNKNOWN
        if isinstance(sharding, SpecVal):
            sharding = ShardingVal(UNKNOWN, sharding)
        sh = sharding if isinstance(sharding, ShardingVal) else None
        if isinstance(arr, ArrayVal):
            return ArrayVal(arr.shape, arr.dtype, sh or arr.sharding)
        return ArrayVal(None, None, sh)

    # -- array constructors
    if short in _ZEROS_LIKE and node.args:
        shape = _shape_tuple(self.eval(node.args[0]))
        dtype = None
        idx = 2 if short == "full" else 1
        if len(node.args) > idx:
            dtype = _dtype_str(self.eval(node.args[idx]))
        for kw in node.keywords:
            if kw.arg == "dtype":
                dtype = _dtype_str(self.eval(kw.value))
        if shape is not None:
            return ArrayVal(shape, dtype)
        return UNKNOWN
    if short == "arange" and len(node.args) == 1:
        n = dim_of(self.eval(node.args[0]))
        return ArrayVal((n,)) if n is not None else ArrayVal(None)
    if short in ("normal", "uniform") and len(node.args) >= 2 and \
            "random" in resolved:
        shape = _shape_tuple(self.eval(node.args[1]))
        return ArrayVal(shape) if shape is not None else ArrayVal(None)
    if short in ("zeros_like", "ones_like") and node.args:
        src = self.eval(node.args[0])
        if isinstance(src, ArrayVal):
            return ArrayVal(src.shape, src.dtype)
        return UNKNOWN

    # -- structural ops
    if short == "reshape":
        # jnp.reshape(x, shape) or x.reshape(shape) / x.reshape(*dims)
        if isinstance(node.func, ast.Attribute) and not (
            resolved.startswith(("jax", "numpy")) or short != "reshape"
        ) and node.args and call_name(node) is None:
            pass
        if resolved.startswith(("jax.numpy", "numpy", "jnp")) and len(node.args) >= 2:
            arr, shape_v = self.eval(node.args[0]), self.eval(node.args[1])
        elif isinstance(node.func, ast.Attribute):
            arr = self.eval(node.func.value)
            dims = [self.eval(a) for a in node.args]
            shape_v = dims[0] if len(dims) == 1 and isinstance(dims[0], tuple) \
                else tuple(dims)
        else:
            return UNKNOWN
        return _reshape(arr, shape_v)
    if short == "transpose":
        if isinstance(node.func, ast.Attribute) and not resolved.startswith(
            ("jax", "numpy")
        ):
            arr = self.eval(node.func.value)
            perm = self.eval(node.args[0]) if node.args else None
        else:
            arr = self.eval(node.args[0]) if node.args else UNKNOWN
            perm = self.eval(node.args[1]) if len(node.args) >= 2 else None
            for kw in node.keywords:
                if kw.arg == "axes":
                    perm = self.eval(kw.value)
        return _transpose(arr, perm)
    if short == "concatenate" and node.args:
        parts = self.eval(node.args[0])
        axis = 0
        if len(node.args) >= 2:
            axis = self.eval(node.args[1])
        for kw in node.keywords:
            if kw.arg == "axis":
                axis = self.eval(kw.value)
        return _concatenate(parts, axis)
    if short in _REDUCTIONS and resolved.startswith(("jax.numpy", "numpy")):
        arr = self.eval(node.args[0]) if node.args else UNKNOWN
        axis = None
        keepdims = False
        if len(node.args) >= 2:
            axis = self.eval(node.args[1])
        for kw in node.keywords:
            if kw.arg == "axis":
                axis = self.eval(kw.value)
            elif kw.arg == "keepdims":
                keepdims = self.eval(kw.value) is True
        return _reduce(arr, axis, keepdims)
    if short in ("matmul", "dot") and len(node.args) >= 2:
        return _matmul(self.eval(node.args[0]), self.eval(node.args[1]))
    if short == "einsum" and node.args:
        spec = self.eval(node.args[0])
        if isinstance(spec, str):
            return _einsum(spec, [self.eval(a) for a in node.args[1:]])
        return UNKNOWN
    if short == "astype" and isinstance(node.func, ast.Attribute):
        arr = self.eval(node.func.value)
        dtype = _dtype_str(self.eval(node.args[0])) if node.args else None
        if isinstance(arr, ArrayVal):
            return ArrayVal(arr.shape, dtype or arr.dtype, arr.sharding)
        return UNKNOWN

    # -- collectives (shape semantics; axis legality is DK104/DK108's job)
    if short in _SAME_SHAPE_COLLECTIVES and node.args:
        arr = self.eval(node.args[0])
        if isinstance(arr, ArrayVal):
            return ArrayVal(arr.shape, arr.dtype)
        return UNKNOWN
    if short == "all_gather" and node.args:
        return _all_gather(self, node)
    if short == "psum_scatter" and node.args:
        return _psum_scatter(self, node)
    if short == "axis_size" and node.args:
        axis = self.eval(node.args[0])
        if isinstance(axis, str):
            return axis_sym(axis)
        return UNKNOWN
    if short == "len" and len(node.args) == 1:
        got = self.eval(node.args[0])
        if isinstance(got, tuple):
            return len(got)
        if isinstance(got, ArrayVal) and got.shape and got.shape[0] is not None:
            return got.shape[0].as_int() or UNKNOWN
        return UNKNOWN
    if short in ("int", "min", "max") and resolved in ("int", "min", "max"):
        vals = [self.eval(a) for a in node.args]
        if all(isinstance(v, int) for v in vals) and vals:
            if short == "int":
                return vals[0]
            return min(vals) if short == "min" else max(vals)
        return UNKNOWN
    return UNKNOWN


Evaluator._eval_call = _evaluator_eval_call  # type: ignore[attr-defined]


def _evaluator_eval_attribute(self: Evaluator, node: ast.Attribute):
    # dtype literals: jnp.float32, np.int32, ...
    if node.attr in _DTYPE_NAMES:
        return node.attr.rstrip("_")
    base = self.eval(node.value)
    if isinstance(base, ArrayVal):
        if node.attr == "shape":
            return base.shape if base.shape is not None else UNKNOWN
        if node.attr == "dtype":
            return base.dtype or UNKNOWN
        if node.attr == "T":
            return _transpose(base, None)
        if node.attr == "ndim":
            return base.rank if base.rank is not None else UNKNOWN
        if node.attr == "sharding":
            return base.sharding or UNKNOWN
    if isinstance(base, MeshVal):
        if node.attr == "axis_names":
            return base.names
        if node.attr == "shape":
            return UNKNOWN
    if isinstance(base, ShapeDtypeVal):
        if node.attr == "shape":
            return base.shape if base.shape is not None else UNKNOWN
        if node.attr == "dtype":
            return base.dtype or UNKNOWN
    if isinstance(base, ShardingVal):
        if node.attr == "mesh":
            return base.mesh
        if node.attr == "spec":
            return base.spec
    return UNKNOWN


Evaluator._eval_attribute = _evaluator_eval_attribute  # type: ignore[attr-defined]


def _evaluator_eval_subscript(self: Evaluator, node: ast.Subscript):
    base = self.eval(node.value)
    if base is UNKNOWN:
        return UNKNOWN
    idx = node.slice
    if isinstance(base, tuple):
        if isinstance(idx, ast.Slice):
            lo = self.eval(idx.lower) if idx.lower else 0
            hi = self.eval(idx.upper) if idx.upper else len(base)
            if isinstance(lo, int) and isinstance(hi, int) and idx.step is None:
                return base[lo:hi]
            return UNKNOWN
        i = self.eval(idx)
        if isinstance(i, int) and -len(base) <= i < len(base):
            return base[i]
        return UNKNOWN
    if isinstance(base, ArrayVal):
        return _index_array(self, base, idx)
    return UNKNOWN


Evaluator._eval_subscript = _evaluator_eval_subscript  # type: ignore[attr-defined]


def _index_array(ev: Evaluator, arr: ArrayVal, idx: ast.AST) -> object:
    if arr.shape is None:
        return ArrayVal(None)
    items = list(idx.elts) if isinstance(idx, ast.Tuple) else [idx]
    out: List[Optional[Dim]] = []
    pos = 0
    ndim = len(arr.shape)
    explicit = sum(1 for it in items if not (
        isinstance(it, ast.Constant) and it.value is Ellipsis
    ))
    for it in items:
        if isinstance(it, ast.Constant) and it.value is Ellipsis:
            keep = ndim - explicit
            out.extend(arr.shape[pos:pos + keep])
            pos += keep
            continue
        if pos >= ndim:
            return UNKNOWN
        dim = arr.shape[pos]
        if isinstance(it, ast.Slice):
            if it.lower is None and it.upper is None and it.step is None:
                out.append(dim)
            else:
                lo = ev.eval(it.lower) if it.lower else 0
                hi = ev.eval(it.upper) if it.upper else None
                if (
                    it.step is None and isinstance(lo, int)
                    and isinstance(hi, int) and lo >= 0 and hi >= lo
                ):
                    out.append(Dim(hi - lo))
                else:
                    out.append(None)
            pos += 1
            continue
        got = ev.eval(it)
        if isinstance(got, int) or isinstance(got, Dim):
            pos += 1  # integer index drops the dim
            continue
        if got is None:
            out.append(Dim(1))  # np.newaxis
            continue
        return UNKNOWN
    out.extend(arr.shape[pos:])
    return ArrayVal(tuple(out), arr.dtype)


def _evaluator_eval_binop(self: Evaluator, node: ast.BinOp):
    left, right = self.eval(node.left), self.eval(node.right)
    if isinstance(node.op, ast.MatMult):
        return _matmul(left, right)
    if isinstance(left, ArrayVal) or isinstance(right, ArrayVal):
        if isinstance(node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.Pow)):
            if isinstance(left, ArrayVal):
                return _broadcast(left, right)
            return _broadcast(right, left)
        return UNKNOWN
    if isinstance(left, tuple) and isinstance(right, tuple) and \
            isinstance(node.op, ast.Add):
        return left + right
    if isinstance(left, tuple) and isinstance(right, int) and \
            isinstance(node.op, ast.Mult):
        return left * right
    la, rb = dim_of(left), dim_of(right)
    if la is not None and rb is not None:
        if isinstance(node.op, ast.Mult):
            got = dim_mul(la, rb)
        elif isinstance(node.op, ast.Add):
            got = dim_add(la, rb)
        elif isinstance(node.op, ast.Sub):
            got = dim_sub(la, rb)
        elif isinstance(node.op, ast.FloorDiv):
            got = dim_floordiv(la, rb)
        elif isinstance(node.op, ast.Mod) and la.is_int and rb.is_int and \
                rb.coeff != 0:
            got = Dim(la.coeff % rb.coeff)
        else:
            got = None
        if got is None:
            return UNKNOWN
        return got.as_int() if got.is_int else got
    return UNKNOWN


Evaluator._eval_binop = _evaluator_eval_binop  # type: ignore[attr-defined]


def _reshape(arr, shape_v) -> object:
    new = _shape_tuple(shape_v)
    if new is None:
        return ArrayVal(None)
    if isinstance(arr, ArrayVal) and arr.shape is not None and \
            any(d == Dim(-1) for d in new):
        total = Dim(1)
        for d in arr.shape:
            total = dim_mul(total, d)
        known = Dim(1)
        for d in new:
            if d != Dim(-1):
                known = dim_mul(known, d)
        fill = dim_floordiv(total, known)
        new = tuple(fill if d == Dim(-1) else d for d in new)
    dtype = arr.dtype if isinstance(arr, ArrayVal) else None
    return ArrayVal(new, dtype)


def _transpose(arr, perm) -> object:
    if not isinstance(arr, ArrayVal):
        return UNKNOWN
    if arr.shape is None:
        return ArrayVal(None)
    if perm is None:
        return ArrayVal(tuple(reversed(arr.shape)), arr.dtype)
    axes = _shape_tuple(perm)
    if axes is None or len(axes) != len(arr.shape):
        return ArrayVal(None)
    idx = [d.as_int() if d is not None else None for d in axes]
    if any(i is None or not (0 <= i < len(arr.shape)) for i in idx):
        return ArrayVal(None)
    return ArrayVal(tuple(arr.shape[i] for i in idx), arr.dtype)


def _concatenate(parts, axis) -> object:
    if not isinstance(parts, tuple) or not parts:
        return UNKNOWN
    arrays = [p for p in parts if isinstance(p, ArrayVal)]
    if len(arrays) != len(parts):
        return UNKNOWN
    if any(a.shape is None for a in arrays):
        return ArrayVal(None)
    rank = len(arrays[0].shape)
    if any(len(a.shape) != rank for a in arrays) or not isinstance(axis, int):
        return ArrayVal(None)
    if not (-rank <= axis < rank):
        return UNKNOWN
    axis %= rank
    out: List[Optional[Dim]] = []
    for i in range(rank):
        if i == axis:
            total: Optional[Dim] = Dim(0)
            for a in arrays:
                total = dim_add(total, a.shape[i])
            out.append(total)
        else:
            dims = {a.shape[i] for a in arrays}
            out.append(dims.pop() if len(dims) == 1 else None)
    return ArrayVal(tuple(out), arrays[0].dtype)


def _reduce(arr, axis, keepdims) -> object:
    if not isinstance(arr, ArrayVal):
        return UNKNOWN
    if arr.shape is None:
        return ArrayVal(None)
    if axis is None:
        return ArrayVal(() if not keepdims else tuple(
            Dim(1) for _ in arr.shape
        ), arr.dtype)
    axes = axis if isinstance(axis, tuple) else (axis,)
    if not all(isinstance(a, int) for a in axes):
        return ArrayVal(None)
    norm = {a % len(arr.shape) for a in axes if -len(arr.shape) <= a < len(arr.shape)}
    out = [
        (Dim(1) if keepdims else None) if i in norm else d
        for i, d in enumerate(arr.shape)
        if keepdims or i not in norm
    ]
    return ArrayVal(tuple(out), arr.dtype)


def _collective_axis(ev: Evaluator, node: ast.Call) -> object:
    for kw in node.keywords:
        if kw.arg == "axis_name":
            return ev.eval(kw.value)
    if len(node.args) >= 2:
        return ev.eval(node.args[1])
    return UNKNOWN


def _all_gather(ev: Evaluator, node: ast.Call) -> object:
    arr = ev.eval(node.args[0])
    axis_name = _collective_axis(ev, node)
    dim_idx: object = 0
    tiled: object = False
    for kw in node.keywords:
        if kw.arg == "axis":
            dim_idx = ev.eval(kw.value)
        elif kw.arg == "tiled":
            tiled = ev.eval(kw.value)
    if not isinstance(arr, ArrayVal) or arr.shape is None or \
            not isinstance(axis_name, str):
        return ArrayVal(None) if isinstance(arr, ArrayVal) else UNKNOWN
    n = axis_sym(axis_name)
    if tiled is True:
        if isinstance(dim_idx, int) and 0 <= dim_idx < len(arr.shape):
            shape = list(arr.shape)
            shape[dim_idx] = dim_mul(shape[dim_idx], n)
            return ArrayVal(tuple(shape), arr.dtype)
        return ArrayVal(None, arr.dtype)
    if isinstance(dim_idx, int) and 0 <= dim_idx <= len(arr.shape):
        shape = list(arr.shape)
        shape.insert(dim_idx, n)
        return ArrayVal(tuple(shape), arr.dtype)
    return ArrayVal(None, arr.dtype)


def _psum_scatter(ev: Evaluator, node: ast.Call) -> object:
    arr = ev.eval(node.args[0])
    axis_name = _collective_axis(ev, node)
    dim_idx: object = 0
    for kw in node.keywords:
        if kw.arg == "scatter_dimension":
            dim_idx = ev.eval(kw.value)
    if not isinstance(arr, ArrayVal) or arr.shape is None or \
            not isinstance(axis_name, str):
        return ArrayVal(None) if isinstance(arr, ArrayVal) else UNKNOWN
    if isinstance(dim_idx, int) and 0 <= dim_idx < len(arr.shape):
        shape = list(arr.shape)
        shape[dim_idx] = dim_floordiv(shape[dim_idx], axis_sym(axis_name))
        return ArrayVal(tuple(shape), arr.dtype)
    return ArrayVal(None, arr.dtype)


# ------------------------------------------------------------ shard_map sites

SHARD_MAP_SUFFIXES = (
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
)

COMPAT_MODULE_SUFFIX = "utils.compat"


class ShardMapSite:
    """One resolved ``shard_map(...)`` call (optionally with the call that
    invokes the mapped function, so operand shapes can be judged)."""

    __slots__ = ("call", "invoke", "via", "fn_expr", "mesh", "in_specs",
                 "out_specs", "axis_names", "encl")

    def __init__(self, call: ast.Call, via: str, encl: Optional[ast.AST]):
        self.call = call
        self.via = via              # "jax" | "compat" | "bare"
        self.encl = encl
        self.invoke: Optional[ast.Call] = None
        self.fn_expr: Optional[ast.AST] = call.args[0] if call.args else None
        self.mesh: object = UNKNOWN
        self.in_specs: object = UNKNOWN
        self.out_specs: object = UNKNOWN
        self.axis_names: object = None


def _shard_map_via(fi: FileInfo, node: ast.Call) -> Optional[str]:
    resolved, short = resolved_call(fi, node)
    if short != "shard_map":
        return None
    resolved = resolved or ""
    if resolved.endswith("compat.shard_map") or \
            COMPAT_MODULE_SUFFIX + ".shard_map" in resolved:
        return "compat"
    for suffix in SHARD_MAP_SUFFIXES:
        if resolved == suffix or resolved.endswith("." + suffix):
            return "jax"
    if resolved == "shard_map" or resolved.endswith(".shard_map"):
        return "bare"
    return None


def shard_map_sites(project: Project, fi: FileInfo) -> List[ShardMapSite]:
    """Every shard_map call in the file with mesh/specs resolved, plus the
    invocation call when the mapped function is applied in the same
    function (immediately, or through a single-definition local)."""
    facts = _facts_for(project, fi)
    sites: List[ShardMapSite] = []
    by_call: Dict[int, ShardMapSite] = {}
    for call, encl in facts.calls:
        via = _shard_map_via(fi, call)
        if via is None:
            continue
        site = ShardMapSite(call, via, encl)
        ev = Evaluator(project, fi, encl)
        mesh_expr = None
        in_expr = out_expr = names_expr = None
        pos = list(call.args[1:])
        if pos:
            mesh_expr = pos[0]
        if len(pos) >= 2:
            in_expr = pos[1]
        if len(pos) >= 3:
            out_expr = pos[2]
        for kw in call.keywords:
            if kw.arg == "mesh":
                mesh_expr = kw.value
            elif kw.arg == "in_specs":
                in_expr = kw.value
            elif kw.arg == "out_specs":
                out_expr = kw.value
            elif kw.arg == "axis_names":
                names_expr = kw.value
        if mesh_expr is not None:
            site.mesh = ev.eval(mesh_expr)
        if in_expr is not None:
            site.in_specs = ev.eval(in_expr)
        if out_expr is not None:
            site.out_specs = ev.eval(out_expr)
        if names_expr is not None:
            got = ev.eval(names_expr)
            site.axis_names = got if got is not UNKNOWN else UNKNOWN
        sites.append(site)
        by_call[id(call)] = site

    # invocations: shard_map(...)(x, y) or name = shard_map(...); name(x, y)
    for call, encl in facts.calls:
        func = call.func
        if isinstance(func, ast.Call) and id(func) in by_call:
            by_call[id(func)].invoke = call
            continue
        if isinstance(func, ast.Name) and encl is not None:
            flow = dataflow.function_flow(encl, facts.flows)
            if not flow.is_use(func):
                continue
            defs = flow.reaching(func)
            if len(defs) == 1 and defs[0].value is not None and \
                    id(defs[0].value) in by_call:
                site = by_call[id(defs[0].value)]
                if site.invoke is None:
                    site.invoke = call
    return sites


# --------------------------------------------------------- pallas call sites

class PallasSite:
    __slots__ = ("call", "invoke", "encl", "kernel", "grid", "in_specs",
                 "out_specs", "out_shape", "scratch")

    def __init__(self, call: ast.Call, encl: Optional[ast.AST]):
        self.call = call
        self.encl = encl
        self.invoke: Optional[ast.Call] = None
        self.kernel: object = UNKNOWN
        self.grid: object = UNKNOWN
        self.in_specs: object = UNKNOWN
        self.out_specs: object = UNKNOWN
        self.out_shape: object = UNKNOWN
        self.scratch: object = None


def pallas_sites(project: Project, fi: FileInfo) -> List[PallasSite]:
    facts = _facts_for(project, fi)
    sites: List[PallasSite] = []
    by_call: Dict[int, PallasSite] = {}
    for call, encl in facts.calls:
        resolved, short = resolved_call(fi, call)
        if short != "pallas_call":
            continue
        site = PallasSite(call, encl)
        ev = Evaluator(project, fi, encl)
        if call.args:
            site.kernel = ev.eval(call.args[0])
        for kw in call.keywords:
            if kw.arg == "grid":
                site.grid = ev.eval(kw.value)
            elif kw.arg == "in_specs":
                site.in_specs = ev.eval(kw.value)
            elif kw.arg == "out_specs":
                site.out_specs = ev.eval(kw.value)
            elif kw.arg == "out_shape":
                site.out_shape = ev.eval(kw.value)
            elif kw.arg == "scratch_shapes":
                site.scratch = ev.eval(kw.value)
        sites.append(site)
        by_call[id(call)] = site
    for call, encl in facts.calls:
        func = call.func
        if isinstance(func, ast.Call) and id(func) in by_call:
            by_call[id(func)].invoke = call
        elif isinstance(func, ast.Name) and encl is not None:
            flow = dataflow.function_flow(encl, facts.flows)
            if flow.is_use(func):
                defs = flow.reaching(func)
                if len(defs) == 1 and defs[0].value is not None and \
                        id(defs[0].value) in by_call:
                    site = by_call[id(defs[0].value)]
                    if site.invoke is None:
                        site.invoke = call
    return sites


# ---------------------------------------------------------------- rendering

def render_value(value) -> str:
    if value is UNKNOWN:
        return "?"
    if value is None:
        return "None"
    if isinstance(value, tuple):
        return "(" + ", ".join(render_value(v) for v in value) + ")"
    if isinstance(value, (SpecVal, MeshVal, ShardingVal, ArrayVal, Dim)):
        return repr(value)
    return repr(value)


_ENGINE_BUCKETS = (
    ("parallel/engine", "engine"),
    ("parallel/gspmd", "gspmd"),
    ("parallel/pipeline", "pipeline"),
    ("parallel/ring", "engine"),
    ("models/generate", "serving decode"),
    ("serving/", "serving"),
    ("ops/pallas", "kernels"),
)


def _bucket(relpath: str) -> str:
    for needle, bucket in _ENGINE_BUCKETS:
        if needle in relpath:
            return bucket
    return "other"


def layout_report(paths: Sequence[str], root: str) -> str:
    """The ``--shapes-report`` artifact: every shard_map / NamedSharding /
    with_sharding_constraint / pallas_call site with its inferred layout,
    grouped per engine — layout changes show up in PR diffs."""
    from tools.dklint import core

    files = [core.load_file(p, root) for p in sorted(
        core.discover(paths), key=lambda p: p.replace("\\", "/")
    )]
    project = Project(root, files)
    rows: Dict[str, List[str]] = {}

    for fi in files:
        facts = _facts_for(project, fi)
        for site in shard_map_sites(project, fi):
            manual = "all" if site.axis_names in (None,) else \
                render_value(site.axis_names)
            rows.setdefault(_bucket(fi.relpath), []).append(
                f"{fi.relpath}:{site.call.lineno} shard_map[{site.via}] "
                f"mesh={render_value(site.mesh)} manual={manual} "
                f"in_specs={render_value(site.in_specs)} "
                f"out_specs={render_value(site.out_specs)}"
            )
        for site in pallas_sites(project, fi):
            rows.setdefault(_bucket(fi.relpath), []).append(
                f"{fi.relpath}:{site.call.lineno} pallas_call "
                f"grid={render_value(site.grid)} "
                f"in_specs={render_value(site.in_specs)} "
                f"out_specs={render_value(site.out_specs)} "
                f"out_shape={render_value(site.out_shape)}"
            )
        for call, encl in facts.calls:
            _resolved, short = resolved_call(fi, call)
            if short not in ("NamedSharding", "with_sharding_constraint",
                             "device_put"):
                continue
            ev = Evaluator(project, fi, encl)
            got = ev.eval(call)
            if short == "NamedSharding":
                if not isinstance(got, ShardingVal):
                    continue
                desc = render_value(got)
            else:
                sh = got.sharding if isinstance(got, ArrayVal) else None
                if sh is None:
                    continue
                desc = f"{short} -> {render_value(sh)}"
            rows.setdefault(_bucket(fi.relpath), []).append(
                f"{fi.relpath}:{call.lineno} {desc}"
            )

    lines = ["dkshape layout report — inferred meshes & partition specs",
             "(? = not statically resolvable; judged as trusted)", ""]
    order = ["engine", "gspmd", "pipeline", "serving", "serving decode",
             "kernels", "other"]
    for bucket in order + sorted(set(rows) - set(order)):
        if bucket not in rows:
            continue
        lines.append(f"==== {bucket} ====")
        lines.extend(sorted(rows[bucket]))
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"
