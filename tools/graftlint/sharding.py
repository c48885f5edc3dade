"""graftlint sharding pass — whole-program SPMD/collective analysis.

The trace-hygiene, concurrency and precision passes leave one
discipline unchecked: *placement*.  PR 12/14 shipped hand-audited GSPMD
annotations (``zero_shardings``, ``paged_pool_shardings``, the planner's
emitted specs) whose silent failure mode is a correct-but-fully-
replicated — or per-step host-syncing — program, and every open ROADMAP
item (pipeline over a ``pipe`` axis, multi-host fleet) multiplies the
mesh/collective surface.  This pass makes the placement contract
machine-checked:

1. **Axis-binding inference** — mesh constructions (``Mesh(devs,
   axis_names)``, ``jax.make_mesh``), mesh *factories* (any function
   whose body builds a mesh with resolvable axes — ``initialize_mesh``,
   ``tp_mesh`` — transitively through ``return factory(...)``),
   ``shard_map(mesh=, in_specs=, out_specs=)`` call sites and
   decorators, and ``pmap(axis_name=)``.  Axis names resolve through
   module-level string constants program-wide (``TENSOR_AXIS =
   "tensor"`` in ``core/mesh.py`` resolves at every import site), and
   bindings flow interprocedurally through same-file bare-name /
   ``self.m()`` calls and lexical nesting, exactly like the trace-path
   closure in ``core.py``.

2. **Five rules** on top of that state (catalog in
   ``docs/graftlint.md``): ``unbound-axis-name``,
   ``spec-mesh-mismatch``, ``unreplicated-out-spec``,
   ``host-sync-in-step`` and ``donation-after-use``.

Annotation convention (the concurrency/precision twins of which are
``unguarded(<why>)`` / ``lowprec(<why>)``):

- ``# graftlint: hot-step`` on a ``def`` line marks a *host-side* step
  entry point (an engine decode step, a train-loop step, a bench leg):
  code that runs once per token/step and must not force device→host
  syncs beyond its declared output read.  Rule 4 checks only marked
  functions, so the blast radius is exactly the annotated step set.
- ``# graftlint: unsharded(<why>)`` on a finding line (or a standalone
  comment directly above it) is a justified, deliberate exception to
  any sharding rule — the why is mandatory; an empty ``unsharded()`` is
  itself flagged, matching the guarded-by/lowprec convention.

The runtime twin is :mod:`apex_tpu.utils.shardcheck`, which records the
*actual* output shardings of the compiled step executables against the
declared spec trees under the chaos soaks (``APEX_TPU_SHARDCHECK=
strict``).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from tools.graftlint.core import (
    Finding,
    ModuleContext,
    ProgramRule,
    dotted_name,
    last_attr,
    register_program,
)

__all__ = ["analyze_program"]

# ----------------------------------------------------------------- marks

_MARK_RE = re.compile(
    r"graftlint:\s*(?:(hot-step)\b|(unsharded)\(([^)]*)\))")

#: collective primitives -> positional index of their axis-name operand
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "psum_scatter": 1,
    "all_gather": 1, "all_to_all": 1, "ppermute": 1, "pshuffle": 1,
    "collective_permute": 1,
    "axis_index": 0, "axis_size": 0,
}
#: collectives that REDUCE across shards (clear rule-3 divergence)
_REDUCING = {"psum", "pmean", "pmax", "pmin", "psum_scatter",
             "all_gather", "all_to_all"}
#: collectives that PERMUTE across shards: they bind an axis name
#: (rule S1 checks it, via _COLLECTIVES above) but they are NOT
#: reductions — every shard still holds a DIFFERENT (neighbor's)
#: value afterward, so they must not sanitize per-shard divergence
#: in the rule-S3 lattice (the 1F1B pipeline moves activations with
#: exactly this op; a misclassification would blind S3 inside every
#: pipeline body)
_PERMUTING = {"ppermute", "pshuffle", "collective_permute"}

_FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)
_FuncNode = _FuncDef + (ast.Lambda,)


def _marks_for_line(ctx: ModuleContext, line: int) -> List[Tuple[str, str]]:
    """Sharding marks on ``line`` — trailing, or on a *standalone*
    comment directly above (same contract as the other passes)."""
    sup = ctx.suppressions
    text = sup.graftlint_comments.get(line, "")
    if line - 1 in sup.standalone_comment_lines:
        text += " " + sup.graftlint_comments.get(line - 1, "")
    out: List[Tuple[str, str]] = []
    for m in _MARK_RE.finditer(text):
        if m.group(1):
            out.append(("hot-step", ""))
        else:
            out.append(("unsharded", (m.group(3) or "").strip()))
    return out


def _key(node: ast.AST) -> Optional[str]:
    """``x`` / ``self.x`` → a trackable dotted key, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name):
        return f"{node.value.id}.{node.attr}"
    return None


# ----------------------------------------------- program-wide constants

class _Consts:
    """Module-level string / string-tuple constants, program-wide.

    Axis names in this repo are module constants (``TENSOR_AXIS =
    "tensor"``, ``AXIS_ORDER = (DATA_AXIS, ...)`` in ``core/mesh.py``)
    imported by simple name everywhere — so one flat name→value map
    over every module resolves them at any use site."""

    def __init__(self, contexts: List[ModuleContext]):
        self.strings: Dict[str, str] = {}
        self.tuples: Dict[str, Tuple[str, ...]] = {}
        pending: List[Tuple[str, ast.AST]] = []
        for ctx in contexts:
            for node in ctx.tree.body:
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    name, value = node.targets[0].id, node.value
                elif isinstance(node, ast.AnnAssign) \
                        and node.value is not None \
                        and isinstance(node.target, ast.Name):
                    # AXIS_ORDER: Tuple[str, ...] = (...) — annotated
                    name, value = node.target.id, node.value
                else:
                    continue
                if isinstance(value, ast.Constant) \
                        and isinstance(value.value, str):
                    self.strings.setdefault(name, value.value)
                elif isinstance(value, (ast.Tuple, ast.List)):
                    pending.append((name, value))
        for name, value in pending:         # second pass: tuples of names
            elems = self.axis_strings(value)
            if elems:
                self.tuples.setdefault(name, tuple(elems))

    def axis_strings(self, node: Optional[ast.AST]
                     ) -> Optional[List[str]]:
        """Resolve ``node`` to a list of axis-name strings, or None if
        it is not statically resolvable (a parameter, a call, ...)."""
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str):
                return [node.value]
            if node.value is None:
                return []
            return None
        if isinstance(node, ast.Name):
            if node.id in self.strings:
                return [self.strings[node.id]]
            if node.id in self.tuples:
                return list(self.tuples[node.id])
            return None
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            out: List[str] = []
            for elt in node.elts:
                sub = self.axis_strings(elt)
                if sub is None:
                    return None
                out.extend(sub)
            return out
        return None


# -------------------------------------------------------- mesh resolution

def _mesh_ctor_axes(call: ast.Call, consts: _Consts
                    ) -> Optional[List[str]]:
    """Axes of a ``Mesh(devs, axis_names)`` / ``make_mesh(shape,
    axis_names)`` construction, when literal/constant-resolvable."""
    la = last_attr(call.func)
    if la not in ("Mesh", "make_mesh", "AbstractMesh"):
        return None
    node = None
    for kw in call.keywords:
        if kw.arg == "axis_names":
            node = kw.value
    if node is None and len(call.args) >= 2:
        node = call.args[1]
    return consts.axis_strings(node)


class _MeshResolver:
    """Resolve a mesh *expression* at a call site to its axis names.

    Handles: a direct ``Mesh(...)`` construction; a name assigned one
    in the enclosing function or at module level; a call to a known
    mesh factory (a function whose body constructs a mesh — found
    program-wide, with one propagation round for ``return
    other_factory(...)``); ``self.mesh`` through the owning class's
    ``__init__`` assignment.  Unresolvable → None (checks skip)."""

    def __init__(self, contexts: List[ModuleContext], consts: _Consts):
        self.consts = consts
        self.factories: Dict[str, FrozenSet[str]] = {}
        self._fn_defs: List[Tuple[ModuleContext, ast.AST]] = []
        for ctx in contexts:
            for fn in ctx.functions():
                if isinstance(fn, ast.Lambda):
                    continue
                self._fn_defs.append((ctx, fn))
                axes: Set[str] = set()
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        got = _mesh_ctor_axes(node, consts)
                        if got:
                            axes.update(got)
                if axes:
                    self.factories.setdefault(fn.name, frozenset(axes))
        # one propagation round: `def tp_mesh(): return initialize_mesh(..)`
        for ctx, fn in self._fn_defs:
            if fn.name in self.factories:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) \
                        and isinstance(node.value, ast.Call):
                    callee = last_attr(node.value.func)
                    if callee in self.factories:
                        self.factories[fn.name] = self.factories[callee]

    def resolve(self, ctx: ModuleContext, expr: Optional[ast.AST],
                site: ast.AST) -> Optional[FrozenSet[str]]:
        if expr is None:
            return None
        if isinstance(expr, ast.Call):
            axes = _mesh_ctor_axes(expr, self.consts)
            if axes:
                return frozenset(axes)
            callee = last_attr(expr.func)
            return self.factories.get(callee) if callee else None
        if isinstance(expr, ast.Name):
            return self._resolve_name(ctx, expr.id, site)
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self":
            return self._resolve_self_attr(ctx, expr.attr, site)
        return None

    def _assigned_value(self, scope: ast.AST, name: str
                        ) -> Optional[ast.AST]:
        found = None
        for node in ast.walk(scope):
            if isinstance(node, _FuncNode) and node is not scope:
                continue
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        found = node.value
        return found

    def _resolve_name(self, ctx: ModuleContext, name: str,
                      site: ast.AST) -> Optional[FrozenSet[str]]:
        fn = ctx.enclosing_function(site)
        while fn is not None:
            value = self._assigned_value(fn, name)
            if value is not None:
                return self.resolve(ctx, value, site)
            fn = ctx.enclosing_function(fn)
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        return self.resolve(ctx, node.value, site)
        return None

    def _resolve_self_attr(self, ctx: ModuleContext, attr: str,
                           site: ast.AST) -> Optional[FrozenSet[str]]:
        cur = ctx.parent(site)
        while cur is not None and not isinstance(cur, ast.ClassDef):
            cur = ctx.parent(cur)
        if cur is None:
            return None
        for node in ast.walk(cur):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and t.attr == attr \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        return self.resolve(ctx, node.value, site)
        return None


# -------------------------------------------------------- shard_map sites

@dataclasses.dataclass
class _ShardMapSite:
    ctx: ModuleContext
    call: ast.Call
    wrapped: Optional[ast.AST]           # resolved function def, if any
    mesh_axes: Optional[FrozenSet[str]]  # None = unresolvable
    manual_axes: Optional[FrozenSet[str]]    # axis_names= subset, if given
    in_specs: Optional[ast.AST]
    out_specs: Optional[ast.AST]

    @property
    def bound_axes(self) -> Optional[FrozenSet[str]]:
        """Axes manual (collective-visible) inside the wrapped body."""
        if self.manual_axes is not None:
            return self.manual_axes
        return self.mesh_axes


def _call_kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _is_partial_of(call: ast.Call, attr: str) -> bool:
    return (last_attr(call.func) == "partial" and call.args
            and last_attr(call.args[0]) == attr)


def _shard_map_sites(ctx: ModuleContext, resolver: _MeshResolver,
                     consts: _Consts) -> List[_ShardMapSite]:
    sites = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        is_direct = last_attr(node.func) == "shard_map"
        is_partial = _is_partial_of(node, "shard_map")
        if not (is_direct or is_partial):
            continue
        # the wrapped callable: arg 0 (direct), the decorated def
        # (decorator form), or the operand of the partial's later call
        wrapped: Optional[ast.AST] = None
        pos = list(node.args[1:]) if is_direct else []
        cand = node.args[0] if (is_direct and node.args) else None
        if is_partial:
            cand = None
        parent = ctx.parent(node)
        if isinstance(parent, _FuncDef) \
                and node in parent.decorator_list:
            wrapped = parent                 # @shard_map(...) decorator
        elif isinstance(cand, ast.Lambda):
            wrapped = cand
        elif isinstance(cand, ast.Name):
            for fn in ctx.functions():
                if getattr(fn, "name", None) == cand.id:
                    wrapped = fn
                    break
        elif cand is None and isinstance(parent, ast.Call) \
                and parent.func is node and parent.args \
                and isinstance(parent.args[0], ast.Name):
            # partial(shard_map, ...)(f) — rare; resolve f
            for fn in ctx.functions():
                if getattr(fn, "name", None) == parent.args[0].id:
                    wrapped = fn
                    break
        mesh_expr = _call_kw(node, "mesh")
        if mesh_expr is None and is_direct and pos:
            mesh_expr = pos[0]
            pos = pos[1:]
        in_specs = _call_kw(node, "in_specs")
        if in_specs is None and is_direct and pos:
            in_specs = pos[0]
            pos = pos[1:]
        out_specs = _call_kw(node, "out_specs")
        if out_specs is None and is_direct and pos:
            out_specs = pos[0]
        manual = _call_kw(node, "axis_names")
        manual_axes = None
        if manual is not None:
            got = consts.axis_strings(manual)
            if got is not None:
                manual_axes = frozenset(got)
        sites.append(_ShardMapSite(
            ctx, node, wrapped,
            resolver.resolve(ctx, mesh_expr, node),
            manual_axes, in_specs, out_specs))
    return sites


# --------------------------------------------------------- the analysis

@dataclasses.dataclass
class _Binding:
    """Axis-binding state of one function body."""
    axes: Set[str] = dataclasses.field(default_factory=set)
    has_binder: bool = False
    unknown: bool = False        # reached by a binder we cannot resolve

    def merge(self, other: "_Binding") -> bool:
        before = (len(self.axes), self.has_binder, self.unknown)
        self.axes |= other.axes
        self.has_binder |= other.has_binder
        self.unknown |= other.unknown
        return before != (len(self.axes), self.has_binder, self.unknown)


class _Analysis:
    """One whole-program sharding analysis over a module set."""

    def __init__(self, contexts: List[ModuleContext]):
        self.contexts = list(contexts)
        self.consts = _Consts(self.contexts)
        self.resolver = _MeshResolver(self.contexts, self.consts)
        self.findings: List[Finding] = []
        self.sites: Dict[str, List[_ShardMapSite]] = {}
        #: every axis any mesh/pmap/spec in the program declares — the
        #: fallback set for collectives in unwrapped library functions
        self.declared_axes: Set[str] = set()

    # ---------------------------------------------------------- helpers
    def _finding(self, rule: str, ctx: ModuleContext, node: ast.AST,
                 message: str) -> None:
        f = Finding(rule, ctx.path, getattr(node, "lineno", 1),
                    getattr(node, "col_offset", 0) + 1, message)
        if f not in self.findings:
            self.findings.append(f)

    def _spec_axes_in(self, node: Optional[ast.AST]
                      ) -> Iterator[Tuple[ast.Call, List[str]]]:
        """Every ``P(...)``/``PartitionSpec(...)`` call under ``node``
        with its constant-resolvable axis names."""
        if node is None:
            return
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            la = last_attr(sub.func)
            if la not in ("P", "PartitionSpec"):
                continue
            axes: List[str] = []
            for arg in sub.args:
                got = self.consts.axis_strings(arg)
                if got:
                    axes.extend(got)
            yield sub, axes

    # -------------------------------------------------------------- run
    def run(self) -> List[Finding]:
        for ctx in self.contexts:
            self.sites[ctx.path] = _shard_map_sites(
                ctx, self.resolver, self.consts)
        self._collect_declared_axes()
        bindings = self._infer_bindings()
        for ctx in self.contexts:
            self._check_unbound_axes(ctx, bindings)
            self._check_shard_map_sites(ctx)
            self._check_hot_steps(ctx)
            self._check_donation(ctx)
        return self._apply_marks()

    # ------------------------------------------------- declared axis set
    def _collect_declared_axes(self) -> None:
        for axes in self.resolver.factories.values():
            self.declared_axes |= axes
        for ctx in self.contexts:
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                got = _mesh_ctor_axes(node, self.consts)
                if got:
                    self.declared_axes.update(got)
                la = last_attr(node.func)
                if la == "pmap" or _is_partial_of(node, "pmap"):
                    axis = _call_kw(node, "axis_name")
                    got = self.consts.axis_strings(axis)
                    if got:
                        self.declared_axes.update(got)
            for site in self.sites[ctx.path]:
                if site.mesh_axes:
                    self.declared_axes |= site.mesh_axes
                if site.manual_axes:
                    self.declared_axes |= site.manual_axes
                for spec_expr in (site.in_specs, site.out_specs):
                    for _call, axes in self._spec_axes_in(spec_expr):
                        self.declared_axes.update(axes)

    # ------------------------------------------- rule 1: axis bindings
    def _infer_bindings(self) -> Dict[int, _Binding]:
        bindings: Dict[int, _Binding] = {}

        def bind(fn: Optional[ast.AST],
                 axes: Optional[FrozenSet[str]]) -> None:
            if fn is None:
                return
            b = bindings.setdefault(id(fn), _Binding())
            b.has_binder = True
            if axes is None:
                b.unknown = True
            else:
                b.axes |= axes

        for ctx in self.contexts:
            for site in self.sites[ctx.path]:
                bind(site.wrapped, site.bound_axes)
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                la = last_attr(node.func)
                if la == "pmap" or _is_partial_of(node, "pmap"):
                    axis = _call_kw(node, "axis_name")
                    got = self.consts.axis_strings(axis)
                    axes = frozenset(got) if got is not None else None
                    # pmap(fn, ...) call / @partial(pmap, ...) decorator
                    target: Optional[ast.AST] = None
                    parent = ctx.parent(node)
                    if isinstance(parent, _FuncDef) \
                            and node in parent.decorator_list:
                        target = parent
                    elif la == "pmap" and node.args:
                        cand = node.args[0]
                        if isinstance(cand, ast.Lambda):
                            target = cand
                        elif isinstance(cand, ast.Name):
                            for fn in ctx.functions():
                                if getattr(fn, "name", None) == cand.id:
                                    target = fn
                                    break
                    bind(target, axes)

        # interprocedural fixpoint: lexical nesting + same-file
        # bare-name / self.method calls flow the caller's binding in
        changed = True
        while changed:
            changed = False
            for ctx in self.contexts:
                by_name: Dict[str, List[ast.AST]] = {}
                for fn in ctx.functions():
                    if not isinstance(fn, ast.Lambda):
                        by_name.setdefault(fn.name, []).append(fn)
                for fn in ctx.functions():
                    src = bindings.get(id(fn))
                    if src is None or not src.has_binder:
                        continue
                    for node in ast.walk(fn):
                        if isinstance(node, _FuncNode) and node is not fn:
                            dst = bindings.setdefault(id(node),
                                                      _Binding())
                            if dst.merge(src):
                                changed = True
                        if isinstance(node, ast.Call):
                            callee = None
                            if isinstance(node.func, ast.Name):
                                callee = node.func.id
                            elif (isinstance(node.func, ast.Attribute)
                                  and isinstance(node.func.value,
                                                 ast.Name)
                                  and node.func.value.id == "self"):
                                callee = node.func.attr
                            for cand in by_name.get(callee or "", ()):
                                if cand is fn:
                                    continue
                                dst = bindings.setdefault(id(cand),
                                                          _Binding())
                                if dst.merge(src):
                                    changed = True
        return bindings

    def _collective_axis_args(self, call: ast.Call
                              ) -> Optional[List[str]]:
        la = last_attr(call.func)
        pos = _COLLECTIVES.get(la or "")
        if pos is None:
            return None
        axis = _call_kw(call, "axis_name")
        if axis is None and len(call.args) > pos:
            axis = call.args[pos]
        if axis is None:
            return None
        return self.consts.axis_strings(axis)

    def _check_unbound_axes(self, ctx: ModuleContext,
                            bindings: Dict[int, _Binding]) -> None:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            axes = self._collective_axis_args(node)
            if not axes:
                continue
            fn = ctx.enclosing_function(node)
            state = _Binding()
            cur = fn
            while cur is not None:
                b = bindings.get(id(cur))
                if b is not None:
                    state.merge(b)
                cur = ctx.enclosing_function(cur)
            if state.unknown:
                continue
            la = last_attr(node.func)
            for axis in axes:
                if state.has_binder and axis not in state.axes:
                    self._finding(
                        "unbound-axis-name", ctx, node,
                        f"`{la}` names axis '{axis}' but the enclosing "
                        f"shard_map/pmap binds only "
                        f"{sorted(state.axes) or '[]'} — a typo'd axis "
                        f"fails only at trace time (or silently no-ops "
                        f"on a 1-sized axis)")
                elif not state.has_binder \
                        and axis not in self.declared_axes:
                    self._finding(
                        "unbound-axis-name", ctx, node,
                        f"`{la}` names axis '{axis}' but no mesh, "
                        f"shard_map or pmap anywhere in the program "
                        f"declares that axis (declared: "
                        f"{sorted(self.declared_axes) or '[]'}) — "
                        f"likely a typo'd axis name")

    # ----------------------------------- rules 2+3: shard_map contracts
    def _check_shard_map_sites(self, ctx: ModuleContext) -> None:
        for site in self.sites[ctx.path]:
            self._check_spec_mesh(site)
            self._check_out_spec_replication(site)

    def _check_spec_mesh(self, site: _ShardMapSite) -> None:
        mesh_axes = site.mesh_axes
        if mesh_axes is not None:
            for spec_expr in (site.in_specs, site.out_specs):
                for call, axes in self._spec_axes_in(spec_expr):
                    for axis in axes:
                        if axis not in mesh_axes:
                            self._finding(
                                "spec-mesh-mismatch", site.ctx, call,
                                f"P(...) names axis '{axis}' which the "
                                f"mesh in scope does not have (mesh "
                                f"axes: {sorted(mesh_axes)}) — this "
                                f"spec cannot commit and the value "
                                f"falls back to replication")
        # arity: literal in_specs tuple vs the wrapped fn's signature
        fn = site.wrapped
        if fn is None or isinstance(fn, ast.Lambda) \
                or not isinstance(site.in_specs, (ast.Tuple, ast.List)):
            return
        args = fn.args
        if args.vararg is not None or args.kwarg is not None:
            return
        params = [a.arg for a in
                  list(args.posonlyargs) + list(args.args)
                  if a.arg not in ("self", "cls")]
        total = len(params)
        required = total - len(args.defaults)
        n = len(site.in_specs.elts)
        if n < required or n > total:
            self._finding(
                "spec-mesh-mismatch", site.ctx, site.in_specs,
                f"in_specs has {n} entr{'y' if n == 1 else 'ies'} but "
                f"`{site.ctx.func_name(fn)}` takes "
                f"{total if total == required else f'{required}..{total}'}"
                f" positional argument(s) — the zip misaligns specs "
                f"and operands")

    def _sharded_param_names(self, site: _ShardMapSite) -> Set[str]:
        """Wrapped-fn params whose in_spec is (or may be) sharded."""
        fn = site.wrapped
        if fn is None:
            return set()
        args = fn.args
        params = [a.arg for a in
                  list(args.posonlyargs) + list(args.args)
                  if a.arg not in ("self", "cls")]
        if not isinstance(site.in_specs, (ast.Tuple, ast.List)):
            # unknown spec shape: assume every param may be sharded
            return set(params)
        sharded: Set[str] = set()
        for param, elt in zip(params, site.in_specs.elts):
            if self._spec_is_replicated(elt):
                continue
            sharded.add(param)
        return sharded

    def _spec_is_replicated(self, elt: ast.AST) -> bool:
        """True only for a *provably* replicated spec element: ``P()``
        / ``P(None, ...)`` with no axis names."""
        if isinstance(elt, ast.Call) \
                and last_attr(elt.func) in ("P", "PartitionSpec"):
            return all(isinstance(a, ast.Constant) and a.value is None
                       for a in elt.args)
        if isinstance(elt, ast.Constant) and elt.value is None:
            return True
        return False

    def _check_out_spec_replication(self, site: _ShardMapSite) -> None:
        fn = site.wrapped
        if fn is None or isinstance(fn, ast.Lambda) \
                or site.out_specs is None:
            return
        sharded = self._sharded_param_names(site)
        if not sharded:
            return
        tainted = self._shard_taint(fn, sharded)
        returns = [n for n in ast.walk(fn) if isinstance(n, ast.Return)
                   and n.value is not None]
        if not returns:
            return

        def element_checks(out_elt: ast.AST, ret_expr: ast.AST) -> None:
            if not self._spec_is_replicated(out_elt):
                return
            if self._contains_reduction(ret_expr):
                return
            if self._divergent_expr(ret_expr, tainted):
                self._finding(
                    "unreplicated-out-spec", site.ctx, out_elt,
                    f"out_spec claims replication (P()) but "
                    f"`{site.ctx.func_name(fn)}` returns a value "
                    f"derived from sharded inputs with no "
                    f"psum/all_gather on the return path — each shard "
                    f"returns a DIFFERENT value; jax's "
                    f"check_vma rejects this at trace time "
                    f"(see docs/graftlint.md)")

        for ret in returns:
            out = site.out_specs
            if isinstance(out, (ast.Tuple, ast.List)) \
                    and isinstance(ret.value, (ast.Tuple, ast.List)) \
                    and len(out.elts) == len(ret.value.elts):
                for out_elt, ret_elt in zip(out.elts, ret.value.elts):
                    element_checks(out_elt, ret_elt)
            else:
                element_checks(out, ret.value)

    def _contains_reduction(self, expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) \
                    and last_attr(node.func) in _REDUCING:
                return True
        return False

    def _divergent_expr(self, expr: Optional[ast.AST],
                        tainted: Set[str]) -> bool:
        """Does ``expr`` carry shard-divergent data derived from
        ``tainted`` names?  Reducing collectives sanitize (a psum'd
        value is shard-uniform again), and so does any call we cannot
        see into (``pipeline_fn(...)``, a helper from another module —
        it may reduce internally; flagging through it would make every
        composed pipeline a false positive).  Element-wise jnp/lax/np
        math and method calls (``x.sum()`` is a LOCAL reduce — still
        per-shard) propagate."""
        if expr is None or not isinstance(expr, ast.AST):
            return False
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        if isinstance(expr, ast.Call):
            la = last_attr(expr.func)
            if la in _REDUCING:
                return False
            d = dotted_name(expr.func) or ""
            root = d.split(".", 1)[0]
            operands = (list(expr.args)
                        + [k.value for k in expr.keywords])
            if la in _PERMUTING:
                # a permute moves shard-divergent data between shards
                # — the output is exactly as divergent as the input,
                # whichever spelling (bare `ppermute(...)` included:
                # without this branch it would fall through to the
                # unknown-callee sanitizer below)
                return any(self._divergent_expr(a, tainted)
                           for a in operands)
            if root in ("jnp", "lax", "np", "jax", "numpy"):
                return any(self._divergent_expr(a, tainted)
                           for a in operands)
            if isinstance(expr.func, ast.Attribute):
                # x.sum() / x.reshape(...) — a method of the operand
                return self._divergent_expr(expr.func.value, tainted) \
                    or any(self._divergent_expr(a, tainted)
                           for a in operands)
            return False          # unknown callee: may reduce inside
        return any(self._divergent_expr(c, tainted)
                   for c in ast.iter_child_nodes(expr)
                   if isinstance(c, ast.AST))

    def _shard_taint(self, fn: ast.AST, seeds: Set[str]) -> Set[str]:
        """Names derived (visibly) from sharded params."""
        tainted = set(seeds)
        for _ in range(2):        # two passes ≈ fixpoint, like core
            for node in ast.walk(fn):
                targets: List[ast.AST] = []
                value: Optional[ast.AST] = None
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, ast.AugAssign):
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.NamedExpr):
                    targets, value = [node.target], node.value
                if value is not None \
                        and self._divergent_expr(value, tainted):
                    for t in targets:
                        for name in self._target_names(t):
                            tainted.add(name)
        return tainted

    @staticmethod
    def _target_names(target: ast.AST) -> Iterator[str]:
        if isinstance(target, ast.Name):
            yield target.id
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                yield from _Analysis._target_names(elt)
        elif isinstance(target, ast.Starred):
            yield from _Analysis._target_names(target.value)

    # -------------------------------------- rule 4: host-sync-in-step
    def _jit_map(self, ctx: ModuleContext
                 ) -> Dict[str, Tuple[int, ...]]:
        """``name``/``self.attr`` → donated positions for every
        assignment of a jit/retrace_guard-wrapped callable (donation
        tuple empty when none declared).  Shared by rules 4 and 5."""
        out: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                donate = self._donated_positions(node.value)
                if donate is None:
                    continue
                for t in node.targets:
                    key = _key(t)
                    if key:
                        out[key] = donate
            elif isinstance(node, _FuncDef):
                # @jax.jit / @partial(jax.jit, donate_argnums=...) defs
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        donate = self._donated_positions(dec)
                        if donate is not None:
                            out[node.name] = donate
                    elif last_attr(dec) in ("jit", "pjit"):
                        out.setdefault(node.name, ())
        return out

    def _donated_positions(self, call: ast.Call
                           ) -> Optional[Tuple[int, ...]]:
        """() for a jit-family call without donation; (i, ...) with;
        None when the call is not jit-like at all."""
        la = last_attr(call.func)
        is_jit = la in ("jit", "pjit", "retrace_guard") \
            or _is_partial_of(call, "jit") or _is_partial_of(call, "pjit")
        if not is_jit:
            return None
        donate = _call_kw(call, "donate_argnums")
        if donate is None:
            return ()
        if isinstance(donate, ast.Constant) \
                and isinstance(donate.value, int):
            return (donate.value,)
        if isinstance(donate, (ast.Tuple, ast.List)):
            out = []
            for elt in donate.elts:
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, int):
                    out.append(elt.value)
                else:
                    return ()
            return tuple(out)
        return ()

    def _check_hot_steps(self, ctx: ModuleContext) -> None:
        jit_map = self._jit_map(ctx)
        for fn in ctx.functions():
            if isinstance(fn, ast.Lambda):
                continue
            if not any(m == "hot-step" for m, _ in
                       _marks_for_line(ctx, fn.lineno)):
                continue
            self._check_hot_step_body(ctx, fn, jit_map)

    def _check_hot_step_body(self, ctx: ModuleContext, fn: ast.AST,
                             jit_map: Dict[str, Tuple[int, ...]]
                             ) -> None:
        # device-derived values: results of calls to jit-wrapped
        # callables (incl. self._step attrs) and jnp/jax ops, flowed
        # forward through assignments
        tainted: Set[str] = set()

        def sync_kind(node: ast.Call) -> Optional[str]:
            d = dotted_name(node.func) or ""
            la = last_attr(node.func)
            if d in ("np.asarray", "numpy.asarray", "np.array",
                     "numpy.array"):
                return d
            if d in ("jax.device_get", "device_get"):
                return "jax.device_get"
            if isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool"):
                return f"{node.func.id}()"
            if la == "item":
                return ".item()"
            if la == "callback" and "debug" in d:
                return d
            return None

        def device_expr(expr: ast.AST) -> bool:
            if isinstance(expr, ast.Call):
                if sync_kind(expr) is not None:
                    return False  # the sync materializes a host value
                key = _key(expr.func)
                if key is not None and key in jit_map:
                    return True
                d = dotted_name(expr.func)
                if d and (d.startswith("jnp.") or d.startswith("jax.")
                          or d.startswith("lax.")):
                    return True
            if isinstance(expr, (ast.Name, ast.Attribute)):
                key = _key(expr)
                return key in tainted
            return any(device_expr(c) for c in ast.iter_child_nodes(expr)
                       if isinstance(c, ast.AST))

        checked: Set[int] = set()

        def check_sync(call: ast.Call) -> None:
            if id(call) in checked:
                return
            checked.add(id(call))
            sync = sync_kind(call)
            if sync is None:
                return
            args = list(call.args) + [k.value for k in call.keywords]
            if last_attr(call.func) == "item":
                args.append(call.func.value)
            if not any(device_expr(a) for a in args):
                return
            self._finding(
                "host-sync-in-step", ctx, call,
                f"`{sync}` on a device value inside "
                f"`{ctx.func_name(fn)}` (# graftlint: hot-step) forces "
                f"a device→host sync every step — batch the read, keep "
                f"it on device, or justify it with `# graftlint: "
                f"unsharded(<why>)`")

        own = [n for n in ast.walk(fn)
               if ctx.enclosing_function(n) is fn
               or n is fn]
        # forward taint over the fn's own statements (nested defs are
        # traced callees, checked by host-sync-in-trace instead)
        for node in sorted(own, key=lambda n: (getattr(n, "lineno", 0),
                                               getattr(n, "col_offset",
                                                       0))):
            if isinstance(node, ast.Assign):
                # the RHS evaluates before the targets rebind: check
                # its syncs against the pre-assignment taint, THEN let
                # a host-valued RHS (e.g. a device_get) clear the
                # targets and a device RHS taint them
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Call):
                        check_sync(sub)
                is_dev = device_expr(node.value)
                for t in node.targets:
                    keys = [k for k in [_key(t)] if k]
                    keys.extend(self._target_names(t))
                    for key in keys:
                        (tainted.add if is_dev
                         else tainted.discard)(key)
            elif isinstance(node, ast.Call):
                check_sync(node)

    # ------------------------------------ rule 5: donation-after-use
    def _check_donation(self, ctx: ModuleContext) -> None:
        jit_map = {k: v for k, v in self._jit_map(ctx).items() if v}
        for fn in ctx.functions():
            if isinstance(fn, ast.Lambda):
                continue
            own = [n for n in ast.walk(fn)
                   if ctx.enclosing_function(n) is fn]
            for node in own:
                if not isinstance(node, ast.Call):
                    continue
                donate: Optional[Tuple[int, ...]] = None
                key = _key(node.func)
                if key is not None and key in jit_map:
                    donate = jit_map[key]
                elif isinstance(node.func, ast.Call):
                    donate = self._donated_positions(node.func) or None
                if not donate:
                    continue
                self._check_donated_call(ctx, fn, node, donate, own)

    def _check_donated_call(self, ctx: ModuleContext, fn: ast.AST,
                            call: ast.Call, donate: Tuple[int, ...],
                            own: List[ast.AST]) -> None:
        line = getattr(call, "lineno", 0)
        # keys rebound by the very statement holding the call (the
        # `state = step(state, ...)` idiom) are fresh afterwards
        rebound: Set[str] = set()
        stmt = ctx.parent(call)
        while stmt is not None and not isinstance(stmt, ast.stmt):
            stmt = ctx.parent(stmt)
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                k = _key(t)
                if k:
                    rebound.add(k)
                rebound.update(self._target_names(t))
        # the call's own argument list can span lines — those reads
        # happen BEFORE the donation, never after it
        in_call = {id(n) for n in ast.walk(call)}
        for pos in donate:
            if pos >= len(call.args):
                continue
            key = _key(call.args[pos])
            if key is None or key in rebound:
                continue
            # first later touch wins: a Store clears, a Load flags
            events: List[Tuple[int, int, str, ast.AST]] = []
            for node in own:
                if id(node) in in_call or _key(node) != key:
                    continue
                nline = getattr(node, "lineno", 0)
                if nline <= line:
                    continue
                kind = "store" if isinstance(
                    getattr(node, "ctx", None),
                    (ast.Store, ast.Del)) else "load"
                events.append((nline, getattr(node, "col_offset", 0),
                               kind, node))
            for nline, _col, kind, node in sorted(
                    events, key=lambda e: (e[0], e[1])):
                if kind == "store":
                    break
                self._finding(
                    "donation-after-use", ctx, node,
                    f"`{key}` was donated (donate_argnums position "
                    f"{pos}) to the call on line {line} — its buffer "
                    f"is dead here; reading it returns garbage or "
                    f"raises on TPU.  Rebind it from the call's "
                    f"output or drop the donation")
                break

    # ------------------------------------------------- mark application
    def _apply_marks(self) -> List[Finding]:
        out: List[Finding] = []
        by_path = {ctx.path: ctx for ctx in self.contexts}
        for f in self.findings:
            ctx = by_path.get(f.path)
            if ctx is None:
                out.append(f)
                continue
            marks = [why for mark, why in _marks_for_line(ctx, f.line)
                     if mark == "unsharded"]
            if not marks:
                out.append(f)
            elif any(why for why in marks):
                continue                    # justified exception
            else:
                out.append(Finding(
                    f.rule, f.path, f.line, f.col,
                    f"marked unsharded() with no justification — the "
                    f"reason is the point of the annotation; say why "
                    f"this placement/sync is deliberate"))
        return out


def analyze_program(contexts: List[ModuleContext]) -> List[Finding]:
    """Run the sharding analysis; returns every finding (all five
    rules) unfiltered — the runner applies suppressions."""
    return _Analysis(list(contexts)).run()


# --------------------------------------------------------- program rules

class _ShardingRule(ProgramRule):
    """Shared driver: the analysis runs once per program (memoized on
    the Program object by :meth:`prepare`, timed under the
    ``sharding-pass`` row); each registered rule yields its slice."""

    shared_pass = "sharding-pass"

    def prepare(self, program) -> None:
        if getattr(program, "_sharding_findings", None) is None:
            program._sharding_findings = analyze_program(
                program.contexts)

    def check_program(self, program) -> Iterator[Finding]:
        self.prepare(program)
        for finding in program._sharding_findings:
            if finding.rule == self.name:
                yield finding


@register_program
class UnboundAxisName(_ShardingRule):
    """Rule S1 — a collective naming an axis nothing binds.

    ``psum``/``all_gather``/``all_to_all``/``ppermute``/``axis_index``
    (etc.) naming an axis the enclosing shard_map/pmap does not bind —
    or, for unwrapped library functions, an axis no mesh anywhere in
    the program declares.  The typo class that today fails only at
    trace time, or silently no-ops on a 1-sized axis.
    """

    name = "unbound-axis-name"
    summary = ("collective names an axis no enclosing shard_map/pmap "
               "binds (or no mesh in the program declares)")


@register_program
class SpecMeshMismatch(_ShardingRule):
    """Rule S2 — PartitionSpec axes absent from the mesh in scope, or
    in_specs arity misaligned with the wrapped function's signature.

    A ``P("tenosr")`` against a ``("data", "tensor")`` mesh cannot
    commit — the value silently falls back to replication; a spec
    tuple shorter/longer than the operand list zips wrong.
    """

    name = "spec-mesh-mismatch"
    summary = ("P(...) axis not in the mesh in scope, or "
               "in_specs/out_specs arity vs the wrapped signature")


@register_program
class UnreplicatedOutSpec(_ShardingRule):
    """Rule S3 — out_spec claims replication for a shard-divergent
    value.

    ``out_specs=P()`` asserts every shard returns the SAME value; a
    return derived from sharded inputs with no psum/all_gather on the
    path violates that — the shape ``check_vma`` rejects at trace
    time.
    """

    name = "unreplicated-out-spec"
    summary = ("out_specs=P() on a value computed from sharded inputs "
               "with no reduction on the return path")


@register_program
class HostSyncInStep(_ShardingRule):
    """Rule S4 — device→host sync inside a ``hot-step`` function.

    ``np.asarray``/``float()``/``.item()``/``jax.device_get``/debug
    callbacks on device values inside a function marked ``# graftlint:
    hot-step`` (engine decode steps, train steps, bench legs) force a
    per-step sync; deliberate end-of-step reads carry ``# graftlint:
    unsharded(<why>)``.
    """

    name = "host-sync-in-step"
    summary = ("device->host sync on a device value inside a "
               "# graftlint: hot-step function")


@register_program
class DonationAfterUse(_ShardingRule):
    """Rule S5 — a donated buffer read after the donating call.

    An argument at a ``donate_argnums`` position is dead once the call
    returns: XLA may have aliased its buffer into the outputs.  A later
    read in the same scope (without rebinding from the call's result)
    returns garbage on TPU.
    """

    name = "donation-after-use"
    summary = ("buffer passed under donate_argnums read after the "
               "donating call in the same scope")
