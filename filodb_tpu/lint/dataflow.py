"""Device-dataflow layer over the project call graph (graftlint v3).

The PR 7 engine (``callgraph.py``) answers "who calls whom, holding
which locks". This layer adds the *value*-level facts the SPMD and
cache families need, still as pure AST work:

  * **Entry points** — every ``jax.jit`` / ``pjit`` / ``shard_map`` /
    ``pallas_call`` wrapping site in the project (decorator form,
    ``functools.partial`` form, and direct-call form), with its parsed
    mesh axes, ``in_specs``/``out_specs`` PartitionSpecs, static
    argument names, and ``donate_argnums``/``donate_argnames``.
  * **Per-site closure** — the functions reachable from each entry
    point's body over call/callback edges: the code that actually runs
    under that trace, across modules.
  * **Static-ness propagation** — which parameters of closure functions
    are trace-static (bound from ``static_argnames``, constants, or
    other static names, including through lexical nesting): Python
    control flow on a static value is uniform across devices; control
    flow on anything else is where collectives go to deadlock.
  * **Donation bindings** — which local/module/attribute names hold a
    donating jitted callable, and the argument expressions at each of
    its call sites (the donation-safety rule's input).
  * **Listener bridges** — classes that collect callbacks
    (``subscribe``/``add_*_listener`` registrars appending a function
    parameter to instance state) and later dispatch them (iterating the
    same container and calling the elements). The AST cannot resolve
    ``for cb in self._subscribers: cb(ev)``; the bridge pairs each
    dispatcher with the callbacks registered at project call sites of
    the matching registrar, giving ``reaches()`` the edge an event
    needs to travel from a mutation publisher through a subscription to
    a cache's invalidation hook.

Everything is derived from the shared :class:`~filodb_tpu.lint.
callgraph.CallGraph`; nothing is imported or executed.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import (Dict, FrozenSet, List, Optional, Sequence, Set,
                    Tuple)

from filodb_tpu.lint import ModuleSource
from filodb_tpu.lint import callgraph as cgmod

# collective primitives that synchronize across a named mesh axis: every
from filodb_tpu.lint.astwalk import walk_nodes
# participant must execute the same sequence or the program deadlocks
# (multi-host) or silently computes over a partial group
COLLECTIVE_LEAVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter", "pbroadcast", "pdot",
})

# host-identity reads: Python control flow on these is *guaranteed* to
# diverge across processes in a multi-controller deployment
_HOST_DIVERGENT_LEAVES = frozenset({
    "process_index", "host_id", "gethostname", "getpid", "urandom",
    "random", "randint", "choice",
})

_STRUCTURED_CONTROL = frozenset({"cond", "switch", "while_loop"})

_SPMD_WRAPPERS = ("jit", "pjit", "shard_map", "pallas_call", "pmap")


def _dotted(node) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _leaf(node) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _wrapper_kind(fn_expr) -> Optional[str]:
    """'jit' / 'shard_map' / 'pallas_call' when the expression names a
    tracing wrapper, else None."""
    d = _dotted(fn_expr) or ""
    leaf = d.rsplit(".", 1)[-1]
    if "shard_map" in leaf:
        return "shard_map"
    if leaf in ("jit", "pjit"):
        return "jit"
    if leaf == "pallas_call":
        return "pallas_call"
    if leaf == "pmap":
        return "shard_map"      # same balance semantics: mapped axis
    return None


# -- PartitionSpec parsing ----------------------------------------------------

@dataclass
class SpecInfo:
    """One parsed ``P(...)`` / ``None`` spec literal."""
    axes: Tuple[str, ...] = ()      # axis names mentioned
    arity: Optional[int] = None     # positional entries declared
    known: bool = False
    line: int = 0
    bad_entries: Tuple[str, ...] = ()   # non-str/int/None constants
    # POSITIONAL axis indices (jax positional-PartitionSpec semantics:
    # n = n-th mesh axis name, a single -1 = every axis not otherwise
    # mentioned) — resolved against the site's mesh axis ORDER
    pos_entries: Tuple[int, ...] = ()


def resolve_positional(spec: "SpecInfo",
                       order: Optional[Tuple[str, ...]]
                       ) -> Tuple[Tuple[str, ...], List[str]]:
    """(resolved axis names, problems) of a spec's positional entries
    against an ordered mesh-axis tuple. With no order known, nothing
    resolves and nothing is flagged; the -1-repeated and
    out-of-range error cases are flagged here because the installed
    jax silently drops a positional 0 (``P(0)`` -> replicated)."""
    problems: List[str] = []
    if sum(1 for i in spec.pos_entries if i == -1) > 1:
        problems.append("-1 appears more than once in one PartitionSpec")
    if order is None:
        return (), problems
    names: List[str] = []
    mentioned = set(spec.axes)
    for i in spec.pos_entries:
        if i != -1:
            if not -len(order) <= i < len(order):
                problems.append(
                    f"positional index {i} out of range for mesh axes "
                    f"{order}")
            else:
                mentioned.add(order[i])
    for i in spec.pos_entries:
        if i == -1:
            names.extend(n for n in order if n not in mentioned)
        elif -len(order) <= i < len(order):
            names.append(order[i])
    return tuple(names), problems


def parse_spec(expr) -> SpecInfo:
    line = getattr(expr, "lineno", 0)
    if isinstance(expr, ast.Constant) and expr.value is None:
        return SpecInfo(axes=(), arity=0, known=True, line=line)
    if isinstance(expr, ast.Call):
        leaf = _leaf(expr.func)
        if leaf in ("P", "PartitionSpec"):
            axes: List[str] = []
            bad: List[str] = []
            pos: List[int] = []

            def harvest(el) -> None:
                if isinstance(el, ast.UnaryOp) \
                        and isinstance(el.op, ast.USub) \
                        and isinstance(el.operand, ast.Constant) \
                        and isinstance(el.operand.value, int) \
                        and not isinstance(el.operand.value, bool):
                    pos.append(-el.operand.value)   # e.g. the -1 form
                    return
                if not isinstance(el, ast.Constant):
                    return      # Name/expr entries: unknown, still a P
                v = el.value
                if isinstance(v, str):
                    axes.append(v)
                elif isinstance(v, bool):
                    bad.append(repr(v))
                elif isinstance(v, int):
                    pos.append(v)
                elif v is not None:
                    bad.append(repr(v))

            for a in expr.args:
                if isinstance(a, (ast.Tuple, ast.List)):
                    for el in a.elts:
                        harvest(el)
                else:
                    harvest(a)
            return SpecInfo(axes=tuple(axes), arity=len(expr.args),
                            known=True, line=line,
                            bad_entries=tuple(bad),
                            pos_entries=tuple(pos))
    return SpecInfo(line=line)


def parse_specs_arg(expr) -> Tuple[Optional[List[SpecInfo]], List[SpecInfo]]:
    """Parse an ``in_specs``/``out_specs`` kwarg. Returns
    ``(spec_list, all_specs)`` — ``spec_list`` is positional (one entry
    per argument) when the literal is a tuple/list, else None;
    ``all_specs`` is every P literal found (axis harvesting)."""
    if expr is None:
        return None, []
    if isinstance(expr, (ast.Tuple, ast.List)):
        specs = [parse_spec(e) for e in expr.elts]
        return specs, specs
    s = parse_spec(expr)
    return None, [s]


# -- mesh axis resolution -----------------------------------------------------

def _mesh_axes_of_call(call: ast.Call) -> Optional[Tuple[str, ...]]:
    """Axis names of a ``Mesh(devs, ("a", "b"))`` construction."""
    if _leaf(call.func) != "Mesh":
        return None
    cand = None
    if len(call.args) >= 2:
        cand = call.args[1]
    for kw in call.keywords:
        if kw.arg == "axis_names":
            cand = kw.value
    if isinstance(cand, (ast.Tuple, ast.List)):
        axes = [e.value for e in cand.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
        if axes:
            return tuple(axes)
    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
        return (cand.value,)
    return None


class MeshIndex:
    """Mesh constructions per module: variable bindings, mesh-returning
    functions, and the module/project axis universes."""

    def __init__(self, mods: Sequence[ModuleSource]):
        # module -> var name -> axes
        self.vars: Dict[str, Dict[str, Tuple[str, ...]]] = {}
        # module -> function name -> axes (functions returning Mesh(...))
        self.makers: Dict[str, Dict[str, Tuple[str, ...]]] = {}
        self.module_axes: Dict[str, Set[str]] = {}
        self.project_axes: Set[str] = set()
        # module -> distinct ORDERED axis tuples of its Mesh literals:
        # when a module declares exactly one order, positional
        # PartitionSpec indices resolve against it
        self.module_orders: Dict[str, Set[Tuple[str, ...]]] = {}
        self.project_orders: Set[Tuple[str, ...]] = set()
        for mod in mods:
            dotted = cgmod.module_dotted(mod.relpath)
            mvars: Dict[str, Tuple[str, ...]] = {}
            makers: Dict[str, Tuple[str, ...]] = {}
            axes_here: Set[str] = set()
            orders_here: Set[Tuple[str, ...]] = set()
            for node in walk_nodes(mod.tree):
                if isinstance(node, ast.Call):
                    axes = _mesh_axes_of_call(node)
                    if axes:
                        axes_here.update(axes)
                        orders_here.add(axes)
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call):
                    axes = _mesh_axes_of_call(node.value)
                    if axes:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                mvars[t.id] = axes
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    for sub in walk_nodes(node):
                        if isinstance(sub, ast.Return) and \
                                isinstance(sub.value, ast.Call):
                            axes = _mesh_axes_of_call(sub.value)
                            if axes:
                                makers[node.name] = axes
            self.vars[dotted] = mvars
            self.makers[dotted] = makers
            self.module_axes[dotted] = axes_here
            self.project_axes |= axes_here
            self.module_orders[dotted] = orders_here
            self.project_orders |= orders_here

    def axis_order(self, module: str) -> Optional[Tuple[str, ...]]:
        """The unambiguous ordered axis tuple positional PartitionSpec
        indices resolve against: the module's single declared order,
        falling back to the project's single order, else None."""
        orders = self.module_orders.get(module) or set()
        if len(orders) == 1:
            return next(iter(orders))
        if not orders and len(self.project_orders) == 1:
            return next(iter(self.project_orders))
        return None

    def resolve(self, module: str, expr,
                local_assigns: Dict[str, ast.AST]) -> Optional[Tuple[str, ...]]:
        """Axes of a ``mesh=`` expression, best effort."""
        if isinstance(expr, ast.Call):
            axes = _mesh_axes_of_call(expr)
            if axes:
                return axes
            leaf = _leaf(expr.func)
            if leaf and leaf in self.makers.get(module, {}):
                return self.makers[module][leaf]
            for mk in self.makers.values():
                if leaf in mk:
                    return mk[leaf]
        if isinstance(expr, ast.Name):
            src = local_assigns.get(expr.id)
            if src is not None and src is not expr:
                return self.resolve(module, src, {})
            axes = self.vars.get(module, {}).get(expr.id)
            if axes:
                return axes
        return None


# -- SPMD entry points --------------------------------------------------------

@dataclass
class SpmdSite:
    """One jit/shard_map/pallas_call wrapping site."""
    kind: str                       # jit | shard_map | pallas_call
    module: str
    relpath: str
    line: int
    body_keys: Tuple[str, ...]      # FuncInfo keys of the wrapped body
    body_param_count: Optional[int] = None
    static_names: FrozenSet[str] = frozenset()
    donate_nums: Tuple[int, ...] = ()
    donate_names: Tuple[str, ...] = ()
    mesh_axes: Optional[Tuple[str, ...]] = None
    in_specs: Optional[List[SpecInfo]] = None       # positional list
    out_specs: Optional[List[SpecInfo]] = None
    all_specs: List[SpecInfo] = field(default_factory=list)
    out_specs_is_tuple: bool = False
    binding: Optional[str] = None   # name the wrapped callable binds to


def _static_names_from_kwargs(keywords) -> Set[str]:
    out: Set[str] = set()
    for kw in keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                out |= {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return out


def _donate_from_kwargs(keywords) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    nums: List[int] = []
    names: List[str] = []
    for kw in keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums += [e.value for e in v.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, int)]
        elif kw.arg == "donate_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                names += [e.value for e in v.elts
                          if isinstance(e, ast.Constant)
                          and isinstance(e.value, str)]
    return tuple(nums), tuple(names)


class DeviceDataflow:
    """SPMD entry points + per-function trace environments + donation
    bindings + listener bridges, over one CallGraph."""

    def __init__(self, mods: Sequence[ModuleSource], cg: cgmod.CallGraph):
        self.mods = list(mods)
        self.cg = cg
        self.mesh = MeshIndex(mods)
        self.sites: List[SpmdSite] = []
        # func key -> merged axis env over every site reaching it
        self.axes_env: Dict[str, Set[str]] = {}
        # func key -> True when reachable from at least one collective-
        # bearing (shard_map/pmap) context
        self.spmd_reachable: Set[str] = set()
        # func key -> True when reachable from any trace entry at all
        self.traced: Set[str] = set()
        # func key -> param name -> "static" | "dynamic" (absent=unknown)
        self.param_status: Dict[str, Dict[str, str]] = {}
        # (module, "name") or (module, "Cls.attr") -> donating SpmdSite
        self.donation_bindings: Dict[Tuple[str, str], SpmdSite] = {}
        self._funcinfo_by_node: Dict[int, cgmod.FuncInfo] = {
            id(fi.node): fi for fi in cg.funcs.values()}
        self._lambda_by_line: Dict[Tuple[str, int], str] = {}
        for key, fi in cg.funcs.items():
            if fi.name == "<lambda>":
                self._lambda_by_line.setdefault(
                    (fi.module, fi.lineno), key)
        # func key -> directly nested (lexical) function keys
        self._lexical_children: Dict[str, List[str]] = {}
        for key, fi in cg.funcs.items():
            if ".<locals>." in fi.qualname:
                pq = fi.qualname.rsplit(".<locals>.", 1)[0]
                self._lexical_children.setdefault(
                    f"{fi.module}:{pq}", []).append(key)
        self._discover_sites()
        self._compute_closures()
        self._propagate_static()
        self._build_bridges()

    # -- site discovery -----------------------------------------------------

    def _body_keys_for(self, mod_dotted: str, expr,
                       enclosing: Optional[cgmod.FuncInfo]) -> Tuple[str, ...]:
        """Resolve the wrapped-callable expression to FuncInfo keys."""
        if isinstance(expr, ast.Lambda):
            k = self._lambda_by_line.get((mod_dotted, expr.lineno))
            return (k,) if k else ()
        if isinstance(expr, ast.Call):
            # functools.partial(f, ...) — unwrap
            d = _dotted(expr.func) or ""
            if d.rsplit(".", 1)[-1] == "partial" and expr.args:
                return self._body_keys_for(mod_dotted, expr.args[0],
                                           enclosing)
            return ()
        name = _leaf(expr)
        if name is None:
            return ()
        keys = [k for k, fi in self.cg.funcs.items()
                if fi.module == mod_dotted and fi.name == name]
        if len(keys) > 1 and enclosing is not None:
            near = [k for k in keys
                    if self.cg.funcs[k].qualname.startswith(
                        enclosing.qualname)]
            if near:
                return tuple(near)
        return tuple(keys)

    def _discover_sites(self) -> None:
        for mod in self.mods:
            dotted = cgmod.module_dotted(mod.relpath)
            # local Name -> assigned value expr, for mesh resolution
            assigns: Dict[str, ast.AST] = {}
            for node in walk_nodes(mod.tree):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            assigns.setdefault(t.id, node.value)
            for node in walk_nodes(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    self._sites_from_decorators(mod, dotted, node, assigns)
                elif isinstance(node, ast.Call):
                    self._site_from_call(mod, dotted, node, assigns)

    def _sites_from_decorators(self, mod, dotted, node, assigns) -> None:
        fi = self._funcinfo_by_node.get(id(node))
        if fi is None:
            return
        for d in node.decorator_list:
            call = d if isinstance(d, ast.Call) else None
            target = call.func if call else d
            kind = _wrapper_kind(target)
            keywords = list(call.keywords) if call else []
            if kind is None and call is not None:
                # functools.partial(jax.jit, ...) decorator form
                dname = _dotted(call.func) or ""
                if dname.rsplit(".", 1)[-1] == "partial" and call.args:
                    kind = _wrapper_kind(call.args[0])
            if kind is None:
                continue
            self._add_site(mod, dotted, kind, getattr(d, "lineno",
                                                      node.lineno),
                           (fi.key,), keywords, assigns,
                           binding=node.name,
                           param_count=len(node.args.args)
                           + len(node.args.posonlyargs))

    def _site_from_call(self, mod, dotted, node: ast.Call, assigns) -> None:
        kind = _wrapper_kind(node.func)
        if kind is None or not node.args:
            return
        enclosing = self._enclosing_func(mod, node)
        body = self._body_keys_for(dotted, node.args[0], enclosing)
        binding = None
        param_count = None
        if body:
            bfi = self.cg.funcs.get(body[0])
            if bfi is not None and not isinstance(bfi.node, ast.Lambda):
                param_count = len(bfi.node.args.args) \
                    + len(bfi.node.args.posonlyargs)
            elif bfi is not None:
                param_count = len(bfi.node.args.args)
        self._add_site(mod, dotted, kind, node.lineno, body,
                       list(node.keywords), assigns, binding=binding,
                       param_count=param_count)

    def _enclosing_func(self, mod, node) -> Optional[cgmod.FuncInfo]:
        """The innermost FunctionDef lexically containing ``node`` (by
        line span, best effort)."""
        best = None
        line = getattr(node, "lineno", 0)
        for fi in self.cg.funcs.values():
            if fi.relpath != mod.relpath:
                continue
            end = getattr(fi.node, "end_lineno", fi.lineno)
            if fi.lineno <= line <= end:
                if best is None or fi.lineno > best.lineno:
                    best = fi
        return best

    def _add_site(self, mod, dotted, kind, line, body_keys, keywords,
                  assigns, binding=None, param_count=None) -> None:
        in_specs_expr = out_specs_expr = mesh_expr = None
        for kw in keywords:
            if kw.arg == "in_specs":
                in_specs_expr = kw.value
            elif kw.arg == "out_specs":
                out_specs_expr = kw.value
            elif kw.arg == "mesh":
                mesh_expr = kw.value
        in_list, in_all = parse_specs_arg(in_specs_expr)
        out_list, out_all = parse_specs_arg(out_specs_expr)
        nums, names = _donate_from_kwargs(keywords)
        site = SpmdSite(
            kind=kind, module=dotted, relpath=mod.relpath, line=line,
            body_keys=tuple(k for k in body_keys if k),
            body_param_count=param_count,
            static_names=frozenset(_static_names_from_kwargs(keywords)),
            donate_nums=nums, donate_names=names,
            mesh_axes=(self.mesh.resolve(dotted, mesh_expr, assigns)
                       if mesh_expr is not None else None),
            in_specs=in_list, out_specs=out_list,
            all_specs=in_all + out_all,
            out_specs_is_tuple=isinstance(out_specs_expr,
                                          (ast.Tuple, ast.List)),
            binding=binding)
        self.sites.append(site)

    # -- closures + axis env -------------------------------------------------

    def site_order(self, site: SpmdSite) -> Optional[Tuple[str, ...]]:
        """Ordered mesh axes positional spec indices resolve against at
        this site."""
        return site.mesh_axes or self.mesh.axis_order(site.module)

    def site_axes(self, site: SpmdSite) -> Set[str]:
        axes: Set[str] = set(site.mesh_axes or ())
        order = self.site_order(site)
        for s in site.all_specs:
            axes |= set(s.axes)
            if s.pos_entries:
                names, _ = resolve_positional(s, order)
                axes |= set(names)
        if not axes:
            axes |= self.mesh.module_axes.get(site.module, set())
        if not axes:
            axes |= self.mesh.project_axes
        return axes

    def closure_of(self, keys: Sequence[str]) -> Set[str]:
        seen: Set[str] = set()
        stack = [k for k in keys if k in self.cg.funcs]
        while stack:
            k = stack.pop()
            if k in seen:
                continue
            seen.add(k)
            fi = self.cg.funcs[k]
            for s in fi.sites:
                if s.kind in ("call", "callback"):
                    for c in s.callees:
                        if c not in seen and c in self.cg.funcs:
                            stack.append(c)
            # lexically nested functions run under the same trace
            for k2 in self._lexical_children.get(k, ()):
                if k2 not in seen:
                    stack.append(k2)
        return seen

    def _compute_closures(self) -> None:
        self._site_closures: Dict[int, Set[str]] = {}
        for i, site in enumerate(self.sites):
            clo = self.closure_of(site.body_keys)
            self._site_closures[i] = clo
            axes = self.site_axes(site)
            for k in clo:
                self.traced.add(k)
                env = self.axes_env.setdefault(k, set())
                if site.kind in ("shard_map",):
                    self.spmd_reachable.add(k)
                    env |= axes
                elif axes:
                    env |= axes

    # -- static-ness --------------------------------------------------------

    def _params_of(self, fi: cgmod.FuncInfo) -> List[str]:
        node = fi.node
        if isinstance(node, ast.Lambda):
            a = node.args
        else:
            a = node.args
        out = [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
        return out

    def _lexical_static(self, fi: cgmod.FuncInfo,
                        status: Dict[str, Dict[str, str]]) -> Set[str]:
        """Static names visible from lexical ancestors."""
        out: Set[str] = set()
        qual = fi.qualname
        while ".<locals>." in qual:
            qual = qual.rsplit(".<locals>.", 1)[0]
            pk = f"{fi.module}:{qual}"
            pfi = self.cg.funcs.get(pk)
            if pfi is None:
                continue
            st = status.get(pk, {})
            for p in self._params_of(pfi):
                if st.get(p) == "static":
                    out.add(p)
        return out

    def _propagate_static(self) -> None:
        status: Dict[str, Dict[str, str]] = {}
        # seeds: entry bodies get static_argnames; everything else unknown
        for site in self.sites:
            for bk in site.body_keys:
                fi = self.cg.funcs.get(bk)
                if fi is None:
                    continue
                st = status.setdefault(bk, {})
                for p in self._params_of(fi):
                    if p in site.static_names:
                        if st.get(p) != "dynamic":
                            st[p] = "static"
                    else:
                        st[p] = "dynamic"
        traced = self.traced
        # one AST pass per traced function, cached: the fixpoint rounds
        # below only re-evaluate the recorded (callees, args) tuples
        call_args: Dict[str, List[Tuple[Tuple[str, ...], List,
                                        List]]] = {}
        for k in traced:
            fi = self.cg.funcs.get(k)
            if fi is None:
                continue
            entries = []
            for node in walk_nodes(fi.node):
                if isinstance(node, ast.Call):
                    callee_keys = self._callees_at(fi, node.lineno)
                    if callee_keys:
                        entries.append((callee_keys, list(node.args),
                                        list(node.keywords)))
            call_args[k] = entries
        for _round in range(6):
            changed = False
            for k in traced:
                fi = self.cg.funcs.get(k)
                if fi is None:
                    continue
                st = status.setdefault(k, {})
                eff_static = {p for p, v in st.items() if v == "static"} \
                    | self._lexical_static(fi, status)
                for callee_keys, args, keywords in call_args.get(k, ()):
                    for ck in callee_keys:
                        cfi = self.cg.funcs.get(ck)
                        if cfi is None or ck not in traced:
                            continue
                        params = self._params_of(cfi)
                        drop_self = 1 if (cfi.cls and params
                                          and params[0] == "self") else 0
                        cst = status.setdefault(ck, {})
                        for i, a in enumerate(args):
                            pi = i + drop_self
                            if pi >= len(params):
                                break
                            p = params[pi]
                            s = self._arg_static(a, eff_static)
                            prev = cst.get(p)
                            new = self._meet(prev, s)
                            if new != prev:
                                cst[p] = new
                                changed = True
                        for kw in keywords:
                            if kw.arg and kw.arg in params:
                                s = self._arg_static(kw.value, eff_static)
                                prev = cst.get(kw.arg)
                                new = self._meet(prev, s)
                                if new != prev:
                                    cst[kw.arg] = new
                                    changed = True
            if not changed:
                break
        self.param_status = status

    @staticmethod
    def _meet(prev: Optional[str], new: str) -> str:
        if prev == "dynamic" or new == "dynamic":
            return "dynamic"
        if prev == "static" or new == "static":
            return "static"
        return new

    def _arg_static(self, expr, eff_static: Set[str]) -> str:
        if isinstance(expr, ast.Constant):
            return "static"
        if isinstance(expr, ast.Name):
            if expr.id in eff_static:
                return "static"
            # module-level constants / imports are trace-static
            # (they cannot vary per device within one build)
            return "dynamic"
        return "dynamic"

    def _callees_at(self, fi: cgmod.FuncInfo, line: int) -> Tuple[str, ...]:
        out: List[str] = []
        for s in fi.sites:
            if s.line == line and s.kind == "call":
                out.extend(s.callees)
        return tuple(out)

    # -- queries used by the SPMD rules -------------------------------------

    def dynamic_names(self, key: str) -> Set[str]:
        """Names inside ``key`` whose value can differ across devices /
        hosts: non-static params plus locals derived from them."""
        fi = self.cg.funcs.get(key)
        if fi is None:
            return set()
        st = self.param_status.get(key, {})
        dyn = {p for p in self._params_of(fi)
               if st.get(p, "unknown") == "dynamic" and p != "self"}
        # one derivation pass: locals assigned from dynamic reads
        for _ in range(2):
            grew = False
            for node in walk_nodes(fi.node):
                if isinstance(node, ast.Assign):
                    reads = {n.id for n in ast.walk(node.value)
                             if isinstance(n, ast.Name)}
                    if reads & dyn:
                        for t in node.targets:
                            if isinstance(t, ast.Name) \
                                    and t.id not in dyn:
                                dyn.add(t.id)
                                grew = True
            if not grew:
                break
        return dyn

    # -- listener bridges ---------------------------------------------------

    def _build_bridges(self) -> None:
        cg = self.cg
        # (class name, attr) -> registrar FuncInfo keys
        registrars: Dict[Tuple[str, str], List[str]] = {}
        # (class name, attr) -> dispatcher FuncInfo keys
        dispatchers: Dict[Tuple[str, str], List[str]] = {}
        for ci in cg._classes_by_mod.values():
            for mname, mfi in ci.methods.items():
                node = mfi.node
                params = {a.arg for a in node.args.args} - {"self"}
                for sub in walk_nodes(node):
                    # registrar: self.<attr>.append(<param>)
                    if isinstance(sub, ast.Call) \
                            and isinstance(sub.func, ast.Attribute) \
                            and sub.func.attr in ("append", "add") \
                            and isinstance(sub.func.value, ast.Attribute) \
                            and isinstance(sub.func.value.value, ast.Name) \
                            and sub.func.value.value.id == "self" \
                            and len(sub.args) == 1 \
                            and isinstance(sub.args[0], ast.Name) \
                            and sub.args[0].id in params:
                        registrars.setdefault(
                            (ci.name, sub.func.value.attr), []).append(
                                mfi.key)
                    # dispatcher: for cb in [list(]self.<attr>[)]: cb(...)
                    if isinstance(sub, ast.For) \
                            and isinstance(sub.target, ast.Name):
                        attr = self._self_attr_in_iter(sub.iter)
                        if attr is None:
                            continue
                        tgt = sub.target.id
                        for inner in ast.walk(sub):
                            if isinstance(inner, ast.Call) \
                                    and isinstance(inner.func, ast.Name) \
                                    and inner.func.id == tgt:
                                dispatchers.setdefault(
                                    (ci.name, attr), []).append(mfi.key)
                                break
        # registrar method name -> [(class, attr)] for unresolved calls
        by_name: Dict[str, List[Tuple[str, str]]] = {}
        reg_keys: Dict[str, Tuple[str, str]] = {}
        for (cls, attr), keys in registrars.items():
            for k in keys:
                reg_keys[k] = (cls, attr)
                by_name.setdefault(cg.funcs[k].name, []).append(
                    (cls, attr))
        # registered callbacks per (class, attr)
        callbacks: Dict[Tuple[str, str], Set[str]] = {}
        for fi in cg.funcs.values():
            call_sites = [s for s in fi.sites if s.kind == "call"]
            cb_sites = [s for s in fi.sites if s.kind == "callback"]
            for s in call_sites:
                target: Optional[Tuple[str, str]] = None
                for c in s.callees:
                    if c in reg_keys:
                        target = reg_keys[c]
                        break
                if target is None:
                    # unresolved receiver: accept a UNIQUE registrar name
                    name = s.label.rsplit(".", 1)[-1]
                    owners = by_name.get(name, [])
                    if len(set(owners)) == 1 and not s.callees:
                        target = owners[0]
                if target is None:
                    continue
                for s2 in cb_sites:
                    if s2.line == s.line:
                        callbacks.setdefault(target, set()).update(
                            s2.callees)
        # bridge edges: dispatcher -> registered callbacks
        self.bridge_edges: Dict[str, Set[str]] = {}
        for key, disp_keys in dispatchers.items():
            cbs = callbacks.get(key)
            if not cbs:
                continue
            for dk in disp_keys:
                self.bridge_edges.setdefault(dk, set()).update(cbs)

    def reaches(self, start: str, target: str,
                max_depth: int = 64) -> Optional[List[str]]:
        """A call-graph path (list of func keys) from ``start`` to
        ``target`` over call/callback/thread + bridge edges, or None."""
        if start == target:
            return [start]
        prev: Dict[str, str] = {}
        seen = {start}
        frontier = [start]
        depth = 0
        while frontier and depth < max_depth:
            depth += 1
            nxt: List[str] = []
            for k in frontier:
                fi = self.cg.funcs.get(k)
                succ: Set[str] = set(self.bridge_edges.get(k, ()))
                if fi is not None:
                    for s in fi.sites:
                        succ.update(s.callees)
                for c in succ:
                    if c in seen:
                        continue
                    seen.add(c)
                    prev[c] = k
                    if c == target:
                        path = [c]
                        while path[-1] != start:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    nxt.append(c)
            frontier = nxt
        return None

    @staticmethod
    def _self_attr_in_iter(it) -> Optional[str]:
        """`self.<attr>` mentioned by a for-iter expression (directly,
        or through list(...)/tuple(...)/.values())."""
        cand = it
        if isinstance(cand, ast.Call):
            if isinstance(cand.func, ast.Name) \
                    and cand.func.id in ("list", "tuple", "sorted") \
                    and cand.args:
                cand = cand.args[0]
            elif isinstance(cand.func, ast.Attribute) \
                    and cand.func.attr == "values":
                cand = cand.func.value
        if isinstance(cand, ast.Attribute) \
                and isinstance(cand.value, ast.Name) \
                and cand.value.id == "self":
            return cand.attr
        return None


def build(mods: Sequence[ModuleSource],
          cg: Optional[cgmod.CallGraph] = None) -> DeviceDataflow:
    if cg is None:
        cg = cgmod.build(mods)
    return DeviceDataflow(mods, cg)
