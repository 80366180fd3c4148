"""Device-memory residency & capacity dataflow rules (graftlint v5).

Built on the v3 :mod:`filodb_tpu.lint.callgraph` /
:mod:`filodb_tpu.lint.dataflow` engine: a residency analysis tracks
device-allocation sites (``jnp.zeros``/``jnp.full``/``jnp.asarray``/
``jax.device_put``/…) through local bindings into LONG-LIVED stores —
object attributes, module-level caches, ``@cache_registry`` inventory
dicts — and holds every escape to the ``@capacity`` bytes budgets of
:mod:`filodb_tpu.lint.capacity` (certified dynamically by
:mod:`filodb_tpu.lint.memcert`). Four error families:

  * ``hbm-residency-budget`` — a device allocation escapes into a
    long-lived store from a host-side (untraced) function that carries
    no ``@capacity(bytes_per_sample=..., reason=...)`` claim on
    itself, a lexical ancestor, or its class. Unaccounted residency is
    exactly how "tens of millions of series per chip" dies quietly:
    HBM fills with buffers nobody priced.
  * ``device-buffer-leak`` — lifetime analysis over the registered
    cache inventory: a ``@cache_registry`` store that accumulates
    device arrays by subscript must have an eviction operation
    (``pop``/``del``/``clear``/FIFO cap/weakref finalizer) on that
    attribute, and when the registry declares ``invalidated_by``
    hooks, an eviction site reachable from a hook through the call
    graph. Also: one tainted buffer stored into two different stores
    in one function (double-retention — the ledger double-counts and
    neither store owns eviction).
  * ``oversized-transfer`` — inside ``@hot_path`` functions: a
    device→host pull of a whole resident channel (``np.asarray`` /
    ``jax.device_get`` of a bare store attribute — slice on device
    first), or a host→device transfer of a buffer whose allocation is
    pow2-capacity-padded (``_next_pow2``/``_pad_pow2`` in the shape)
    when the unpadded slice would do; ``@capacity`` on the site
    declares the padding priced and exempts it.
  * ``vmem-frontier-budget`` — a kernel's tile/DMA-buffer frontier
    chooser against the kernel contracts: a ``vmem_budget`` parameter
    must stay under the physical per-core VMEM
    (:data:`filodb_tpu.lint.contracts.VMEM_BYTES`), and a chooser that
    walks a frontier must actually TEST against its declared budget.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from filodb_tpu.lint import Finding, ModuleSource, register_rule
from filodb_tpu.lint import callgraph as cgmod
from filodb_tpu.lint import dataflow as dfmod
from filodb_tpu.lint import contracts as contracts_mod
from filodb_tpu.lint.rules_cache import _collect_registries
from filodb_tpu.lint.rules_spmd import _own_nodes

register_rule("hbm-residency-budget", "capacity",
              "a device allocation escapes into a long-lived store "
              "(object attr / module cache / registry dict) without a "
              "@capacity(bytes_per_sample=..., reason=...) claim")
from filodb_tpu.lint.astwalk import walk_nodes
register_rule("device-buffer-leak", "capacity",
              "device arrays retained in a registered store with no "
              "eviction path reachable from its invalidation events, "
              "or one buffer double-retained by two stores")
register_rule("oversized-transfer", "capacity",
              "hot-path host<->device transfer of a whole resident "
              "channel or of a capacity-padded buffer where a slice "
              "suffices")
register_rule("vmem-frontier-budget", "capacity",
              "kernel frontier arithmetic disagrees with the declared "
              "VMEM budget: budget above physical VMEM, or a chooser "
              "that never tests its budget")

# host-side constructors whose result is a device buffer under JAX
# (jnp.* array factories; jax.device_put). np.* allocations are host
# memory and do NOT count — residency is HBM.
_ALLOC_LEAVES = {"zeros", "ones", "full", "empty", "zeros_like",
                 "ones_like", "full_like", "asarray", "array",
                 "arange", "linspace", "where", "concatenate", "stack"}
_JNP_BASES = {"jnp", "jax.numpy"}

# device->host pull calls (the oversized-transfer whole-channel check)
_PULL_LEAVES = {"asarray", "array", "device_get"}


def _call_base(e: ast.Call) -> Optional[str]:
    """Dotted base of a call's function ('jnp' for jnp.zeros(...))."""
    d = dfmod._dotted(e.func)
    if d is None or "." not in d:
        return None
    return d.rsplit(".", 1)[0]


def _is_device_alloc(e) -> bool:
    """``e`` is a call that manufactures a device buffer."""
    if not isinstance(e, ast.Call):
        return False
    leaf = dfmod._leaf(e.func)
    base = _call_base(e)
    if leaf == "device_put":
        return base in ("jax", None)
    return leaf in _ALLOC_LEAVES and base in _JNP_BASES


def _contains_device_alloc(e) -> bool:
    return any(_is_device_alloc(n) for n in ast.walk(e)
               if isinstance(n, ast.Call))


def _is_self_attr(e) -> Optional[str]:
    """'attr' when ``e`` is ``self.attr``, else None."""
    if isinstance(e, ast.Attribute) and isinstance(e.value, ast.Name) \
            and e.value.id == "self":
        return e.attr
    return None


# -- @capacity annotation discovery ------------------------------------------


class _CapacityAnnotations:
    """Function keys and class names carrying ``@capacity``."""

    def __init__(self, cg: cgmod.CallGraph):
        self.funcs: Set[str] = set()
        self.classes: Set[Tuple[str, str]] = set()   # (module, cls)
        for key, fi in cg.funcs.items():
            node = fi.node
            if isinstance(node, ast.Lambda):
                continue
            for d in node.decorator_list:
                target = d.func if isinstance(d, ast.Call) else d
                if dfmod._leaf(target) == "capacity":
                    self.funcs.add(key)
        for (module, cls), ci in cg._classes_by_mod.items():
            for d in ci.node.decorator_list:
                target = d.func if isinstance(d, ast.Call) else d
                if dfmod._leaf(target) == "capacity":
                    self.classes.add((module, cls))

    def covers(self, cg: cgmod.CallGraph, key: str) -> bool:
        fi = cg.funcs.get(key)
        if fi is None:
            return False
        qual = fi.qualname
        keys = [key]
        while ".<locals>." in qual:
            qual = qual.rsplit(".<locals>.", 1)[0]
            keys.append(f"{fi.module}:{qual}")
        if any(k in self.funcs for k in keys):
            return True
        return fi.cls is not None and (fi.module, fi.cls) in self.classes


# -- per-function residency analysis -----------------------------------------


class _Escapes:
    """Device-alloc taint + store escapes inside one function body."""

    def __init__(self, fn_node):
        self.tainted: Set[str] = set()       # locals bound to allocs
        # local container names that received tainted subscript stores
        self.tainted_containers: Set[str] = set()
        # (store label, line, tainted local or None) per escape
        self.stores: List[Tuple[str, int, Optional[str], ast.AST]] = []
        nodes = list(_own_nodes(fn_node))
        # two taint-propagation passes (no store recording), then one
        # recording pass — stores must not duplicate across passes
        self._record = False
        for _ in range(2):
            for node in nodes:
                self._visit(node)
        self._record = True
        for node in nodes:
            self._visit(node)

    def _value_taint(self, value) -> Optional[str]:
        """The tainted local a stored value carries, '<alloc>' for a
        direct allocation, None for clean values. Dict/list/tuple
        literals of tainted names are containers of device buffers."""
        if isinstance(value, ast.Name):
            if value.id in self.tainted \
                    or value.id in self.tainted_containers:
                return value.id
            return None
        if _is_device_alloc(value):
            return "<alloc>"
        if isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            for e in value.elts:
                t = self._value_taint(e)
                if t is not None:
                    return t
            return None
        if isinstance(value, ast.Dict):
            for e in value.values:
                t = self._value_taint(e)
                if t is not None:
                    return t
        return None

    def _visit(self, node) -> None:
        if not isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
            return
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        value = node.value
        if value is None:
            return
        taint = self._value_taint(value)
        for t in targets:
            # local binding: x = jnp.zeros(...)
            if isinstance(t, ast.Name):
                if taint is not None:
                    self.tainted.add(t.id)
                continue
            # tuple unpack of allocs taints every name
            if isinstance(t, ast.Tuple) and taint is not None:
                for e in t.elts:
                    if isinstance(e, ast.Name):
                        self.tainted.add(e.id)
                continue
            # self.attr = X
            attr = _is_self_attr(t)
            if attr is not None and taint is not None:
                if self._record:
                    self.stores.append((f"self.{attr}", node.lineno,
                                        taint, t))
                continue
            if isinstance(t, ast.Subscript):
                attr = _is_self_attr(t.value)
                if attr is not None and taint is not None:
                    # self.attr[k] = X — dict-store growth
                    if self._record:
                        self.stores.append((f"self.{attr}[]",
                                            node.lineno, taint, t))
                elif isinstance(t.value, ast.Name) and taint is not None:
                    # local[k] = alloc: container becomes tainted; it
                    # escapes if the container itself is stored
                    self.tainted_containers.add(t.value.id)


# -- vmem frontier budget ----------------------------------------------------


def _check_vmem_frontier(cg: cgmod.CallGraph
                         ) -> List[Tuple[Optional[str], Finding]]:
    out: List[Tuple[Optional[str], Finding]] = []
    for key, fi in sorted(cg.funcs.items()):
        node = fi.node
        if isinstance(node, ast.Lambda):
            continue
        args = node.args
        names = [a.arg for a in args.args] \
            + [a.arg for a in args.kwonlyargs]
        if "vmem_budget" not in names:
            continue
        # (1) declared default must fit physical VMEM
        defaults = list(zip(reversed(args.args), reversed(args.defaults)))
        declared: Optional[int] = None
        for a, d in defaults:
            if a.arg == "vmem_budget":
                declared = _int_const(d)
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if a.arg == "vmem_budget" and d is not None:
                declared = _int_const(d)
        if declared is not None and declared > contracts_mod.VMEM_BYTES:
            out.append((fi.relpath, Finding(
                rule="vmem-frontier-budget", path=fi.relpath,
                line=fi.lineno,
                message=(f"{fi.qualname}: vmem_budget default "
                         f"{declared} exceeds physical per-core VMEM "
                         f"({contracts_mod.VMEM_BYTES}) — a chooser "
                         f"can admit footprints the chip cannot hold"),
                context=f"{fi.qualname}:budget-over-vmem")))
        # (2) a chooser (a function that WALKS a frontier — it loops)
        # must TEST against its budget somewhere; declaration helpers
        # that merely forward the kwarg are not choosers
        is_chooser = any(isinstance(n, (ast.For, ast.While))
                         for n in ast.walk(node))
        uses_budget = any(
            isinstance(n, ast.Compare) and any(
                isinstance(side, ast.Name) and side.id == "vmem_budget"
                for side in [n.left] + list(n.comparators))
            for n in ast.walk(node))
        if is_chooser and not uses_budget:
            out.append((fi.relpath, Finding(
                rule="vmem-frontier-budget", path=fi.relpath,
                line=fi.lineno,
                message=(f"{fi.qualname}: takes a vmem_budget but "
                         f"never compares a footprint against it — "
                         f"the frontier walk is unbudgeted"),
                context=f"{fi.qualname}:budget-unused")))
    return out


def _int_const(e) -> Optional[int]:
    from filodb_tpu.lint.rules_numerics import _int_const as f
    return f(e)


# -- hot-path transfer scope -------------------------------------------------


def _hot_keys(cg: cgmod.CallGraph, mods: Sequence[ModuleSource]
              ) -> Set[str]:
    hot: Set[str] = set()
    for key, fi in cg.funcs.items():
        node = fi.node
        if isinstance(node, ast.Lambda):
            continue
        for d in node.decorator_list:
            target = d.func if isinstance(d, ast.Call) else d
            if dfmod._leaf(target) == "hot_path":
                hot.add(key)
    for mod in mods:
        dotted = cgmod.module_dotted(mod.relpath)
        for node in mod.tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__hot_path__":
                        from filodb_tpu.lint.rules_cache import _const
                        v = _const(node.value)
                        if isinstance(v, tuple):
                            for name in v:
                                hot.add(f"{dotted}:{name}")
    return hot


def _pow2_padded_locals(fn_node) -> Set[str]:
    """Locals whose allocation shape runs through a pow2 capacity pad
    (``_next_pow2``/``_pad_pow2``), plus the pad-width names feeding
    them."""
    padded: Set[str] = set()
    pad_names: Set[str] = set()
    for _ in range(2):
        for node in _own_nodes(fn_node):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not isinstance(t, ast.Name):
                continue
            uses_pad = False
            for n in ast.walk(node.value):
                if isinstance(n, ast.Call) and dfmod._leaf(n.func) in \
                        ("_next_pow2", "_pad_pow2", "next_pow2"):
                    uses_pad = True
                if isinstance(n, ast.Name) and n.id in pad_names:
                    uses_pad = True
            if uses_pad:
                pad_names.add(t.id)
                if isinstance(node.value, ast.Call) and \
                        dfmod._leaf(node.value.func) in (
                            "zeros", "full", "empty", "ones"):
                    padded.add(t.id)
    return padded


def _check_transfers(cg: cgmod.CallGraph, mods: Sequence[ModuleSource],
                     ann: _CapacityAnnotations
                     ) -> List[Tuple[Optional[str], Finding]]:
    out: List[Tuple[Optional[str], Finding]] = []
    for key in sorted(_hot_keys(cg, mods)):
        fi = cg.funcs.get(key)
        if fi is None or ann.covers(cg, key):
            continue
        padded = _pow2_padded_locals(fi.node)
        for call in _own_nodes(fi.node):
            if isinstance(call, ast.Call):
                leaf = dfmod._leaf(call.func)
                base = _call_base(call)
                # (i) whole-resident-channel pull to host
                if leaf in _PULL_LEAVES and base in ("np", "numpy",
                                                     "jax") \
                        and call.args:
                    attr = _is_self_attr(call.args[0])
                    if attr is not None:
                        out.append((fi.relpath, Finding(
                            rule="oversized-transfer", path=fi.relpath,
                            line=call.lineno,
                            message=(
                                f"{fi.qualname}: pulls the whole "
                                f"resident channel self.{attr} to the "
                                f"host on the hot path — slice on "
                                f"device and transfer the window"),
                            context=f"{fi.qualname}:pull:{attr}")))
                # (ii) capacity-padded buffer shipped to device
                if leaf == "device_put" or (leaf == "asarray"
                                            and base in _JNP_BASES):
                    for a in call.args[:1]:
                        if isinstance(a, ast.Name) and a.id in padded:
                            out.append((fi.relpath, Finding(
                                rule="oversized-transfer",
                                path=fi.relpath, line=call.lineno,
                                message=(
                                    f"{fi.qualname}: transfers the "
                                    f"pow2-capacity-padded buffer "
                                    f"{a.id!r} to the device on the "
                                    f"hot path — pad on device or "
                                    f"ship the exact slice "
                                    f"(@capacity declares the "
                                    f"padding priced if deliberate)"),
                                context=(f"{fi.qualname}:padded:"
                                         f"{a.id}"))))
    return out


# -- leak analysis -----------------------------------------------------------

_EVICT_CALL_LEAVES = {"pop", "popitem", "clear"}


def _evicts_attr(fn_node, attr: str) -> bool:
    """The function body evicts from ``self.<attr>`` (pop/del/clear/
    reassign-to-empty) or wires a weakref finalizer."""
    for node in walk_nodes(fn_node):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) \
                    and f.attr in _EVICT_CALL_LEAVES:
                tgt = f.value
                if _is_self_attr(tgt) == attr:
                    return True
            leaf = dfmod._leaf(f)
            if leaf in ("ref", "finalize") \
                    and (_call_base(node) or "").endswith("weakref"):
                return True
        if isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript) \
                        and _is_self_attr(t.value) == attr:
                    return True
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if _is_self_attr(t) == attr and isinstance(
                        node.value, (ast.Dict, ast.List)) \
                        and not getattr(node.value, "keys",
                                        getattr(node.value, "elts", ())):
                    return True
    return False


def _check_leaks(cg: cgmod.CallGraph, df: dfmod.DeviceDataflow,
                 mods: Sequence[ModuleSource],
                 escapes_by_key: Dict[str, _Escapes]
                 ) -> List[Tuple[Optional[str], Finding]]:
    out: List[Tuple[Optional[str], Finding]] = []
    regs, _ = _collect_registries(cg, mods)
    regs_by_cls: Dict[str, list] = {}
    for reg in regs:
        if reg.owner_cls:
            regs_by_cls.setdefault(reg.owner_cls, []).append(reg)

    # (a) registered stores accumulating device arrays need eviction
    for (module, cls), ci in sorted(cg._classes_by_mod.items()):
        if cls not in regs_by_cls:
            continue
        grown: Dict[str, Tuple[str, int]] = {}   # attr -> (key, line)
        for mname, mfi in ci.methods.items():
            esc = escapes_by_key.get(mfi.key)
            if esc is None:
                continue
            for label, line, _taint, _t in esc.stores:
                if label.endswith("[]"):
                    grown.setdefault(label[5:-2], (mfi.key, line))
        for attr, (store_key, line) in sorted(grown.items()):
            evictors = [m for m in ci.methods.values()
                        if m.name != "__init__"
                        and _evicts_attr(m.node, attr)]
            # a finalizer/FIFO-cap in the storing method itself counts
            store_fi = cg.funcs.get(store_key)
            if store_fi is not None \
                    and _evicts_attr(store_fi.node, attr):
                evictors.append(store_fi)
            if not evictors:
                out.append((ci.relpath, Finding(
                    rule="device-buffer-leak", path=ci.relpath,
                    line=line,
                    message=(
                        f"{cls}.{attr} accumulates device arrays with "
                        f"no eviction operation anywhere in the class "
                        f"(no pop/del/clear/weakref finalizer) — the "
                        f"store can only grow"),
                    context=f"{cls}.{attr}:no-eviction")))
                continue
            # invalidation-event reachability: when the registry
            # declares hooks, some eviction site must be reachable
            # from one of them
            hooks: List[str] = []
            for reg in regs_by_cls[cls]:
                for hook in reg.invalidated_by.values():
                    hk = cg.resolve_method(cls, hook)
                    if hk:
                        hooks.append(hk)
            if hooks:
                reachable = False
                for hk in hooks:
                    for ev in evictors:
                        if hk == ev.key \
                                or df.reaches(hk, ev.key) is not None:
                            reachable = True
                if not reachable:
                    out.append((ci.relpath, Finding(
                        rule="device-buffer-leak", path=ci.relpath,
                        line=line,
                        message=(
                            f"{cls}.{attr} holds device arrays but no "
                            f"eviction site is reachable from the "
                            f"registry's invalidation hooks — the "
                            f"declared events never free the bytes"),
                        context=f"{cls}.{attr}:unreachable-eviction")))

    # (b) double-retention of one buffer by two stores
    for key, esc in sorted(escapes_by_key.items()):
        fi = cg.funcs.get(key)
        if fi is None:
            continue
        by_name: Dict[str, List[Tuple[str, int]]] = {}
        for label, line, taint, _t in esc.stores:
            if taint and taint != "<alloc>":
                by_name.setdefault(taint, []).append((label, line))
        for name, sites in sorted(by_name.items()):
            stores = sorted({lab for lab, _ in sites})
            if len(stores) > 1:
                line = min(ln for _, ln in sites)
                out.append((fi.relpath, Finding(
                    rule="device-buffer-leak", path=fi.relpath,
                    line=line,
                    message=(
                        f"{fi.qualname}: buffer {name!r} is retained "
                        f"by {len(stores)} stores "
                        f"({', '.join(stores)}) — double-counted "
                        f"residency with no single eviction owner"),
                    context=f"{fi.qualname}:double:{name}")))
    return out


# -- entry -------------------------------------------------------------------


def check_project(mods: Sequence[ModuleSource],
                  cg: Optional[cgmod.CallGraph] = None,
                  df: Optional[dfmod.DeviceDataflow] = None
                  ) -> List[Tuple[Optional[str], Finding]]:
    if df is None:
        df = dfmod.build(mods, cg)
    cg = df.cg
    ann = _CapacityAnnotations(cg)
    out: List[Tuple[Optional[str], Finding]] = []

    # traced functions don't retain — jit outputs escape through the
    # dispatch, and Pallas bodies are on-chip; residency is a HOST
    # code property
    traced: Set[str] = set(df.traced)
    for site in df.sites:
        if site.kind == "pallas_call":
            traced |= df.closure_of(site.body_keys)

    escapes_by_key: Dict[str, _Escapes] = {}
    for key, fi in sorted(cg.funcs.items()):
        if key in traced or isinstance(fi.node, ast.Lambda):
            continue
        esc = _Escapes(fi.node)
        if esc.stores:
            escapes_by_key[key] = esc

    # (1) hbm-residency-budget
    for key, esc in sorted(escapes_by_key.items()):
        fi = cg.funcs[key]
        if ann.covers(cg, key):
            continue
        for label, line, _taint, _t in esc.stores:
            out.append((fi.relpath, Finding(
                rule="hbm-residency-budget", path=fi.relpath, line=line,
                message=(
                    f"{fi.qualname}: a device allocation escapes into "
                    f"the long-lived store {label} with no "
                    f"@capacity(bytes_per_sample=..., reason=...) "
                    f"claim on the function or its class — "
                    f"unaccounted HBM residency"),
                context=f"{fi.qualname}:resident:{label}")))

    # module-level resident globals: NAME = jnp.zeros(...) at top level
    for mod in mods:
        dotted = cgmod.module_dotted(mod.relpath)
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) \
                    and _contains_device_alloc(node.value):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if not names:
                    continue
                out.append((mod.relpath, Finding(
                    rule="hbm-residency-budget", path=mod.relpath,
                    line=node.lineno,
                    message=(
                        f"module-level device allocation bound to "
                        f"{', '.join(names)} lives for the process "
                        f"lifetime with no @capacity claim — "
                        f"unaccounted HBM residency"),
                    context=f"{dotted}:{names[0]}:module-resident")))

    # (2) device-buffer-leak
    out.extend(_check_leaks(cg, df, mods, escapes_by_key))
    # (3) oversized-transfer
    out.extend(_check_transfers(cg, mods, ann))
    # (4) vmem-frontier-budget
    out.extend(_check_vmem_frontier(cg))
    return out
