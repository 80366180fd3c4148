"""graftlint: static analysis for the invariants this repo's hot path
lives by.

The JAX hot loop is hand-budgeted — the int31 relative-timestamp span
guard, exact f64->3xf32 splits, and for any Pallas kernel its VMEM
footprint and (8, 128) trailing-dim tiling — and the threaded layers
(memstore, ingest streams, gRPC service, resilience) grow locks
organically. Those invariants
historically lived in docstrings and in the builder's head; graftlint
makes them *checked*, on every PR, on CPU-only CI, before anything
touches a TPU.

Rule families (see the rule modules for the catalog):

  * ``rules_kernel`` — kernel contracts: every ``pallas_call`` site
    carries a :func:`filodb_tpu.lint.contracts.kernel_contract`
    declaration (block shapes, dtypes, scratch, budget); the checker
    recomputes the VMEM footprint, verifies trailing-dim tiling,
    grid/index-map bounds, the int31 span guard, and abstract-evals the
    wrapper via ``jax.eval_shape`` — no TPU needed.
  * ``rules_trace`` — trace safety: AST pass over functions reachable
    under ``jax.jit`` / ``shard_map`` / ``pallas_call`` flagging Python
    side effects, tracer leaks, captured-container mutation, and 64-bit
    dtypes inside Pallas kernel bodies.
  * ``rules_lock`` — lock discipline:
    :func:`filodb_tpu.lint.locks.guarded_by` annotations on shared
    fields, checked for access outside a ``with <lock>:`` scope and for
    blocking calls made while a lock is held.
  * ``rules_concurrency`` — whole-program analysis over the project
    call graph (``callgraph.py``): lock-order cycles + the canonical
    order policy (``lockorder.py``), blocking primitives reachable
    through call chains while a lock is held, and inference of shared
    state mutated from >=2 thread roots (``threads.thread_root``) with
    no common guard and no ``@guarded_by``.
  * ``rules_spmd`` (v3) — SPMD/device dataflow over the entry-point
    layer in ``dataflow.py``: collectives under divergent control flow
    or with axis names absent from the enclosing mesh/spec
    (``spmd-collective-balance``), use-after-donate / double-donate /
    donate-of-live-state (``donation-safety``, advisory
    ``donation-missing``), and PartitionSpec arity + axis-name
    consistency (``partition-spec-consistency``).
  * ``rules_promql`` (promlint) — the PromQL surface
    (``filodb_tpu/promql/semant.py``): every shipped rule file
    (``examples/*.yaml``) loads through the rules loader with semantic
    analysis (type/schema checking, label dataflow, normalized
    duplicate detection), and a seeded differential micro-soak runs
    generated well-typed queries engine-vs-reference
    (``promql-differential-mismatch``); ``--changed-only`` skips the
    soak (the full rail runs in tier-1).
  * ``rules_numerics`` (v4) — numeric-precision & determinism dataflow
    (``numerics.py`` annotations): provable f64/int64 values narrowing
    into f32/int32 without a ``@precision(bits=..., reason=...)``
    budget (``precision-narrowing``), f32 accumulations without a
    static term bound under the mantissa (``accumulation-bound``),
    mesh-shape-dependent float reductions without
    ``@order_insensitive(tolerance=...)``
    (``reduction-order-determinism``), and f32/f64-mixed or
    int-cast-to-float comparisons inside Pallas bodies
    (``mixed-dtype-comparison``). The inversion: ``ulpcert.py``
    evaluates every annotation on seeded inputs, f64-reference vs
    production dtype (order claims at 1/2/4/8 virtual devices), and
    CERTIFIES the claimed tolerance — an uncertifiable annotation is
    an error (``ulp-certification``).
  * ``rules_cache`` (v3) — the cache inventory (``caches.py``):
    every ``@publishes`` mutation publisher must reach every
    registered cache's invalidation hook (through inferred
    listener-registration bridges), every pull-validated lookup hook
    must still read its ``@event_source``
    (``cache-invalidation-completeness``); cache-looking classes
    without a registry are ``cache-unregistered``.

Mechanics:

  * run it: ``python -m filodb_tpu.lint`` (add ``--json`` for
    machine-readable findings, ``--changed-only`` for a git-diff-scoped
    pre-commit run — the interprocedural rules still analyze the whole
    graph but only findings anchored in changed files are reported);
    tier-1 runs it via ``tests/test_lint_clean.py``.
  * suppress one finding: ``# graftlint: disable=<rule> (reason)`` on
    the offending line or the line above it. A reason string is
    required — bare disables are themselves a finding.
  * grandfather findings: ``filodb_tpu/lint/baseline.json`` holds keys
    of known findings; the run fails only on NEW findings. The shipped
    baseline is empty — keep it that way.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

ERROR = "error"
WARNING = "warning"

_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*disable=([\w\-,]+)\s*(?:\(([^)]*)\))?")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str               # repo-relative, forward slashes
    line: int
    message: str
    severity: str = ERROR
    context: str = ""       # enclosing qualname (stable across line drift)

    def key(self) -> str:
        """Stable identity for baseline matching: deliberately excludes
        the line number so unrelated edits don't churn the baseline."""
        return f"{self.path}::{self.rule}::{self.context or self.message}"

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] "
                f"{self.message}")


@dataclass(frozen=True)
class Rule:
    """One registered rule: AST rules get a per-module hook, runtime
    rules (kernel contracts) run once over the imported registry."""
    id: str
    family: str             # kernel | trace | lock | meta
    severity: str
    doc: str


_RULES: Dict[str, Rule] = {}


def register_rule(id: str, family: str, doc: str,
                  severity: str = ERROR) -> Rule:
    rule = Rule(id=id, family=family, severity=severity, doc=doc)
    _RULES[id] = rule
    return rule


def rules() -> Dict[str, Rule]:
    """The rule catalog (id -> Rule), importing all rule modules."""
    _load_rule_modules()
    return dict(_RULES)


register_rule(
    "pragma-no-reason", "meta",
    "a `# graftlint: disable=` pragma must carry a (reason) string")
register_rule(
    "pragma-unknown-rule", "meta",
    "a pragma disables a rule id that does not exist")


@dataclass
class ModuleSource:
    """Parsed view of one file handed to AST rules."""
    path: str               # absolute
    relpath: str            # repo/package-relative, forward slashes
    source: str
    tree: ast.Module
    lines: List[str]
    # line -> (set of disabled rule ids, reason or None)
    pragmas: Dict[int, Tuple[frozenset, Optional[str]]]


def _parse_pragmas(lines: Sequence[str]
                   ) -> Dict[int, Tuple[frozenset, Optional[str]]]:
    out: Dict[int, Tuple[frozenset, Optional[str]]] = {}
    for i, text in enumerate(lines, start=1):
        m = _PRAGMA_RE.search(text)
        if m:
            ids = frozenset(x.strip() for x in m.group(1).split(",")
                            if x.strip())
            out[i] = (ids, m.group(2))
    return out


def load_module(path: str, root: Optional[str] = None
                ) -> Optional[ModuleSource]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError):
        return None
    rel = os.path.relpath(path, root) if root else path
    rel = rel.replace(os.sep, "/")
    lines = source.splitlines()
    return ModuleSource(path=path, relpath=rel, source=source, tree=tree,
                        lines=lines, pragmas=_parse_pragmas(lines))


def _suppressed(mod: ModuleSource, f: Finding) -> bool:
    """A finding is suppressed by a pragma on its line or the line
    directly above it naming its rule (or `all`)."""
    for ln in (f.line, f.line - 1):
        entry = mod.pragmas.get(ln)
        if entry and (f.rule in entry[0] or "all" in entry[0]):
            return True
    return False


def _pragma_findings(mod: ModuleSource) -> List[Finding]:
    out = []
    known = set(_RULES)
    for ln, (ids, reason) in mod.pragmas.items():
        if not reason or not reason.strip():
            out.append(Finding(
                rule="pragma-no-reason", path=mod.relpath, line=ln,
                message="disable pragma without a (reason) string",
                context=f"pragma:{','.join(sorted(ids))}"))
        for rid in ids:
            if rid != "all" and rid not in known:
                out.append(Finding(
                    rule="pragma-unknown-rule", path=mod.relpath, line=ln,
                    message=f"pragma disables unknown rule {rid!r}",
                    context=f"pragma:{rid}"))
    return out


# -- baseline ---------------------------------------------------------------

def baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "baseline.json")


def load_baseline(path: Optional[str] = None) -> frozenset:
    path = path or baseline_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return frozenset()
    return frozenset(data.get("findings", []))


# -- runner -----------------------------------------------------------------

@dataclass
class LintResult:
    findings: List[Finding] = field(default_factory=list)   # new (fail)
    baselined: List[Finding] = field(default_factory=list)  # grandfathered
    suppressed: int = 0
    files: int = 0

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == ERROR]

    def to_json(self) -> Dict:
        return {"files": self.files,
                "findings": [f.to_json() for f in self.findings],
                "baselined": [f.to_json() for f in self.baselined],
                "suppressed": self.suppressed,
                "exit_code": 1 if self.errors else 0}


def package_root() -> str:
    """Directory containing the ``filodb_tpu`` package (the repo root
    when run from a checkout)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(os.path.abspath(p))
        else:
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__",)]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        out.append(os.path.abspath(
                            os.path.join(dirpath, fn)))
    return out


_rule_modules_loaded = False


def _load_rule_modules() -> None:
    global _rule_modules_loaded
    if _rule_modules_loaded:
        return
    _rule_modules_loaded = True
    from filodb_tpu.lint import (memcert,  # noqa: F401
                                 rules_cache, rules_capacity,
                                 rules_concurrency, rules_hot,
                                 rules_kernel, rules_lock,
                                 rules_numerics, rules_promql,
                                 rules_span, rules_spmd, rules_trace,
                                 ulpcert)


def run_lint(paths: Optional[Sequence[str]] = None, *,
             baseline: Optional[frozenset] = None,
             check_contracts: bool = True,
             report_only: Optional[frozenset] = None) -> LintResult:
    """Lint ``paths`` (default: the ``filodb_tpu`` package).

    AST rules run per file; the concurrency families run once over the
    whole module set (the call graph is a project artifact); when
    ``check_contracts`` is set, files that belong to an importable
    package are imported and every registered
    :class:`~filodb_tpu.lint.contracts.KernelContract` they declare is
    verified (VMEM budget, tiling, grid bounds, span guard,
    ``jax.eval_shape``).

    ``report_only`` (a set of repo-relative paths) keeps the analysis
    whole-program but drops findings anchored outside those files —
    the ``--changed-only`` pre-commit mode."""
    # the ulp-certification rail needs 1/2/4/8 virtual devices; the
    # flag must land before ANY rule initializes the jax backend (the
    # promql soak and the kernel contracts both do). No-op when a
    # backend is already up (tests force 8 devices in conftest).
    from filodb_tpu.lint.ulpcert import ensure_virtual_devices
    ensure_virtual_devices()
    from filodb_tpu.lint import astwalk
    astwalk.clear()     # fresh memoized-walk cache per run
    _load_rule_modules()
    from filodb_tpu.lint import (rules_cache, rules_capacity,
                                 rules_concurrency, rules_hot,
                                 rules_kernel, rules_lock,
                                 rules_numerics, rules_promql,
                                 rules_span, rules_spmd, rules_trace)
    from filodb_tpu.lint import callgraph as _cgmod
    from filodb_tpu.lint import dataflow as _dfmod
    root = package_root()
    if paths is None:
        paths = [os.path.join(root, "filodb_tpu")]
    if baseline is None:
        baseline = load_baseline()
    files = iter_py_files(paths)
    result = LintResult(files=len(files))
    mods: List[ModuleSource] = []
    for path in files:
        mod = load_module(path, root=root)
        if mod is None:
            continue
        mods.append(mod)
    # two passes: lock declarations are collected package-wide first so
    # cross-class (foreign-object) guarded accesses resolve
    lock_decls = rules_lock.collect_declarations(mods)
    raw: List[Tuple[ModuleSource, Finding]] = []
    for mod in mods:
        for f in _pragma_findings(mod):
            raw.append((mod, f))
        for f in rules_kernel.check_module(mod):
            raw.append((mod, f))
        for f in rules_trace.check_module(mod):
            raw.append((mod, f))
        for f in rules_hot.check_module(mod):
            raw.append((mod, f))
        for f in rules_span.check_module(mod):
            raw.append((mod, f))
        for f in rules_lock.check_module(mod, lock_decls):
            raw.append((mod, f))
    bymod_path = {m.relpath: m for m in mods}
    # one call graph + one dataflow layer shared by every
    # interprocedural family (concurrency, SPMD, cache completeness)
    cg = _cgmod.build(mods)
    df = _dfmod.DeviceDataflow(mods, cg)
    for relpath, f in rules_concurrency.check_project(mods, cg=cg):
        raw.append((bymod_path.get(relpath), f))
    for relpath, f in rules_spmd.check_project(mods, cg=cg, df=df):
        raw.append((bymod_path.get(relpath), f))
    for relpath, f in rules_cache.check_project(mods, cg=cg, df=df):
        raw.append((bymod_path.get(relpath), f))
    for relpath, f in rules_numerics.check_project(mods, cg=cg, df=df):
        raw.append((bymod_path.get(relpath), f))
    for relpath, f in rules_capacity.check_project(mods, cg=cg, df=df):
        raw.append((bymod_path.get(relpath), f))
    # promql family: shipped rule-file sweep + (full runs only) the
    # seeded differential micro-soak. --changed-only skips the soak —
    # the fast pre-commit path; tier-1 runs the full rail.
    for relpath, f in rules_promql.check_project(
            mods, root, skip_soak=report_only is not None):
        raw.append((bymod_path.get(relpath), f))
    if check_contracts:
        bymod = {m.relpath: m for m in mods}
        for relpath, f in rules_kernel.check_contracts(mods, root):
            mod = bymod.get(relpath)
            raw.append((mod, f) if mod is not None else (None, f))
        # the ulp-certification rail (numerics annotations evaluated
        # f64-reference vs production, order claims at 1/2/4/8 virtual
        # devices) rides the same runtime-verification gate as the
        # kernel contracts; skipped under --changed-only (pre-commit
        # fast path — tier-1 runs the full rail). Results are memoized
        # per process, so fixture-scoped run_lint calls stay fast.
        if report_only is None:
            from filodb_tpu.lint import ulpcert
            for relpath, f in ulpcert.check_certifications(mods):
                mod = bymod.get(relpath)
                raw.append((mod, f) if mod is not None else (None, f))
            # the capacity-certification rail (v5): every @capacity
            # residency claim is built at seeded sizes and its real
            # device bytes measured; sharded claims at 1/2/4/8 virtual
            # devices. Memoized like ulpcert.
            from filodb_tpu.lint import memcert
            for relpath, f in memcert.check_certifications(mods):
                mod = bymod.get(relpath)
                raw.append((mod, f) if mod is not None else (None, f))
    for mod, f in raw:
        if mod is not None and _suppressed(mod, f):
            result.suppressed += 1
        elif report_only is not None and f.path not in report_only:
            continue
        elif f.key() in baseline:
            result.baselined.append(f)
        else:
            result.findings.append(f)
    result.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    result.baselined.sort(key=lambda f: (f.path, f.line, f.rule))
    return result
