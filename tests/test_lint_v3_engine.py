"""graftlint v3 engine-as-assertion tests: the dataflow layer run over
the REAL modules, pinning the wiring the cache/SPMD families verify.

These are regression pins, not fixture games: if someone deletes the
ShardMapper subscription in http/server.py, stops reading the epoch in
the results-cache lookup, or renames a mesh axis, the assertions here
fail with a named path — the same condition the tier-1 lint gate
enforces, but stated directly against the production wiring."""

import os

import pytest

from filodb_tpu.lint import (iter_py_files, load_module, package_root,
                             run_lint)
from filodb_tpu.lint import callgraph as cgmod
from filodb_tpu.lint import dataflow as dfmod
from filodb_tpu.lint import rules_cache, rules_spmd


@pytest.fixture(scope="module")
def df():
    root = package_root()
    files = iter_py_files([os.path.join(root, "filodb_tpu")])
    mods = [m for m in (load_module(p, root=root) for p in files) if m]
    cg = cgmod.build(mods)
    return dfmod.DeviceDataflow(mods, cg), mods


PUB_TOPOLOGY = "filodb_tpu.parallel.shardmapper:ShardMapper.update"
PUB_SCHEMA = ("filodb_tpu.http.server:"
              "FiloHttpServer.invalidate_plan_cache")
HOOK_PLAN = "filodb_tpu.query.plancache:PlanCache.invalidate"
HOOK_RESULTS = "filodb_tpu.query.resultcache:ResultCache.invalidate"


def test_topology_publisher_reaches_both_cache_hooks(df):
    flow, _ = df
    for hook in (HOOK_PLAN, HOOK_RESULTS):
        path = flow.reaches(PUB_TOPOLOGY, hook)
        assert path is not None, \
            f"ShardMapper.update no longer reaches {hook} — the " \
            f"subscription wiring in http/server.py is gone"
    # the path genuinely crosses the listener bridge (publish loop ->
    # registered lambda), not some accidental direct edge
    path = flow.reaches(PUB_TOPOLOGY, HOOK_RESULTS)
    quals = [flow.cg.funcs[k].qualname for k in path]
    assert "ShardMapper._publish" in quals
    assert any("<lambda>" in q or "_bus_publish" in q for q in quals)


def test_schema_publisher_reaches_both_cache_hooks(df):
    flow, _ = df
    for hook in (HOOK_PLAN, HOOK_RESULTS):
        assert flow.reaches(PUB_SCHEMA, hook) is not None


def test_result_cache_lookups_read_every_pull_source(df):
    flow, _ = df
    sources = {
        "watermark": "filodb_tpu.query.resultcache:shards_watermark",
        "coverage": "filodb_tpu.query.resultcache:watermark_coverage",
        "backfill": "filodb_tpu.query.resultcache:shards_epoch",
        "scope": "filodb_tpu.query.resultcache:dispatch_scope",
    }
    for hook in ("filodb_tpu.query.resultcache:ResultCache.begin",
                 "filodb_tpu.query.resultcache:ResultCache.stale_serve"):
        for name, src in sources.items():
            assert flow.reaches(hook, src) is not None, \
                f"{hook} no longer reads the {name} event source"


def test_mesh_spmd_sites_discovered(df):
    flow, _ = df
    mesh_sites = [s for s in flow.sites
                  if s.relpath == "filodb_tpu/parallel/mesh.py"
                  and s.kind == "shard_map"]
    assert len(mesh_sites) >= 3      # _step, _step_topk, check site
    for s in mesh_sites:
        assert flow.site_axes(s) <= {"shard", "time"}
    # the grouped-reduce collective helper runs under shard_map context
    # with the merged axis environment
    gr = "filodb_tpu.parallel.mesh:_grouped_reduce"
    assert gr in flow.spmd_reachable
    assert {"shard", "time"} >= flow.axes_env[gr] >= {"shard"}


def test_mesh_static_propagation(df):
    """`agg` flows into _grouped_reduce from the jit wrapper's
    static_argnames through the shard_map body's closure — which is
    exactly why its `if agg == ...` branches around psum are uniform
    and NOT collective-balance findings."""
    flow, _ = df
    st = flow.param_status.get("filodb_tpu.parallel.mesh:_grouped_reduce",
                               {})
    assert st.get("agg") == "static", st
    assert st.get("local") == "dynamic", st


def test_spmd_and_cache_families_clean_on_real_modules(df):
    flow, mods = df
    assert not [f for _, f in rules_spmd.check_project(mods, df=flow)
                if f.severity == "error"]
    assert not [f for _, f in rules_cache.check_project(mods, df=flow)]


def test_registered_cache_inventory_names(df):
    """The README inventory table and the registry must agree — every
    declared cache the docs promise exists in code."""
    flow, mods = df
    regs, _ = rules_cache._collect_registries(flow.cg, mods)
    names = {r.name for r in regs}
    assert {"plan", "results", "device-tile", "packed-executable",
            "partition-decode", "partition-merge", "mesh-executable",
            "tilestore-executables", "shardstore-executables",
            "sharded-tile-placement"} <= names


# -- the multi-chip serving wiring (PR 14): non-vacuous family pins ----------
#
# graftlint's donation-safety / donation-missing /
# partition-spec-consistency families were error-severity with nothing
# in-tree to police. These assertions pin that the NEW production sites
# — the donated tile-refresh jit and the sharded-evaluator shard_map
# lowerings — are DISCOVERED by the engine on the real modules, so the
# families can never go silently vacuous again.

SHARDSTORE = "filodb_tpu/parallel/shardstore.py"


def test_shardstore_donate_site_discovered(df):
    flow, _ = df
    sites = [s for s in flow.sites if s.relpath == SHARDSTORE
             and s.kind == "jit" and s.donate_nums]
    assert sites, "the donated tile-refresh jit site is gone"
    assert any(s.donate_nums == (0, 1, 2) for s in sites), \
        [s.donate_nums for s in sites]
    # it wraps _append_step (decorator form -> body key resolved)
    assert any("_append_step" in bk for s in sites for bk in s.body_keys)


def test_shardstore_shard_map_sites_discovered_with_named_axes(df):
    flow, _ = df
    sites = [s for s in flow.sites if s.relpath == SHARDSTORE
             and s.kind == "shard_map"]
    # counter single+batch, grouped, grouped-pair lowerings at least
    assert len(sites) >= 4, [s.line for s in sites]
    for s in sites:
        assert flow.site_axes(s) <= {"shard", "time"}, \
            (s.line, flow.site_axes(s))
    # the specs name mesh.axis_names: under the installed jax a positional
    # 0 means "replicated" (positional parsing itself is pinned by
    # test_graftlint.test_partition_spec_positional_indices)
    assert not any(sp.pos_entries for s in sites for sp in s.all_specs), \
        "positional spec entries are back in shardstore"


def test_shardstore_families_clean_and_nonvacuous(df):
    """The real modules sweep clean — and the SAME engine flags a
    mutated twin of the refresh idiom, so 'clean' is a checked verdict,
    not an unimplemented one."""
    import ast

    flow, mods = df
    spmd = [f for _, f in rules_spmd.check_project(mods, df=flow)
            if f.path == SHARDSTORE]
    assert not spmd, [f"{f.rule}:{f.line}" for f in spmd]
    # mutate: drop the same-statement rebind from the donated call —
    # the donate-of-live-state finding MUST appear
    path = os.path.join(package_root(), SHARDSTORE)
    with open(path) as f:
        src = f.read()
    mutated = src.replace(
        "        self._tsr, self._v, self._cv = _append_step(",
        "        _ignored = _append_step(")
    assert mutated != src
    from filodb_tpu.lint import ModuleSource, _parse_pragmas
    lines = mutated.splitlines()
    mod = ModuleSource(path=path, relpath=SHARDSTORE, source=mutated,
                       tree=ast.parse(mutated), lines=lines,
                       pragmas=_parse_pragmas(lines))
    finds = [f for _, f in rules_spmd.check_project([mod])
             if f.rule == "donation-safety"]
    assert finds, "donation-safety missed the un-rebound refresh twin"


# -- CI wiring: the v3 families flow through --json/--github/--changed-only

SPMD_VIOLATION = """
import functools
import jax
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(jax.devices(), ("shard",))

@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("shard"),),
                   out_specs=P())
def f(x):
    if jax.process_index() == 0:
        return jax.lax.psum(x, "shard")
    return x
"""


def test_v3_findings_flow_through_json_and_github(tmp_path):
    p = tmp_path / "viol.py"
    p.write_text(SPMD_VIOLATION)
    res = run_lint([str(p)], baseline=frozenset(),
                   check_contracts=False)
    js = res.to_json()
    assert js["exit_code"] == 1
    assert any(f["rule"] == "spmd-collective-balance"
               for f in js["findings"])
    from filodb_tpu.lint.ci_annotations import github_annotations
    lines = github_annotations(js)
    assert any(l.startswith("::error") and "spmd-collective-balance"
               in l for l in lines)


def test_v3_findings_respect_changed_only_scope(tmp_path):
    p = tmp_path / "viol.py"
    p.write_text(SPMD_VIOLATION)
    root = package_root()
    rel = os.path.relpath(str(p), root).replace(os.sep, "/")
    hit = run_lint([str(p)], baseline=frozenset(),
                   check_contracts=False,
                   report_only=frozenset({rel}))
    assert hit.findings
    miss = run_lint([str(p)], baseline=frozenset(),
                    check_contracts=False,
                    report_only=frozenset({"filodb_tpu/other.py"}))
    assert not miss.findings
