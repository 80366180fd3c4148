"""Tier-1 gate: the real package lints clean against the shipped
baseline — with the interprocedural concurrency families AND the v3
SPMD/cache families enabled at error severity — every pallas_call site
carries a verified contract, the baseline itself is empty (nothing
grandfathered), and a full run stays inside the pre-commit latency
budget."""

import json
import time

from filodb_tpu.lint import baseline_path, load_baseline, run_lint


def test_package_lints_clean_and_fast():
    t0 = time.monotonic()
    res = run_lint()        # full package, contracts included
    elapsed = time.monotonic() - t0
    assert res.files > 50
    msgs = [f.render() for f in res.findings]
    assert not msgs, "graftlint findings:\n" + "\n".join(msgs)
    # perf guard: the whole-program analysis (call graph + lock
    # propagation + contracts) must stay pre-commit-fast; ~4s on the
    # dev rig, 30s is the hard ceiling before it stops being run
    assert elapsed < 30.0, f"full lint run took {elapsed:.1f}s"


def test_concurrency_families_enabled_at_error():
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("lock-order-cycle", "lock-order-policy",
                "lock-blocking-reachable",
                "thread-unguarded-shared-state"):
        assert cat[rid].severity == "error"


def test_v3_families_enabled_at_error():
    """The four graftlint v3 families ride the tier-1 gate at error
    severity (donation-missing is the one deliberate advisory). The
    perf guard above covers them: run_lint() builds the shared call
    graph + dataflow layer with every v3 family enabled."""
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("spmd-collective-balance", "donation-safety",
                "partition-spec-consistency",
                "cache-invalidation-completeness",
                "cache-unregistered"):
        assert cat[rid].severity == "error"
    assert cat["donation-missing"].severity == "warning"


def test_v4_families_enabled_at_error():
    """The four graftlint v4 numerics families + the ulp-certification
    rail ride the tier-1 gate at error severity. The full run above
    exercises them: the tree sweep covers every traced/pallas body and
    check_contracts=True runs the certification rail over every
    @precision/@order_insensitive annotation (order claims at 1/2/4/8
    virtual devices)."""
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("precision-narrowing", "accumulation-bound",
                "reduction-order-determinism", "mixed-dtype-comparison",
                "ulp-certification"):
        assert cat[rid].severity == "error"


def test_v5_families_enabled_at_error():
    """The four graftlint v5 capacity families + the capacity-
    certification rail ride the tier-1 gate at error severity. The
    full run above exercises them: the residency dataflow sweeps every
    untraced function, the frontier rule holds any chooser that takes a
    vmem_budget to it, and check_contracts=True
    certifies every @capacity claim (sharded claims at 1/2/4/8 virtual
    devices)."""
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("hbm-residency-budget", "device-buffer-leak",
                "oversized-transfer", "vmem-frontier-budget",
                "capacity-certification"):
        assert cat[rid].severity == "error"
        assert cat[rid].family == "capacity"


def test_tree_annotations_all_certified():
    """Belt-and-braces alongside the run_lint sweep: the certification
    results themselves (memoized from the gate run) are all green."""
    from filodb_tpu.lint import ulpcert
    results = ulpcert.certify_all()
    assert len(results) >= 8
    bad = [r for r in results if not r.ok]
    assert not bad, bad


def test_tree_capacity_claims_all_certified():
    """Same for the v5 rail: every in-tree @capacity claim certifies
    (memoized from the gate run — the resident shardstore channels,
    tilestore tiles, executable constants, the tile cache, and the
    downsample staging buffers)."""
    from filodb_tpu.lint import memcert
    results = memcert.certify_all()
    assert len(results) >= 5
    bad = [r for r in results if not r.ok]
    assert not bad, bad


def test_shipped_baseline_is_empty():
    with open(baseline_path()) as f:
        data = json.load(f)
    assert data["findings"] == []
    assert load_baseline() == frozenset()


def test_every_pallas_call_site_has_contract():
    import importlib
    from filodb_tpu.lint.contracts import CONTRACTS
    for m in ("filodb_tpu.query.tilestore", "filodb_tpu.query.tpu",
              "filodb_tpu.downsample.kernels",
              "filodb_tpu.parallel.mesh"):
        importlib.import_module(m)
    names = {k[1] for k in CONTRACTS}
    # the fused group-sum's dispatcher and the counters'
    assert {"groupsum_dispatch", "counters_t_dispatch"} <= names
    # kernel entry points across the named modules
    assert {"window_endpoint", "window_gather", "downsample_gauge",
            "downsample_regular", "counter_emit_mask", "cascade_aligned",
            "mesh_grouped_reduce"} <= names
