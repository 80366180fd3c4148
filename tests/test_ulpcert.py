"""graftlint v4 ulp-certification rail: every numeric annotation in
the tree is dynamically certified (engine-as-assertion), order claims
run at 1/2/4/8 virtual devices, and a LYING annotation — the mutated
twin — is flagged by the rail. The annotations are real production
claims; these tests make the rail's teeth non-vacuous."""

import math

import numpy as np
import pytest

from filodb_tpu.lint import numerics as nmod
from filodb_tpu.lint import ulpcert


@pytest.fixture(scope="module")
def results():
    return {r.name: r for r in ulpcert.certify_all()}


def test_every_tree_annotation_is_certified(results):
    """Every @precision/@order_insensitive claim registered by the
    engine modules certifies against its declared tolerance."""
    nmod.import_annotated_modules()
    assert nmod.PRECISION and nmod.ORDER, "annotations disappeared"
    for name in list(nmod.PRECISION) + list(nmod.ORDER):
        assert name in results, f"claim {name!r} never certified"
        r = results[name]
        assert r.ok, (f"claim {name!r} failed certification: "
                      f"measured {r.measured} vs {r.claimed} "
                      f"({r.detail})")


def test_expected_claim_inventory(results):
    """The in-tree hybrid sites the issue names are all annotated —
    the counter fast/slide path, the f32 epilogue (instant division
    chain), the donated append carry, and both mesh psum
    collectives."""
    assert {"counter-fast-hybrid", "counter-slide-hybrid",
            "counter-epilogue-f32", "counter-exact-slot-index",
            "append-carry-exact", "extrapolated-rate-f64"} \
        <= set(nmod.PRECISION)
    assert {"grouped-reduce-psum", "grouped-pair-psum"} \
        <= set(nmod.ORDER)


def test_order_claims_ran_at_1_2_4_8_devices(results):
    """The acceptance pin: order-insensitivity is certified across the
    full virtual device sweep, not vacuously at one count."""
    for name in nmod.ORDER:
        r = results[name]
        assert r.device_counts == (1, 2, 4, 8), (name, r.device_counts)


def test_measured_values_leave_headroom(results):
    """The claims are tight-but-honest: measured error is nonzero
    where rounding exists (the certification is not comparing a
    function against itself) and under the claim with margin."""
    fast = results["counter-fast-hybrid"]
    assert 0 < fast.measured <= fast.claimed
    epi = results["counter-epilogue-f32"]
    assert 0 < epi.measured <= epi.claimed
    # exact claims certify bitwise
    assert results["append-carry-exact"].measured == 0.0


def test_mutated_twin_lying_precision_claim_is_flagged():
    """THE teeth test: register a claim whose tolerance the site
    cannot meet; the rail must fail it. Restores the registry and the
    memo so the surrounding suite sees the clean world."""
    saved_memo = ulpcert._MEMO
    claim = nmod.PrecisionClaim(
        name="lying-claim", bits=24, reason="deliberately wrong",
        rel_ulps=0.01, module="filodb_tpu.query.tilestore",
        qualname="lying")

    def lying_harness():
        ref = np.linspace(1.0, 2.0, 64)
        prod = (ref + 64 * np.spacing(ref.astype(np.float32),
                                      dtype=np.float64)
                ).astype(np.float32)       # ~64 ulps off
        return prod, ref, 0.0

    nmod.PRECISION["lying-claim"] = claim
    ulpcert.HARNESSES["lying-claim"] = ("precision", lying_harness)
    try:
        res = {r.name: r for r in ulpcert.certify_all(force=True)}
        r = res["lying-claim"]
        assert not r.ok and r.measured > r.claimed
        findings = ulpcert.check_certifications()
        assert any(f.rule == "ulp-certification"
                   and "lying-claim" in f.message
                   for _rel, f in findings)
    finally:
        del nmod.PRECISION["lying-claim"]
        del ulpcert.HARNESSES["lying-claim"]
        ulpcert._MEMO = saved_memo


def test_mutated_twin_lying_order_claim_is_flagged():
    """An order claim of byte-identity over a grouping-dependent f32
    sum must fail bitwise certification."""
    saved_memo = ulpcert._MEMO
    claim = nmod.OrderClaim(
        name="lying-order", tolerance=0.0,
        reason="claims bitwise, is not",
        module="filodb_tpu.parallel.mesh", qualname="lying")
    rng = np.random.default_rng(7)
    data = rng.uniform(0.1, 1.0, 4096).astype(np.float32)

    def lying_harness(ndev):
        # grouping-dependent f32 sum: the accumulation order
        # interleaves per-"device" lanes, so the rounding sequence
        # moves with the device count
        seq = data.reshape(ndev, -1).T.ravel()
        acc = np.float32(0.0)
        for x in seq:
            acc = np.float32(acc + x)
        return np.asarray([acc], dtype=np.float32)

    nmod.ORDER["lying-order"] = claim
    ulpcert.HARNESSES["lying-order"] = ("order", lying_harness)
    try:
        res = {r.name: r for r in ulpcert.certify_all(force=True)}
        assert not res["lying-order"].ok
    finally:
        del nmod.ORDER["lying-order"]
        del ulpcert.HARNESSES["lying-order"]
        ulpcert._MEMO = saved_memo


def test_annotation_without_harness_is_flagged():
    """An annotation the rail cannot evaluate is itself a failure —
    future hybrid sites must ship a harness with the claim."""
    saved_memo = ulpcert._MEMO
    claim = nmod.PrecisionClaim(
        name="orphan-claim", bits=24, reason="no harness",
        rel_ulps=1.0, module="filodb_tpu.query.tilestore",
        qualname="orphan")
    nmod.PRECISION["orphan-claim"] = claim
    try:
        res = {r.name: r for r in ulpcert.certify_all(force=True)}
        r = res["orphan-claim"]
        assert not r.ok and "no certification harness" in r.detail
    finally:
        del nmod.PRECISION["orphan-claim"]
        ulpcert._MEMO = saved_memo


def test_certification_rides_the_lint_gate():
    """run_lint (full, contracts on) carries ulp-certification
    findings — the rail IS tier-1, via tests/test_lint_clean.py."""
    from filodb_tpu.lint import rules
    cat = rules()
    assert cat["ulp-certification"].severity == "error"
    assert cat["ulp-certification"].family == "numerics"


def test_v4_families_registered_at_error():
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("precision-narrowing", "accumulation-bound",
                "reduction-order-determinism",
                "mixed-dtype-comparison", "ulp-certification"):
        assert cat[rid].severity == "error"
        assert cat[rid].family == "numerics"


def test_claim_lookup_and_rel_bound():
    """The certified epilogue claim exposes the bound the mesh-serving
    instant pin uses: rel_ulps f32 ulps, doubled across two
    independently-lowered programs."""
    c = nmod.precision_claim("counter-epilogue-f32")
    assert c.bits == 24 and c.rel_ulps == 4
    assert c.rel_bound() == pytest.approx(4 * 2.0 ** -23)
    assert c.rel_bound(cross_program=True) == \
        pytest.approx(8 * 2.0 ** -23)
    o = nmod.order_claim("grouped-reduce-psum")
    assert 0 < o.tolerance <= 1e-12


def test_duplicate_claim_name_rejected():
    from filodb_tpu.lint.numerics import precision
    with pytest.raises(ValueError):
        @precision("counter-fast-hybrid", bits=24, rel_ulps=1,
                   reason="collides with the tilestore claim")
        def other():
            pass


def test_empty_reason_rejected():
    from filodb_tpu.lint.numerics import order_insensitive, precision
    with pytest.raises(ValueError):
        precision("x", bits=24, reason="  ")
    with pytest.raises(ValueError):
        order_insensitive("y", tolerance=0.0, reason="")
