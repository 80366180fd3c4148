"""graftlint v5 capacity-certification rail: every @capacity residency
claim in the tree is dynamically certified (live-buffer walk against
the declared bytes budget), sharded claims run at 1/2/4/8 virtual
devices, and a LYING claim — the mutated twin — is flagged by the
rail. The annotations are real production claims; these tests make the
rail's teeth non-vacuous."""

import math

import pytest

from filodb_tpu.lint import capacity as cmod
from filodb_tpu.lint import memcert


@pytest.fixture(scope="module")
def results():
    return {r.name: r for r in memcert.certify_all()}


def test_every_tree_claim_is_certified(results):
    """Every @capacity claim registered by the engine modules
    certifies against its declared bytes budget."""
    cmod.import_annotated_modules()
    assert cmod.CAPACITY, "annotations disappeared"
    for name in cmod.CAPACITY:
        assert name in results, f"claim {name!r} never certified"
        r = results[name]
        assert r.ok, (f"claim {name!r} failed certification: "
                      f"measured {r.measured} vs {r.claimed} "
                      f"({r.detail})")


def test_expected_claim_inventory(results):
    """The resident inventory the issue names is all annotated — the
    shardstore slot-major channels, the tilestore aligned tiles, the
    packed-executable constants, the backend tile cache, and the
    downsample staging buffers."""
    assert {"shardstore-resident-channels", "tilestore-aligned-tiles",
            "tilestore-executable-constants", "device-tile-cache",
            "downsample-pack-buffers"} <= set(cmod.CAPACITY)


def test_sharded_claim_ran_at_1_2_4_8_devices(results):
    """The acceptance pin: shard-alignment padding is priced at every
    mesh width, not vacuously at one count."""
    r = results["shardstore-resident-channels"]
    assert r.device_counts == (1, 2, 4, 8), r.device_counts


def test_measured_bytes_are_real_and_tight(results):
    """The claims are tight-but-honest: the walk measures real live
    buffers (nonzero) and the claim sits within the 1.25x band."""
    for name, r in results.items():
        assert 0 < r.measured <= r.claimed <= \
            memcert.OVERCLAIM_RATIO * r.measured, (name, r)
    # the shardstore channels price 20 B per padded slot exactly, and
    # its programs' constants int64[3] once
    st = results["shardstore-resident-channels"]
    assert st.measured == st.claimed == 20 * st.n_samples + 24


def test_mutated_twin_understated_claim_is_flagged():
    """THE teeth test: register a claim smaller than the store it
    covers; the rail must fail it and surface a capacity-certification
    finding. Restores the registry and the memo so the surrounding
    suite sees the clean world."""
    saved_memo = memcert._MEMO
    claim = cmod.CapacityClaim(
        name="lying-claim", bytes_per_sample=1.0,
        reason="deliberately understates the store",
        module="filodb_tpu.query.tilestore", qualname="lying")

    def lying_harness():
        # the "store" holds 4096 device bytes but the claim covers
        # 64 x 1 B — residency above budget
        return 4096, 64, 1

    cmod.CAPACITY["lying-claim"] = claim
    memcert.HARNESSES["lying-claim"] = lying_harness
    try:
        res = {r.name: r for r in memcert.certify_all(force=True)}
        r = res["lying-claim"]
        assert not r.ok and r.measured > r.claimed
        findings = memcert.check_certifications()
        assert any(f.rule == "capacity-certification"
                   and "lying-claim" in f.message
                   for _rel, f in findings)
    finally:
        del cmod.CAPACITY["lying-claim"]
        del memcert.HARNESSES["lying-claim"]
        memcert._MEMO = saved_memo


def test_mutated_twin_slack_claim_is_flagged():
    """A claim padding more than 25% over the measured footprint fails
    too — slack claims hide regressions the way slack ULP tolerances
    do."""
    saved_memo = memcert._MEMO
    claim = cmod.CapacityClaim(
        name="slack-claim", bytes_per_sample=1000.0,
        reason="pads 1000x over reality",
        module="filodb_tpu.query.tilestore", qualname="slack")
    cmod.CAPACITY["slack-claim"] = claim
    memcert.HARNESSES["slack-claim"] = lambda: (64, 64, 1)
    try:
        res = {r.name: r for r in memcert.certify_all(force=True)}
        r = res["slack-claim"]
        assert not r.ok and r.claimed > \
            memcert.OVERCLAIM_RATIO * r.measured
    finally:
        del cmod.CAPACITY["slack-claim"]
        del memcert.HARNESSES["slack-claim"]
        memcert._MEMO = saved_memo


def test_claim_without_harness_is_flagged():
    """An annotation the rail cannot evaluate is itself a failure —
    future resident stores must ship a harness with the claim."""
    saved_memo = memcert._MEMO
    claim = cmod.CapacityClaim(
        name="orphan-claim", bytes_per_sample=8.0, reason="no harness",
        module="filodb_tpu.query.tilestore", qualname="orphan")
    cmod.CAPACITY["orphan-claim"] = claim
    try:
        res = {r.name: r for r in memcert.certify_all(force=True)}
        r = res["orphan-claim"]
        assert not r.ok and "no certification harness" in r.detail
        assert not math.isfinite(r.measured)
    finally:
        del cmod.CAPACITY["orphan-claim"]
        memcert._MEMO = saved_memo


def test_device_bytes_walk_dedups_aliases():
    """Aliased references to one buffer count once; host numpy does
    not count at all."""
    import jax.numpy as jnp
    import numpy as np
    arr = jnp.zeros((64,), jnp.float64)

    class Box:
        pass

    b = Box()
    b.a = arr
    b.alias = arr
    b.host = np.zeros((1024,))
    b.nest = {"again": [arr, (arr,)]}
    assert memcert.device_bytes(b) == arr.nbytes


def test_capacity_ledger_rows(results):
    """The ledger renders one certified row per family with the
    projected resident series per 16 GB chip — the baseline the
    compressed-chunks work must move."""
    rows = {row["family"]: row for row in memcert.capacity_ledger()}
    assert set(rows) == set(cmod.CAPACITY)
    st = rows["shardstore-resident-channels"]
    assert st["certified"] and st["sharded"]
    assert st["measured_bytes"] == results[
        "shardstore-resident-channels"].measured
    assert st["projected_series_per_chip_16gb"] == \
        (16 << 30) // (20 * 2880)
    assert st["device_counts"] == [1, 2, 4, 8]


def test_certification_rides_the_lint_gate():
    """run_lint (full, contracts on) carries capacity-certification
    findings — the rail IS tier-1, via tests/test_lint_clean.py."""
    from filodb_tpu.lint import rules
    cat = rules()
    assert cat["capacity-certification"].severity == "error"
    assert cat["capacity-certification"].family == "capacity"


def test_v5_families_registered_at_error():
    from filodb_tpu.lint import rules
    cat = rules()
    for rid in ("hbm-residency-budget", "device-buffer-leak",
                "oversized-transfer", "vmem-frontier-budget",
                "capacity-certification"):
        assert cat[rid].severity == "error"
        assert cat[rid].family == "capacity"


def test_claim_lookup_and_projection():
    """The certified shardstore claim exposes the per-chip projection
    the ledger emits."""
    c = cmod.capacity_claim("shardstore-resident-channels")
    assert c.sharded and c.bytes_per_sample == 20.0
    assert c.claimed_total(1024, 16) == pytest.approx(
        20.0 * 1024 + c.bytes_per_series * 16 + c.overhead_bytes)
    assert c.projected_series_per_chip(2880) == \
        int((cmod.HBM_BYTES_PER_CHIP - c.overhead_bytes)
            // (20.0 * 2880 + c.bytes_per_series))


def test_duplicate_claim_name_rejected():
    with pytest.raises(ValueError):
        @cmod.capacity("shardstore-resident-channels",
                       bytes_per_sample=1.0,
                       reason="collides with the shardstore claim")
        def other():
            pass


def test_empty_reason_rejected():
    with pytest.raises(ValueError):
        cmod.capacity("x", bytes_per_sample=1.0, reason="  ")


def test_residency_gauge_collector():
    """Annotated stores report live device bytes through the
    filodb_device_memory_bytes{family,shard} gauge (satellite 2)."""
    from filodb_tpu.obs import metrics as obs_metrics
    cmod.ensure_residency_collector()
    cmod.record_resident("memcert-test-family", "3", 0xBEEF, 12345)
    try:
        snap = cmod.residency_snapshot()
        assert snap["memcert-test-family"]["3"] == 12345
        b = obs_metrics.ExpositionBuilder()
        obs_metrics.GLOBAL_REGISTRY.collect_into(b)
        text = b.render()
        assert ('filodb_device_memory_bytes{family="memcert-test-'
                'family",shard="3"} 12345') in text
    finally:
        cmod.drop_resident("memcert-test-family", "3", 0xBEEF)
    assert "memcert-test-family" not in cmod.residency_snapshot()


def test_shardstore_records_residency():
    """A live ShardedTiles reports its channel bytes under its shard
    count, and dropping the store drops the bytes."""
    import gc

    from filodb_tpu.parallel.shardstore import ShardedTiles
    st = ShardedTiles(memcert._shard_mesh(1), memcert._seed_tiles())
    fam = "shardstore-resident-channels"
    snap = cmod.residency_snapshot()
    assert snap.get(fam, {}).get("1", 0) >= st.cap * st.S_pad * 20
    del st
    gc.collect()
    assert cmod.residency_snapshot().get(fam, {}).get("1", 0) == 0
