"""Benchmark: PromQL `sum(rate(counter[5m])) by (job)` on device — the
BASELINE.json north-star workload at a scaled shape.

Shape: 65,536 series x 8h at 10s scrape (2,880 samples) = 188.7M samples
resident as aligned device tiles; the query grid covers the whole span
(475 steps at 60s, 5m windows). This is 1/57th of the full north star
(10M series x 24h on v5e-8); the printed extrapolation states what the
measured per-chip throughput implies for that target.

Path measured: the production fused Pallas group-sum kernel
(`pallas_kernels.counter_groupsum`, dispatched by
`tilestore.groupsum_counters`): the whole `sum by` of `rate` runs as
ONE pass — per step-tile, the window-end and window-start boundary
families ride ONE merged DMA (they share a stride-residue plane when
the window is a whole number of steps), the jitter-fallback families
are separate streams only for queries whose grid phase straddles the
tile's max scrape jitter, the f32 extrapolation epilogue runs in VMEM
on int32 relative timestamps + exact 2xint32 fixed-point boundary
deltas, and group sums/counts leave the chip as [T, G] only. The K
chained queries sweep grid phases 0..±5s, so the measured mix
exercises both the full 3-stream path and the phase-elided 2-stream
path the way a population of dashboards would. Parity vs the f64
oracle is asserted ON DEVICE every run (parity_max_rel_err below; the
compiled Mosaic kernel's group sums vs the same-algorithm numpy f64
oracle at 1e-5), so a miscompile cannot ship a green number. XLA
formulations of the same computation measured 5.5-12ms/query: row
gathers run at ~140 GB/s, and the [T, S] rate intermediate + its
grouping consumers cost an extra materialization pass.

Honesty notes:
- Data is generated ON DEVICE (3 GB of tiles need not cross the host
  link). Tile build + compile are excluded (warm store, like the
  reference's QueryInMemoryBenchmark which also measures a warm
  in-memory store).
- K queries with shifted step grids are chained in one program; each rep
  is timed on the host clock around a forced host transfer of the
  result, and nothing is subtracted.
- This process is the only one that touches the chip: it starts no child
  process. The multi-chip dry run (`__graft_entry__.py`) and the served
  path's benchmark (`benchmarks/run.py`) run as their own processes.
- `vs_baseline` divides by a BATCHED numpy oracle (the same aligned
  prefix-sum/boundary algorithm vectorized over a 8,192-series
  subsample, no per-series Python loop), not an interpreter-bound loop.

Prints ONE JSON line.
"""

import json
import sys
import time

import numpy as np


def _mark(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from filodb_tpu.query.cumsum import cumsum_f64  # noqa: E402

S = 65_536          # series
N = 2_880           # slots = 8h at 10s
DT = 10_000
WINDOW = 300_000
STEP = 60_000
N_GROUPS = 16
K = 32              # chained shifted-grid queries in one program
BASE = 1_600_000_000_000


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _gen_device():
    """Tiles generated on device: jittered timestamps + counter values."""
    key = jax.random.PRNGKey(42)
    k1, k2 = jax.random.split(key)

    @jax.jit
    def gen():
        jit_ms = jax.random.uniform(k1, (S, N), dtype=jnp.float64,
                                    minval=-2000, maxval=2000)
        ts = BASE + jnp.arange(N, dtype=jnp.float64)[None, :] * DT + jit_ms
        incs = jax.random.uniform(k2, (S, N), dtype=jnp.float64,
                                  minval=0.0, maxval=5.0)
        vals = cumsum_f64(incs, axis=1)
        return ts, vals
    ts, vals = gen()
    return jax.block_until_ready(ts), jax.block_until_ready(vals)


def main():
    from filodb_tpu.query import pallas_kernels as pk
    from filodb_tpu.query import tilestore as tst

    ts, vals = _gen_device()
    tiles = tst.AlignedTiles([{} for _ in range(S)], BASE, DT,
                             np.ones((S, N), bool), ts, vals)
    del ts, vals
    # warm the kernel's s-tile-major stride-permuted channels (tile-store
    # pack time, excluded like the reference's warm store), staged so
    # intermediates free before the next build step (the full chain would
    # transiently exceed HBM at this shape)
    ST = STEP // DT
    DSPAN = WINDOW // STEP
    J = 2000                                # generator's jitter bound
    cv_t = tiles.t_channel("cv")
    cv_t.block_until_ready()
    tiles._channels.clear()
    tiles.vals = None                       # cv is cached transposed
    v_p = tiles.t_perm_fixed_tiled("cv", ST)   # needs ts/valid (ts plane)
    base = tiles.t_fixed_base("cv")
    v_p.block_until_ready()
    del cv_t
    tiles.ts = tiles.valid = None
    tiles._tch.clear()
    tiles._tperm.clear()

    # grid covers the whole span, minus headroom for the per-rep
    # whole-slot shifts (max 28 slots) and the per-query phase offsets
    T = (N * DT - WINDOW - 300_000) // STEP
    SG = S // N_GROUPS                      # group-contiguous series
    onehot = jnp.zeros((S, N_GROUPS), jnp.float32).at[
        jnp.arange(S), jnp.arange(S) // SG].set(1.0)

    # the K chained queries shift the grid phase by 0..15s in 1s steps
    # (static per query, like distinct dashboards); the per-rep shift
    # moves whole slots so each rep reads different tile rows. Modes are
    # the same static jitter-phase elision groupsum_counters derives
    # (bench calls the kernel directly because kc0/kl0 stay traced).
    def _modes(o_k):
        c_k = (o_k + DT // 2) // DT
        phase = o_k - c_k * DT              # == w0e_rel - kc0*DT
        hi = (pk.GS_CUR if phase >= J else
              pk.GS_ALT if phase < -J else pk.GS_BOTH)
        lo = (pk.GS_CUR if -phase >= J else
              pk.GS_ALT if -phase < -J else pk.GS_BOTH)
        return c_k, hi, lo

    # group the K phase configs by their static mode pair so each pair
    # compiles ONE Pallas kernel (driven by lax.scan over the per-query
    # slot/phase params) instead of K instantiations
    groups: dict = {}
    for k in range(K):
        c_k, hi_mode, lo_mode = _modes(k * 1000)
        groups.setdefault((hi_mode, lo_mode), []).append((k, c_k))

    @jax.jit
    def many(shift_slots, v_p, base, oh):
        acc = jnp.zeros((T, N_GROUPS), jnp.float32)
        for (hi_mode, lo_mode), ks in sorted(groups.items()):
            kl0s = jnp.asarray([WINDOW // DT + c_k - DSPAN * ST
                                for _, c_k in ks], jnp.int32) \
                + shift_slots
            w0es = jnp.asarray([WINDOW + o * 1000 for o, _ in ks],
                               jnp.int32) + shift_slots * DT

            def body(a, p, hi=hi_mode, lo=lo_mode):
                kl0, w0e_rel = p
                sums, cnts = pk.counter_groupsum(
                    "rate", ST, DSPAN, hi, lo, v_p, base, oh,
                    kl0, w0e_rel, WINDOW, STEP, T)
                return a + jnp.where(cnts > 0, sums, 0.0), jnp.int32(0)
            acc, _ = jax.lax.scan(body, acc, (kl0s, w0es))
        return acc.T

    _mark("compiling query chain")
    np.asarray(many(jnp.int32(0), v_p, base, onehot))   # compile
    _mark("compiled; measuring")
    # host clock around a forced host transfer of the [G, T] result
    runs = [_timed(lambda: np.asarray(
        many(jnp.int32(i * 7), v_p, base, onehot))) / K for i in range(5)]
    per_query_p50 = float(np.median(runs))
    # samples one query's windows cover: the union of T sliding windows
    # of DSPAN*ST+1 slots stepping ST
    scanned = S * (DSPAN * ST + 1 + (T - 1) * ST)
    device_sps = scanned / per_query_p50

    # bytes the kernel actually reads per query, averaged over the K
    # phase configs: the merged kc/kl stream always, plus one
    # (tt+AL)-row fallback stream per non-elided side; 3 planes (i32
    # ts + fixed-point hi/lo) per row. tt/pipeline depth are per-query
    # (the _gs_pipeline chooser widens tiles when VMEM allows).
    rows_per_step = 0.0
    for k in range(K):
        _, hi_mode, lo_mode = _modes(k * 1000)
        tt_k, _nb = pk._gs_pipeline(ST, DSPAN, hi_mode, lo_mode, T,
                                    N_GROUPS)
        mlen_k = pk._gs_mlen(ST, DSPAN, tt_k)
        rows_per_step += (mlen_k + (tt_k + pk._GS_AL)
                          * ((hi_mode != pk.GS_CUR)
                             + (lo_mode != pk.GS_CUR))) / tt_k
    touched = int(T * S * 12 * (rows_per_step / K))
    hbm_gbps = touched / per_query_p50 / 1e9

    # --- on-device compiled-kernel parity gate -------------------------
    # the SAME compiled kernel shape (masked one-hot selecting the first
    # S_par series into 16 contiguous groups) vs the numpy f64 oracle;
    # guards the only link tests can't cover: Mosaic compilation on the
    # real chip (tests run the kernel in interpret mode)
    S_par = 8_192
    gpar = S_par // N_GROUPS
    oh_par = jnp.zeros((S, N_GROUPS), jnp.float32).at[
        jnp.arange(S_par), jnp.arange(S_par) // gpar].set(1.0)

    @jax.jit
    def one_query(v_p, base, oh):
        kc0 = jnp.int32(WINDOW // DT)
        return pk.counter_groupsum(
            "rate", ST, DSPAN, pk.GS_BOTH, pk.GS_BOTH, v_p, base, oh,
            kc0 - DSPAN * ST, jnp.int32(WINDOW), WINDOW, STEP, T)

    _mark("parity gate")
    sums_par, cnts_par = one_query(v_p, base, oh_par)
    sums_par = np.asarray(sums_par)

    # batched numpy oracle (same algorithm, vectorized, subsampled) —
    # doubles as the parity reference for the on-device gate above
    S_cpu = S_par
    # un-permute the ts plane (lanes 0:SS) of the packed tile:
    # [n_s, st, G, 3SS] with slot k of series (si*SS + j) at
    # [si, k % st, k // st, j]
    n_keep = S_cpu // pk._GS_SS
    perm_h = np.asarray(v_p[:n_keep, :, :, :pk._GS_SS])
    ts_h = perm_h.transpose(0, 3, 2, 1).reshape(
        S_cpu, -1)[:, :N].astype(np.float64) + BASE
    vals_raw = _gen_vals_host(S_cpu)
    vals_h = vals_raw
    t0 = time.perf_counter()
    want_par = _oracle_batched(ts_h, vals_h, T)      # [G, T] f64
    oracle_sps = S_cpu * N / (time.perf_counter() - t0)

    err = np.abs(sums_par - want_par.T)
    denom = np.maximum(np.abs(want_par.T), 1e-30)
    parity_max_rel_err = float((err / denom).max())
    assert np.all(np.asarray(cnts_par) > 0)
    assert parity_max_rel_err < 1e-5, (
        f"compiled-kernel parity vs f64 oracle failed: "
        f"{parity_max_rel_err}")

    full_series = 10_000_000
    full_samples = full_series * 8_640      # 24h at 10s
    chips = 8
    est_full_ms = full_samples / chips / device_sps * 1000.0

    del v_p, tiles

    # capacity ledger (graftlint v5): certify the @capacity inventory
    # in-process and write CAPACITY.json beside this line; the resident
    # numbers below price the CERTIFIED shardstore claim at this bench
    # shape (pow2 slot capacity over N — padding is real HBM), the
    # baseline the compressed-chunks work must move (ROADMAP item 1)
    _mark("capacity certification + ledger")
    from filodb_tpu.lint import memcert
    from filodb_tpu.lint.capacity import capacity_claim
    from filodb_tpu.parallel.shardstore import _next_pow2
    ledger = memcert.capacity_ledger(samples_per_series=N)
    assert all(row["certified"] for row in ledger), \
        [r["family"] for r in ledger if not r["certified"]]
    dev = jax.devices()[0]
    hbm_bytes = dev.memory_stats()["bytes_limit"]   # asked, not assumed
    with open("CAPACITY.json", "w") as f:
        json.dump({"samples_per_series": N,
                   "device_kind": dev.device_kind,
                   "hbm_bytes_per_chip": hbm_bytes,
                   "families": ledger}, f, indent=2, sort_keys=True)
        f.write("\n")
    cl = capacity_claim("shardstore-resident-channels")
    cap_slots = _next_pow2(N, 64)
    resident_bps = round(cl.bytes_per_sample * cap_slots / N, 2)
    projected_spc = cl.projected_series_per_chip(cap_slots, hbm_bytes)

    print(json.dumps({
        "metric": "rate_sum_by_samples_scanned_per_sec",
        "value": round(device_sps),
        "unit": "samples/s",
        "vs_baseline": round(device_sps / oracle_sps, 2),
        "per_query_p50_ms": round(per_query_p50 * 1000, 2),
        "shape": f"{S}x{N} (8h@10s), T={T}, window=5m",
        "hbm_read_gbps": round(hbm_gbps, 1),
        "parity_max_rel_err": parity_max_rel_err,
        "northstar_est_ms_v5e8": round(est_full_ms, 1),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        # certified residency (graftlint v5 capacity rail): bytes per
        # LOGICAL sample at this shape (the 20 B/padded-slot shardstore
        # claim times the pow2 capacity pad) and the resident-series
        # ceiling this chip's reported HBM implies at 8h@10s retention
        "resident_bytes_per_sample": resident_bps,
        "projected_series_per_chip": projected_spc,
        "hbm_bytes_per_chip": hbm_bytes,
    }))


def _gen_vals_host(s_cpu):
    """Regenerate the first s_cpu series' RAW values host-side for the
    oracle (the device tiles hold the reset-corrected channel)."""
    key = jax.random.PRNGKey(42)
    _, k2 = jax.random.split(key)
    incs = jax.random.uniform(k2, (S, N), dtype=jnp.float64,
                              minval=0.0, maxval=5.0)[:s_cpu]
    return np.cumsum(np.asarray(incs), axis=1)


def _oracle_batched(ts, vals, T):
    """Batched numpy rate + grouped sum: the aligned-slot algorithm with
    fancy indexing — no per-series Python loop."""
    Sb, Nb = vals.shape
    prev = np.concatenate([np.full((Sb, 1), np.nan), vals[:, :-1]], axis=1)
    drop = vals < prev
    cv = vals + np.cumsum(np.where(drop, prev, 0.0), axis=1)
    ps = np.concatenate([np.zeros((Sb, 1)), np.cumsum(
        np.ones_like(vals), axis=1)], axis=1)
    t = np.arange(T, dtype=np.int64)
    wend = BASE + WINDOW + t * STEP
    wstart = wend - WINDOW
    k_hi = np.floor((wend - BASE + DT / 2.0) / DT).astype(np.int64)
    k_lo = np.ceil((wstart - BASE - DT / 2.0) / DT).astype(np.int64)
    khc = np.clip(k_hi, 0, Nb - 1)
    khp = np.clip(k_hi - 1, 0, Nb - 1)
    klc = np.clip(k_lo, 0, Nb - 1)
    kln = np.clip(k_lo + 1, 0, Nb - 1)
    cnt = ps[:, np.clip(k_hi, -1, Nb - 1) + 1] - ps[:, np.clip(k_lo, 0, Nb)]
    cnt -= (ts[:, khc] > wend[None, :])
    cnt -= (ts[:, klc] < wstart[None, :])
    use1 = ts[:, khc] <= wend[None, :]
    t2 = np.where(use1, ts[:, khc], ts[:, khp])
    v2 = np.where(use1, cv[:, khc], cv[:, khp])
    useb = ts[:, klc] >= wstart[None, :]
    t1 = np.where(useb, ts[:, klc], ts[:, kln])
    v1 = np.where(useb, cv[:, klc], cv[:, kln])
    sampled = (t2 - t1) / 1000.0
    delta = v2 - v1
    with np.errstate(all="ignore"):
        # Prometheus extrapolatedRate (RateFunctions.scala:23-79): gaps
        # under 1.1x the average sample interval extrapolate to the
        # window boundary; larger gaps add half an interval. The branch
        # is decided EXACTLY on integer milliseconds (10*(cnt-1)*gap <=
        # 11*sampled) — the same deterministic rule the Pallas kernel
        # uses; f64-in-seconds would resolve exact ties by rounding dust
        avg = sampled / (cnt - 1.0)
        ds_ms = t1 - wstart[None, :]
        de_ms = wend[None, :] - t2
        s11 = 11.0 * (t2 - t1)
        use_ds = 10.0 * (cnt - 1.0) * ds_ms <= s11
        use_de = 10.0 * (cnt - 1.0) * de_ms <= s11
        th = avg * 1.1
        ds = ds_ms / 1000.0
        dzero = np.where((delta > 0) & (v1 >= 0),
                         sampled * v1 / delta, np.inf)
        zlt = dzero < ds
        ds = np.where(zlt, dzero, ds)
        use_ds = np.where(zlt, dzero < th, use_ds)
        ext = sampled + np.where(use_ds, ds, avg * 0.5) \
            + np.where(use_de, de_ms / 1000.0, avg * 0.5)
        rate = delta * (ext / sampled) / (WINDOW / 1000.0)
        rate = np.where(cnt >= 2, rate, np.nan)
    g = Sb // N_GROUPS
    ok = ~np.isnan(rate)
    return np.where(ok, rate, 0.0).reshape(N_GROUPS, g, T).sum(axis=1)


if __name__ == "__main__":
    main()
