"""Downsampler kernel benchmark: >= 1B raw samples -> 5m + 1h resolutions
on one chip (BASELINE.md target #3; reference harness
spark-jobs BatchDownsampler over Cassandra splits).

Data is generated on device (in production chunks stream in once and
downsampling is compute-bound; a host->device copy of the batches would
time the link, not the kernel). Timing forces a host sync through a small
checksum transfer per batch. Prints ONE JSON line.
"""

import json
import time

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from filodb_tpu.downsample import kernels  # noqa: E402

S, N = 8_192, 16_384          # 134M samples per batch
BATCHES = 8                   # 1.074B total
DT = 10_000                   # 10s cadence
RESOLUTIONS = (300_000, 3_600_000)


def _gen_batch(seed):
    """Jittered gauge tiles generated on device."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    jitter = jax.random.randint(k1, (S, N), -2000, 2000, dtype=jnp.int32)
    ts = (jnp.arange(1, N + 1, dtype=jnp.int64) * DT)[None, :] \
        + jitter.astype(jnp.int64)
    # |jitter| < DT/2 keeps rows sorted by construction (an explicit
    # i64 sort is software-emulated on TPU and dominates the bench)
    vals = jax.random.normal(k2, (S, N), dtype=jnp.float64) * 10.0 + 50.0
    lens = jnp.full((S,), N, dtype=jnp.int32)
    return ts, vals, lens


def measure(batches_total=BATCHES, reps=2):
    base = np.int64(0)
    span = (N + 1) * DT
    res5, res1h = RESOLUTIONS
    nper5 = int(span // res5) + 1
    nper1h = int(span // res1h) + 1
    # worst-case samples per 5m period with +-2s jitter: 300s/8s + slack
    WB5 = 64
    WB1H = 16        # 12 sub-periods per hour

    def both(b):
        """Finest level from raw, 1h cascaded from 5m (the job's shape).
        Regular-cadence reshape path (the gather kernel is the ragged
        fallback; cadence passed explicitly — the generator guarantees
        it, and the host gate would pull the ts tile back to the host)."""
        fine = kernels.downsample_gauge_fast(
            b[0], b[1], b[2], base, res5, nper5, cadence=(DT, DT))
        coarse = kernels.cascade_gauge_aligned(fine, res1h // res5, 0)
        return fine, coarse

    @jax.jit
    def _checksum(fine0, coarse0):
        return jnp.nansum(fine0[:8]) + jnp.nansum(coarse0[:8])

    t0c = time.perf_counter()
    # a few resident batches (8 would exceed HBM), alternated —
    # per-batch kernel work is data-independent, so throughput is honest
    batches = [jax.block_until_ready(_gen_batch(i))
               for i in range(min(2, batches_total))]
    f, c = both(batches[0])
    # compile EVERYTHING outside the timed region, including the
    # checksum sync op — an op-by-op compile costs seconds and would
    # dominate the measurement
    float(np.asarray(_checksum(f[0], c[0])))
    compile_s = time.perf_counter() - t0c

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        acc = 0.0
        for i in range(batches_total):
            b = batches[i % len(batches)]
            fine, coarse = both(b)
            acc += float(np.asarray(_checksum(fine[0], coarse[0])))  # sync
        best = min(best, time.perf_counter() - t0)
    total = S * N * batches_total
    sps = total / best

    # numpy oracle on a small subsample, extrapolated
    ts0 = np.asarray(batches[0][0][0])
    vs0 = np.asarray(batches[0][1][0])
    t0 = time.perf_counter()
    for res in RESOLUTIONS:
        nper = int(span // res) + 1
        kernels.downsample_gauge_oracle(ts0, vs0, 0, res, nper)
    oracle_sps = N / (time.perf_counter() - t0)

    return ({
        "metric": "downsample_raw_samples_per_sec",
        "value": round(sps),
        "unit": "samples/s",
        "vs_baseline": round(sps / oracle_sps, 2),
        "total_samples": total,
        "resolutions_ms": list(RESOLUTIONS),
        "compile_s": round(compile_s, 1),
    })


def main():
    print(json.dumps(measure()))


if __name__ == "__main__":
    main()
