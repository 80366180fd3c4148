"""Pallas window-extract kernel parity tests (interpret mode on CPU; the
real-TPU compile is asked of the chip's compiler in
tests/test_tpu_compile.py and run on the chip by chip_smoke.py).

Brute-force oracle over random ragged series incl. duplicate timestamps,
boundary-coincident samples and empty windows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.query.pallas_kernels import (TR_PAD, combine3, split3,
                                             window_extract)


def _oracle(ts, vals, lens, step, window, T):
    S = ts.shape[0]
    cnt = np.zeros((S, T), np.int64)
    tlo = np.zeros((S, T), np.int64)
    thi = np.zeros((S, T), np.int64)
    vlo = np.zeros((S, T))
    vhi = np.zeros((S, T))
    for s in range(S):
        r_ts, r_v = ts[s, :lens[s]], vals[s, :lens[s]]
        for t in range(T):
            m = (r_ts >= t * step) & (r_ts <= t * step + window)
            cnt[s, t] = m.sum()
            if cnt[s, t]:
                i0 = np.argmax(m)
                i1 = len(m) - 1 - np.argmax(m[::-1])
                tlo[s, t], thi[s, t] = r_ts[i0], r_ts[i1]
                vlo[s, t], vhi[s, t] = r_v[i0], r_v[i1]
    return cnt, tlo, thi, vlo, vhi


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_window_extract_matches_bruteforce(seed):
    rng = np.random.default_rng(seed)
    S = int(rng.integers(1, 12))
    N = int(rng.integers(2, 150))
    T = int(rng.integers(1, 80))
    step = int(rng.integers(1_000, 120_000))
    window = int(rng.integers(1_000, 600_000))
    ts = np.sort(rng.integers(0, 3_000_000, (S, N))).astype(np.int64)
    lens = rng.integers(1, N + 1, S)
    vals = rng.normal(1e6, 1.0, (S, N))   # large offset stresses split3
    tr = ts.astype(np.int32)
    for i, n in enumerate(lens):
        tr[i, n:] = TR_PAD
    masked = np.where(np.arange(N)[None, :] < lens[:, None], vals, 0.0)
    pay = split3(jnp.asarray(masked)).astype(jnp.float32)
    cnt, tlo, thi, plo, phi = window_extract(
        jnp.asarray(tr), pay, step, window, T, interpret=True)
    v_lo = np.asarray(combine3(plo))
    v_hi = np.asarray(combine3(phi))
    ocnt, otlo, othi, ovlo, ovhi = _oracle(ts, vals, lens, step, window, T)
    np.testing.assert_array_equal(np.asarray(cnt), ocnt)
    has = ocnt >= 1
    np.testing.assert_array_equal(np.asarray(tlo)[has], otlo[has])
    np.testing.assert_array_equal(np.asarray(thi)[has], othi[has])
    # triple-f32 extraction must be bit-exact
    np.testing.assert_array_equal(v_lo[has], ovlo[has])
    np.testing.assert_array_equal(v_hi[has], ovhi[has])


def test_split3_exact_roundtrip():
    rng = np.random.default_rng(3)
    v = rng.normal(0, 1e12, (4, 64)) + rng.normal(0, 1e-6, (4, 64))
    s = split3(jnp.asarray(v))
    back = np.asarray(s[:, 0, :].astype(np.float64)
                      + s[:, 1, :].astype(np.float64)
                      + s[:, 2, :].astype(np.float64))
    np.testing.assert_array_equal(back, v)
