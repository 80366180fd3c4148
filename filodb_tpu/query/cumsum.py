"""The one f64 cumulative sum every device counter path shares.

``jnp.cumsum`` lowers to a reduce-window, and the TPU compiler's
expansion of an f64 reduce-window takes minutes for an [8, 128] input
and grows with the shape (f64 is emulated on the chip). A ``lax.scan``
carrying one row compiles in about a second at any length, stays in
f64, and adds in the same left-to-right order as ``np.cumsum`` — so the
device channel is bit-for-bit what the host codecs and the numpy
oracle compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cumsum_f64(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive cumulative sum of ``x`` along ``axis`` as a sequential
    scan (one carried row, ``x.shape[axis]`` steps)."""
    rows = jnp.moveaxis(x, axis, 0)

    def step(carry, row):
        carry = carry + row
        return carry, carry

    _, out = jax.lax.scan(step, jnp.zeros(rows.shape[1:], x.dtype), rows,
                          unroll=8)
    return jnp.moveaxis(out, 0, axis)
