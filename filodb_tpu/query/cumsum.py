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

import functools

import jax
import jax.numpy as jnp


# jitted: called eagerly (the tile build is op by op) the scan's fresh
# closure would otherwise retrace and recompile on EVERY call
@functools.partial(jax.jit, static_argnames=("axis",))
def cumsum_f64(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive cumulative sum of ``x`` along ``axis`` as a sequential
    scan (one carried row, ``x.shape[axis]`` steps)."""
    rows = jnp.moveaxis(x, axis, 0)
    if rows.shape[0] == 0:
        return x

    def step(carry, row):
        carry = carry + row
        return carry, carry

    # the carry starts as the first row (not as fresh zeros): it then has
    # x's own type under shard_map / vmap, whatever axes x varies over
    _, out = jax.lax.scan(step, rows[0], rows[1:], unroll=8)
    return jnp.moveaxis(jnp.concatenate([rows[:1], out], axis=0), 0, axis)
