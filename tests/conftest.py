"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths can be exercised without TPU hardware (mirrors the reference's
sbt-multi-jvm strategy of multi-node tests without a real cluster —
reference: project/FiloBuild.scala:100).

The platform is set with jax.config.update as well as through the driver's
JAX_PLATFORMS=cpu, so a bare ``pytest`` run stays on the CPU too.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# let the CPU test node take the one-device fused programs; production
# CPU nodes keep the flag off (tpu.py gate) and serve on the host
from filodb_tpu.query import tpu as _tpu  # noqa: E402

_tpu.FUSED_GROUPSUM_INTERPRET = True
