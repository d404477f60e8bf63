"""``python -m tools.mgxla`` entry point.

The forced multi-device mesh must exist BEFORE jax initializes, so the
env plumbing happens here — prior to any import that could pull jax in.
Contracts are structural properties of the lowered programs, so the
checker always runs them against the CPU backend with 8 virtual
devices: the same artifact shapes the tests validate, available on
every dev box and in CI.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

from .cli import main  # noqa: E402

sys.exit(main())
