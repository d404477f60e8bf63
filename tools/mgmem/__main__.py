"""``python -m tools.mgmem`` entry point.

Memory facts come from the SAME forced 8-virtual-device CPU mesh the
mgxla contract checker lowers on, so the env plumbing must happen
BEFORE any import that could pull jax in.
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
