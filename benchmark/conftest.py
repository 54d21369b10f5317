"""The benchmark's tests see four virtual CPU devices, so that a cell
that asks for four chips is rehearsed like the others. Set before JAX
is imported; a run that sets the device count itself keeps its own."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()
