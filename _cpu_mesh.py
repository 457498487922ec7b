"""Force the CPU backend with n virtual XLA devices — shared preamble.

Used by tests/conftest.py and __graft_entry__.dryrun_multichip: the device
count flag must be in XLA_FLAGS, and jax_platforms pinned, before the first
backend touch (backend init happens at the first jax.devices()/jit call, not
at import). Keep this module import-light; it must be safe to import first.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_mesh(n_devices: int, assert_count: bool = True) -> None:
    """Pin jax to the CPU platform with >= n_devices virtual devices.

    Must run before any backend initialization in this process. Also sets
    the env vars so subprocesses inherit the same platform.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if existing is None:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(existing.group(1)) < n_devices:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+",
                       f"{_COUNT_FLAG}={n_devices}", flags)
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    if assert_count:
        assert jax.default_backend() == "cpu", (
            "need the CPU backend; another backend initialized first"
        )
        assert jax.device_count() >= n_devices, (
            f"need {n_devices} virtual CPU devices, have "
            f"{jax.device_count()} (backend initialized before the "
            "device-count flag was set?)"
        )
