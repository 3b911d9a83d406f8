"""Force JAX onto the hermetic CPU platform with N virtual devices.

Package home of the helper (the repo-root ``_hermetic`` shim re-exports
it for tests/bench): sharding code is exercised on virtual CPU devices,
no accelerator required — the reference's localhost mock-cluster pattern
(``tests/distributed/_test_distributed.py:168-196``).

Env vars (read by XLA once, at backend init) carry the device count; the
``jax.config.update`` after import also covers a process whose
``JAX_PLATFORMS`` was read before this ran.
"""

import os
import re

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def cpu_env(n_devices, env=None):
    """Env-var dict forcing ``n_devices`` virtual CPU devices.

    Pure (never imports jax) so a parent process can build a child
    environment without touching the accelerator stack.  Replaces
    any existing device-count flag instead of skipping, so an inherited
    XLA_FLAGS value cannot pin the count to a stale number.
    """
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    flags = env.get("XLA_FLAGS", "")
    flags = _COUNT_RE.sub(flag, flags) if _COUNT_RE.search(flags) \
        else (flags + " " + flag).strip()
    env["XLA_FLAGS"] = flags
    return env


def force_cpu(n_devices):
    """Force THIS process onto the hermetic CPU platform; returns jax.

    Must run before jax's backend initializes (XLA_FLAGS is read exactly
    once at backend init); importing jax beforehand is fine.
    """
    for key, val in cpu_env(n_devices).items():
        os.environ[key] = val

    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax
