"""Which device JAX runs on: platform choice, identity, compile cache.

``JAX_PLATFORMS`` works on this installation, so choosing a platform is
setting it before JAX first touches a device; nothing here re-binds a
backend that is already up. Everything that asks "am I on a TPU" —
kernel dispatch, interpret mode, device tracing — asks ``device_platform``
(tests steer that one function), and every entry point that builds an
engine calls ``configure_compile_cache`` first.
"""

from __future__ import annotations

import os
from pathlib import Path

# Persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed path inside the checkout. The path is part of the cache key, so
# a directory that moves (tmp name, pid, timestamp) never hits.
_REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_platform(platform: str, n_devices: int | None = None) -> None:
    """Bind JAX to ``platform`` ("cpu" | "tpu"). Must run before the
    first device op. ``n_devices`` with "cpu" asks for that many virtual
    host devices (mesh tests, dry runs); JAX raises if a backend is
    already initialised, instead of handing back one device."""
    import jax

    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)
    if n_devices and platform == "cpu":
        jax.config.update("jax_num_cpu_devices", n_devices)


def device_platform() -> str:
    import jax

    return jax.devices()[0].platform


def on_tpu() -> bool:
    """The one "am I on a TPU" decision: compiled Pallas kernels and
    device traces on a TPU, interpret mode and the pure-JAX
    twins anywhere else."""
    return device_platform() == "tpu"


def device_info() -> dict:
    """Platform, ``device_kind`` and count as JAX reports them — what
    the sidecar logs at start-up and serves under ``accelerator`` in
    /debug/status, so a reader never has to assume the device."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
    itself; no other directory is named in code); otherwise the fixed
    ``<repo>/.jax_cache``."""
    import jax

    # Keep the small programs too (JAX's default skips compiles under a
    # second): start-up runs a few hundred of them before the step
    # programs, and a restart should load every one, not compile it.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)
