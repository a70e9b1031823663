"""JAX set-up shared by every process that opens the device: where the
persistent compilation cache lives, which platform JAX found, and the CPU
emulation the tests run the device graph under.

One process owns a chip at a time. Nothing here starts a process or
touches a device until it is called; callers run ``setup_compile_cache``
before their first JAX computation.
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Enable JAX's persistent compilation cache at a place an operator
    can choose, and return that directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and no
    directory is set in code. Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, because the directory is
    looked up again by every later process and a moving one never hits.
    The cache keeps the XLA/Mosaic compile only — tracing and lowering a
    shape is paid again per process. ``JAX_ENABLE_COMPILATION_CACHE=0``
    turns it off."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cpu_emulation_requested() -> bool:
    """True when the operator asked for the device graph on XLA:CPU by
    setting ``JAX_PLATFORMS=cpu`` (the tests and the verify skill)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def device_info() -> dict:
    """What JAX found, as it reports it. Initializes the backend; a
    failing ``jax.devices()`` raises."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_platform() -> str:
    return device_info()["platform"]


def require_tpu(what: str, allow_emulation: bool = True) -> dict:
    """The device gate: returns ``device_info()`` when JAX found a TPU
    and otherwise raises SystemExit naming the platform found. A launch
    with an explicit ``tpu`` backend also passes when CPU emulation was
    asked for; a measurement passes ``allow_emulation=False`` — a number
    from XLA:CPU is never printed under a device metric's name."""
    info = device_info()
    if info["platform"] == "tpu":
        return info
    if allow_emulation and cpu_emulation_requested():
        return info
    hint = ("set JAX_PLATFORMS=cpu to run the device graph on XLA:CPU, "
            "or choose backend 'cpu'" if allow_emulation else
            "device numbers come from a chip run only")
    raise SystemExit(
        f"{what}: a TPU is required but JAX found platform "
        f"{info['platform']!r} ({info['count']} x {info['kind']}); {hint}")


def force_cpu_backend(n_devices: int = 8) -> None:
    """CPU emulation for tests and dry runs: ``JAX_PLATFORMS=cpu`` with
    ``n_devices`` virtual host devices (a multi-chip mesh without chips),
    plus the persistent cache — XLA:CPU compiles of the curve graphs run
    minutes each and every test process would repeat them. Call before
    any JAX computation; it cannot move an initialized backend."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    # the environment is read when jax is imported; a caller that
    # imported it earlier still gets the CPU platform through the config
    jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()
