"""JAX backend selection and compile-cache placement.

One rule for every entry point that starts JAX (the CLI agent,
chip_smoke.py, benchmark/run.py):

  - ``JAX_PLATFORMS=cpu`` set by the caller means CPU (tests, laptops).
  - otherwise the process initializes the ambient backend itself, as
    the first and only process to do so — an accelerator belongs to one
    process at a time, so nothing probes it from a child — and reports
    platform / device_kind / device count. A backend that fails to
    initialize raises; no entry point continues on the CPU instead.

``jax.config.update`` only takes effect while no backend has been
*initialized* (backends init lazily at first device use); afterwards it
is silently ignored, hence assert_cpu_devices.
"""

from __future__ import annotations

import os
from typing import Dict

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    device use. When JAX_COMPILATION_CACHE_DIR is set JAX reads it
    itself and nothing is touched; otherwise the cache lives at ONE
    fixed path inside the checkout — the directory is part of the cache
    key, so a path that moves between runs never hits. Returns the
    directory in effect."""
    import jax

    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def compile_cache_entries() -> int:
    """Executables in the persistent compile cache (0 before the first
    write): a second run over a preserved directory shows its hits as
    entries that did not have to be added."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))


def init_backend() -> Dict[str, object]:
    """Initialize the ambient JAX backend in THIS process and describe
    it as JAX reports it, under the keys every artifact is stamped
    with. Raises RuntimeError when the backend cannot initialize (e.g.
    JAX_PLATFORMS names an accelerator that is absent); callers turn
    that into a non-zero exit."""
    configure_compile_cache()
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def force_cpu_platform(n_devices: int = 1) -> None:
    """Point JAX at an n-device virtual CPU platform. Must run before the
    process initializes any backend; assert_cpu_devices verifies it."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def assert_cpu_devices(n_devices: int) -> None:
    """Verify force_cpu_platform took effect. It silently does not when a
    backend was already initialized in this process (e.g. something ran a
    computation on the ambient accelerator first) — fail loudly instead
    of quietly running on the wrong platform."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "cpu" or len(devs) < n_devices:
        plat = devs[0].platform if devs else "none"
        raise RuntimeError(
            f"expected >= {n_devices} cpu devices but found {len(devs)} "
            f"{plat!r} devices — a JAX backend was already initialized "
            f"before force_cpu_platform(); call it first in a fresh "
            f"process")
