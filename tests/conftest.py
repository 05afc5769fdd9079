"""Test configuration: force JAX onto a virtual 8-device CPU platform so
sharding/pjit tests exercise multi-chip layouts without TPU hardware
(nomad_tpu.utils.platform.force_cpu_platform; the config must be set
before any backend initializes). The persistent compile cache is left
alone: JAX uses one only if JAX_COMPILATION_CACHE_DIR is set."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_ENABLE_X64", "0")

from nomad_tpu.utils.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(8)


def pytest_configure(config):
    """API-rot guard (nomad_tpu/analysis PR satellite): JAX
    deprecation warnings become errors at test time, so an upstream
    API removal surfaces as a red test here instead of breakage on the
    next jax bump. Later lines take precedence, so the targeted
    ignores for known-noisy upstream warnings (not actionable from
    this repo) sit after the error filters."""
    config.addinivalue_line(
        "filterwarnings", "error:.*[jJ]ax.*:DeprecationWarning")
    config.addinivalue_line(
        "filterwarnings", "error::DeprecationWarning:jax")
    for noisy in (
        # setuptools/pkg_resources self-deprecation noise
        "ignore::DeprecationWarning:pkg_resources",
        "ignore:.*pkg_resources.*:DeprecationWarning",
        # stdlib utcnow deprecation raised from third-party code
        "ignore:.*datetime\\.datetime\\.utcnow.*:DeprecationWarning",
    ):
        config.addinivalue_line("filterwarnings", noisy)
