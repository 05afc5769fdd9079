"""Native runtime components.

The reference keeps its wire codec compiled (go-msgpack + generated
encoders); here codec.cpp is a CPython extension built on demand with
g++, from the tracked source, on the machine that runs it. The build
is cached beside the source (`_build/`, git-ignored;
NOMAD_TPU_NATIVE_CACHE moves it) keyed by source hash + python ABI.
A build or self-check failure logs a warning and the loader returns
None: callers then run their pure-python path (same wire format), and
chip_smoke.py reports whether the module loaded.

NOMAD_TPU_NATIVE=0 disables the native path.
"""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
import sys
import sysconfig
from typing import Optional

LOG = logging.getLogger("nomad_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "codec.cpp")
_loaded = None
_attempted = False


def _cache_path(src: str, name: str) -> str:
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    abi = sysconfig.get_config_var("SOABI") or "abi3"
    cache_dir = os.environ.get("NOMAD_TPU_NATIVE_CACHE",
                               os.path.join(_HERE, "_build"))
    os.makedirs(cache_dir, exist_ok=True)
    return os.path.join(cache_dir, f"{name}-{digest}.{abi}.so")


def _build(src: str, so_path: str) -> bool:
    """Compile into a temp name of this process's own and publish it
    with os.replace: processes that start together on an empty cache
    (two agents on a fresh install, six test workers) each build a
    whole file, and none takes another's from under it. One that has
    already loaded the file it replaces keeps that file's mapping."""
    include = sysconfig.get_path("include")
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
           f"-I{include}", src, "-o", tmp]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        LOG.warning("native build failed to run: %s", e)
        return False
    if out.returncode != 0:
        LOG.warning("native build failed:\n%s", out.stderr[-2000:])
        return False
    os.replace(tmp, so_path)    # over another process's: the same build
    return True


def _load_module(src: str, name: str):
    """Build (cached by source hash) and import one native module, or
    None on any failure — callers keep their pure-python fallback."""
    if os.environ.get("NOMAD_TPU_NATIVE", "1") == "0":
        return None
    so = _cache_path(src, name)
    if not os.path.exists(so) and not _build(src, so):
        return None
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, so)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_codec():
    """Returns the native codec module, or None (with msgpack fallback
    left to the caller)."""
    global _loaded, _attempted
    if _loaded is not None or _attempted:
        return _loaded
    _attempted = True
    try:
        mod = _load_module(_SRC, "nomad_tpu_native_codec")
        if mod is None:
            return None
        # self-check before trusting it on the wire
        probe = {"a": [1, -7, 2.5, "x", b"\x00\xff", None, True],
                 "nested": {"k": [list(range(40))]}}
        import msgpack
        if msgpack.unpackb(mod.packb(probe), raw=False) != probe or \
                mod.unpackb(msgpack.packb(probe, use_bin_type=True)) \
                != probe:
            LOG.warning("native codec self-check failed; falling back")
            return None
        _loaded = mod
        return mod
    except Exception as e:       # pragma: no cover — env-dependent
        LOG.warning("native codec unavailable: %s", e)
        return None
