"""Pre-jax process bootstrap for CLI entry points.

MUST stay importable before (and without) jax: the train/serve `__main__`
blocks call :func:`ensure_host_devices` before their first jax import so
the XLA host-device-count flag can still take effect.
"""
from __future__ import annotations

import os
import sys


def argv_int(flag: str, default: int = 1) -> int:
    """Parse an int CLI flag from sys.argv, accepting both the
    space-separated (``--tp 4``) and equals (``--tp=4``) forms."""
    for i, a in enumerate(sys.argv):
        try:
            if a == flag:
                return int(sys.argv[i + 1])
            if a.startswith(flag + "="):
                return int(a.split("=", 1)[1])
        except (ValueError, IndexError):
            return default
    return default


def argv_str(flag: str, default: str = "") -> str:
    """Parse a string CLI flag from sys.argv (``--x v`` / ``--x=v``)."""
    for i, a in enumerate(sys.argv):
        if a == flag and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return default


def ensure_host_devices(n: int) -> None:
    """Request n XLA host devices if jax has not been initialized yet and
    the run is pinned to the CPU (``JAX_PLATFORMS=cpu``). On any other
    platform the flag stays off, so a run whose accelerator fails to start
    errors out instead of silently continuing on virtual CPU devices
    (library users set XLA_FLAGS themselves)."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return
    if n > 1 and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed path inside the checkout (the path is part of the
#: cache key, so a moving directory would never hit). Listed in .gitignore.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself,
    so when it is set this changes nothing; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`. Called from ``main``s only, never at
    import time or from tests."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# XLA latency-hiding presets (ISSUE 7): the cheap compiler-side baseline
# for collective/compute overlap, next to the chunked-psum epilogue (the
# kernel-side measure). Must be applied BEFORE jax initializes — flag
# strings only, no jax imports here.
# ---------------------------------------------------------------------------

XLA_PRESETS = {
    "none": (),
    # async collectives + the latency-hiding scheduler: lets all-reduce
    # -start/-done pairs straddle independent compute
    "latency-hiding": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    ),
}


def _tpu_runtime_present() -> bool:
    # an explicit JAX_PLATFORMS wins over an installed-but-unused libtpu
    # (the common CI case: libtpu on disk, JAX_PLATFORMS=cpu)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats:
        return "tpu" in plats.lower()
    import importlib.util
    return (importlib.util.find_spec("libtpu") is not None
            or bool(os.environ.get("TPU_NAME")))


def xla_preset_flags(name: str) -> tuple:
    """Preset flags valid for THIS host. TPU-prefixed XLA flags are
    FATAL on other backends (unknown-flag check in XLA's
    parse_flags_from_env), so they are dropped unless a TPU runtime is
    importable — a preset can legitimately resolve to no flags."""
    if name not in XLA_PRESETS:
        raise ValueError(
            f"unknown XLA preset {name!r}; choose from "
            f"{sorted(XLA_PRESETS)}")
    flags = XLA_PRESETS[name]
    if not _tpu_runtime_present():
        flags = tuple(f for f in flags if not f.startswith("--xla_tpu_"))
    return flags


def apply_xla_preset(name: str) -> bool:
    """Append the preset's flags to XLA_FLAGS; returns False (no-op)
    when jax is already initialized or the preset is empty."""
    flags = xla_preset_flags(name)
    if not flags or "jax" in sys.modules:
        return False
    os.environ["XLA_FLAGS"] = " ".join(
        (os.environ.get("XLA_FLAGS", ""),) + flags).strip()
    return True
