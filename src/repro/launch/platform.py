"""Process-level platform setup: pick the jax backend and its XLA flags.

``set_platform`` must run BEFORE jax initializes its backends (i.e.
before the first ``jax.devices()``/array op — ideally before importing
anything that imports jax): both ``JAX_PLATFORMS`` and ``XLA_FLAGS`` are
read once at backend init and silently ignored afterwards, so this
module raises instead of letting a late call half-apply.

The GPU flag set is the community-standard performance set (async
collectives + latency-hiding scheduler + triton gemm; see
jax.readthedocs.io gpu_performance_tips): a future GPU CI lane calling
``set_platform("gpu")`` gets overlap-friendly scheduling for the
stream's per-sweep collectives for free.  On CPU,
``host_devices=N`` forces an N-virtual-device host platform — the same
``--xla_force_host_platform_device_count`` idiom the multidevice tests
and benchmarks use via subprocess env today.

``enable_compile_cache`` places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` points when it is set (JAX reads the
variable itself), else a fixed ``.jax_cache/`` at the root of the
checkout.  The path is part of the cache key, so it never depends on a
temporary name, a process id or the time.  Tests leave the cache off.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

# <checkout>/src/repro/launch/platform.py -> <checkout>/.jax_cache
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

# One flag per element so presence checks and joins stay trivial.
GPU_XLA_FLAGS: tuple[str, ...] = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _merge_xla_flags(env: dict, new_flags: tuple[str, ...]) -> None:
    have = env.get("XLA_FLAGS", "").split()
    names = {f.split("=", 1)[0] for f in have}
    for flag in new_flags:
        if flag.split("=", 1)[0] not in names:
            have.append(flag)
    env["XLA_FLAGS"] = " ".join(have)


def set_platform(platform: str | None = None, *,
                 host_devices: int | None = None,
                 env: dict | None = None) -> dict:
    """Select the jax platform and install its XLA flag set.

    ``platform`` is ``"cpu"``/``"gpu"``/``"tpu"`` (None keeps jax's own
    detection order while still applying ``host_devices``).  ``"gpu"``
    additionally merges ``GPU_XLA_FLAGS`` into ``XLA_FLAGS`` — existing
    flags of the same name win, so launch scripts can still override.
    ``host_devices`` forces the CPU host platform to expose N virtual
    devices (multidevice testing on one machine).

    Mutates and returns ``env`` (default ``os.environ``).  Raises
    RuntimeError when jax is already imported and ``env`` is the real
    process environment — the settings would be silently dead.
    """
    if env is None:
        if "jax" in sys.modules:
            raise RuntimeError(
                "set_platform() must run before jax is imported — "
                "JAX_PLATFORMS/XLA_FLAGS are read once at backend init. "
                "Call it first, or pass env= to build a child-process "
                "environment instead.")
        env = os.environ
    if platform is not None:
        if platform not in ("cpu", "gpu", "tpu"):
            raise ValueError(
                f"unknown platform {platform!r}; want cpu, gpu, or tpu")
        env["JAX_PLATFORMS"] = platform
        if platform == "gpu":
            _merge_xla_flags(env, GPU_XLA_FLAGS)
    if host_devices is not None:
        if host_devices < 1:
            raise ValueError(f"host_devices must be >= 1, got {host_devices}")
        _merge_xla_flags(
            env,
            (f"--xla_force_host_platform_device_count={int(host_devices)}",))
    return env


def enable_compile_cache(env: dict | None = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set in ``env`` (default
    ``os.environ``) JAX already uses that directory and no other path is
    set here; otherwise the cache goes to ``DEFAULT_COMPILE_CACHE``.
    Call before the first compile."""
    import jax

    env = os.environ if env is None else env
    path = env.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
