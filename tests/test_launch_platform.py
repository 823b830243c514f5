"""``launch.platform``: XLA flag/env composition.

Pure env-dict tests — the helper takes ``env=`` precisely so tests (and
launcher scripts building child environments) never have to race jax's
one-shot backend init.
"""

import pytest

from repro.launch.platform import (DEFAULT_COMPILE_CACHE, GPU_XLA_FLAGS,
                                   enable_compile_cache, set_platform)


def test_gpu_platform_installs_flag_set():
    env = set_platform("gpu", env={})
    assert env["JAX_PLATFORMS"] == "gpu"
    for flag in GPU_XLA_FLAGS:
        assert flag in env["XLA_FLAGS"].split()
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in env["XLA_FLAGS"]


def test_existing_flags_win_and_merge_is_idempotent():
    env = {"XLA_FLAGS": "--xla_gpu_triton_gemm_any=False"}
    set_platform("gpu", env=env)
    flags = env["XLA_FLAGS"].split()
    # the user's value survives; the helper never duplicates a flag name
    assert "--xla_gpu_triton_gemm_any=False" in flags
    assert "--xla_gpu_triton_gemm_any=True" not in flags
    before = env["XLA_FLAGS"]
    set_platform("gpu", env=env)
    assert env["XLA_FLAGS"] == before
    assert len(flags) == len({f.split("=", 1)[0] for f in flags})


def test_host_devices_forces_virtual_cpu_count():
    env = set_platform("cpu", host_devices=8, env={})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # platform=None still applies host_devices (keep jax's own detection)
    env2 = set_platform(host_devices=4, env={})
    assert "JAX_PLATFORMS" not in env2
    assert "--xla_force_host_platform_device_count=4" in env2["XLA_FLAGS"]


def test_validation_and_late_call_guard():
    with pytest.raises(ValueError, match="unknown platform"):
        set_platform("quantum", env={})
    with pytest.raises(ValueError, match="host_devices"):
        set_platform("cpu", host_devices=0, env={})
    # jax is imported in this process: mutating os.environ would be dead
    with pytest.raises(RuntimeError, match="before jax"):
        set_platform("cpu")


@pytest.fixture
def restore_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    yield jax
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_enable_compilation_cache", saved[1])
    compilation_cache.reset_cache()


def test_compile_cache_env_dir_wins(restore_cache_config):
    jax = restore_cache_config
    before = jax.config.jax_compilation_cache_dir
    path = enable_compile_cache(env={"JAX_COMPILATION_CACHE_DIR": "/x/cc"})
    assert path == "/x/cc"
    # JAX reads the variable itself: nothing here overrides the directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(restore_cache_config):
    jax = restore_cache_config
    path = enable_compile_cache(env={})
    assert path == str(DEFAULT_COMPILE_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert DEFAULT_COMPILE_CACHE.parent.joinpath("pyproject.toml").exists()
