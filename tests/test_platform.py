"""utils/platform.py: the one place that says where JAX runs."""

import jax
import pytest

from inference_gateway_tpu.utils import platform


def test_on_tpu_follows_the_device_platform(monkeypatch):
    assert platform.device_platform() == "cpu" and platform.on_tpu() is False
    monkeypatch.setattr(platform, "device_platform", lambda: "tpu")
    assert platform.on_tpu() is True


def test_device_info_is_what_jax_reports():
    info = platform.device_info()
    dev = jax.devices()
    assert info == {"platform": dev[0].platform, "device_kind": dev[0].device_kind,
                    "device_count": len(dev)}


@pytest.mark.parametrize("env_dir", ["/somewhere/else", None])
def test_compile_cache_dir_is_the_env_var_or_a_fixed_path_in_the_checkout(monkeypatch, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, no directory is named in code
    (JAX reads the variable itself); unset, <repo>/.jax_cache — fixed,
    because the path is part of the cache key."""
    updates = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = platform.configure_compile_cache()
    if env_dir:
        assert got == env_dir and "jax_compilation_cache_dir" not in updates
    else:
        repo = platform.Path(platform.__file__).resolve().parents[2]
        assert got == updates["jax_compilation_cache_dir"] == str(repo / ".jax_cache")
        assert got == platform.configure_compile_cache()


def test_force_platform_after_first_use_fails_loudly():
    """Virtual CPU devices must be asked for before JAX first touches a
    device; asked later, JAX raises — it does not hand back one device."""
    jax.devices()
    with pytest.raises(RuntimeError):
        platform.force_platform("cpu", n_devices=3)
