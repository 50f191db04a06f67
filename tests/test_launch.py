"""Launch plumbing: where the compilation cache lives, and the launchers'
refusal to fall back to the CPU, or to spawn members, on an accelerator."""
import os

import numpy as np
import pytest
import jax

from repro.launch import compile_cache, devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of changing the process."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


@pytest.mark.parametrize("env_dir", [None, "/tmp/shared-jax-cache"])
def test_compile_cache_dir(monkeypatch, config_updates, env_dir):
    """$JAX_COMPILATION_CACHE_DIR when set (and nothing set in code, JAX
    reads it itself); else the fixed <repo>/.jax_cache."""
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
        want = env_dir
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == ([] if env_dir else
                              [("jax_compilation_cache_dir", want)])


def test_forced_host_devices_refused_on_an_accelerator(monkeypatch):
    """A TPU host with too few chips stops; it never re-execs onto forced
    CPU devices."""
    monkeypatch.setattr(jax, "devices", lambda *a: [object()])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "execv", lambda *a: pytest.fail("re-exec'd"))
    with pytest.raises(RuntimeError, match="only on the CPU"):
        devices.force_host_devices_or_reexec(4, "repro.launch.cges_run")


@pytest.mark.parametrize("platforms", ["tpu", ""])
def test_ring_async_launcher_refuses_non_cpu_members(monkeypatch, platforms):
    """One OS process per member cannot share a chip: unless the members
    are pinned to the CPU the launcher points to cges(engine='async')."""
    from repro.launch.ring_async_run import launch_ring

    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="engine='async'"):
        launch_ring(np.zeros((4, 2), np.int64), np.array([2, 2]),
                    np.zeros((2, 2, 2), bool), config_kwargs={})
