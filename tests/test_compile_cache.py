"""Where the persistent compilation cache goes
(InstallConfig.enable_jax_compile_cache): the install key when given, else
JAX_COMPILATION_CACHE_DIR left to jax, else the repo's fixed .jax_cache."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from spark_scheduler_tpu.server.config import InstallConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize(
    "env, explicit, want",
    [
        (None, None, InstallConfig.DEFAULT_JAX_CACHE_DIR),
        ("/from/env", None, "sentinel"),  # jax reads the variable itself
        ("/from/env", "/from/install-key", "/from/install-key"),
    ],
)
def test_cache_dir_precedence(
    monkeypatch, cache_dir_config, env, explicit, want
):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    InstallConfig.enable_jax_compile_cache(explicit)
    assert jax.config.jax_compilation_cache_dir == want


def test_default_cache_dir_is_fixed_in_repo():
    assert InstallConfig.DEFAULT_JAX_CACHE_DIR == os.path.join(
        REPO, ".jax_cache"
    )


def test_entries_land_in_env_dir_only(tmp_path):
    """A fresh process with JAX_COMPILATION_CACHE_DIR set writes its cache
    entries there and nothing into the repo's default directory."""
    default = InstallConfig.DEFAULT_JAX_CACHE_DIR
    before = sorted(os.listdir(default)) if os.path.isdir(default) else None
    script = (
        "import jax, jax.numpy as jnp\n"
        "from spark_scheduler_tpu.server.config import InstallConfig\n"
        "print(InstallConfig.enable_jax_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path)
    after = sorted(os.listdir(default)) if os.path.isdir(default) else None
    assert after == before
