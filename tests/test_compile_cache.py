"""The persistent compile cache can be placed from outside.

jax reads ``JAX_COMPILATION_CACHE_DIR`` at import, so each case runs in
a fresh interpreter.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os
env_before = dict(os.environ)
import jax
import jax.numpy as jnp
from risingwave_tpu.config import enable_compile_cache
returned = enable_compile_cache()
# persist even a trivial program, so the directory layout shows
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({
    "returned": returned,
    "configured": jax.config.jax_compilation_cache_dir,
    "env_unchanged": dict(os.environ) == env_before,
}))
"""


def _probe(env_overrides, unset=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_overrides}
    for k in unset:
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_placed_cache_dir_is_used_as_is(tmp_path):
    placed = tmp_path / "placed"
    out = _probe({"JAX_COMPILATION_CACHE_DIR": str(placed)})
    assert out["configured"] == out["returned"] == str(placed)
    assert out["env_unchanged"]
    entries = list(placed.iterdir())
    # cache files sit directly in the placed directory
    assert entries and not any(e.is_dir() for e in entries)


def test_default_cache_dir_is_fixed_under_the_checkout():
    want = os.path.join(ROOT, ".jax_cache")
    # another XLA_FLAGS context than this pytest process: same directory
    out = _probe(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        unset=("JAX_COMPILATION_CACHE_DIR",),
    )
    assert out["configured"] == out["returned"] == want
    assert out["env_unchanged"]
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        # conftest called the same function under 8 virtual devices
        import jax

        assert jax.config.jax_compilation_cache_dir == want
    assert not any(
        e.is_dir() for e in os.scandir(want)
    ), "the default cache directory must stay flat"
