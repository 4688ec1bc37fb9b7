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


def test_the_keys_take_the_metadata_in_and_not_the_checkouts_path():
    """A scope written onto a program whose text stood has to compile
    anew, or the loaded executable carries the names it was first
    compiled with (``trace.program_ops`` reads them off it); and the
    same files at another path, or first called from another place,
    have to find the same entries. conftest called the function."""
    import re

    import jax

    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    assert jax.config.jax_traceback_in_locations_limit == 1
    cut = re.compile(jax.config.jax_hlo_source_file_canonicalization_regex)
    here = os.path.join(ROOT, "risingwave_tpu", "ops", "hash_table.py")
    assert cut.sub("", here) == os.path.join(
        "risingwave_tpu", "ops", "hash_table.py"
    )
    # (a name stack's scopes survive: the limit is on frames, where
    # ``jax_include_full_tracebacks_in_locations`` off drops a called
    # program's scopes from its operations' names)
    assert jax.config.jax_include_full_tracebacks_in_locations is True
