"""Test config: tests run on the CPU backend with 8 virtual devices
(plain ``JAX_PLATFORMS=cpu``), never on a chip; the chip is reached only
through the chip tool, with ``python chip_smoke.py`` as the command.
One process per chip; the compile cache is ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` places it elsewhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# arm jax.transfer_guard("disallow") around the per-barrier device step
# (runtime/pipeline.py + runtime/graph.py wrap it via
# analysis.jax_sanitizer.transfer_guard): an implicit host<->device
# transfer on the hot path raises AT the offending executor. Opt out
# with RW_TRANSFER_GUARD=0.
os.environ.setdefault("RW_TRANSFER_GUARD", "1")

# persistent XLA compilation cache, for every program however small:
# the per-module jax.clear_caches() below makes each module recompile
# the same small kernels, and almost none of them takes the second a
# compile must take to be persisted by default — with the floor at 0
# those recompiles become loads (tests/test_[a-e]*: 300 s -> 262 s cold,
# CPU host clock)
from risingwave_tpu.config import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "smoke: fast CI-signal subset — `pytest -m smoke` runs <2 min "
        "(VERDICT r3 #10)",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-haul tests (subprocess spawns pay "
        "a cold jax import each)",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables between test modules: hundreds of
    accumulated CPU executables have produced in-compile segfaults deep
    into the full suite (observed in jax backend_compile during a late
    module); modules are self-contained, so bounding the live cache
    costs only per-module recompiles. What the program remembers of
    having compiled goes with them."""
    yield
    jax.clear_caches()
    from risingwave_tpu.storage.state_table import _warm_select

    _warm_select.cache_clear()
