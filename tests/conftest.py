"""Test config: hermetic 8-device virtual CPU mesh for the TPU engine.

Tests run on jax's CPU backend (`JAX_PLATFORMS=cpu` unless the caller
says otherwise) with 8 virtual host devices for the mesh suites; a
Pallas kernel runs here only where a test forces it on, and then in
interpreter mode. The chip is `python chip_smoke.py`'s business, not the
tests'. The persistent compile cache needs no wiring here: it is on by
default at `<checkout>/.madsim-jit-cache`
(`madsim_tpu.compile_cache.enable_compile_cache`), so a re-run pays
deserialize instead of rebuild; `JAX_COMPILATION_CACHE_DIR` or
`MADSIM_TPU_COMPILE_CACHE` move it.
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    # registered in pyproject.toml too; kept here so the marker exists
    # even when pytest runs with a different rootdir/ini
    config.addinivalue_line(
        "markers",
        "slow: long-running suites (full engine sweeps, soak); excluded "
        "from the tier-1 fast gate via -m 'not slow'",
    )


@pytest.fixture(autouse=True)
def _fresh_machine_registry():
    """`build_machine` hands out one machine object per name per process,
    and a machine carries the replay programs compiled on it: every test
    starts with none, so what a test compiles (and asserts it compiles)
    does not depend on which tests its worker ran before."""
    cli = sys.modules.get("madsim_tpu.__main__")
    if cli is not None:
        cli._registry_machine.cache_clear()
    yield
