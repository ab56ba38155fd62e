"""Test-process hygiene for the benchmark's tests, as in tests/conftest.py:
drop JAX's compilation caches at module boundaries, so that the
executables these tests compile do not pile up in a worker that later
runs other modules (past a few hundred live executables the CPU
compiler has been seen to crash)."""
import gc

import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    import jax
    jax.clear_caches()
    gc.collect()
