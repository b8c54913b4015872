"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

The reference validates distribution by running one unittest suite under
``mpirun -n 3``/``-n 4`` (SURVEY.md §4); the analog here is a single
process driving 8 virtual XLA host devices, with non-divisible extents in
the tests standing in for the reference's n=3 remainder chunks.
"""

import os

# must be set before jax initializes its backends; HEAT_TPU_TEST_DEVICES
# lets CI sweep mesh sizes (3 and 8) the way the reference sweeps mpirun -n
_N_DEVICES = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_N_DEVICES}"
)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# persistent compilation cache: the suite is compile-dominated (hundreds
# of unique (shape, dtype, mesh) programs on the virtual mesh); warm
# reruns skip XLA entirely.  Run parallel with ``pytest -n auto`` (xdist)
# — workers share this cache, and CI stays inside one timeout window.
# JAX_COMPILATION_CACHE_DIR places it; unset, it is <checkout>/.jax_cache.
from heat_tpu.core.compile_cache import use_compile_cache

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import numpy as np
import pytest


@pytest.fixture
def ht():
    import heat_tpu as ht

    return ht
