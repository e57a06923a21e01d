# NOTE: no XLA_FLAGS here on purpose — smoke tests must see 1 device.
# Multi-device tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves.
import os

import numpy as np
import pytest

# XLA compile time dominates tier-1 (the payloads are tiny); the
# persistent compilation cache makes warm reruns ~2x faster and costs a
# cold run almost nothing.  Opt out with REPRO_NO_JAX_CACHE=1.
if not os.environ.get("REPRO_NO_JAX_CACHE"):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
