"""What ``conftest._mapping_guard`` rests on: XLA's CPU backend holds memory
mappings for every executable JAX caches, and dropping the caches returns
them.  (A whole tier-1 run's workers reached the kernel's ``vm.max_map_count``
and died inside the next compile: PERF.md section 7, PR 60.)"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import _mappings


def test_dropping_jax_caches_returns_the_executables_mappings():
    held, limit = _mappings()
    if not limit:
        pytest.skip("this kernel does not say how many mappings a process holds")
    assert 0 < held < limit
    n = 16
    fns = [jax.jit(lambda x, k=k: jnp.tanh(x * k) + k) for k in range(1, n + 1)]
    for k, f in enumerate(fns):
        f(np.ones(8 + k, np.float32)).block_until_ready()
    grown = _mappings()[0]
    assert grown >= held + n, "an executable no longer costs a mapping"
    jax.clear_caches()
    gc.collect()
    assert _mappings()[0] <= grown - n, "dropping the caches unmapped nothing"
    assert fns[0](np.ones(8, np.float32))[0] == pytest.approx(np.tanh(1.0) + 1)
