"""Pallas factored table lookup (ops/lookup.py::_pallas_factored_lookup):
exactness against the gather oracle in interpret mode (CPU), through the
kernel itself and through ``table_lookup``'s impl switch, and the fallback
of capacities the kernel cannot block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("K,C", [(1000, 8192), (300, 512), (5000, 16384)])
def test_pallas_factored_lookup(K, C):
    from windflow_tpu.ops.lookup import _pallas_factored_lookup, table_lookup

    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.integers(0, 1 << 12, K).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, K, C).astype(np.int32))
    want = np.asarray(table)[np.asarray(idx)]
    got = jax.jit(lambda t, i: _pallas_factored_lookup(t, i, interpret=True))(
        table, idx)
    np.testing.assert_array_equal(np.asarray(got), want)
    # routed through table_lookup's impl switch
    got2 = jax.jit(lambda t, i: table_lookup(t, i, impl="pallas"))(table, idx)
    np.testing.assert_array_equal(np.asarray(got2), want)


def test_pallas_lookup_unblockable_capacity_falls_back():
    """C not a multiple of 128 -> the impl switch silently uses the XLA form."""
    from windflow_tpu.ops.lookup import table_lookup

    rng = np.random.default_rng(6)
    K, C = 1000, 1000
    table = jnp.asarray(rng.integers(0, 1 << 12, K).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, K, C).astype(np.int32))
    got = jax.jit(lambda t, i: table_lookup(t, i, impl="pallas"))(table, idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table)[np.asarray(idx)])


def test_pallas_lookup_fuzz_geometry():
    from windflow_tpu.ops.lookup import _pallas_block, _pallas_factored_lookup

    rng = np.random.default_rng(43)
    for trial in range(10):
        K = int(rng.integers(129, 20000))
        C = int(rng.choice([128, 256, 1024, 8192, 16384, 24576]))
        assert _pallas_block(C), C
        table = jnp.asarray(rng.integers(-(1 << 20), 1 << 20, K)
                            .astype(np.int32))
        idx = jnp.asarray(rng.integers(0, K, C).astype(np.int32))
        got = _pallas_factored_lookup(table, idx, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(table)[np.asarray(idx)],
            err_msg=f"trial={trial} K={K} C={C}")
