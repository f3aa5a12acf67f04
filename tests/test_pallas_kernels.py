"""Pallas kernel tests (interpret mode on the CPU test mesh)."""

import numpy as np
import jax.numpy as jnp

from windflow_tpu.ops.pallas_kernels import masked_window_reduce, ROW_TILE


def test_masked_window_reduce_matches_numpy():
    rng = np.random.default_rng(0)
    W, L = ROW_TILE * 2, 256
    vals = rng.normal(size=(W, L)).astype(np.float32)
    mask = rng.random((W, L)) < 0.5
    got = np.asarray(masked_window_reduce(jnp.asarray(vals), jnp.asarray(mask),
                                          interpret=True))
    expect = np.where(mask, vals, 0).sum(axis=1)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)


def test_masked_window_reduce_fallback_shapes():
    # non-tile-aligned shapes take the XLA fallback path
    vals = jnp.ones((10, 7), jnp.float32)
    mask = jnp.ones((10, 7), bool)
    got = np.asarray(masked_window_reduce(vals, mask))
    np.testing.assert_allclose(got, np.full(10, 7.0))


def test_masked_window_reduce_under_enclosing_jit():
    # traced calls run the same Pallas kernel as eager ones (no trace-time
    # detour to the XLA form: a lowering failure must surface, not hide)
    import jax

    vals = jnp.ones((ROW_TILE * 2, 128), jnp.float32)
    mask = jnp.ones_like(vals, bool)
    fn = jax.jit(lambda v, m: masked_window_reduce(v, m))
    assert "pallas_call" in str(jax.make_jaxpr(fn)(vals, mask))
    np.testing.assert_allclose(np.asarray(fn(vals, mask)),
                               np.full(ROW_TILE * 2, 128.0))
