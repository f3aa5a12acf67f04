"""The count histogram (ops/histogram.py::keyed_pane_fold with no value leaf)
and the factored table lookup (ops/lookup.py): exactness against the
scatter/gather reference on random data, including the locality-violation
fallback and ring wrap-around."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu.ops.histogram import keyed_pane_fold
from windflow_tpu.ops.lookup import table_lookup, _factored_lookup


def count_fold(key, pane, valid, K, P):
    """``keyed_pane_fold`` with no value leaf: its counts."""
    return keyed_pane_fold(key, pane, valid, (), K, P)[0]


def ref_hist(key, pane, valid, K, P):
    out = np.zeros((K, P), np.int32)
    for k, p, v in zip(key, pane, valid):
        if v:
            out[k, p % P] += 1
    return out


@pytest.mark.parametrize("C,K,P", [(4096, 7, 64), (8192, 100, 256)])
def test_hist_sorted_ts(C, K, P):
    rng = np.random.default_rng(0)
    key = rng.integers(0, K, C).astype(np.int32)
    # locally-clustered panes: nondecreasing ts
    pane = (np.arange(C) // 97).astype(np.int32) + 5
    valid = rng.random(C) < 0.7
    got = jax.jit(lambda *a: count_fold(*a, K, P))(
        jnp.asarray(key), jnp.asarray(pane), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got), ref_hist(key, pane, valid, K, P))


def test_hist_wraparound():
    C, K, P = 4096, 5, 32
    rng = np.random.default_rng(1)
    key = rng.integers(0, K, C).astype(np.int32)
    pane = (np.arange(C) // 130 + P - 3).astype(np.int32)   # crosses the ring edge
    valid = np.ones(C, bool)
    got = jax.jit(lambda *a: count_fold(*a, K, P))(
        jnp.asarray(key), jnp.asarray(pane), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got), ref_hist(key, pane, valid, K, P))


def test_hist_fallback_unordered():
    """Panes scattered randomly violate chunk locality -> scatter fallback, same
    result."""
    C, K, P = 4096, 11, 64
    rng = np.random.default_rng(2)
    key = rng.integers(0, K, C).astype(np.int32)
    pane = rng.integers(0, 1000, C).astype(np.int32)
    valid = rng.random(C) < 0.5
    got = jax.jit(lambda *a: count_fold(*a, K, P))(
        jnp.asarray(key), jnp.asarray(pane), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got), ref_hist(key, pane, valid, K, P))


def test_hist_odd_capacity_and_empty():
    C, K, P = 1000, 3, 16          # C not a multiple of the chunk -> scatter path
    key = np.zeros(C, np.int32)
    pane = np.zeros(C, np.int32)
    valid = np.zeros(C, bool)
    got = count_fold(jnp.asarray(key), jnp.asarray(pane),
                     jnp.asarray(valid), K, P)
    assert int(jnp.sum(got)) == 0


@pytest.mark.parametrize("K", [100, 1000, 4000])
def test_factored_lookup_int(K):
    rng = np.random.default_rng(3)
    tbl = rng.integers(0, 1 << 20, K).astype(np.int32)
    idx = rng.integers(0, K, 2048).astype(np.int32)
    got = table_lookup(jnp.asarray(tbl), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), tbl[idx])


def test_factored_lookup_float():
    rng = np.random.default_rng(4)
    tbl = rng.standard_normal(777).astype(np.float32)
    idx = rng.integers(0, 777, 512).astype(np.int32)
    got = _factored_lookup(jnp.asarray(tbl), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), tbl[idx])  # bit-exact selection


def test_lookup_large_int_values_fall_back():
    """Values >= 2^24 are not f32-exact: must take the gather path and stay exact."""
    tbl = np.array([0, (1 << 24) + 1, 5, 7] * 300, np.int32)
    idx = np.array([1, 2, 1199], np.int32)
    got = table_lookup(jnp.asarray(tbl), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), tbl[idx])


def test_count_lift_autodetect():
    from windflow_tpu.operators.win_seqffat import _detect_count_lift
    from windflow_tpu.batch import Batch

    b = Batch(key=jnp.zeros(8, jnp.int32), id=jnp.zeros(8, jnp.int32),
              ts=jnp.zeros(8, jnp.int32),
              payload={"v": jnp.zeros(8, jnp.int32)}, valid=jnp.ones(8, bool))
    assert _detect_count_lift(lambda t: jnp.ones((), jnp.int32), b)
    assert not _detect_count_lift(lambda t: t.data["v"], b)
    assert not _detect_count_lift(lambda t: jnp.zeros((), jnp.int32), b)
    assert not _detect_count_lift(lambda t: {"a": jnp.ones(()), "b": jnp.ones(())}, b)


def test_lookup_inf_float_table_falls_back():
    """inf sentinels (running-max identities) must not NaN-poison other rows."""
    tbl = np.full(1024, -np.inf, np.float32)
    tbl[3] = 3.0
    idx = np.array([3, 5], np.int32)
    got = table_lookup(jnp.asarray(tbl), jnp.asarray(idx))
    assert float(got[0]) == 3.0 and np.isneginf(float(got[1]))


def test_hist_many_keys_tiled():
    C, K, P = 4096, 1500, 64          # K > K_TILE exercises key-axis tiling
    rng = np.random.default_rng(5)
    key = rng.integers(0, K, C).astype(np.int32)
    pane = (np.arange(C) // 511).astype(np.int32)
    valid = rng.random(C) < 0.9
    got = jax.jit(lambda *a: count_fold(*a, K, P))(
        jnp.asarray(key), jnp.asarray(pane), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got), ref_hist(key, pane, valid, K, P))


# ---- exactness under a TPU's default matmul precision ----------------------
# A TPU runs an f32 dot at default precision as ONE bf16 pass (8 mantissa
# bits). The CPU backend does not, so every CPU test of the one-hot matmul
# forms passed while table values / per-chunk counts beyond 256 came back
# rounded on the chip (chip_smoke.py leg C, PR 21). The fixture imitates the
# chip: any dot_general that does not ask for HIGHEST gets its f32 operands
# rounded through bf16 first.

@pytest.fixture
def tpu_default_dot(monkeypatch):
    real = jax.lax.dot_general
    highest = jax.lax.Precision.HIGHEST

    def through_bf16(x):
        if x.dtype == jnp.float32:
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    def emulated(lhs, rhs, dimension_numbers, precision=None, **kw):
        if precision != highest and precision != (highest, highest):
            lhs, rhs = through_bf16(lhs), through_bf16(rhs)
        return real(lhs, rhs, dimension_numbers, precision=precision, **kw)

    monkeypatch.setattr(jax.lax, "dot_general", emulated)
    # the imitation bites: 257 is not a bf16 value
    got = jax.lax.dot_general(jnp.full((1, 1), 257.0), jnp.ones((1, 1)),
                              (((1,), (0,)), ((), ())))
    assert float(got[0, 0]) == 256.0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("table", [
    np.arange(1000, dtype=np.int32) * 65 + 536,                  # up to 2^16
    (np.arange(1000, dtype=np.int32) * 16777 - (1 << 23)),       # |v| < 2^24
    np.random.default_rng(0).standard_normal(1000).astype(np.float32) * 1e3,
], ids=["i32_2e16", "i32_2e24", "f32"])
def test_lookup_exact_beyond_bf16(tpu_default_dot, table, impl):
    idx = np.random.default_rng(1).integers(0, len(table), 1024).astype(np.int32)
    got = table_lookup(jnp.asarray(table), jnp.asarray(idx), impl=impl)
    np.testing.assert_array_equal(np.asarray(got), table[idx])


@pytest.mark.parametrize("impl", ["xla"])
def test_hist_counts_beyond_bf16(tpu_default_dot, impl):
    """A one-key stream: ~1000 counts per (key, pane, chunk), through the
    fold's one form (``impl``: XLA)."""
    C, K, P = 4096, 4, 16
    key = np.zeros(C, np.int32)
    pane = (np.arange(C) // 2000 + P - 1).astype(np.int32)
    valid = np.arange(C) % 41 != 0
    got = count_fold(jnp.asarray(key), jnp.asarray(pane),
                     jnp.asarray(valid), K, P)
    want = ref_hist(key, pane, valid, K, P)
    assert want.max() > 256
    np.testing.assert_array_equal(np.asarray(got), want)


def test_segment_fold_pallas_limbs_beyond_bf16(tpu_default_dot):
    from windflow_tpu.ops.segment import segment_fold
    rng = np.random.default_rng(2)
    C, S = 2048, 64
    vals = rng.integers(-(1 << 31), 1 << 31, C, dtype=np.int64).astype(np.int32)
    seg = rng.integers(0, S, C).astype(np.int32)
    valid = rng.random(C) < 0.9
    want = np.zeros(S, np.int64)
    np.add.at(want, seg[valid], vals[valid].astype(np.int64))
    got = segment_fold(jnp.asarray(vals), jnp.asarray(seg), jnp.asarray(valid),
                       S, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
