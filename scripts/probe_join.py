"""Isolated probe for the YSB campaign-join stage (ROADMAP A3b). Measures each
variant standalone on precomputed inputs AND in the source->filter->join prefix,
one variant per invocation.

Usage: python scripts/probe_join.py <variant> [batch]
Variants:
  prefix2_base    source+filter only (the ablation baseline)
  prefix2_<v>     source+filter+join variant <v>
  standalone_<v>  join variant <v> on precomputed device inputs
where <v> in: factored (current), factored_bf16, take, barrier (factored with
optimization_barrier-pinned inputs), div (integer ad//ADS_PER_CAMPAIGN — the
fixture table is contiguous, bound of any real lookup), pallas_gather (per-lane
VMEM gather in a Pallas kernel, if Mosaic supports it), pallas_onehot (factored
lookup as ONE Pallas kernel, rows intermediate VMEM-resident).
Prints one line: PROBE <name> <ms_per_step>. Set WF_DUMP_HLO=1 to also write the
optimized HLO to scripts/hlo_<name>.txt.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.batch import CTRL_DTYPE
from windflow_tpu.benchmarks import ysb
from windflow_tpu.ops.lookup import _factored_lookup, table_lookup

BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 1 << 20
STEPS = 30
CAMP_OF = jnp.asarray(np.arange(ysb.N_ADS) // ysb.ADS_PER_CAMPAIGN, CTRL_DTYPE)


def _factored_bf16(table, idx):
    """Factored lookup with the one-hot and table in bf16 (campaign ids < 256
    are bf16-exact); halves the matmul-side HBM traffic."""
    K = table.shape[0]
    K2 = 1 << max(1, (K - 1).bit_length() // 2)
    K1 = (K + K2 - 1) // K2
    t2 = jnp.pad(table, (0, K1 * K2 - K)).reshape(K1, K2).astype(jnp.bfloat16)
    hi = idx // K2
    lo = idx - hi * K2
    ohhi = (hi[:, None] == jnp.arange(K1, dtype=idx.dtype)).astype(jnp.bfloat16)
    rows = jax.lax.dot_general(ohhi, t2, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.bfloat16)
    ohlo = lo[:, None] == jnp.arange(K2, dtype=idx.dtype)
    return jnp.sum(jnp.where(ohlo, rows, jnp.bfloat16(0)),
                   axis=1).astype(table.dtype)


def _barrier_factored(table, idx):
    idx = jax.lax.optimization_barrier(idx)
    return jax.lax.optimization_barrier(_factored_lookup(table, idx))


def _pallas_gather(table, idx):
    """Per-lane VMEM gather inside a Pallas kernel — works iff Mosaic supports
    vector dynamic gather on this TPU generation; the probe harness exists to
    find out."""
    import jax.experimental.pallas as pl
    C, K = idx.shape[0], table.shape[0]
    BLK = 8192
    assert C % BLK == 0, f"pallas probe needs batch % {BLK} == 0, got {C}"

    def kern(t_ref, i_ref, o_ref):
        o_ref[...] = t_ref[...][i_ref[...]]

    return pl.pallas_call(
        kern,
        grid=(C // BLK,),
        in_specs=[pl.BlockSpec((K,), lambda i: (0,)),
                  pl.BlockSpec((BLK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((C,), table.dtype),
    )(table, idx)


def _pallas_onehot(table, idx):
    """The PRODUCTION one-kernel factored lookup
    (windflow_tpu.ops.lookup._pallas_factored_lookup): rows intermediate
    VMEM-resident. Imported, not duplicated — the probe decides whether to
    adopt that exact function in the chain, so it must measure it."""
    from windflow_tpu.ops.lookup import _pallas_block, _pallas_factored_lookup
    assert _pallas_block(idx.shape[0]), \
        f"batch {idx.shape[0]} not blockable by the production kernel"
    return _pallas_factored_lookup(table, idx)


VARIANTS = {
    "factored": lambda ad: _factored_lookup(CAMP_OF, ad),
    "factored_bf16": lambda ad: _factored_bf16(CAMP_OF, ad),
    "take": lambda ad: jnp.take(CAMP_OF, ad),
    "barrier": lambda ad: _barrier_factored(CAMP_OF, ad),
    "div": lambda ad: ad // ysb.ADS_PER_CAMPAIGN,
    "pallas_gather": lambda ad: _pallas_gather(CAMP_OF, ad),
    "pallas_onehot": lambda ad: _pallas_onehot(CAMP_OF, ad),
}


def _time(step, carry):
    carry = step(carry, 0)
    jax.block_until_ready(carry)
    times = []
    pos = 1
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            carry = step(carry, pos * BATCH)
            pos += 1
        jax.block_until_ready(carry)
        times.append(time.perf_counter() - t0)
    return sorted(times)[1] / STEPS


def _maybe_dump(name, fn, *args):
    if os.environ.get("WF_DUMP_HLO"):
        txt = jax.jit(fn).lower(*args).compile().as_text()
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"hlo_{name}.txt")
        with open(path, "w") as f:
            f.write(txt)


def prefix(variant):
    src = ysb.make_source(total=(3 * STEPS + 2) * BATCH)
    # None = prefix2_base (source+filter only); anything else must be a known
    # variant — .get would silently measure the baseline under a typo'd name
    look = None if variant is None else VARIANTS[variant]

    @jax.jit
    def step(carry, start):
        b = src.make_batch(jnp.asarray(start, jnp.int32), BATCH)
        keep = b.valid & (b.payload["event_type"] == 0)
        if look is not None:
            cmp = look(b.payload["ad_id"])
            return carry + jnp.sum(jnp.where(keep, cmp, 0))
        return carry + jnp.sum(keep.astype(jnp.int32))

    _maybe_dump(f"prefix2_{variant or 'base'}", step, jnp.int32(0), 0)
    return _time(step, jnp.int32(0))


def standalone(variant):
    look = VARIANTS[variant]
    rng = np.random.default_rng(0)
    ad = jnp.asarray(rng.integers(0, ysb.N_ADS, BATCH).astype(np.int32))

    @jax.jit
    def step(carry, _start):
        # data-depend on carry so steps chain (valid async timing)
        a = (ad + carry % 2).astype(jnp.int32) % ysb.N_ADS
        return carry + jnp.sum(look(a))

    _maybe_dump(f"standalone_{variant}", step, jnp.int32(0), 0)
    return _time(step, jnp.int32(0))


if __name__ == "__main__":
    name = sys.argv[1]
    if name == "prefix2_base":
        dt = prefix(None)
    elif name.startswith("prefix2_"):
        dt = prefix(name[len("prefix2_"):])
    elif name.startswith("standalone_"):
        dt = standalone(name[len("standalone_"):])
    else:
        raise SystemExit(f"unknown probe {name}")
    print(f"PROBE {name} {dt * 1e3:.4f} ms/step (batch={BATCH})")
