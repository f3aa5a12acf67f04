"""Per-prefix YSB ablation in the EXACT bench_ysb configuration (same source,
ops, pane ring, donation, async timing loop): the device time of each prefix
of the chain, one prefix per invocation (ROADMAP A3).

Usage: python scripts/probe_ysb_ablation.py <n_ops> [batch]
  n_ops 0..4: source only, +filter, +join, +rekey, +window
Prints one line: ABLATE <n_ops> <ms_per_step>. WF_DUMP_HLO=1 additionally
writes the optimized HLO to scripts/hlo_ablate_<n_ops>.txt.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from windflow_tpu.benchmarks import ysb
from windflow_tpu.runtime.pipeline import CompiledChain

BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 1 << 20
STEPS = 30


def run(n_ops: int) -> float:
    panes_per_batch = BATCH // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN) + 1
    src = ysb.make_source(total=(3 * STEPS + 2) * BATCH)
    ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                       max_wins=panes_per_batch + 64)[:n_ops]
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH)

    def step(states, start):
        batch = src.make_batch(jnp.asarray(start, jnp.int32), BATCH)
        states = list(states)
        for j, op in enumerate(chain.ops):
            states[j], batch = op.apply(states[j], batch)
        # reduce to a scalar so every prefix returns the same tiny output
        # (a full-batch D2H would distort the compare)
        tot = jnp.sum(batch.valid.astype(jnp.int32))
        if "cmp" in batch.payload:
            tot = tot + jnp.sum(jnp.where(batch.valid, batch.payload["cmp"], 0))
        return tuple(states), tot

    step = jax.jit(step, donate_argnums=0)
    if os.environ.get("WF_DUMP_HLO"):
        import bench
        specs = bench._arg_specs((tuple(chain.states), 0))
        txt = step.lower(*specs).compile().as_text()
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"hlo_ablate_{n_ops}.txt")
        with open(path, "w") as f:
            f.write(txt)

    states, out = step(tuple(chain.states), 0)
    jax.block_until_ready(out)
    times = []
    pos = 1
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            states, out = step(states, pos * BATCH)
            pos += 1
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return sorted(times)[1] / STEPS


if __name__ == "__main__":
    n = int(sys.argv[1])
    dt = run(n)
    print(f"ABLATE {n} {dt * 1e3:.4f} ms/step (batch={BATCH})")
