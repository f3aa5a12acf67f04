"""Checkpoint mid-stream, 'crash', restore into a NEW process-fresh pipeline,
and continue — ending bit-identical to an uninterrupted run.

The reference has no checkpointing (state dies with the process,
SURVEY §5); here every operator's state is a pytree, so save/restore is
np.savez of the chain (runtime/checkpoint.py). The same mechanism powers
supervised exactly-once recovery (SupervisedPipeline) and elastic mesh
rescaling.
"""
import _common
_common.bootstrap()

import os

import tempfile
import jax.numpy as jnp
import numpy as np
import windflow_tpu as wf
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.basic import win_type_t

TOTAL, BATCH, K = 4000, 256, 8

def make_chain():
    src = wf.Source(lambda i: {"v": ((i * 13) % 23).astype(jnp.float32)},
                    total=TOTAL, num_keys=K)
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(64, 32, win_type_t.CB), num_keys=K)
    chain = wf.CompiledChain([op], src.payload_spec(), batch_capacity=BATCH)
    return src, chain

def collect(out, batch):
    v = np.asarray(batch.valid)
    out.extend(zip(np.asarray(batch.key)[v].tolist(),
                   np.asarray(batch.id)[v].tolist(),
                   np.asarray(batch.payload)[v].tolist()))

# ---- golden: uninterrupted run
src, chain = make_chain()
golden = []
for b in src.batches(BATCH):
    collect(golden, chain.push(b))
for fb in chain.flush():
    collect(golden, fb)

# ---- interrupted run: checkpoint at the half-way batch, then "crash"
src, chain = make_chain()
part1, seen = [], 0
ckpt = os.path.join(tempfile.mkdtemp(), "chain.npz")
for b in src.batches(BATCH):
    collect(part1, chain.push(b))
    seen += BATCH
    if seen >= TOTAL // 2:
        wf.save_chain(chain, ckpt, meta={"position": seen})
        break
del chain                                  # the "crash"

# ---- resume: fresh chain, restore state, fast-forward the source
src2, chain2 = make_chain()
meta = wf.load_chain(chain2, ckpt)
pos = meta["position"]
part2 = []
it = src2.batches(BATCH)
for _ in range(pos // BATCH):          # replayable source: skip committed batches
    next(it)
for b in it:
    collect(part2, chain2.push(b))
for fb in chain2.flush():
    collect(part2, fb)

assert sorted(part1 + part2) == sorted(golden), "resume diverged from golden run"
print(f"checkpoint/resume OK: {len(part1)}+{len(part2)} window results == "
      f"{len(golden)} golden")
