"""Fault recovery with an O(1) seekable source, and lossless routing under
deliberate overflow — the two r05 hardening contracts, end to end.

1. A SupervisedPipeline takes injected device faults mid-stream and recovers
   from the last aligned checkpoint. The source's ``it_factory`` declares a
   ``from_batch`` parameter, so restart resumes AT the committed chunk index
   (the factory owns the cursor — here plain arithmetic, in production a file
   offset) instead of replaying the stream. Output must be exactly-once,
   bit-identical to a fault-free run.

2. A Standard_Emitter with a per-destination budget far below one skewed
   key's share must deliver EVERY tuple anyway: overflowing lanes are
   re-partitioned in further passes (the blocking bounded-queue backpressure
   of the reference's FF_BOUNDED_BUFFER — it blocks, it never drops).
"""
import _common
_common.bootstrap()

import numpy as np
import jax
import jax.numpy as jnp
import windflow_tpu as wf
from windflow_tpu.basic import routing_modes_t, win_type_t
from windflow_tpu.batch import Batch
from windflow_tpu.operators.source import GeneratorSource
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.parallel.emitters import Standard_Emitter
from windflow_tpu.runtime.supervisor import SupervisedPipeline

TOTAL, BATCH, K = 2000, 100, 4

# ---- 1. supervised recovery through the seekable-source cursor --------------


def factory(from_batch=0):
    """Chunk k is pure arithmetic on k — seeking is O(1). The supervisor calls
    factory(from_batch=committed_chunk) on restart."""
    def gen():
        for s in range(from_batch * BATCH, TOTAL, BATCH):
            ids = np.arange(s, s + BATCH, dtype=np.int32)
            yield ({"v": ((ids * 7) % 31).astype(np.float32)}, ids % K, ids)
    return gen()


def build(sink_cb, **kw):
    src = GeneratorSource(factory, {"v": jnp.zeros((), jnp.float32)})
    op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                    WindowSpec(25, 25, win_type_t.TB), num_keys=K)
    return SupervisedPipeline(src, [op], wf.Sink(sink_cb),
                              batch_size=BATCH, **kw)


def collect(results):
    def cb(view):
        if view is None:
            return
        results.extend(zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()))
    return cb


golden = []
build(collect(golden)).run()

got = []
p = build(collect(got), checkpoint_every=3, max_restarts=5)
inner, fail_at = p.chain.push, {5, 11}
calls = [0]


def flaky(batch):
    calls[0] += 1
    if calls[0] in fail_at:
        raise RuntimeError(f"injected device fault at push #{calls[0]}")
    return inner(batch)


p.chain.push = flaky
p.run()
assert p.restarts == 2, p.restarts
assert sorted(got) == sorted(golden) and golden, "lost/duplicated results"
print(f"recovery: {p.restarts} faults recovered, "
      f"{len(got)} window results exactly-once, O(1) resume")

# ---- 2. lossless routing under overflow -------------------------------------

rng = np.random.default_rng(3)
C = 256
keys = np.where(rng.random(C) < 0.6, 0, rng.integers(0, 32, C)).astype(np.int32)
valid = rng.random(C) < 0.9
b = Batch(key=jnp.asarray(keys), id=jnp.arange(C, dtype=jnp.int32),
          ts=jnp.zeros(C, jnp.int32),
          payload={"v": jnp.arange(C, dtype=jnp.float32)},
          valid=jnp.asarray(valid))
em = Standard_Emitter(4, routing_modes_t.KEYBY, capacity_per_dest=8)
outs = em.route(b)
delivered = []
for d, ob in enumerate(outs):
    ob = jax.tree.map(np.asarray, ob)
    assert np.all(ob.key[ob.valid] % 4 == d)
    delivered.extend(ob.payload["v"][ob.valid].tolist())
want = [float(i) for i, ok in enumerate(valid) if ok]
assert sorted(delivered) == sorted(want)
print(f"backpressure: {len(want)} tuples through a budget of 8/dest in "
      f"{em.overflow_rounds + 1} passes, zero loss")
print("OK")
