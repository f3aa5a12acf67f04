"""Multi-chip execution: the same keyed-window pipeline sharded over a device
mesh — batch axis on ``dp`` (operator replication), key-state tables on ``key``
(Key_Farm whole-key ownership) — and verified oracle-identical to the
single-device run.

Run on a host with several chips, or anywhere on a virtual CPU mesh:

    JAX_PLATFORMS=cpu python examples/04_multichip.py
"""
import _common
_common.bootstrap(virtual_devices=8)

import sys

import jax
import jax.numpy as jnp
import numpy as np
import windflow_tpu as wf
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.basic import win_type_t

TOTAL, BATCH, K = 8000, 512, 16

def make_chain():
    src = wf.Source(lambda i: {"v": ((i * 7) % 31).astype(jnp.float32)},
                    total=TOTAL, num_keys=K)
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(50, 25, win_type_t.TB), num_keys=K)
    return src, wf.CompiledChain([op], src.payload_spec(), batch_capacity=BATCH)

def run(sharded):
    src, chain = make_chain()
    if sharded:
        n = min(8, jax.device_count())
        mesh = wf.make_mesh_2d((2, n // 2), axes=("dp", "key"))
        chain = wf.ShardedChain(chain, mesh, axis="dp", key_axis="key")
    out = []
    for b in src.batches(BATCH):
        ob = chain.push(b)
        v = np.asarray(ob.valid)
        out.extend(zip(np.asarray(ob.key)[v].tolist(),
                       np.asarray(ob.id)[v].tolist(),
                       np.asarray(ob.payload)[v].tolist()))
    for fb in (chain.flush() or []):
        v = np.asarray(fb.valid)
        out.extend(zip(np.asarray(fb.key)[v].tolist(),
                       np.asarray(fb.id)[v].tolist(),
                       np.asarray(fb.payload)[v].tolist()))
    return sorted(out)

if jax.device_count() < 2:
    print("multichip example needs >= 2 devices: run on a multi-chip host or\n"
          "  JAX_PLATFORMS=cpu python examples/04_multichip.py   (virtual mesh)")
    sys.exit(1)

single = run(sharded=False)
multi = run(sharded=True)
assert single == multi and single, "sharded run diverged from single-device oracle"
print(f"multichip OK: {len(multi)} window results identical on the "
      f"{min(8, jax.device_count())}-device mesh")
