"""Pallas TPU kernels for dense hot ops.

Scope note (measured; see docs/ARCHITECTURE.md §5): the framework's irregular ops —
scatter-add pane folds, dynamic gathers — are NOT expressible efficiently in Mosaic
(dynamic VMEM indexing must be provably tile-aligned; a random-index store fails with
"cannot statically prove that index ... is a multiple of 1024"), and XLA's scatter
emitter is the fastest available path. Pallas is used where its tiling model fits:
dense batched reductions over the fired-window axis — the compute inside the
reference GPU engine's ``ComputeBatch_Kernel`` (one thread per window,
``wf/win_seq_gpu.hpp:57-82``), here one *tile row* per window.

``masked_window_reduce``: given window contents ``[W, L]`` + occupancy mask, produce
per-window sums — the hot aggregation of Win_Seq non-incremental sum windows. The
data path itself uses the XLA formulation (``Iterable.sum``); whether this kernel
beats it has not been measured on the current code (ROADMAP A10 decides, C4 deletes
the loser).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from .registry import pallas_interpret

#: row-tile height per grid step (W axis); L is processed whole per row-tile.
ROW_TILE = 256


def _reduce_kernel(vals_ref, mask_ref, out_ref):
    v = vals_ref[...]
    m = mask_ref[...]
    s = jnp.sum(jnp.where(m, v, jnp.zeros_like(v)), axis=1, keepdims=True)
    out_ref[...] = jnp.broadcast_to(s.T, out_ref.shape)


def _xla_masked_sum(vals, mask):
    return jnp.sum(jnp.where(mask, vals, jnp.zeros_like(vals)), axis=1)


_xla_jit = jax.jit(_xla_masked_sum)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_masked_sum(vals, mask, *, interpret=False):
    # The [W] result is produced as an [8, W] lane-oriented buffer: a 1-D out
    # operand would get XLA's T(1024) linear tiling, which Mosaic's
    # (sublane, lane) block model cannot match ("XLA layout {0:T(1024)} does
    # not match Mosaic layout {0:T(256)}"), and a (1, T) block violates the
    # sublane-divisible-by-8 rule. 8 sublanes × ROW_TILE lanes satisfies both;
    # the extra 7 rows are dead writes (W*28 B — noise next to the W*L*4 read).
    W, L = vals.shape
    out = pl.pallas_call(
        _reduce_kernel,
        grid=(W // ROW_TILE,),
        in_specs=[pl.BlockSpec((ROW_TILE, L), lambda i: (i, 0)),
                  pl.BlockSpec((ROW_TILE, L), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, ROW_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, W), vals.dtype),
        interpret=interpret,
    )(vals, mask)
    return out[0]


def masked_window_reduce(vals: jax.Array, mask: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """Per-window masked sum of ``vals [W, L]`` under ``mask [W, L]`` -> ``[W]``.

    Shapes the row-tile grid cannot block (``W % ROW_TILE`` or ``L % 128``)
    take the XLA formulation; every other shape runs the Pallas kernel —
    interpreted on the CPU backend, compiled by Mosaic on TPU, where a
    lowering failure raises (it is never swapped for the XLA form)."""
    W, L = vals.shape
    if W % ROW_TILE or L % 128:
        return _xla_jit(vals, mask)
    return _pallas_masked_sum(vals, mask,
                              interpret=interpret or pallas_interpret())
