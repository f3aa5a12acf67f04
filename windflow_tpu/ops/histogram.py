"""Keyed-pane histograms and integer folds on the MXU — the FFAT-insert hot path.

The reference's incremental window engines fold each tuple into a per-(key, pane)
partial (``wf/flatfat.hpp:134-240`` leaf update; ``wf/win_seqffat.hpp:389-396``).
The direct TPU translation is a scatter-add, but XLA lowers scatter to a serialized
per-update loop (~18 ns/update measured on v5e) — at 1M-tuple batches that is the
whole step budget.

This module computes the same ``[K, P]`` accumulation as two one-hot matmuls that run
on the MXU:

1. **Chunk-local histogram.** The batch is viewed as ``[R, chunk]`` rows. Event
   timestamps in a stream are *locally clustered*: the panes touched inside one chunk
   of consecutive lanes span a tiny range ``L`` (for a time-ordered stream,
   ``chunk/rate`` time units). Per chunk we take ``base_r = min(pane)`` and build two
   one-hots — key ``[R, chunk, K]`` and local pane ``[R, chunk, L]`` — whose batched
   contraction ``einsum('rck,rcl->rkl')`` is an MXU matmul producing per-chunk
   ``[K, L]`` histograms. 0/1 inputs with f32 accumulation are exact (sums ≤ chunk).
2. **Ring placement.** ``[R, K, L] -> [K, P]`` is one more matmul against the one-hot
   of ``(base_r + l) % P`` — column placement into the pane ring, wrap-around
   included. f32 accumulation stays exact while every count ≤ 2^24.

Batches that violate the locality bound (a chunk spanning ≥ L panes: late or
wildly out-of-order timestamps) are detected on device and routed under
``lax.cond``: the fast path is an optimization, never a semantics change.

**Values as well as counts.** :func:`keyed_pane_fold` is the one additive
pane fold. With no value leaf it is the count histogram; an additive fold of
integers into the same (key, pane) cells rides the same contraction: the local
pane one-hot is 8 columns wide where the MXU takes 128, so beside the count's
column group the right-hand operand carries one group per 8-bit limb of each
value (``v = l3*2^24 + l2*2^16 + l1*2^8 + l0``, the sign in the top limb).
A limb is an integer of magnitude ≤ 255, exact in bf16, and a chunk's limb sum
stays under 2^24, exact in f32. The ring placement adds the chunk partials in
groups small enough for f32 (:func:`_place_group`), the groups add in wrapping
int32, and the limbs recombine with wrapping shifts: bit for bit
``jax.ops.segment_sum`` on the integers, overflow included. Its fallback is
partial: a batch whose chunks are not local at their oldest pane folds the
lanes near each chunk's newest pane in the same contraction and scatters the
stragglers alone; only a chunk with more stragglers than an eighth of it
sends the whole batch to the scatters.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

#: default lanes per chunk-local histogram row
DEFAULT_CHUNK = 1024
#: default pane-locality bound per chunk (panes spanned by one chunk)
DEFAULT_L = 8
#: lanes of one chunk that :func:`keyed_pane_fold`'s partial branch may
#: scatter (those its one-hot window does not hold): an eighth of a chunk
SPILL_M = DEFAULT_CHUNK // 8
#: :func:`keyed_pane_fold`'s branches, as its ``branch`` output numbers them
FOLD_FAST, FOLD_PARTIAL, FOLD_WHOLE = 0, 1, 2
#: key-axis tile for the chunk-local one-hot (caps transient memory at ~C*K_TILE B)
K_TILE = 512


def _chunk_locality(pane, valid, R, chunk, locality):
    """Per chunk of ``chunk`` consecutive lanes: the smallest valid pane
    ``base`` [R], every lane's distance from it ``local`` [R, chunk], the
    lanes the chunk-local one-hot can hold ``ok_local``, and whether that is
    every valid lane of the batch (``in_bounds``: the fast branch is exact)."""
    pane_r = pane.reshape(R, chunk)
    valid_r = valid.reshape(R, chunk)
    big = jnp.iinfo(pane.dtype).max
    base = jnp.min(jnp.where(valid_r, pane_r, big), axis=1)      # [R]
    base = jnp.where(base == big, 0, base)
    local = pane_r - base[:, None]                               # [R, chunk]
    ok_local = valid_r & (local < locality)

    in_bounds = jnp.all(ok_local == valid_r)
    return base, local, ok_local, in_bounds


def _chunk_window(pane, valid, R, chunk, locality):
    """Per chunk: the window of ``locality`` panes that ends at the chunk's
    newest valid pane, ``base`` [R] its first, every lane's distance from it
    ``local`` [R, chunk], and the valid lanes inside it ``fits``. Stragglers
    fall behind such a window and in-order lanes stay in it; the distance
    wraps in int32 as ``local`` does, and lies in the window exactly when
    the lane's pane does."""
    pane_r = pane.reshape(R, chunk)
    valid_r = valid.reshape(R, chunk)
    small = jnp.iinfo(pane.dtype).min
    newest = jnp.max(jnp.where(valid_r, pane_r, small), axis=1)  # [R]
    base = jnp.where(newest == small, 0, newest - (locality - 1))
    local = pane_r - base[:, None]
    fits = valid_r & (local >= 0) & (local < locality)
    return base, local, fits


def _compact(spill, cols, M):
    """Each row's first ``M`` lanes that ``spill`` marks, in lane order, of
    every ``[R, chunk]`` column: ``[R, M]`` each, 0 in the slots past a
    row's count. A lane's slot is the count of marked lanes before it in its
    row; a column's slots are one select-reduce over the ``[R, M, chunk]``
    one-hot of those places (no gather, no scatter).

    The count is one dot against a triangular 0/1 matrix, not ``cumsum``:
    bf16 holds the operands and f32 the sums (at most ``chunk``), so it is
    exact, and on a v5e it costs a tenth of the reduce-window ``cumsum``
    lowers to (``PERF.md`` §6 has the prices of both)."""
    lane = jnp.arange(spill.shape[1], dtype=jnp.int32)
    upto = (lane[:, None] <= lane).astype(jnp.bfloat16)          # c <= c'
    pos = jnp.dot(spill.astype(jnp.bfloat16), upto,
                  preferred_element_type=jnp.float32).astype(jnp.int32) - 1
    sel = spill[:, None, :] & (
        pos[:, None, :] == jnp.arange(M, dtype=pos.dtype)[:, None])
    return [jnp.sum(jnp.where(sel, c[:, None, :], 0), axis=2) for c in cols]


def _chunk_contract(key, local, ok_local, K, R, chunk, locality, weights=None):
    """The chunk-local contraction ``rck,rcm->rkm``: key one-hot against the
    local-pane one-hot -> ``f32[R, K, L]`` counts. With ``weights``
    (``bf16[R, chunk, J]``, integers of magnitude <= 255) the right-hand
    operand holds one column group a weight, the one-hot times it:
    ``f32[R, K, J * L]``, column ``j * L + l``. Exact: bf16 holds the
    operands, f32 a chunk's sum (at most 255 x chunk)."""
    lr = jnp.where(ok_local, local, 0)
    key_r = key.reshape(R, chunk)
    ohl = ((lr[:, :, None] == jnp.arange(locality, dtype=lr.dtype))
           & ok_local[:, :, None]).astype(jnp.bfloat16)
    if weights is not None:
        ohl = (weights[:, :, :, None] * ohl[:, :, None, :]).reshape(
            R, chunk, weights.shape[2] * locality)
    # tile the key axis: bounds the transient [R, chunk, K_tile] one-hot to
    # ~C * K_TILE bytes instead of C * K (K can be thousands)
    tiles = []
    for k0 in range(0, K, K_TILE):
        kn = min(K_TILE, K - k0)
        ohk = ((key_r[:, :, None]
                == jnp.arange(k0, k0 + kn, dtype=key.dtype))
               & ok_local[:, :, None]).astype(jnp.bfloat16)
        tiles.append(jnp.einsum("rck,rcl->rkl", ohk, ohl,
                                preferred_element_type=jnp.float32))
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _ring_onehot(base, locality, P):
    """``f32[R * L, P]``: chunk r's local pane l goes to ring column
    ``(base[r] + l) % P``."""
    slot = (base[:, None] + jnp.arange(locality, dtype=base.dtype)) % P
    return (slot.reshape(-1)[:, None]
            == jnp.arange(P, dtype=slot.dtype)).astype(jnp.float32)


def _scatter_hist(key, pane, valid, K, P):
    seg = jnp.where(valid, key * P + pane % P, K * P)
    return jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                               num_segments=K * P).reshape(K, P)


def pane_fold_applies(values: Any, *, chunk: int = DEFAULT_CHUNK) -> bool:
    """Whether :func:`keyed_pane_fold` takes these lifted values (arrays or
    their ``ShapeDtypeStruct``): every leaf ``[C]`` of an integer dtype of at
    most 4 bytes, ``C`` whole chunks. Floats (a matmul re-orders their sum),
    leaves of higher rank and odd capacities are the scatter path's."""
    leaves = jax.tree.leaves(values)
    return bool(leaves) and all(
        len(v.shape) == 1 and v.shape[0] >= chunk and v.shape[0] % chunk == 0
        and jnp.issubdtype(v.dtype, jnp.integer)
        and jnp.dtype(v.dtype).itemsize <= 4 for v in leaves)


def _as_int32(v):
    """An integer leaf as int32, sign- or zero-extended (uint32 bit for
    bit): wrapping int32 sums of these, cut to the leaf's width
    (:func:`_from_limbs`), are the leaf's own sums."""
    return (jax.lax.bitcast_convert_type(v, jnp.int32) if v.dtype == jnp.uint32
            else v.astype(jnp.int32))


def _limbs(v):
    """An integer leaf as 8-bit limbs of an int32 (its own width's bits, sign-
    or zero-extended), low byte first: ``sum(limb[i] << 8 * i)`` is the value
    in wrapping int32. The low limbs lie in [0, 255]; the top one carries the
    sign (an arithmetic shift: [-128, 127], or [0, 255] of a narrower unsigned
    leaf)."""
    n = jnp.dtype(v.dtype).itemsize
    x = _as_int32(v)
    return [(x >> (8 * i)) & 255 for i in range(n - 1)] + [x >> (8 * (n - 1))]


def _from_limbs(sums, dtype):
    """The limbs' folds back into one value of ``dtype``: wrapping int32
    shifts and adds, then the leaf's own width of the result."""
    acc = sums[0]
    for i, s in enumerate(sums[1:], 1):
        acc = acc + (s << (8 * i))
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(acc, jnp.uint32)
    spare = 32 - 8 * dtype.itemsize
    if spare:
        # a narrower sum wraps at its own width: keep the low bits, and
        # extend them as the dtype reads them
        acc = ((acc << spare) >> spare if jnp.issubdtype(dtype, jnp.signedinteger)
               else acc & ((1 << 8 * dtype.itemsize) - 1))
    return acc.astype(dtype)


def _place_group(R: int, chunk: int, weight: int = 255) -> int:
    """Chunks whose partials one f32 placement dot may add: the largest
    power of two that divides ``R`` and keeps ``weight`` x chunk x group
    under 2^24, so every sum the dot makes is exact whatever the batch holds
    (all of it one key's one pane at 2^31 - 1 included). For 8-bit limbs
    (``weight`` 255) that is 64 chunks of 1,024; for counts alone (1), every
    chunk of a batch of up to 2^24 lanes."""
    most = ((1 << 24) - 1) // (weight * chunk)
    if most < 1:
        raise ValueError(f"a chunk of {chunk} lanes can sum past 2^24")
    return math.gcd(R, 1 << (most.bit_length() - 1))


def keyed_pane_fold(key: jax.Array, pane: jax.Array, valid: jax.Array,
                    values: Any, num_keys: int, ring: int, *,
                    chunk: int = DEFAULT_CHUNK, locality: int = DEFAULT_L):
    """The occupancy histogram and the additive fold of integer ``values``
    into the same (key, ``pane % ring``) cells, in ONE chunk-local contraction:
    ``(counts i32[K, P], folds: the values' pytree of [K, P], branch,
    spilled)``.

    ``values``: a pytree that :func:`pane_fold_applies` accepts, or ``()``:
    with no leaf the fold is the count histogram
    ``counts[k, pane % ring] = #{valid lanes: key == k, pane}``. ``folds``
    equals ``jax.ops.segment_sum`` of each masked leaf bit for bit (wrapping at
    the leaf's width) and ``counts`` that of the valid lanes, for any input,
    whichever of three branches the batch takes (``branch``, i32[]):

    - :data:`FOLD_FAST`: every chunk of ``chunk`` consecutive lanes lies
      within ``locality`` panes of its oldest valid lane, and the contraction
      holds them all.
    - :data:`FOLD_PARTIAL`: it does not, and the contraction takes the lanes
      within ``locality`` panes of each chunk's newest valid lane instead
      (:func:`_chunk_window`); the rest, at most :data:`SPILL_M` a chunk,
      are compacted (:func:`_compact`, scope ``spill``) and scattered (scope
      ``scatter``), and the two parts add in wrapping int32. In a stream with
      late tuples the in-order lanes stay in the contraction and the
      stragglers alone are scattered: ``spilled`` (i32[]) counts them.
    - :data:`FOLD_WHOLE`: a chunk spills more than :data:`SPILL_M` lanes, and
      the whole batch takes the two scatters (:func:`_scatter_hist`,
      ``ops/segment.py::segment_reduce``) under ``scatter``. A batch that is
      not a whole number of chunks takes them statically.

    The first test is one ``lax.cond`` whose taken side is the fast branch
    alone; the partial and whole branches lie in its other side, behind a
    second ``cond`` on the spill counts.

    The contraction's right-hand operand holds ``1 + limbs`` column groups of
    ``locality`` columns (40 for one int32 leaf), under the 128 the MXU takes
    in one pass, so values cost what counts alone cost."""
    from .segment import segment_reduce
    leaves, treedef = jax.tree.flatten(values)
    C = key.shape[0]
    K, P, L = int(num_keys), int(ring), int(locality)

    def scatter(_):
        # its own scope, so that a profile tells the fallback's device time
        # from the fast branch's
        with jax.named_scope("scatter"):
            seg = jnp.where(valid, key * P + pane % P, K * P)
            return (_scatter_hist(key, pane, valid, K, P),
                    [u.reshape(K, P)
                     for u in segment_reduce(leaves, seg, valid, K * P)])

    if C % chunk != 0 or C < chunk:
        counts, folds = scatter(None)
        return (counts, jax.tree.unflatten(treedef, folds),
                np.int32(FOLD_WHOLE), np.int32(0))
    R = C // chunk
    group = _place_group(R, chunk, 255 if leaves else 1)
    # Materialize the inputs before the one-hot tiles consume them: in a
    # fused chain ``key`` is often itself the result of a matmul-formulated
    # lookup (the YSB campaign join), which XLA would otherwise re-fuse into
    # every K_TILE tile (measured: 15 us standalone, ~5 ms fused in the YSB
    # chain). Semantics-neutral.
    key, pane, valid, leaves = jax.lax.optimization_barrier(
        (key, pane, valid, leaves))
    base, local, ok_local, in_bounds = _chunk_locality(
        pane, valid, R, chunk, L)

    def contract(base, local, ok_local):
        """Counts and every leaf's fold of the lanes ``ok_local`` holds: the
        chunk-local contraction and the ring placement."""
        limbs = [_limbs(v) for v in leaves]
        J = 1 + sum(map(len, limbs))
        # column group 0 counts (weight 1), then every leaf's limbs; a dead
        # lane's weights meet an all-zero one-hot row. No leaf: the one-hot
        # alone counts
        weights = None
        if limbs:
            weights = jnp.stack(
                [jnp.ones((C,), jnp.int32)] + [w for ws in limbs for w in ws],
                axis=-1).astype(jnp.bfloat16).reshape(R, chunk, -1)
        h = _chunk_contract(key, local, ok_local, K, R, chunk, L, weights)
        # ring placement, `group` chunks a dot: sums under 2^24, exact in f32
        # (HIGHEST: a TPU's default f32 dot is one bf16 pass)
        placed = jax.lax.dot_general(                   # gakjl,galp->gkjp
            h.reshape(R // group, group, K, J, L),
            _ring_onehot(base, L, P).reshape(R // group, group, L, P),
            (((1, 4), (1, 2)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        sums = jnp.sum(placed.astype(jnp.int32), axis=0)         # [K, J, P]
        folds, j = [], 1
        for v, ws in zip(leaves, limbs):
            folds.append(_from_limbs(
                [sums[:, j + i] for i in range(len(ws))], v.dtype))
            j += len(ws)
        return sums[:, 0], folds

    def fast(_):
        counts, folds = contract(base, local, ok_local)
        return counts, folds, np.int32(FOLD_FAST), np.int32(0)

    def slow(_):
        base_n, local_n, fits = _chunk_window(pane, valid, R, chunk, L)
        spill = valid.reshape(R, chunk) & ~fits
        n_spill = jnp.sum(spill.astype(jnp.int32), axis=1)        # [R]

        def partial(_):
            counts, folds = contract(base_n, local_n, fits)
            with jax.named_scope("spill"):
                # a spill lane's cell less the dead one, so that a slot no
                # lane filled (0) reads as the dead cell K * P, which the
                # scatter drops
                dead = K * P
                seg, *xs = _compact(
                    spill, [(key * P + pane % P - dead).reshape(R, chunk)]
                    + [_as_int32(v).reshape(R, chunk) for v in leaves],
                    SPILL_M)
                seg = seg.reshape(-1) + dead
                rows = jnp.stack([jnp.ones_like(seg)]
                                 + [x.reshape(-1) for x in xs], axis=-1)
            with jax.named_scope("scatter"):
                sums = jax.ops.segment_sum(rows, seg, num_segments=dead)
            return (counts + sums[:, 0].reshape(K, P),
                    [f + _from_limbs([sums[:, 1 + i]], v.dtype).reshape(K, P)
                     for i, (f, v) in enumerate(zip(folds, leaves))],
                    np.int32(FOLD_PARTIAL), jnp.sum(n_spill))

        def whole(_):
            counts, folds = scatter(None)
            return counts, folds, np.int32(FOLD_WHOLE), np.int32(0)

        return jax.lax.cond(jnp.all(n_spill <= SPILL_M), partial, whole, None)

    counts, folds, branch, spilled = jax.lax.cond(in_bounds, fast, slow, None)
    return counts, jax.tree.unflatten(treedef, folds), branch, spilled
