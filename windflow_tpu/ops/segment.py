"""Segmented (per-key) reductions and scans over micro-batches.

The device-side replacement for the reference's KEYBY routing (hash(key) -> replica
queue, ``wf/standard_emitter.hpp:85-110``): a whole batch stays on device and per-key
semantics come from segment operations. Sort-by-key is the plan, as the reference's own
GPU scattering study found (``src/GPU_Tests/scattering/results_scattering.org``).

TPU cost discipline (docs/ARCHITECTURE.md §5): permutation gathers cost ~5.6 ns/elem,
so sorting carries companion arrays through multi-operand ``lax.sort`` (one fused sort,
no ``take(order)``). A scatter over the lanes is no cheaper: at C = 1,048,576 on one
v5e each costs 4.8-9.2 ms whether it writes 512 segments or 2 M, where the sort of
the same lanes with three companions costs 1.7 ms (PERF.md section 6, PR 26). So
what only needs per-group results stays in sorted order (:func:`segment_run_fold`:
closed-form run boundaries, a ``cumsum`` or a segmented scan, run-sized writes), and
only what a caller needs lane by lane (:func:`segment_rank`,
:func:`segment_prefix_scan`) returns to stream order, with a single scatter. Lanes
that have to land in a keyed table go there from sorted order too, as whole rows
(:func:`sort_segments`, :func:`enumerate_runs`, :func:`take_windows`,
:func:`range_max`: the ``Win_Seq`` archives, PR 28).

All functions are mask-aware: invalid lanes contribute the combine identity."""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .lookup import table_lookup


def _bmask(valid, v):
    """Broadcast a [C] mask against a [C, ...] value."""
    return valid.reshape(valid.shape + (1,) * (v.ndim - 1))


# ------------------------------------------------- combines over structs

#: the combines that act leaf by leaf: applied to a struct, each maps over
#: its leaves. Any other combine is called on whole structs (a lifted
#: pytree), so that a field may read the others and the operands' order is
#: kept: ``combine(a, b)`` with ``a`` the earlier.
ELEMENTWISE = (jnp.add, jnp.maximum, jnp.minimum)


def on_structs(combine: Callable) -> Callable:
    """``combine`` as a function of two whole structs: ``None`` (addition)
    and :data:`ELEMENTWISE` map over the leaves, any other is one already."""
    if combine is None or combine in ELEMENTWISE:
        op = combine or jnp.add
        return lambda a, b: jax.tree.map(op, a, b)
    return combine


def leafwise(combine: Callable) -> Callable:
    """A combine of single leaves as one of whole structs, mapped over the
    leaves; :data:`ELEMENTWISE` and ``None`` are returned as they are (so
    callers keep their fast paths)."""
    if combine is None or combine in ELEMENTWISE:
        return combine
    return lambda a, b: jax.tree.map(combine, a, b)


def identity_like(identity: Any, values: Any) -> Any:
    """``identity`` as a pytree with ``values``' structure. ``identity`` is
    a pytree prefix of ``values``: each of its leaves (a scalar, or one
    array) stands for every leaf of ``values`` below it, so a scalar serves
    every leaf and a struct like ``values`` gives one identity a leaf.
    Raises ``ValueError`` where it is no prefix of ``values``."""
    try:
        return jax.tree.map(
            lambda ident, sub: jax.tree.map(lambda _: ident, sub),
            identity, values)
    except ValueError as e:
        raise ValueError(
            f"identity {identity!r} is no pytree prefix of the lifted "
            f"structure {jax.tree.structure(values)}") from e


def ordered_reduce(combine: Callable, xs: Any, axis: int = 0) -> Any:
    """``combine`` folded along ``axis`` of every leaf of ``xs`` at once,
    operands in axis order: each round pairs neighbours, the lower index on
    the left, and carries an odd last element into the next round, so the
    association changes but no operand passes another (FlatFAT's guarantee
    for a non-commutative combine, ``wf/flatfat.hpp:80-133``). ``combine``
    sees whole structs, each leaf without ``axis`` (``jax.vmap`` over the
    pairs). Returns ``xs`` without that axis."""
    xs = jax.tree.map(lambda x: jnp.moveaxis(x, axis, 0), xs)
    pair = jax.vmap(on_structs(combine))
    n = jax.tree.leaves(xs)[0].shape[0]
    while n > 1:
        m = n // 2
        paired = pair(jax.tree.map(lambda x: x[0:2 * m:2], xs),
                      jax.tree.map(lambda x: x[1:2 * m:2], xs))
        if n > 2 * m:
            paired = jax.tree.map(
                lambda p, x: jnp.concatenate([p, x[2 * m:n]], axis=0),
                paired, xs)
        xs, n = paired, m + n - 2 * m
    return jax.tree.map(lambda x: x[0], xs)


# ------------------------------------------------------- fused window fold

#: lanes per grid step of the Pallas segment fold
FOLD_CHUNK = 1024
#: segment-axis tile inside the kernel (bounds the [chunk, S_TILE] one-hot)
FOLD_S_TILE = 512
#: largest segment space the fused fold accepts (beyond this the C*S one-hot
#: matmul work exceeds what the scatter path costs — and the per-chunk
#: accumulator stops paying for itself)
FOLD_MAX_SEGMENTS = 4096


def segment_fold(values: jax.Array, seg: jax.Array, valid: jax.Array,
                 num_segments: int, *, impl: str = None,
                 interpret: bool = False) -> jax.Array:
    """Masked 1-D segment sum ``out[s] = sum(values[i] : seg[i]==s, valid[i])``
    — the Win_SeqFFAT pane-fold primitive (``operators/win_seqffat.py``
    ``_insert``/``_g_insert`` reduce every batch into its ``[K*P]`` pane
    partials through this op via :func:`segment_reduce`).

    The ``"segment_fold"`` kernel of the per-backend registry:

    - ``xla`` (reference): ``jax.ops.segment_sum`` — XLA lowers the scatter
      to a serialized per-update loop (~18 ns/update measured on v5e, the
      same pathology ``ops/histogram.py`` documents for the count path).
    - ``pallas``: the fold as one-hot matmuls on the MXU — one kernel owns
      the whole ``[C] -> [S]`` accumulation, the per-chunk one-hot and the
      running ``[S]`` partial living in VMEM throughout (one grid step per
      :data:`FOLD_CHUNK` lanes; TPU grids run sequentially so read-modify-
      write accumulation across steps is sound).

    Exactness: the Pallas path takes INTEGER values (itemsize <= 4) and is
    byte-identical to ``segment_sum`` for the FULL int32 domain — each value
    is split into 11-bit limbs so every per-chunk one-hot matmul sums are
    f32-exact, and limbs recombine/accumulate with wrapping int32 adds (the
    same two's-complement semantics XLA's integer segment_sum has on
    overflow). Floats, more than :data:`FOLD_MAX_SEGMENTS` segments and
    batches that are not whole chunks route to the XLA reference inside the
    same call — selection is an optimization, never a semantics change —
    and the registry's trace record names the form that ran. Invalid lanes
    contribute 0; out-of-range segment ids are dropped (both impls)."""
    from .registry import REGISTRY, resolve_impl
    C, S = values.shape[0], int(num_segments)
    spec_key = f"C{C}xS{S}:{values.dtype}"
    taken = resolve_impl("segment_fold", impl=impl, spec_key=spec_key,
                         record=False)
    if not (jnp.issubdtype(values.dtype, jnp.integer)
            and jnp.dtype(values.dtype).itemsize <= 4
            and C % FOLD_CHUNK == 0 and C >= FOLD_CHUNK
            and S <= FOLD_MAX_SEGMENTS):
        taken = "xla"
    if impl is None:
        REGISTRY.record_impl("segment_fold", spec_key, taken)
    if taken == "pallas":
        return _pallas_segment_fold(values, seg, valid, S,
                                    interpret=interpret)
    return _xla_segment_fold(values, seg, valid, S)


def _xla_segment_fold(values, seg, valid, S):
    """Reference impl: masked ``segment_sum`` (the pre-registry formulation
    of ``segment_reduce``'s default path, verbatim)."""
    v = jnp.where(valid, values, 0)
    return jax.ops.segment_sum(v, seg, num_segments=S)


def _pallas_segment_fold(values, seg, valid, S, *, interpret: bool = False):
    """One kernel: per chunk, one-hot ``[chunk, S_tile]`` f32 tiles contract
    against the masked values on the MXU and accumulate into the resident
    ``[8, S_pad]`` i32 output block (8 sublanes — 7 dead rows, the Mosaic
    1-D-output workaround of ``ops/pallas_kernels.py``).

    Exact for the FULL int32 domain: each masked value splits into 11-bit
    limbs ``v = l2*2^22 + l1*2^11 + l0`` (``l0``/``l1`` unsigned low bits,
    ``l2`` the arithmetic-shift top — sign rides there), so every per-chunk
    limb matmul sums at most ``2^11 * FOLD_CHUNK = 2^21 < 2^24`` and stays
    f32-exact. Limbs recombine and accumulate across chunks with WRAPPING
    int32 adds — two's-complement mod-2^32 arithmetic is associative, so the
    result equals XLA's integer ``segment_sum`` bit-for-bit, including on
    overflow and after the final cast to a narrower input dtype."""
    import jax.experimental.pallas as pl
    from .registry import pallas_interpret

    C = values.shape[0]
    dtype = values.dtype
    S_pad = -(-S // FOLD_S_TILE) * FOLD_S_TILE
    R = C // FOLD_CHUNK
    interpret = interpret or pallas_interpret()

    def kern(v_ref, s_ref, ok_ref, out_ref):
        r = pl.program_id(0)

        @pl.when(r == 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        sg = s_ref[...]
        ok = ok_ref[...] != 0
        vi = jnp.where(ok, v_ref[...].astype(jnp.int32), 0)
        limbs = [(vi & 0x7FF).astype(jnp.float32),
                 ((vi >> 11) & 0x7FF).astype(jnp.float32),
                 (vi >> 22).astype(jnp.float32)]
        for s0 in range(0, S_pad, FOLD_S_TILE):
            # dead lanes need no mask here: their limbs are already 0
            oh = ((sg[:, None] - s0) == jax.lax.broadcasted_iota(
                sg.dtype, (FOLD_CHUNK, FOLD_S_TILE), 1)).astype(jnp.float32)
            # HIGHEST: an 11-bit limb does not survive the single bf16 pass
            # a TPU gives an f32 dot by default
            p0, p1, p2 = (jax.lax.dot_general(
                l[None, :], oh, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).astype(jnp.int32)
                for l in limbs)                            # [1, S_TILE] each
            part = p0 + (p1 << 11) + (p2 << 22)            # wrapping i32
            out_ref[:, s0:s0 + FOLD_S_TILE] += jnp.broadcast_to(
                part, (8, FOLD_S_TILE))

    out = pl.pallas_call(
        kern,
        grid=(R,),
        in_specs=[pl.BlockSpec((FOLD_CHUNK,), lambda r: (r,)),
                  pl.BlockSpec((FOLD_CHUNK,), lambda r: (r,)),
                  pl.BlockSpec((FOLD_CHUNK,), lambda r: (r,))],
        out_specs=pl.BlockSpec((8, S_pad), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, S_pad), jnp.int32),
        interpret=interpret,
    )(values, seg, valid.astype(jnp.int32))
    return out[0, :S].astype(dtype)


def _sort_by_key(keys, valid, arrays):
    """Stable multi-operand sort by (invalid, key): returns
    (sorted_key_or_max, original_index, sorted arrays...). One fused sort — the
    companion arrays ride along instead of being permutation-gathered afterwards."""
    big = jnp.iinfo(keys.dtype).max
    sort_key = jnp.where(valid, keys, big)
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    flat, treedef = jax.tree.flatten(arrays)
    rides = [l for l in flat if l.ndim == 1]       # lax.sort needs equal shapes
    out = jax.lax.sort((sort_key, iota, *rides), num_keys=1, is_stable=True)
    sorted_keys, orig_idx = out[0], out[1]
    it = iter(out[2:])
    sorted_flat = [next(it) if l.ndim == 1 else jnp.take(l, orig_idx, axis=0)
                   for l in flat]
    return sorted_keys, orig_idx, jax.tree.unflatten(treedef, sorted_flat)


def segment_rank(keys: jax.Array, valid: jax.Array) -> jax.Array:
    """Rank of each live lane among live lanes with the same key (0-based), in stream
    order. Sort-pairs + first-occurrence subtraction; one sort, one scatter-min, one
    small-table lookup, one scatter back to stream order."""
    c = keys.shape[0]
    # rank only needs segment grouping: sort (key, index) pairs, segment starts from
    # boundaries, propagate the start index with a cummax, subtract
    sorted_keys, orig_idx, _ = _sort_by_key(keys, valid, ())
    iota = jnp.arange(c, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                              sorted_keys[1:] != sorted_keys[:-1]])
    seg_start_idx = jax.lax.cummax(jnp.where(starts, iota, 0))
    rank_sorted = iota - seg_start_idx
    # back to stream order with one scatter
    return jnp.zeros((c,), jnp.int32).at[orig_idx].set(rank_sorted)


def segment_reduce(values: Any, keys: jax.Array, valid: jax.Array, num_keys: int,
                   combine: Callable = None, identity=0) -> Any:
    """Per-key reduction of a batch: returns a pytree of ``[num_keys, ...]`` arrays.

    Default combine is addition (lowered to ``segment_sum``); max/min use scatter
    fast paths; a custom associative ``combine`` uses sort + segmented scan
    (scopes ``sort``, ``scan``, ``place``), called on whole structs, the
    earlier lane on the left. ``identity`` may be a pytree of one identity a
    leaf (:func:`identity_like`)."""
    ids = identity_like(identity, values)
    if combine is None:
        def red(v):
            if v.ndim == 1:
                # the Win_SeqFFAT fold path: registry-selectable impl
                # (xla segment_sum / fused Pallas one-hot matmul)
                return segment_fold(v, keys, valid, num_keys)
            v = jnp.where(_bmask(valid, v), v, 0)
            return jax.ops.segment_sum(v, keys, num_segments=num_keys)
        return jax.tree.map(red, values)
    if combine in (jnp.maximum, jnp.minimum):
        seg = jax.ops.segment_max if combine is jnp.maximum else jax.ops.segment_min
        def red(v, ident):
            v = jnp.where(_bmask(valid, v), v, jnp.asarray(ident, v.dtype))
            out = seg(v, keys, num_segments=num_keys)
            touched = jax.ops.segment_sum(valid.astype(jnp.int32), keys,
                                          num_segments=num_keys) > 0
            return jnp.where(_bmask(touched, out), out,
                             jnp.asarray(ident, v.dtype))
        return jax.tree.map(red, values, ids)
    # general associative combine: sorted segmented scan, then scatter each segment's
    # last element into its key row
    scanned, seg_keys, seg_valid, _ = _sorted_segment_scan(
        values, keys, valid, combine, identity)
    with jax.named_scope("place"):
        nxt = jnp.concatenate([seg_keys[1:],
                               jnp.full((1,), -1, seg_keys.dtype)])
        is_last = (seg_keys != nxt) & seg_valid
        out_idx = jnp.where(is_last, jnp.minimum(seg_keys, num_keys), num_keys)

        def scatter(v, ident):
            shape = (num_keys + 1,) + v.shape[1:]
            init = jnp.broadcast_to(jnp.asarray(ident, v.dtype), shape)
            return init.at[out_idx].set(v, mode="drop")[:num_keys]
        return jax.tree.map(scatter, scanned, ids)


def _sorted_segment_scan(values, keys, valid, combine, identity):
    """Multi-operand sort by key (stable: a key's lanes stay in stream
    order), then segmented inclusive associative scan, ``combine`` called on
    whole structs with the earlier lanes on the left (scopes ``sort`` and
    ``scan``).

    Returns (scanned values in sorted order, sorted keys, sorted valid,
    original indices)."""
    comb = on_structs(combine)
    with jax.named_scope("sort"):
        seg_keys, orig_idx, sv = _sort_by_key(keys, valid, values)
    with jax.named_scope("scan"):
        big = jnp.iinfo(keys.dtype).max
        seg_valid = seg_keys != big
        sv = jax.tree.map(lambda v, ident: jnp.where(
            _bmask(seg_valid, v), v, jnp.asarray(ident, v.dtype)),
            sv, identity_like(identity, sv))
        starts = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                  seg_keys[1:] != seg_keys[:-1]])

        def seg_combine(a, b):
            a_f, a_v = a
            b_f, b_v = b
            # a segment's first lane starts afresh: the flag selects per struct
            v = jax.tree.map(lambda y, c: jnp.where(_bmask(b_f, y), y, c),
                             b_v, comb(a_v, b_v))
            return (a_f | b_f, v)

        _, scanned = jax.lax.associative_scan(seg_combine, (starts, sv),
                                              axis=0)
    return scanned, seg_keys, seg_valid, orig_idx


# ------------------------------------------------- folds in sorted order

def run_budget(capacity: int, num_keys: int, run_len: int) -> int:
    """Most (key, chunk) runs a batch of ``capacity`` lanes can hold when a
    key's lanes are numbered onward from any offset and cut every ``run_len``
    positions: a key with n lanes touches at most ``n // run_len + 2`` chunks
    (it may start and end mid-chunk), and at most ``min(num_keys, capacity)``
    keys are present. Never more than one run a lane."""
    return min(capacity, capacity // run_len + 2 * min(num_keys, capacity))


class RunFold(NamedTuple):
    """What :func:`segment_run_fold` returns: ``R = run_budget(...)`` rows,
    the live ones first, in (key, chunk) order; and one row per key."""
    key: jax.Array        # i32[R] key of the run (K - 1 on dead rows)
    chunk: jax.Array      # i32[R] position // run_len of the run's lanes
    length: jax.Array     # i32[R] live lanes in the run (0 on dead rows)
    live: jax.Array       # bool[R]
    key_count: jax.Array  # i32[K] live lanes per key
    folded: tuple         # per fold: pytree of [R, ...] (identity on dead rows)


def segment_run_fold(folds, keys: jax.Array, valid: jax.Array, num_keys: int,
                     offset: jax.Array, run_len: int) -> RunFold:
    """Fold a batch per (key, chunk) run without leaving sorted order.

    A key's live lanes, in stream order, hold positions ``offset[key]``,
    ``offset[key] + 1``, ...; chunk ``c`` is positions ``[c * run_len,
    (c + 1) * run_len)`` (a count-based window's pane). ``folds`` is a
    sequence of ``(values, combine, identity)``: ``values`` a pytree of
    ``[C, ...]`` leaves, ``combine`` associative (``None`` or ``jnp.add`` for
    addition), the earlier lanes on the left: :data:`ELEMENTWISE` leaf by
    leaf, any other on whole structs; ``identity`` a pytree like
    ``values`` or one for every leaf (:func:`identity_like`).

    One stable multi-operand sort by (dead, key) puts every run's lanes side
    by side in stream order; nothing returns to stream order. The runs are
    never searched for among the lanes: the K + 1 key boundaries of the sorted
    keys and ``offset`` give every run's first and last lane in closed form,
    K- and R-sized work. An integer sum is then a wrapping ``cumsum``
    differenced at the run ends (exact: two's complement); every other fold —
    float sums too, where a prefix difference would cancel — is a segmented
    scan that combines lane ``i - d`` into lane ``i`` for ``d = 1, 2, 4, ...
    < run_len`` while both lie in one run, read at the run's last lane.

    Live lanes are ``valid`` with a key in ``[0, num_keys)``; the rest
    contribute nothing, as in :func:`segment_reduce`."""
    c, K, L = keys.shape[0], int(num_keys), int(run_len)
    R = run_budget(c, K, L)
    ok = valid & (keys >= 0) & (keys < K)
    with jax.named_scope("sort"):
        sorted_keys, _, sorted_vals = _sort_by_key(
            keys, ok, [v for v, _, _ in folds])
    with jax.named_scope("runs"):
        # live keys are < K and dead lanes sort last (key = max): key k's
        # lanes are [edges[k], edges[k + 1])
        edges = jnp.searchsorted(
            sorted_keys, jnp.arange(K + 1, dtype=keys.dtype),
            side="left").astype(jnp.int32)
        lo, n = edges[:-1], edges[1:] - edges[:-1]
        first = offset // L
        n_runs = jnp.where(n > 0, (offset + n - 1) // L - first + 1, 0)
        k, i, live = enumerate_runs(n_runs, R)
        # (a run's reads of its key's tables: 0.025 ms a gather at 3,072 runs)
        first_k, lo_k, n_k, off_k = (
            table_lookup(t, k) for t in (first, lo, n, offset))
        chunk = first_k + i
        start = jnp.where(live, lo_k + jnp.maximum(chunk * L - off_k, 0), 0)
        end = jnp.where(live, lo_k + jnp.minimum((chunk + 1) * L - off_k, n_k),
                        0)
    with jax.named_scope("scan"):
        # lane -> its distance from the first lane of its run: the run starts
        # are increasing, so a cummax spreads each over its run (R-sized
        # write, one pass; dead code where every fold is an integer sum)
        run_start = jax.lax.cummax(jnp.zeros((c,), jnp.int32).at[
            jnp.where(live, start, c)].set(start, mode="drop"))
        rank = jnp.arange(c, dtype=jnp.int32) - run_start
        folded = tuple(
            _fold_runs(sv, combine, identity, start, end, live, rank, L)
            for sv, (_, combine, identity) in zip(sorted_vals, folds))
    return RunFold(key=k, chunk=chunk, length=end - start, live=live,
                   key_count=n, folded=folded)


def _fold_runs(values, combine, identity, start, end, live, rank, run_len):
    """Per-run fold of ``values`` (sorted order) over the lane ranges
    ``[start, end)``, none longer than ``run_len``; ``rank`` is each lane's
    distance from its run's first lane. ``[R, ...]`` leaves. A combine
    other than :data:`ELEMENTWISE` is called on whole structs."""
    add = combine is None or combine is jnp.add
    last = jnp.maximum(end - 1, 0)
    ids = identity_like(identity, values)

    def wrapping_sum(v):
        cs = jnp.cumsum(v, axis=0, dtype=v.dtype)
        before = jnp.take(cs, jnp.maximum(start - 1, 0), axis=0)
        return (jnp.take(cs, last, axis=0)
                - jnp.where(_bmask(start > 0, before), before, 0))

    def scan(v, identity):
        op = jnp.add if add else combine
        d = 1
        while d < min(run_len, v.shape[0]):
            pad = jnp.broadcast_to(jnp.asarray(identity, v.dtype),
                                   (d,) + v.shape[1:])
            prev = jnp.concatenate([pad, v[:-d]], axis=0)
            v = jnp.where(_bmask(rank >= d, v), op(prev, v), v)
            d *= 2
        return jnp.take(v, last, axis=0)

    def fold(v, identity):
        exact = add and jnp.issubdtype(v.dtype, jnp.integer)
        u = wrapping_sum(v) if exact else scan(v, identity)
        return jnp.where(_bmask(live, u), u, jnp.asarray(identity, u.dtype))
    if add or combine in ELEMENTWISE:
        return jax.tree.map(fold, values, ids)
    # the same scan over whole structs: lane i - d (earlier) on the left
    v, d = values, 1
    n = jax.tree.leaves(values)[0].shape[0]
    while d < min(run_len, n):
        prev = jax.tree.map(lambda x, ident: jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(ident, x.dtype), (d,) + x.shape[1:]),
             x[:-d]], axis=0), v, ids)
        v = jax.tree.map(lambda x, c: jnp.where(_bmask(rank >= d, x), c, x),
                         v, combine(prev, v))
        d *= 2

    def at_last(x, ident):
        u = jnp.take(x, last, axis=0)
        return jnp.where(_bmask(live, u), u, jnp.asarray(ident, u.dtype))
    return jax.tree.map(at_last, v, ids)


def segment_prefix_scan(values: Any, keys: jax.Array, valid: jax.Array,
                        combine: Callable, identity=0, *, carry_in: Any = None) -> Any:
    """Per-key *inclusive* prefix scan in stream order: lane i receives the combine of
    all earlier live same-key lanes (plus an optional per-key ``carry_in`` table
    ``[num_keys, ...]``), returned in original batch positions. ``combine``
    is called on whole structs (:func:`on_structs`).

    Batched counterpart of the reference Accumulator's per-key rolling reduce
    (``wf/accumulator.hpp:61``, keyMap ``:103-104``) for associative user combines.
    Addition gets a cumsum fast path (segment prefix = cumsum - segment-start base);
    general combines use the segmented ``associative_scan``."""
    c = keys.shape[0]
    if combine in (jnp.add,):
        seg_keys, orig_idx, sv = _sort_by_key(keys, valid, values)
        big = jnp.iinfo(keys.dtype).max
        seg_valid = seg_keys != big
        iota = jnp.arange(c, dtype=jnp.int32)
        starts = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                  seg_keys[1:] != seg_keys[:-1]])
        seg_start_idx = jax.lax.cummax(jnp.where(starts, iota, 0))

        def one(v):
            v = jnp.where(_bmask(seg_valid, v), v, jnp.asarray(identity, v.dtype))
            cs = jnp.cumsum(v, axis=0)
            base = jnp.take(cs, jnp.maximum(seg_start_idx - 1, 0), axis=0)
            base = jnp.where(_bmask(seg_start_idx > 0, base), base,
                             jnp.zeros_like(base))
            # subtract the running total up to the lane before the segment start
            pref = cs - base
            return jnp.zeros_like(pref).at[orig_idx].set(pref)
        out = jax.tree.map(one, sv)
    else:
        scanned, _, _, orig_idx = _sorted_segment_scan(
            values, keys, valid, combine, identity)
        out = jax.tree.map(
            lambda v: jnp.zeros_like(v).at[orig_idx].set(v), scanned)
    if carry_in is not None:
        # associativity: fold(carry, v1..vr) == combine(carry, fold(v1..vr))
        out = on_structs(combine)(
            jax.tree.map(lambda t: table_lookup(t, keys), carry_in), out)
    return out


# ------------------------------------------------ rows in sorted order

def sort_segments(arrays: Any, keys: jax.Array, valid: jax.Array,
                  num_keys: int):
    """``arrays`` (a pytree of ``[C, ...]`` leaves) in the order of one stable
    sort by (dead, key), with every key's lane range in it: returns
    ``(sorted arrays, first[K], n[K])``; key k's live lanes are
    ``[first[k], first[k] + n[k])``, in stream order. Live lanes are ``valid``
    with a key in ``[0, num_keys)``, as in :func:`segment_run_fold`."""
    K = int(num_keys)
    ok = valid & (keys >= 0) & (keys < K)
    sorted_keys, _, sorted_arrays = _sort_by_key(keys, ok, arrays)
    edges = jnp.searchsorted(sorted_keys, jnp.arange(K + 1, dtype=keys.dtype),
                             side="left").astype(jnp.int32)
    return sorted_arrays, edges[:-1], edges[1:] - edges[:-1]


#: what finding the owners of R rows among K keys costs on one v5e (PERF.md
#: section 6, PR 37). ``jnp.searchsorted``'s default is a binary search, a
#: ``while`` of ``K.bit_length()`` rounds, each a gather of one element a row
#: from the K-sized table, and XLA:TPU serializes such a gather:
#: ``SEARCH_ROUND_NS`` a row a round (6.65-6.8 from 4,096 keys up, 7.7-8.2 at
#: 512), the ``jnp.take`` of the key's first row after it one round more. The
#: count of ``csum <= r`` over the K axis is the same integer from R x K cells
#: of VPU work with nothing serialized: ``COMPARE_CELL_NS`` a cell, the masked
#: max for the key's first row included (0.0014-0.0015 from 4,096 keys up,
#: 0.0017-0.0020 at 512-1,024: 6.6-7.7 us for (8,704, 512) where the search
#: takes 741).
SEARCH_ROUND_NS = 6.7
COMPARE_CELL_NS = 0.0015


def owner_compare_cells(rows: int, num_keys: int) -> int:
    """The ``rows x num_keys`` cells :func:`enumerate_runs` compares at these
    shapes to find every row's key, 0 where it keeps the binary search (a key
    space so large that a row's K cells cost more than its ``K.bit_length() +
    1`` serialized rounds)."""
    rows, K = int(rows), int(num_keys)
    by_compare = K * COMPARE_CELL_NS <= (K.bit_length() + 1) * SEARCH_ROUND_NS
    return rows * K if by_compare else 0


def enumerate_runs(n_runs: jax.Array, budget: int):
    """List ``n_runs[k]`` (none negative) runs for every key k, key by key, in
    ``budget`` rows: returns ``(key[budget], index[budget], live[budget])``
    with ``index`` counting a key's runs from 0. K- and budget-sized results;
    rows past the total are dead (``key`` clipped to K - 1, ``index`` counted
    on from that key's first row).

    Row r belongs to key ``#{j : csum[j] <= r}`` and its key's first row is
    ``max{off[j] : off[j] <= r}`` (``off = csum - n_runs`` never decreases):
    two reductions over one ``[budget, K]`` comparison. That is
    ``jnp.searchsorted(csum, r, side="right")`` and a ``jnp.take`` of ``off``,
    which stay where :func:`owner_compare_cells` prices them lower: the
    default binary search is a loop of per-row gathers, which cost this chip
    more than all K comparisons do up to 65,536 keys (801 us against 1,041
    at 8,704 rows) and less from 131,072 on (1,101 against 1,601)."""
    K = n_runs.shape[0]
    csum = jnp.cumsum(n_runs)
    off = csum - n_runs
    r = jnp.arange(budget, dtype=jnp.int32)
    if owner_compare_cells(budget, K):
        k = jnp.sum(csum[None, :] <= r[:, None], axis=1, dtype=jnp.int32)
        first = jnp.max(jnp.where(off[None, :] <= r[:, None], off[None, :], 0),
                        axis=1)
        k = jnp.minimum(k, K - 1)
    else:
        k = jnp.minimum(jnp.searchsorted(csum, r, side="right"),
                        K - 1).astype(jnp.int32)
        first = jnp.take(off, k)
    return k, r - first, r < csum[-1]


#: lanes of one aligned block of :func:`range_max`
RANGE_BLOCK = 1024


def range_max(values: jax.Array, first: jax.Array, n: jax.Array,
              identity) -> jax.Array:
    """``max(values[first[k] : first[k] + n[k]])`` for every k (``identity``
    where ``n[k]`` is 0), without a scan over the lanes: of each range, the
    two aligned blocks of :data:`RANGE_BLOCK` lanes it starts and ends in are
    read as rows (K-row gathers of the column viewed ``[C / B, B]``) and
    masked; the whole blocks between them are a range of the block maxima,
    which are a column ``B`` times shorter, taken the same way."""
    B = RANGE_BLOCK
    fill = jnp.asarray(identity, values.dtype)
    lo, hi = first, first + n
    out = jnp.full(first.shape, fill)
    while True:
        size = values.shape[0]
        blocks = jnp.pad(values, (0, -size % B),
                         constant_values=fill).reshape(-1, B)
        for block in (lo // B, (hi - 1) // B):
            lane = block[:, None] * B + jnp.arange(B, dtype=lo.dtype)[None, :]
            inside = (lane >= lo[:, None]) & (lane < hi[:, None])
            rows = jnp.take(blocks, block, axis=0, mode="clip")
            out = jnp.maximum(out, jnp.max(jnp.where(inside, rows, fill), axis=1))
        if size <= B:
            return out
        values = jnp.max(blocks, axis=1)
        lo, hi = lo // B + 1, (hi - 1) // B


#: what :func:`take_windows` pays on one v5e (PERF.md section 6, PR 33): XLA:TPU
#: runs the gather as a loop of one ``dynamic-slice`` a window, and a row of an
#: insert pass, that step and the row's trip through the ring tables, costs
#: ``SLICE_US`` microseconds whatever it holds (0.97 by ``Win_Seq._insert`` at
#: 5,120, 3,072 and 2,048 rows a batch; 0.80-0.95 for the slice alone at 8 to
#: 512 lanes of three columns). ``SLICE_GBPS``: the rate, in 16-byte lanes
#: (payload word, id, ts, position), at which ``_insert`` moves the lanes it
#: adds between rows of 1,024 and of 4,096 slots (15.3 at 512 keys, 13.7 at
#: 100): under 0.4 ns a lane up to 2,048 slots and 1.4-1.7 beyond, where a
#: slice of three stacked columns goes from 1.18 us to 2.89 (5.74 at 8,192)
SLICE_US = 0.97
SLICE_GBPS = 15.0


def window_groups(leaves) -> dict:
    """Which ``[C, ...]`` leaves share one :func:`take_windows` gather:
    ``{(carrier dtype, trailing shape): [their indices]}``. 32-bit numbers
    ride as int32 (bit patterns, never converted), anything else as itself."""
    groups = {}
    for i, leaf in enumerate(leaves):
        dtype = jnp.dtype(leaf.dtype)
        if dtype.itemsize == 4 and dtype.kind in "iuf":
            dtype = jnp.dtype(jnp.int32)
        groups.setdefault((dtype, tuple(leaf.shape[1:])), []).append(i)
    return groups


def take_windows(columns: Any, start: jax.Array, length: int) -> Any:
    """``column[start[r] : start[r] + length]`` for every r and every ``[C,
    ...]`` leaf of ``columns``, as the same pytree of ``[R, length, ...]``:
    gathers of R contiguous slices (R indices, not one a lane). XLA:TPU runs
    such a gather as a loop of R ``dynamic-slice``s that cost the same
    whatever they hold (``SLICE_US``), so the leaves of one
    :func:`window_groups` group are stacked ``[n, C, ...]`` and every window
    is taken once for all of them; floats ride as their bit patterns, so the
    result is exact. The caller keeps every window inside the columns
    (``dynamic_slice`` would shift one that is not)."""
    def bits_as(x, dtype):
        return x if x.dtype == dtype else jax.lax.bitcast_convert_type(x, dtype)

    leaves, treedef = jax.tree.flatten(columns)
    out = [None] * len(leaves)
    for (carrier, _), members in window_groups(leaves).items():
        stacked = jnp.stack([bits_as(leaves[i], carrier) for i in members])
        windows = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
            stacked, s, length, axis=1))(start)          # [R, n, length, ...]
        for j, i in enumerate(members):
            out[i] = bits_as(windows[:, j], leaves[i].dtype)
    return treedef.unflatten(out)


# ------------------------------------------------------------- registration

from .registry import register_kernel  # noqa: E402  (registration footer)

register_kernel("segment_fold", "xla", _xla_segment_fold, reference=True,
                backends=("xla",), default=True)
register_kernel("segment_fold", "pallas", _pallas_segment_fold,
                backends=("pallas-tpu", "pallas-interpret"))
