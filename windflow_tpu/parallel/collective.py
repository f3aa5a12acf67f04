"""Explicit-collective multi-chip patterns over ICI: shard_map formulations of the
reference's cross-replica exchanges.

The GSPMD path (``parallel/sharding.py``) lets XLA infer collectives from sharding
annotations; this module is the hand-written counterpart for the three exchanges whose
communication pattern IS the algorithm — the cases where the reference dedicates a
custom emitter/topology:

- :func:`wmr_map_reduce` — Win_MapReduce with the MAP partition axis sharded over
  devices and the REDUCE combine as an ICI all-reduce (``psum``-style tree combine).
  Reference: WinMap_Emitter round-robin partitioning + REDUCE stage
  (``wf/win_mapreduce.hpp:180-230``, ``wf/wm_nodes.hpp:45-181``). Use when one
  window's content is too large for one chip.
- :func:`ring_pane_windows` — sliding windows over a pane-partial axis sharded in
  contiguous blocks, with boundary panes rotated from ring neighbours via
  ``ppermute`` (the ring-attention communication shape applied to Pane_Farm: each
  device combines local pane partials, pulls the (win_panes-1) successor panes it is
  missing from the next device(s) around the ring, never materializing the full pane
  axis anywhere). Reference: PLQ/WLQ pane sharing (``wf/pane_farm.hpp:175-213``) —
  single-process there, cross-chip here.
- :func:`keyed_all_to_all` — redistribute a batch so every tuple lands on the device
  that owns its key: per-destination compaction + ``lax.all_to_all``. This is the
  KF_Emitter / Standard_EmitterGPU ``create_sub_batch`` exchange
  (``wf/kf_nodes.hpp:74-90``, ``wf/standard_nodes_gpu.hpp:52-238``) carried over
  chip boundaries instead of thread queues.

All functions take an explicit mesh-axis name and run inside
``jax.shard_map``; static shapes throughout (fixed per-destination capacity +
validity masks — the batch discipline of the whole framework).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.segment import ordered_reduce, segment_rank

def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


# -- Win_MapReduce over ICI ------------------------------------------------------------

def wmr_map_reduce(map_fn: Callable, combine: Callable, mesh: Mesh, *,
                   axis: str = "part"):
    """Build ``f(data, valid) -> result`` where ``data`` is one window's content
    [L, ...] sharded over ``axis`` in ``map_parallelism = mesh.shape[axis]``
    partitions. Each device runs ``map_fn(partition, valid)`` on its local slice
    (the reference MAP stage, role MAP), then the partials are tree-combined across
    the axis with an all-reduce built from ``combine`` (the REDUCE stage; for
    ``combine=jnp.add`` this is exactly ``lax.psum`` over ICI).

    ``map_fn``: (local_data [L/p, ...], local_valid [L/p]) -> partial (any pytree of
    arrays with matching shapes across devices). ``combine``: (partial, partial) ->
    partial, associative."""
    p = _axis_size(mesh, axis)
    known = combine in (jnp.add, jnp.maximum, jnp.minimum)
    reducer = {jnp.add: jax.lax.psum, jnp.maximum: jax.lax.pmax,
               jnp.minimum: jax.lax.pmin}.get(combine)

    def local(data, valid):
        partial = map_fn(data, valid)
        if known:
            return jax.tree.map(lambda x: reducer(x, axis), partial)
        # generic associative combine: all_gather + order-preserving tree fold
        # (combine sees whole partials, neighbours paired in axis order)
        g = jax.tree.map(lambda x: jax.lax.all_gather(x, axis), partial)
        return ordered_reduce(combine, g)

    # the folded all_gather of the generic path is replicated by construction, but
    # the static varying-axes checker can't prove it — disable the check there
    return _shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=P(), check_vma=known)


# -- ring pane exchange ----------------------------------------------------------------

def ring_pane_windows(combine: Callable, identity, mesh: Mesh, *,
                      win_panes: int, slide_panes: int, axis: str = "win"):
    """Build ``f(panes [Ptot], pane_valid [Ptot]) -> (win_results, win_valid)`` for
    sliding windows of ``win_panes`` pane partials sliding by ``slide_panes``, with
    the pane axis sharded in contiguous blocks over ``axis``.

    Each device owns panes [d*B, (d+1)*B). A window starting in block d can extend
    ``win_panes - 1`` panes into successor blocks, so the ring rotates each block to
    its left neighbour ``ceil((win_panes-1)/B)`` times via ``ppermute``; the device
    appends the halo and computes its windows locally — O(halo) bytes over ICI per
    step, full pane axis never gathered. Window starts are global multiples of
    ``slide_panes``; each window is emitted by the device whose block contains its
    start pane — the WF_Emitter ownership rule applied to a sharded pane axis, and
    the emitted window set is identical to the single-device computation regardless
    of the device count.

    Only windows fully covered by panes present on the ring are valid (trailing
    windows whose halo would wrap past the end of the pane axis are masked, and the
    wrap-around halo from device 0 is marked invalid)."""
    p = _axis_size(mesh, axis)

    def local(panes, pane_valid):
        B = panes.shape[0]
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i - 1) % p) for i in range(p)]     # send left = pull from right
        # per-step halo widths: step s ships the first min(B, remaining) panes of
        # block idx+s+1 — only the panes windows can actually read, so ICI traffic
        # is O(win_panes) total, not O(B) per step
        widths, rem = [], max(win_panes - 1, 0)
        while rem > 0:
            widths.append(min(B, rem))
            rem -= widths[-1]
        ext, ext_valid = panes, pane_valid
        buf, buf_valid = panes, pane_valid
        for s, w in enumerate(widths):                  # widths are non-increasing
            buf = jax.lax.ppermute(buf[:w], axis, perm)
            buf_valid = jax.lax.ppermute(buf_valid[:w], axis, perm)
            # buffer received on step s holds the leading panes of block idx+s+1:
            # wrapped past the end of the pane axis if idx+s+1 >= p — mask off
            wrapped = idx + s + 1 >= p
            ext = jnp.concatenate([ext, buf], axis=0)
            ext_valid = jnp.concatenate(
                [ext_valid, jnp.where(wrapped, False, buf_valid)], axis=0)
        # windows start at GLOBAL pane indices that are multiples of slide_panes;
        # this device owns the ones falling inside its block [idx*B, (idx+1)*B).
        # First owned start as a local offset (0..slide-1), then every slide after
        # it; nwin is the worst-case count, extras masked by (start < B).
        base = idx.astype(jnp.int32) * B
        off = (-base) % slide_panes
        nwin = (B + slide_panes - 1) // slide_panes
        starts = off + jnp.arange(nwin, dtype=jnp.int32) * slide_panes

        def one(start):
            sl = jax.lax.dynamic_slice_in_dim(ext, start, win_panes, axis=0)
            vl = jax.lax.dynamic_slice_in_dim(ext_valid, start, win_panes, axis=0)
            masked = jnp.where(vl.reshape(vl.shape + (1,) * (sl.ndim - 1)),
                               sl, identity)
            res = masked[0]
            for i in range(1, win_panes):
                res = combine(res, masked[i])
            return res, jnp.all(vl) & (start < B)
        res, valid = jax.vmap(one)(starts)
        return res, valid

    return _shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis), P(axis)))


# -- keyed all-to-all ------------------------------------------------------------------

def keyed_all_to_all(mesh: Mesh, *, axis: str = "key", capacity: int | None = None,
                     return_residue: bool = False):
    """Build ``f(keys [C], valid [C], payload pytree of [C, ...]) ->
    (keys, valid, payload, n_left_behind)`` redistributing every live row to the
    device that owns its key (owner = key % n_devices), over one ``lax.all_to_all``.

    Per (source, destination) lane budget is ``capacity`` rows (default C // p);
    each source compacts its rows per destination into [p, capacity] sub-batches
    (the ``create_sub_batch`` compaction of ``wf/standard_nodes_gpu.hpp``, done with
    a rank-within-destination scatter), exchanges, and flattens back to a [p*cap]
    local batch with a validity mask.

    **Nothing is silently lost.** Rows beyond a lane budget stay on their source and
    are reported in ``n_left_behind`` — a per-source [p] i32 count (all zeros ⇒ the
    exchange was complete; with ``capacity = C`` overflow is impossible). With
    ``return_residue=True`` the per-row residue mask [global C] is also returned so
    the caller can re-run the exchange on exactly the rows left behind —
    :func:`keyed_all_to_all_lossless` wraps that into the multi-round blocking
    discipline of the reference's bounded queues (``FF_BOUNDED_BUFFER`` blocks; it
    never drops)."""
    p = _axis_size(mesh, axis)

    def local(keys, valid, payload):
        C = keys.shape[0]
        cap = capacity if capacity is not None else C // p
        if cap < 1:
            raise ValueError(
                f"keyed_all_to_all: per-(src,dst) lane capacity resolved to "
                f"{cap} (local rows {C}, devices {p}) — no row could ever be "
                f"delivered and the lossless wrapper would loop forever; pass "
                f"an explicit capacity >= 1")
        dest = jnp.where(valid, keys % p, p)            # p = parked lane
        # rank of each row among live rows with the same destination (stream order)
        rank = segment_rank(dest, valid)
        # scatter rows into [p, cap] slots per destination
        slot_ok = valid & (rank < cap)
        flat_slot = jnp.where(slot_ok, dest * cap + rank, p * cap)

        def place(arr, fill=0):
            out = jnp.full((p * cap + 1,) + arr.shape[1:], fill, arr.dtype)
            out = out.at[flat_slot].set(arr)
            return out[:p * cap].reshape((p, cap) + arr.shape[1:])

        sub_keys = place(keys)
        sub_valid = place(slot_ok.astype(jnp.int32)).astype(jnp.bool_)
        sub_pay = jax.tree.map(place, payload)
        # exchange: axis 0 is the destination axis
        ex = lambda a: jax.lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                                          tiled=False)
        rk, rv = ex(sub_keys), ex(sub_valid)
        rp = jax.tree.map(ex, sub_pay)
        flat = lambda a: a.reshape((p * cap,) + a.shape[2:])
        residue = valid & ~slot_ok                       # live rows left behind
        n_left = jnp.sum(residue.astype(jnp.int32)).reshape(1)
        out = (flat(rk), flat(rv), jax.tree.map(flat, rp), n_left)
        return out + (residue,) if return_residue else out

    specs = (P(axis), P(axis), P(axis), P(axis))
    if return_residue:
        specs = specs + (P(axis),)
    return _shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
                      out_specs=specs)


def keyed_all_to_all_lossless(mesh: Mesh, *, axis: str = "key",
                              capacity: int | None = None):
    """Multi-round :func:`keyed_all_to_all` that delivers EVERY live row: rounds of
    exchange run until no source reports rows left behind, and each receiver's
    rounds are concatenated along the batch axis. The host loop is the blocking
    backpressure of the reference's bounded queues — later rounds are the emitter
    thread blocking on a full ``FF_BOUNDED_BUFFER`` until the consumer drains it.
    The round count is identical on every process (it is driven by the summed
    left-behind counts, which all processes compute), so the loop is safe under
    multi-controller execution. Returns ``(keys, valid, payload, n_rounds)``.

    Memory note: receiver rounds are concatenated along the batch axis, so the
    output capacity is ``n_rounds * p * cap`` and the concatenate may leave the
    result partially replicated depending on XLA's layout choice — size
    ``capacity`` so the common case is one round, and treat multi-round as the
    backpressure slow path (exactly like a blocking queue under overload)."""
    ex = jax.jit(keyed_all_to_all(mesh, axis=axis, capacity=capacity,
                                  return_residue=True))

    def run(keys, valid, payload):
        outs = []
        v = valid
        while True:
            rk, rv, rp, n_left, resid = ex(keys, v, payload)
            outs.append((rk, rv, rp))
            if int(jnp.sum(n_left)) == 0:
                break
            v = resid
        cat = lambda parts: jnp.concatenate(parts, axis=0)
        ks = cat([o[0] for o in outs])
        vs = cat([o[1] for o in outs])
        ps = jax.tree.map(lambda *ls: cat(list(ls)), *[o[2] for o in outs])
        return ks, vs, ps, len(outs)

    return run
