"""Bitonic compare-exchange networks — the Ordering_Node merge/sort kernel.

``parallel/ordering.py`` merges each incoming batch into its sorted backlog
with a bitonic merge network: ``log2(n)`` vectorized compare-exchange stages
over a 4-tuple composite key (primary, secondary, channel, unique index).
The XLA formulation (:func:`merge_network`) emits one reshape + lex-compare +
two selects per stride — ``log2(n)`` separate fusions whose intermediates
round-trip HBM between stages in a large program. The critical-path reports
of ``scripts/wf_trace.py`` name the ordering stage's service time as
merge-dominated under DETERMINISTIC modes, so this module adds the fused
restatement (:func:`merge_network_pallas`): ONE Pallas kernel owns all
stages, the four key arrays living in VMEM for the network's entire life
(n=8192: 4 arrays x 32 KB — far under the ~16 MB VMEM budget).

Also here: the full bitonic SORT network (:func:`sort_network` /
:func:`sort_network_pallas`) for ``_sort_batch``'s unsorted-batch branch —
stages ``k = 2, 4, .., n`` of the same compare-exchange butterfly. Because
the composite key always ends in a UNIQUE index lane (``idx``), the order is
total: the network's output is exactly the stable ``jnp.lexsort``
permutation, so the impls are interchangeable byte-for-byte (the parity
property tier-1 asserts in interpret mode).

Registered with the kernel registry as ``"ordering_merge"`` (impls ``xla`` /
``pallas``); ``Ordering_Node`` resolves the impl once at construction — the
jitted cores are cached per (mode, impl), so selection is trace-time like
every other kernel toggle (WF109 catches stale executables).

Exactness: all four lanes are i32 and every op is a compare/select —
bit-exact in any mode, no accumulation-order concerns.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

#: largest network the fused kernel accepts (4 i32 arrays + pair views must
#: fit VMEM with headroom; 1<<15 lanes = 512 KB of key state)
PALLAS_MAX_LANES = 1 << 15


def _lex_lt(a: Tuple, b: Tuple):
    """Strict lexicographic < over equal-length tuples of i32 arrays (the
    ordering composite-key compare, shared by both impls)."""
    out = None
    eq = None
    for x, y in zip(a, b):
        term = (x < y) if eq is None else (eq & (x < y))
        out = term if out is None else (out | term)
        eq = (x == y) if eq is None else (eq & (x == y))
    return out


def _butterfly(arrs, d: int, ascending=None):
    """One stride-``d`` butterfly: pair i with i^d via the [n/(2d), 2, d]
    reshape (positions differing exactly in bit d are CONTIGUOUS under it —
    element [b, s, m] is lane b*2d + s*d + m — so the exchange is slicing +
    elementwise selects, no gather). ``ascending``: None = every pair sorts
    ascending (merge), else a [n/(2d), d] bool direction mask."""
    n = arrs[0].shape[0]
    rs = [a.reshape(n // (2 * d), 2, d) for a in arrs]
    lt = _lex_lt(tuple(r[:, 0] for r in rs), tuple(r[:, 1] for r in rs))
    lo_takes_0 = lt if ascending is None else jnp.where(ascending, lt, ~lt)

    def sel(r):
        lo = jnp.where(lo_takes_0, r[:, 0], r[:, 1])
        hi = jnp.where(lo_takes_0, r[:, 1], r[:, 0])
        return jnp.stack([lo, hi], axis=1).reshape(n)
    return [sel(r) for r in rs]


def _merge_stages(prim, sec, chan, idx):
    """The merge network body (bitonic input -> ascending): shared verbatim
    by the XLA form and the Pallas kernel so the two cannot drift."""
    arrs = [prim, sec, chan, idx]
    n = prim.shape[0]
    d = n // 2
    while d >= 1:
        arrs = _butterfly(arrs, d)
        d //= 2
    return tuple(arrs)


def _sort_stages(prim, sec, chan, idx):
    """The full sort network body (arbitrary input -> ascending): stages
    ``k = 2..n``; within stage ``k`` the pair direction alternates by bit
    ``k`` of the lane index — for the [n/(2d), 2, d] pairing that bit is a
    pure function of the BLOCK index (both pair members agree on it), so the
    direction mask is one broadcast compare, no gather."""
    arrs = [prim, sec, chan, idx]
    n = prim.shape[0]
    k = 2
    while k <= n:
        d = k // 2
        while d >= 1:
            nb = n // (2 * d)
            # ascending iff bit k of the lane index is 0; lane = b*2d + s*d + m
            # and d <= k/2, so bit k is carried entirely by the block index b
            blk = jax.lax.broadcasted_iota(jnp.int32, (nb, d), 0)
            asc = ((blk * (2 * d)) & k) == 0
            arrs = _butterfly(arrs, d, asc)
            d //= 2
        k *= 2
    return tuple(arrs)


# ------------------------------------------------------------------ XLA form


def merge_network(prim, sec, chan, idx):
    """Merge a bitonic (ascending++descending) composite-key sequence into
    ascending order — the XLA reference impl (``log2(n)`` fused
    compare-exchange stages). ``idx`` is the unique tie-break AND the gather
    index that moves the actual rows once at the end."""
    return _merge_stages(prim, sec, chan, idx)


def sort_network(prim, sec, chan, idx):
    """Full bitonic sort of an arbitrary composite-key sequence — the XLA
    network form. Value-identical to ``jnp.lexsort((chan, sec, prim))``
    applied to all four arrays, because ``idx`` makes the key total (network
    output is THE unique ascending order, which equals the stable sort)."""
    return _sort_stages(prim, sec, chan, idx)


# --------------------------------------------------------------- Pallas form


def _pallas_network(prim, sec, chan, idx, stages_fn, interpret: bool):
    import jax.experimental.pallas as pl
    from .registry import pallas_interpret

    n = prim.shape[0]
    interpret = interpret or pallas_interpret()

    def kern(p_ref, s_ref, c_ref, i_ref, po_ref, so_ref, co_ref, io_ref):
        p, s, c, i = stages_fn(p_ref[...], s_ref[...], c_ref[...], i_ref[...])
        po_ref[...] = p
        so_ref[...] = s
        co_ref[...] = c
        io_ref[...] = i

    shape = jax.ShapeDtypeStruct((n,), prim.dtype)
    ishape = jax.ShapeDtypeStruct((n,), idx.dtype)
    return pl.pallas_call(
        kern,
        out_shape=[shape, shape, shape, ishape],
        interpret=interpret,
    )(prim, sec, chan, idx)


def merge_network_pallas(prim, sec, chan, idx, *, interpret: bool = False):
    """:func:`merge_network` as ONE fused Pallas kernel: every stage's
    intermediates stay in VMEM (the XLA form materializes 4 arrays per stage
    between fusions in a large program). Falls back to the XLA form when the
    network exceeds :data:`PALLAS_MAX_LANES` or n is not a power of two.
    ``interpret=True`` (auto off-TPU) runs the kernel on CPU — the tier-1
    parity gate."""
    n = prim.shape[0]
    if n & (n - 1) or n > PALLAS_MAX_LANES or n < 2:
        return merge_network(prim, sec, chan, idx)
    return tuple(_pallas_network(prim, sec, chan, idx, _merge_stages,
                                 interpret))


def sort_network_pallas(prim, sec, chan, idx, *, interpret: bool = False):
    """:func:`sort_network` fused into one Pallas kernel (``log2(n)^2/2``
    compare-exchange substages, zero HBM round-trips between them). Same
    fallback envelope as :func:`merge_network_pallas`."""
    n = prim.shape[0]
    if n & (n - 1) or n > PALLAS_MAX_LANES or n < 2:
        return sort_network(prim, sec, chan, idx)
    return tuple(_pallas_network(prim, sec, chan, idx, _sort_stages,
                                 interpret))


# ------------------------------------------------------------- registration

from .registry import register_kernel  # noqa: E402  (registration footer)

register_kernel("ordering_merge", "xla", merge_network, reference=True,
                backends=("xla",), default=True)
register_kernel("ordering_merge", "pallas", merge_network_pallas,
                backends=("pallas-interpret",),
                tpu_refusal="infer-vector-layout: unsupported shape cast "
                            "(tpu.reshape vector<32x2x128xi32> -> "
                            "vector<64x2x64xi32>) — a butterfly of stride "
                            "< 128 splits the lane dimension (TPU v5 lite, "
                            "jax 0.9.0)")
