"""Keyed-pane histograms on the MXU — the FFAT-insert hot path.

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

Batches that violate the locality bound (a chunk spanning ≥ L panes — wildly
out-of-order timestamps) are detected on device and routed through the exact
scatter-add path with ``lax.cond``: the fast path is an optimization, never a
semantics change.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

#: default lanes per chunk-local histogram row
DEFAULT_CHUNK = 1024
#: default pane-locality bound per chunk (panes spanned by one chunk)
DEFAULT_L = 8
#: key-axis tile for the chunk-local one-hot (caps transient memory at ~C*K_TILE B)
K_TILE = 512


def keyed_pane_histogram(key: jax.Array, pane: jax.Array, valid: jax.Array,
                         num_keys: int, ring: int, *,
                         chunk: int = DEFAULT_CHUNK, locality: int = DEFAULT_L,
                         impl: str = None,
                         ) -> jax.Array:
    """Count histogram ``out[k, pane % ring] = #{lanes: key==k, pane==p}``.

    ``key``: i32[C] in [0, num_keys); ``pane``: i32[C] (arbitrary, ring-mapped);
    ``valid``: bool[C]. Returns i32[num_keys, ring]. Exact for any input (locality
    violations fall back to scatter-add inside the same compiled program).

    ``impl``: "xla" (default; the inline einsum formulation below) or "pallas"
    (:func:`keyed_pane_histogram_pallas`'s kernel as the fast branch — same
    locality cond, same scatter fallback). Defaults from the per-backend
    kernel registry (``ops/registry.py``: ``WF_KERNEL_IMPL``, the deprecated
    ``WF_HISTOGRAM_IMPL`` alias, or a persisted autotuned winner) so a whole
    chain can be A/B'd without code changes.
    """
    C = key.shape[0]
    K, P = int(num_keys), int(ring)
    if C % chunk != 0 or C < chunk:
        # odd capacities: scatter path (capacities are powers of two in practice)
        return _scatter_hist(key, pane, valid, K, P)
    # Force the inputs to materialize before the one-hot tiles consume them.
    # In a fused chain `key` is often itself the result of a matmul-formulated
    # lookup (e.g. the YSB campaign join); without the barrier XLA re-fuses
    # that producer into EVERY K_TILE/locality tile of the histogram,
    # multiplying the producer's cost by the tile count (measured: the same
    # histogram is 15 us standalone vs ~5 ms fused in the YSB chain).
    # Semantics-neutral.
    key, pane, valid = jax.lax.optimization_barrier((key, pane, valid))
    R = C // chunk

    pane_r = pane.reshape(R, chunk)
    valid_r = valid.reshape(R, chunk)
    big = jnp.iinfo(pane.dtype).max
    base = jnp.min(jnp.where(valid_r, pane_r, big), axis=1)      # [R]
    base = jnp.where(base == big, 0, base)
    local = pane_r - base[:, None]                               # [R, chunk]
    ok_local = valid_r & (local < locality)

    in_bounds = jnp.all(ok_local == valid_r)

    def fast(_):
        lr = jnp.where(ok_local, local, 0)
        key_r = key.reshape(R, chunk)
        ohl = ((lr[:, :, None] == jnp.arange(locality, dtype=lr.dtype))
               & ok_local[:, :, None]).astype(jnp.bfloat16)
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
        h3 = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)
        # place chunk histograms into ring columns: one-hot of (base+l) % P
        slot = (base[:, None] + jnp.arange(locality, dtype=base.dtype)) % P
        ohp = (slot.reshape(-1)[:, None]
               == jnp.arange(P, dtype=slot.dtype)).astype(jnp.float32)  # [R*L, P]
        flat = jnp.transpose(h3, (1, 0, 2)).reshape(K, R * locality)
        # `flat` holds per-chunk COUNTS (up to `chunk`): HIGHEST, because a
        # TPU's default f32 dot is one bf16 pass and rounds counts past 256
        out = jax.lax.dot_general(flat, ohp, (((1,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        return out.astype(jnp.int32)

    # NOTE: selection (and the WF_HISTOGRAM_FORCE_FAST read below) happens at
    # TRACE time — a jitted executable compiled before the env change keeps
    # the old impl for the life of the process (XLA caches the traced
    # program, not the env). The registry records this choice and validate()
    # reports disagreements as WF109; for A/B runs force a retrace (fresh
    # jit / different shapes) or pass impl= explicitly. The old
    # WF_HISTOGRAM_IMPL toggle is honored as a deprecated registry alias.
    from .registry import resolve_impl
    impl = resolve_impl("histogram", impl=impl,
                        spec_key=f"C{C}xK{K}xP{P}c{chunk}l{locality}")
    # '0'/empty = off — the WF_ORDERING_SKIP_SORTED convention (a bare bool()
    # of the string made '0' ENABLE the wrong-answer diagnostic bypass)
    force_fast = os.environ.get("WF_HISTOGRAM_FORCE_FAST", "0") not in ("", "0")
    if impl.startswith("pallas"):
        if P < locality:
            # the Pallas kernel's single-fold wrap (padded[:, :P] += padded[:,
            # P:]) assumes locality <= ring; for P < L the [K,P] target vs
            # [K,L] addend shapes mismatch — route to the exact scatter path
            # (the XLA fast branch handles any P via % P, but keeping both
            # guards identical keeps the impls interchangeable)
            return _scatter_hist(key, pane, valid, K, P)
        # "pallas": dynamic-slice store of the [K, L] chunk histogram into the
        # ring (8-wide store at a traced lane offset — Mosaic refuses it on
        # TPU, see the registration below; interpret mode only). "pallas_mm":
        # placement by one-hot matmul into the full [K, P+L] block (static
        # stores only, more VPU adds per chunk) — the form that compiles.
        placement = "mm" if impl == "pallas_mm" else "ds"
        fast = lambda _: _pallas_fast(key, pane, valid, K, P,  # noqa: E731
                                      chunk, locality, placement=placement)
    if force_fast:
        # DIAGNOSTIC ONLY (WF_HISTOGRAM_FORCE_FAST): skip the locality cond and
        # run the fast path unconditionally. If XLA flattens the cond in a
        # larger program (select-both-branches), the serialized scatter branch
        # executes every step even though in_bounds is always true — this
        # bypass isolates that hypothesis in the per-prefix ablation. WRONG for
        # inputs that violate chunk locality; never set it in production.
        return fast(None)
    return jax.lax.cond(in_bounds, fast,
                        lambda _: _scatter_hist(key, pane, valid, K, P), None)


def _scatter_hist(key, pane, valid, K, P):
    seg = jnp.where(valid, key * P + pane % P, K * P)
    return jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                               num_segments=K * P).reshape(K, P)


def keyed_pane_histogram_pallas(key: jax.Array, pane: jax.Array,
                                valid: jax.Array, num_keys: int, ring: int, *,
                                chunk: int = DEFAULT_CHUNK,
                                locality: int = DEFAULT_L,
                                placement: str = "ds",
                                interpret: bool = False) -> jax.Array:
    """Pallas formulation of :func:`keyed_pane_histogram`'s fast path: one
    kernel owns the whole ``[C] -> [K, P]`` accumulation, so the chunk one-hots
    and per-chunk ``[K, L]`` partials live in VMEM for their entire life — no
    fusion decision XLA can get wrong in a larger program (the YSB chain
    measures the XLA form at ~5 ms in-chain vs 15 us standalone; this kernel
    exists to make the standalone cost the only cost).

    Grid = one step per chunk (TPU grids run sequentially, so read-modify-write
    accumulation into the output ref across steps is sound). Ring wrap-around
    is handled by padding the ring with ``locality`` spill columns the kernel
    stores into contiguously (``base % P`` never wraps past ``P + L``) and
    folding them back afterwards — no in-kernel modular scatter.

    PRECONDITION (caller-enforced, same as the XLA fast path): every chunk
    spans < ``locality`` panes among its valid lanes. The framework wraps both
    implementations in the same ``lax.cond`` locality check with the exact
    scatter path as fallback (``keyed_pane_histogram(..., impl="pallas")``).
    ``interpret=True`` runs the kernel in Pallas interpret mode (CPU-testable;
    auto-enabled on the CPU backend)."""
    C = key.shape[0]
    K, P = int(num_keys), int(ring)
    if C % chunk != 0 or C < chunk or P < locality:
        # P < locality: the kernel's single-fold wrap-around (one [K, L] spill
        # block folded onto the ring head) is shape-mismatched and arithmetically
        # wrong when the spill spans the ring more than once — exact scatter
        return _scatter_hist(key, pane, valid, K, P)
    return _pallas_fast(key, pane, valid, K, P, chunk, locality,
                        placement=placement, interpret=interpret)


def _pallas_fast(key, pane, valid, K, P, chunk, locality, *,
                 placement: str = "ds", interpret: bool = False):
    import jax.experimental.pallas as pl
    from .registry import pallas_interpret

    C = key.shape[0]
    L = int(locality)
    R = C // chunk
    big = jnp.iinfo(pane.dtype).max
    interpret = interpret or pallas_interpret()

    def kern(key_ref, pane_ref, valid_ref, out_ref):
        r = pl.program_id(0)

        @pl.when(r == 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        kc = key_ref[...]
        pc = pane_ref[...]
        vc = valid_ref[...] != 0
        base = jnp.min(jnp.where(vc, pc, big))
        base = jnp.where(base == big, 0, base)
        local = pc - base
        ok = vc & (local < L)
        lr = jnp.where(ok, local, 0)
        # dead lanes are masked through the key operand (-1 matches no key):
        # Mosaic has no i1 [chunk] -> [chunk, 1] reshape for `ok[:, None]`
        km = jnp.where(ok, kc, -1)
        ohk = (km[:, None] == jax.lax.broadcasted_iota(
            kc.dtype, (chunk, K), 1)).astype(jnp.bfloat16)
        ohl = (lr[:, None] == jax.lax.broadcasted_iota(
            lr.dtype, (chunk, L), 1)).astype(jnp.bfloat16)
        h = jax.lax.dot_general(ohk, ohl, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [K, L]
        start = base % P                      # [0, P): contiguous in P + L cols
        if placement == "ds":
            cur = out_ref[:, pl.ds(start, L)]
            out_ref[:, pl.ds(start, L)] = cur + h.astype(jnp.float32)
        else:
            # static-store placement: one-hot [L, P+L] matmul scatters the L
            # columns; the accumulate touches the whole block but every memory
            # op has a static shape and offset (always lowers)
            ohp = (jax.lax.broadcasted_iota(jnp.int32, (L, P + L), 1)
                   == start + jax.lax.broadcasted_iota(
                       jnp.int32, (L, P + L), 0)).astype(jnp.float32)
            out_ref[...] += jax.lax.dot_general(
                h, ohp, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,    # h holds counts
                preferred_element_type=jnp.float32)

    padded = pl.pallas_call(
        kern,
        grid=(R,),
        in_specs=[pl.BlockSpec((chunk,), lambda r: (r,)),
                  pl.BlockSpec((chunk,), lambda r: (r,)),
                  pl.BlockSpec((chunk,), lambda r: (r,))],
        out_specs=pl.BlockSpec((K, P + L), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, P + L), jnp.float32),
        interpret=interpret,
    )(key, pane, valid.astype(jnp.int32))
    # fold the spill columns back onto the ring head (wrap-around completion)
    out = padded[:, :P].at[:, :L].add(padded[:, P:])
    return out.astype(jnp.int32)


# ------------------------------------------------------------- registration

from .registry import register_kernel  # noqa: E402  (registration footer)

register_kernel("histogram", "xla", keyed_pane_histogram, reference=True,
                backends=("xla",), default=True)
register_kernel("histogram", "pallas", keyed_pane_histogram_pallas,
                backends=("pallas-interpret",),
                tpu_refusal="cannot statically prove that index in dimension "
                            "1 is a multiple of 128 — the [K, L] chunk "
                            "histogram is stored at the traced lane offset "
                            "base % P (TPU v5 lite, jax 0.9.0)")
register_kernel("histogram", "pallas_mm", keyed_pane_histogram_pallas,
                backends=("pallas-tpu", "pallas-interpret"))
