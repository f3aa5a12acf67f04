"""Win_SeqFFAT — incremental associative window engine with pane-level sharing.

Counterpart of ``wf/win_seqffat.hpp:57-694`` + ``wf/flatfat.hpp:52-400`` (FlatFAT,
Tangwongsan et al. VLDB'15) and their GPU versions (``wf/win_seqffat_gpu.hpp``,
``wf/flatfat_gpu.hpp:51-130``: per-level tree kernels + prefix/suffix walks). The goal
of FlatFAT is *sharing*: O(log n) incremental update instead of recomputing each
window from scratch.

TPU re-design: the tree is replaced by **pane partials** (gcd-free: pane = slide for
tumbling/sliding CB; configurable) — each tuple is lifted once (``lift(t) -> agg``) and
segment-reduced into its (key, pane) partial; a fired window combines its
``win_len/pane_len`` pane partials with a tree reduction over the pane axis. This is
the same work-sharing as FlatFAT (each tuple touches O(1) partials; each window
combines O(L/pane) —  with panes = slide that is the "no pane, no gain" decomposition
the reference's Pane_Farm uses, ``wf/pane_farm.hpp:175``), expressed as segment ops the
MXU/VPU likes instead of pointer-chasing tree levels.

The combine is called on whole lifted structs (upstream's ``result_t``: a field may
read the others) and may be non-commutative: ``combine(a, b)`` always has the earlier
tuples in ``a``. A pane's tuples fold in stream order (a stable sort keeps a (key,
pane)'s lanes in order, the ring's partial comes first), and a window's panes are
reduced by ``ops/segment.py::ordered_reduce`` (neighbours paired, the older on the
left: association changes, operand order does not — the guarantee of FlatFAT's
prefix/suffix walks; see ``tests/test_ffat_noncommutative.py``). ``jnp.add``,
``jnp.maximum`` and ``jnp.minimum`` act leaf by leaf and keep their own paths.

Requirements: ``combine`` associative with ``identity`` (a pytree prefix of the
lift's structure: a scalar for every leaf, or a struct of the lift's type with one
identity a field; ``ops/segment.py::identity_like``); window result = ``fold(combine,
lifted tuples in window)`` — the Win_SeqFFAT contract (winLift + winComb functions,
``wf/builders.hpp`` WinSeqFFAT_Builder:950).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t, DEFAULT_MAX_KEYS
from ..batch import Batch, CTRL_DTYPE, TupleRef
from ..observability import event_time as _et
from ..ops.lookup import table_lookup
from ..ops.segment import (ELEMENTWISE, enumerate_runs, identity_like,
                           on_structs, ordered_reduce, owner_compare_cells,
                           run_budget, segment_reduce, segment_run_fold)
from .base import Basic_Operator
from .window import WindowSpec

#: what a fired window's read of its key's pane ring costs on one v5e
#: (PERF.md section 6; ``_emit``): a ``jnp.take`` of single elements out of
#: the flat ``[K*P]`` ring is serialized, ``ELEMENT_TAKE_NS`` an element
#: (7.17 for ``[33280, 64]`` slots out of ``s32[131072]``, 13.0 for
#: ``[2112, 2]`` out of ``s32[2097152]``); a take of whole ``[P]`` ring rows
#: by key streams, ``ROW_LANE_NS`` a lane (0.0129 for ``[33280, 256]`` rows
#: out of ``s32[512, 256]``, 0.0105 for ``[2112, 4096]``). ``_emit`` takes
#: whole rows where a window's ``P`` lanes cost less than its ``wpanes``
#: elements.
ELEMENT_TAKE_NS = 7.2
ROW_LANE_NS = 0.013


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FFATState:
    panes: Any            # pytree [K, P, ...] ring of pane partials
    pane_count: jax.Array  # i32[K, P] tuples folded into each pane slot
    pane_of: jax.Array    # i32[K, P] pane id held by each ring slot (-1 empty)
    count: jax.Array      # i32[K] tuples seen per key (CB position source)
    wm: jax.Array         # i32[K] per-key max ts
    next_win: jax.Array   # i32[K]
    dropped_old: jax.Array  # i32[] tuples dropped as OLD (TB straggler drops)
    #: i32[NB] observed-lateness histogram (event-time monitoring only —
    #: None otherwise, an empty pytree subtree, so the off program is
    #: unchanged; observability/event_time.py)
    lat_hist: Any = None
    #: i32[] lanes folded into a ring slot that an unfired pane still held:
    #: their pane lay P or more past their key's first unfired one
    #: (time-based specs; None for count-based ones, an empty pytree subtree,
    #: so that program is unchanged)
    ring_overruns: Any = None
    #: i32[] batches whose integer value fold took ``keyed_pane_fold``'s
    #: whole-batch scatters, i32[] those that took its partial branch, and
    #: i32[] the lanes that branch scattered (``GFFATState``'s three; counted
    #: where the fold rides the contraction; time-based specs, None for
    #: count-based ones)
    fold_fallbacks: Any = None
    fold_partials: Any = None
    fold_spill_lanes: Any = None
    #: i32[] (key, pane) runs the ordered fold combined (a combine that is
    #: not ``ELEMENTWISE``; None otherwise, so that program is unchanged)
    ordered_runs: Any = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GFFATState:
    """State of the global-time TB fast path: the stream shares one event clock, so
    watermark/next-window are scalars and no per-tuple gather from per-key tables is
    needed — the insert is one chunk-local one-hot contraction on the MXU for the
    occupancy counts and, where the lift is a count or gives integers to add, for
    the values too (``ops/histogram.py``); other lifts and combines pay one
    scatter (or sorted scan) a leaf beside the counts' contraction."""

    panes: Any            # pytree [K, P, ...] ring of pane partials
    cnt: jax.Array        # i32[K, P] tuples per pane slot (emptiness filter)
    wm: jax.Array         # i32[] global max ts seen
    next_win: jax.Array   # i32[] next window id to fire (global)
    dropped_old: jax.Array  # i32[] tuples dropped as OLD (pane < fired horizon)
    #: i32[] lanes folded into a ring slot that an unfired pane still held: their
    #: pane lay P or more past the first unfired one (counted where the fold
    #: goes by slot; the count-lift branch passes it through)
    ring_overruns: jax.Array
    #: i32[] batches whose pane fold (the counts, and the values that ride
    #: them) took the whole-batch scatters (a chunk of the batch held more
    #: stragglers than the partial branch scatters)
    fold_fallbacks: jax.Array
    #: i32[] batches whose pane fold took the partial branch (a
    #: chunk spanned more panes than the one-hot holds: ticks out of order),
    #: and i32[] the lanes that branch scattered
    fold_partials: jax.Array
    fold_spill_lanes: jax.Array
    #: i32[NB] observed-lateness histogram (event-time monitoring only)
    lat_hist: Any = None
    #: i32[] lanes folded after a window that holds them had fired: their pane
    #: was not behind the horizon, their ``ts`` was before the end of the
    #: newest fired window, so they count in the windows still open alone
    #: (upstream's OLD-for-that-window); None where the spec allows no
    #: lateness (``delay`` 0), an empty pytree subtree, so that program is
    #: unchanged
    late_lanes: Any = None
    #: i32[] (key, pane) runs the ordered fold combined (``FFATState``'s)
    ordered_runs: Any = None


class Win_SeqFFAT(Basic_Operator):
    """The pane-partial window engine (``Key_FFAT`` is this class with its
    pattern tag).

    Budgets: ``pane_capacity=`` ring slots per key (rounded up to a power of
    two) and ``max_wins=`` fired windows a step (a key on the global-time
    path); the time-based defaults follow the batch, not the deployment.

    What it publishes (``stage_counters()``): at ``bind_geometry`` the static
    sizes, ``ffat_keys``, ``ffat_pane_slots``, for count-based specs
    ``ffat_run_budget``, for time-based ones ``fired_window_budget`` once it
    is known (``max_wins=``, the ring on the global-time path, else the first
    ``apply``) and, off the global-time path, ``owner_compare_cells`` with
    it (how the runs and the fired windows find their key:
    ``ops/segment.py::enumerate_runs``) and ``ffat_emit_row_lanes`` (the
    ring lanes a step's emit reads as whole key rows, 0 where it takes
    single elements: ``_emit``); at ``collect_stats`` ``old_drops``
    and, for time-based specs (on the global-time path with a lift that
    reads the tuple), ``ffat_ring_overruns`` (lanes whose pane lay ``P`` or
    more past the first unfired pane, their key's on the per-key path: they
    were folded into a slot that an unfired pane still held; the count-lift
    branch folds no value by slot and publishes none), on the per-key
    time-based path ``ffat_key_clock_spread`` (the largest per-key watermark
    less the smallest, over the keys that have had a tuple, in ticks: how far
    the keys' event clocks lie apart), on the global-time path (every
    batch's counts take ``keyed_pane_fold``) and on the per-key one where
    an additive integer lift rides its contraction, ``ffat_fold_partials``
    (batches whose ticks were out of order: the contraction held the lanes
    near each chunk's newest pane and the stragglers were scattered),
    ``ffat_fold_spill_lanes`` (those stragglers) and ``ffat_fold_fallbacks``
    (batches with a chunk of more stragglers than that: they took the whole
    batch's exact scatters; all three 0 for an in-order stream in batches
    of whole chunks) and, where
    the spec allows
    lateness (``delay > 0``), ``ffat_late_lanes`` (lanes folded after a
    window that holds them had fired: they count in the windows still open),
    on any path whose combine takes whole structs (not ``jnp.add``,
    ``maximum`` or ``minimum``) ``ffat_ordered_runs`` (the (key, pane) runs
    its ordered fold combined, summed over batches; absent, not 0,
    elsewhere); at ``flush`` ``windows_undelivered_at_eos``.

    ``flush`` returns one batch of open windows a call and None once none is
    left; a pass that holds only windows without a tuple is passed over."""

    routing = routing_modes_t.KEYBY

    def __init__(self, lift: Callable, combine: Callable, *, spec: WindowSpec,
                 identity: Any = 0, num_keys: int = DEFAULT_MAX_KEYS,
                 pane_len: int = None, pane_capacity: int = None,
                 max_wins: int = None, name: str = "win_seqffat",
                 parallelism: int = 1, global_time: bool = None,
                 count_lift: bool = None):
        super().__init__(name, parallelism)
        import math
        # global_time (TB only): all keys share the event clock — watermark and the
        # fired-window frontier become scalars, removing every per-tuple gather from
        # the hot path (take() costs ~5.6 ns/elem on TPU; scatter-add ~7 — the insert
        # becomes two folds by (key, pane), on the MXU where _g_insert can put
        # them there). Default on for TB: streaming benchmarks and
        # real event streams share one clock (the reference's TB windows likewise
        # advance on tuple timestamps, wf/window.hpp:83-121). CAVEAT: the frontier
        # advances on the GLOBAL watermark, so a key whose tuples lag more than
        # `delay` behind the fastest key's clock has its stragglers dropped as OLD
        # once their panes fall behind the fired horizon — per-key skew > delay DOES
        # change window contents (the per-key-watermark path only delays firing).
        # Drops are counted on device (state.dropped_old) and surfaced through
        # Stats_Record.tuples_dropped_old / the monitoring graph snapshot.
        self.global_time = (not spec.is_cb) if global_time is None else global_time
        if self.global_time and spec.is_cb:
            raise ValueError("global_time applies to TB windows only")
        self.lift = lift
        self.combine = combine
        self.identity = identity
        #: lift(t) == 1 for every t (windowed count): the pane-value update equals
        #: the occupancy histogram and rides the MXU path. None = auto-detect.
        self.count_lift = count_lift
        self.spec = spec
        self.num_keys = int(num_keys)
        # pane length: gcd(win, slide) — every window is a whole number of panes and
        # every pane belongs to a whole number of windows (wf/pane_farm.hpp:175)
        self.pane_len = pane_len or math.gcd(spec.win_len, spec.slide)
        if spec.win_len % self.pane_len or spec.slide % self.pane_len:
            raise ValueError("pane_len must divide both win_len and slide")
        self.wpanes = spec.win_len // self.pane_len     # panes per window
        self.spanes = spec.slide // self.pane_len       # panes per slide
        self._pane_capacity = pane_capacity
        self.P = None
        self.max_wins = max_wins
        self._w = None
        #: whether the time-based per-key value fold shares the counts'
        #: contraction (settled by the first trace of ``_insert``, from the
        #: lift's result)
        self._fold_rides = False
        self.bind_geometry(256)        # provisional; compiler re-binds with real C

    def bind_geometry(self, batch_capacity: int) -> None:
        if self._pane_capacity is not None:
            self.P = _next_pow2(self._pane_capacity)
        elif self.spec.is_cb:
            # one batch on a single key touches at most C/pane_len + 1 new panes
            self.P = _next_pow2(self.wpanes + batch_capacity // self.pane_len + 2)
        else:
            # TB: panes indexed by ts//pane_len; a batch touches at most
            # ts_span/pane_len distinct panes — bounded by C but normally far fewer.
            # Default to C/pane_len + window span (override with pane_capacity for
            # very bursty timestamp distributions).
            self.P = _next_pow2(self.wpanes
                                + max(64, batch_capacity // self.pane_len) + 2)
        # the static sizes the step is compiled for: keys, ring slots per key
        gauges = {"ffat_keys": self.num_keys, "ffat_pane_slots": self.P}
        if self.spec.is_cb:
            # the (key, pane) runs a batch may hold
            gauges["ffat_run_budget"] = self._run_budget = run_budget(
                batch_capacity, self.num_keys, self.pane_len)
        else:
            gauges.update(self._fired_budget_gauge())
        gauges.update(self._per_key_gauges())
        self._publish_stage_counters(gauges)

    def _fired_budget_gauge(self) -> dict:
        """Fired windows one step may emit (a key on the global-time path, in
        all otherwise), time-based specs: known from ``max_wins=`` or the
        ring (global time), else once the first ``apply`` has settled it."""
        W = self.max_wins if self.max_wins is not None else self._w
        if W is None and self.global_time:
            W = self._resolve_w(0)
        return {} if W is None else {"fired_window_budget": W}

    def _per_key_gauges(self) -> dict:
        """``owner_compare_cells``: the rows x keys cells a step compares to
        find the key of every run it folds (count-based specs) and of every
        window it fires, 0 where both lists kept the binary search
        (``ops/segment.py::enumerate_runs``); ``ffat_emit_row_lanes``: the
        ring lanes ``_emit`` reads as whole key rows, W x P, 0 where it keeps
        the element takes. Known once the fired-window budget is; the
        global-time path lists no rows and publishes neither."""
        W = self.max_wins if self.max_wins is not None else self._w
        if self.global_time or W is None:
            return {}
        runs = self._run_budget if self.spec.is_cb else 0
        return {"owner_compare_cells":
                owner_compare_cells(W, self.num_keys)
                + owner_compare_cells(runs, self.num_keys),
                "ffat_emit_row_lanes": W * self.P if self._emit_reads_rows()
                else 0}

    def _emit_reads_rows(self) -> bool:
        """Whether ``_emit`` takes a fired window's whole ring row: where its
        ``P`` lanes cost less than its ``wpanes`` single-element takes, and
        only under ``jnp.add`` (a row's panes come in slot order, not pane
        order, across the ring's wrap: integer sums are the same bit for
        bit, a float sum may round otherwise)."""
        return (self.combine is jnp.add
                and self.P * ROW_LANE_NS < self.wpanes * ELEMENT_TAKE_NS)

    def out_capacity(self, in_capacity: int) -> int:
        if self.global_time:
            return self.num_keys * self._resolve_w(in_capacity)
        return self._resolve_w(in_capacity)

    # ------------------------------------------------------------------ state

    def _lift_spec(self, payload_spec):
        t = TupleRef(key=jax.ShapeDtypeStruct((), CTRL_DTYPE),
                     id=jax.ShapeDtypeStruct((), CTRL_DTYPE),
                     ts=jax.ShapeDtypeStruct((), CTRL_DTYPE), data=payload_spec)
        return jax.eval_shape(self.lift, t)

    def _ordered(self) -> bool:
        """Whether the combine is called on whole structs in stream order
        (any but ``ELEMENTWISE``): the insert's ordered fold."""
        return self.combine not in ELEMENTWISE

    def _reduce_panes(self, g, axis):
        """A window's panes (``axis`` of every leaf of ``g``, empty panes the
        identity) reduced: ``jnp.maximum`` and ``jnp.minimum`` leaf by leaf,
        a combine of whole structs in order under ``ordered``."""
        if not self._ordered():
            red = jnp.max if self.combine is jnp.maximum else jnp.min
            return jax.tree.map(lambda x: red(x, axis=axis), g)
        with jax.named_scope("ordered"):
            return ordered_reduce(self.combine, g, axis=axis)

    def _ring(self, agg):
        """The ``[K, P]`` ring of pane partials, every slot the identity."""
        K, P = self.num_keys, self.P
        return jax.tree.map(
            lambda s, ident: jnp.broadcast_to(
                jnp.asarray(ident, s.dtype), (K, P) + s.shape).copy(),
            agg, identity_like(self.identity, agg))

    def init_state(self, payload_spec: Any):
        K, P = self.num_keys, self.P
        agg = self._lift_spec(payload_spec)
        runs = jnp.zeros((), CTRL_DTYPE) if self._ordered() else None
        # lateness histogram: event-time monitoring on TB specs only (CB has
        # no event-time frontier); None = absent from the pytree
        lat = (_et.lateness_init()
               if self._event_time and not self.spec.is_cb else None)
        if self.global_time:
            return GFFATState(
                panes=self._ring(agg),
                cnt=jnp.zeros((K, P), CTRL_DTYPE),
                wm=jnp.asarray(-1, CTRL_DTYPE),
                next_win=jnp.asarray(0, CTRL_DTYPE),
                dropped_old=jnp.zeros((), CTRL_DTYPE),
                ring_overruns=jnp.zeros((), CTRL_DTYPE),
                fold_fallbacks=jnp.zeros((), CTRL_DTYPE),
                fold_partials=jnp.zeros((), CTRL_DTYPE),
                fold_spill_lanes=jnp.zeros((), CTRL_DTYPE),
                lat_hist=lat,
                late_lanes=(jnp.zeros((), CTRL_DTYPE) if self.spec.delay > 0
                            else None),
                ordered_runs=runs,
            )
        # time-based specs count ring overruns and the fold's branches
        def zero():
            return None if self.spec.is_cb else jnp.zeros((), CTRL_DTYPE)
        return FFATState(
            panes=self._ring(agg),
            pane_count=jnp.zeros((K, P), CTRL_DTYPE),
            pane_of=jnp.full((K, P), -1, CTRL_DTYPE),
            count=jnp.zeros((K,), CTRL_DTYPE),
            wm=jnp.full((K,), -1, CTRL_DTYPE),
            next_win=jnp.zeros((K,), CTRL_DTYPE),
            dropped_old=jnp.zeros((), CTRL_DTYPE),
            lat_hist=lat,
            ring_overruns=zero(),
            fold_fallbacks=zero(),
            fold_partials=zero(),
            fold_spill_lanes=zero(),
            ordered_runs=runs,
        )

    def out_spec(self, payload_spec: Any) -> Any:
        return self._lift_spec(payload_spec)

    # ---------------------------------------------------- global-time fast path (TB)

    def _g_insert(self, state: GFFATState, batch: Batch):
        """Fold a batch into the [K, P] pane ring. The occupancy counts go
        through ``ops/histogram.py::keyed_pane_fold`` (a chunk-local one-hot
        contraction on the MXU, with a partial and a whole scatter fallback
        under ``lax.cond``) instead of a serialized scatter-add, and so do
        the partials where the lift allows it, which the code sees for
        itself: a count-like lift (lift(t) == 1, the YSB/windowed-count
        case) IS the counts, and an additive lift whose leaves are ``[C]``
        integers of at most 4 bytes rides the counts' contraction as 8-bit
        limbs (bit for bit ``segment_sum``). Floats, leaves of higher rank,
        odd capacities and every other combine fold the values by
        ``ops/segment.py::segment_reduce`` beside the counts. The fold's
        branches are counted: ``fold_partials`` -> ``ffat_fold_partials``,
        ``fold_spill_lanes`` -> ``ffat_fold_spill_lanes`` and
        ``fold_fallbacks`` -> ``ffat_fold_fallbacks``. A combine on whole
        structs (any but ``ELEMENTWISE``) folds under ``ordered``: the sorted
        segmented scan (``sort``, ``scan``, ``place``) and the combine into
        the ring, the ring's partial on the left; the (key, pane) runs it
        folds are counted (``ordered_runs`` -> ``ffat_ordered_runs``). Slot
        cleanliness is maintained by clear-on-fire in ``_g_emit`` so no
        pane-id bookkeeping is needed; OLD tuples (pane already fired) are dropped with a scalar
        horizon compare. A kept lane whose ``ts`` an already fired window
        holds too (``delay`` shorter than its lateness) counts in the open
        windows alone; with ``delay > 0`` such lanes are counted
        (``late_lanes`` -> ``ffat_late_lanes``). The ring holds the ``P``
        panes from the first unfired
        one: a lane further ahead shares its slot with a pane that has not
        fired, and where the partials are folded from the lift such lanes
        are counted (``ring_overruns`` -> ``ffat_ring_overruns``; size the
        ring with ``pane_capacity=``). Scopes: ``fold`` (the lift, the
        contraction that counts and folds what rides it, the values'
        scatter or ``ordered`` fold, the add into the ring), ``hist`` (the
        add into ``cnt``)."""
        from ..ops.histogram import (FOLD_PARTIAL, FOLD_WHOLE, keyed_pane_fold,
                                     pane_fold_applies)
        K, P = self.num_keys, self.P
        pane = batch.ts // self.pane_len
        horizon = state.next_win * self.spanes       # first un-fired pane (global)
        valid = batch.valid & (pane >= horizon)
        # stragglers behind the fired horizon are DROPPED, not merely delayed
        # (global clock: per-key skew > delay loses tuples) — count them
        n_dropped = jnp.sum((batch.valid & ~valid).astype(CTRL_DTYPE))
        late_lanes = state.late_lanes
        if late_lanes is not None:
            # a kept lane is late when the first window holding it has fired:
            # the windows before ``next_win`` do not see it. In window units,
            # so that no tick past 2^31 is formed
            first_w = jnp.maximum(
                0, (batch.ts - self.spec.win_len) // self.spec.slide + 1)
            late_lanes = late_lanes + jnp.sum(
                (valid & (first_w < state.next_win)).astype(CTRL_DTYPE))
        if self.count_lift is None:
            self.count_lift = _detect_count_lift(self.lift, batch)
        tuples = TupleRef(key=batch.key, id=batch.id, ts=batch.ts,
                          data=batch.payload)
        counts_are_values = self._counts_are_values()
        # integers to add: counts and values share one contraction
        rides = (not counts_are_values and self.combine is jnp.add
                 and pane_fold_applies(jax.eval_shape(jax.vmap(self.lift),
                                                      tuples)))
        ring_overruns = state.ring_overruns
        ordered_runs = state.ordered_runs
        with jax.named_scope("fold"):
            if not counts_are_values:
                # the ring holds panes [horizon, horizon + P): a lane further
                # ahead lands in the slot of a pane that has not fired yet
                ring_overruns = ring_overruns + jnp.sum(
                    (valid & (pane >= horizon + P)).astype(CTRL_DTYPE))
                lifted = jax.vmap(self.lift)(tuples)
            cnt_upd, upd, branch, spilled = keyed_pane_fold(
                batch.key, pane, valid, lifted if rides else (), K, P)
            fold_fallbacks = state.fold_fallbacks + (
                branch == FOLD_WHOLE).astype(CTRL_DTYPE)
            fold_partials = state.fold_partials + (
                branch == FOLD_PARTIAL).astype(CTRL_DTYPE)
            fold_spill_lanes = state.fold_spill_lanes + spilled
            if counts_are_values:
                panes = jax.tree.map(
                    lambda t: t + cnt_upd.astype(t.dtype), state.panes)
            elif rides:
                panes = jax.tree.map(jnp.add, state.panes, upd)
            else:
                seg = jnp.where(valid, batch.key * P + pane % P, K * P)
                with (jax.named_scope("ordered") if ordered_runs is not None
                      else contextlib.nullcontext()):
                    upd = segment_reduce(
                        lifted, seg, valid, K * P,
                        combine=None if self.combine is jnp.add
                        else self.combine,
                        identity=self.identity)
                    # the ring's partial holds the pane's earlier tuples
                    panes = on_structs(self.combine)(state.panes, jax.tree.map(
                        lambda u: u.reshape((K, P) + u.shape[1:]), upd))
                    if ordered_runs is not None:
                        ordered_runs = ordered_runs + jnp.sum(
                            (cnt_upd > 0).astype(CTRL_DTYPE))
        with jax.named_scope("hist"):
            cnt = state.cnt + cnt_upd
        wm_new = jnp.maximum(state.wm,
                             jnp.max(jnp.where(batch.valid, batch.ts, -1)))
        lat = state.lat_hist
        if lat is not None:
            # observed lateness vs the post-batch global watermark: one
            # masked reduction, state-only (event-time monitoring).  A
            # delay >= the recorded max keeps every straggler's pane ahead
            # of the fired horizon — zero OLD drops (recommend_delay).
            lat = _et.lateness_update(lat, wm_new, batch.ts, batch.valid)
        return dataclasses.replace(
            state,
            panes=panes,
            cnt=cnt,
            wm=wm_new,
            dropped_old=state.dropped_old + n_dropped,
            ring_overruns=ring_overruns,
            fold_fallbacks=fold_fallbacks,
            fold_partials=fold_partials,
            fold_spill_lanes=fold_spill_lanes,
            lat_hist=lat,
            late_lanes=late_lanes,
            ordered_runs=ordered_runs,
        )

    def _counts_are_values(self) -> bool:
        """A count lift (lift == 1) under an additive combine: the pane
        counts are the value fold, and nothing is folded by ring slot."""
        return bool(self.count_lift) and self.combine is jnp.add

    def _g_emit(self, state: GFFATState, W_n: int, flush: bool):
        """Grid emission: the fired window range [lo, hi) is shared by every key, so
        the output is a [W_n, K] grid flattened — no searchsorted, no index math.
        Fired panes are cleared back to identity (ring hygiene) with an elementwise
        cyclic-interval mask over the [K, P] table — no scatter. Scopes:
        ``gather`` (the windows' panes out of both tables), ``reduce`` (a
        window's panes into its result), ``clear``."""
        K, P = self.num_keys, self.P
        s = self.spec
        lo = state.next_win
        hi = jnp.maximum(lo, self._due_hi(state, flush))
        hi = jnp.minimum(hi, lo + W_n)
        n_w = hi - lo

        wid = lo + jnp.arange(W_n, dtype=CTRL_DTYPE)          # [W_n]
        w_valid = jnp.arange(W_n, dtype=CTRL_DTYPE) < n_w
        # The fired windows' panes form a CONTIGUOUS cyclic range starting at
        # lo*spanes: roll the ring so it starts at column 0, then extraction is a
        # static strided window — no dynamic gather at all. (Fallback to a dynamic
        # take when the static window would overrun the ring.)
        static_span = (W_n - 1) * self.spanes + self.wpanes
        if static_span <= P:
            shift = (lo * self.spanes) % P
            idx = (jnp.arange(W_n, dtype=CTRL_DTYPE)[:, None] * self.spanes
                   + jnp.arange(self.wpanes, dtype=CTRL_DTYPE)[None, :])

            def gat(tbl):                                     # tbl [K, P, ...]
                rolled = jnp.roll(tbl, -shift, axis=1)
                g = jnp.take(rolled, idx.reshape(-1), axis=1)  # static indices
                return g.reshape((K, W_n, self.wpanes) + tbl.shape[2:])
        else:
            pane_ids = wid[:, None] * self.spanes + jnp.arange(
                self.wpanes, dtype=CTRL_DTYPE)[None, :]       # [W_n, wpanes]
            slot = pane_ids % P

            def gat(tbl):                                     # tbl [K, P, ...]
                g = jnp.take(tbl, slot.reshape(-1), axis=1)   # [K, W_n*wpanes, ...]
                return g.reshape((K, W_n, self.wpanes) + tbl.shape[2:])
        with jax.named_scope("gather"):
            cnts = gat(state.cnt)                             # [K, W_n, wpanes]
        with jax.named_scope("reduce"):
            win_cnt = jnp.sum(cnts, axis=2)                   # [K, W_n]

        def reduce_w(tbl):
            with jax.named_scope("gather"):
                g = gat(tbl)                                  # [K, W_n, wpanes, ...]
            with jax.named_scope("reduce"):
                m = (cnts > 0).reshape(cnts.shape + (1,) * (g.ndim - 3))
                return jnp.sum(jnp.where(m, g, 0), axis=2)
        if self.combine is jnp.add:
            results = jax.tree.map(reduce_w, state.panes)     # [K, W_n, ...]
        else:
            # empty slots hold the identity (cleared on fire)
            with jax.named_scope("gather"):
                g = jax.tree.map(gat, state.panes)            # [K, W_n, wpanes, ...]
            with jax.named_scope("reduce"):
                results = self._reduce_panes(g, axis=2)

        valid = (win_cnt > 0) & w_valid[None, :]              # empty windows not emitted
        res_ts = wid * s.slide + (s.win_len - 1)              # [W_n]
        flat = lambda a: a.reshape((K * W_n,) + a.shape[2:])
        out = Batch(
            key=flat(jnp.broadcast_to(jnp.arange(K, dtype=CTRL_DTYPE)[:, None],
                                      (K, W_n))),
            id=flat(jnp.broadcast_to(wid[None, :], (K, W_n))),
            ts=flat(jnp.broadcast_to(res_ts[None, :], (K, W_n))),
            payload=jax.tree.map(flat, results),
            valid=flat(valid),
        )
        # clear fired panes [lo*spanes, hi*spanes) — cyclic interval mask over [P]
        with jax.named_scope("clear"):
            first, last = lo * self.spanes, hi * self.spanes  # clear [first, last)
            pos = jnp.arange(P, dtype=CTRL_DTYPE)
            # slot s holds a fired pane iff exists p in [first,last) with
            # p % P == s; since last-first <= P, that is a cyclic interval test
            rel = (pos - first % P) % P
            clear = rel < (last - first)
            panes = jax.tree.map(
                lambda t, ident: jnp.where(
                    clear.reshape((1, P) + (1,) * (t.ndim - 2)),
                    jnp.asarray(ident, t.dtype), t),
                state.panes, identity_like(self.identity, state.panes))
            cnt = jnp.where(clear[None, :], 0, state.cnt)
        return dataclasses.replace(state, panes=panes, cnt=cnt, next_win=hi), out

    # ------------------------------------------------------------------ insert

    def _insert(self, state: FFATState, batch: Batch):
        """Lift each tuple and fold it into its (key, pane) partial: the FlatFAT
        'update leaf + bubble' (wf/flatfat.hpp:134-240) collapsed into one
        per-batch update table ``[K*P]`` and an elementwise fold into the ring.

        Count-based windows build the tables in the order one sort makes
        (:meth:`_cb_updates`: no per-lane scatter). Time-based per-key
        windows, whose panes come from ``ts`` and are not contiguous inside a
        key, fold an additive lift of ``[C]`` integers in
        ``keyed_pane_fold``'s contraction on panes relative to each key's
        first unfired one (:meth:`_pk_fold`, the same test as
        ``_g_insert``'s: ``fold_partials`` -> ``ffat_fold_partials``,
        ``fold_spill_lanes`` -> ``ffat_fold_spill_lanes``, ``fold_fallbacks``
        -> ``ffat_fold_fallbacks`` count its branches) and take the
        watermark by a select-reduce over the key one-hot
        (:func:`_key_max`): no reduction goes over the lanes by scatter.
        Floats, other combines, leaves of higher rank and odd capacities
        keep one segment reduction per table (value and count through the
        registry-selectable ``segment_fold`` kernel, the pane id and the
        watermark through ``segment_max``): four scatters over the lanes; a
        combine on whole structs takes the sorted segmented scan there, under
        ``ordered``, and its runs are counted (``ordered_runs``). A
        pane id or a watermark is read only where a count says it was
        written, so neither pays a count of its own. Everything from
        ``touched`` on is shared.

        Time-based windows keep the ``P`` panes from each key's first unfired
        one: a lane further ahead shares its slot with a pane that has not
        fired, and such lanes are counted (``ring_overruns`` ->
        ``ffat_ring_overruns``). Scopes below ``insert``: count-based
        windows ``rank`` and ``fold`` (:meth:`_cb_updates`); time-based ones
        ``lookup`` (each lane's key's horizon), ``fold`` (the ``[K*P]``
        tables: the contraction and its rotation into the ring, or the
        scatters; the fold into the ring) and ``keys`` (the per-key count, a
        sum of the count table's rows, and watermark)."""
        K, P = self.num_keys, self.P
        valid = batch.valid
        cb = self.spec.is_cb
        ring_overruns = state.ring_overruns
        fold_counters = (state.fold_fallbacks, state.fold_partials,
                         state.fold_spill_lanes)
        if cb:
            upd, cnt_upd, pane_id_upd, counts_add, ts_max = self._cb_updates(
                state, batch)
            n_dropped = jnp.zeros((), CTRL_DTYPE)    # CB never drops OLD tuples
        else:
            with jax.named_scope("lookup"):
                first_win = table_lookup(state.next_win, batch.key)
            horizon = first_win * self.spec.slide
            kept = valid & (batch.ts >= horizon)
            n_dropped = jnp.sum((valid & ~kept).astype(CTRL_DTYPE))
            valid = kept
            pane = batch.ts // self.pane_len
            from ..ops.histogram import pane_fold_applies
            tuples = TupleRef(key=batch.key, id=batch.id, ts=batch.ts,
                              data=batch.payload)
            # integers to add: the fold rides keyed_pane_fold's contraction
            rides = self._fold_rides = (
                self.combine is jnp.add and pane_fold_applies(
                    jax.eval_shape(jax.vmap(self.lift), tuples)))
        with jax.named_scope("fold"):
            if not cb:
                if not rides:
                    slot = pane % P
                    seg = jnp.where(valid, batch.key * P + slot, K * P)
                # the ring holds the key's panes [horizon, horizon + P): a
                # lane further ahead lands in the slot of a pane not yet fired
                n_over = jnp.sum((valid & (pane >= first_win * self.spanes + P)
                                  ).astype(CTRL_DTYPE))
                ring_overruns = ring_overruns + n_over

                lifted = jax.vmap(self.lift)(tuples)
                if rides:
                    upd, cnt_upd, pane_id_upd, fold_counters = self._pk_fold(
                        state, batch, pane, valid, lifted, first_win, n_over,
                        fold_counters)
                else:
                    # per-(key,pane-slot) partial of this batch (the ordered
                    # fold where the combine takes structs)
                    with (jax.named_scope("ordered") if self._ordered()
                          else contextlib.nullcontext()):
                        upd = segment_reduce(
                            lifted, seg, valid, K * P,
                            combine=None if self.combine is jnp.add
                            else self.combine,
                            identity=self.identity)
                    cnt_upd = segment_reduce(valid.astype(CTRL_DTYPE), seg,
                                             valid, K * P)
                    # read only where ``touched``: an untouched slot's needs
                    # no identity, so no count of its own (dead lanes' seg
                    # is K * P)
                    pane_id_upd = jax.ops.segment_max(pane, seg,
                                                      num_segments=K * P)

            touched = cnt_upd.reshape(K, P) > 0
            new_pane_of = jnp.where(touched, pane_id_upd.reshape(K, P), state.pane_of)
            # a slot whose pane id advanced (ring wrap) restarts from identity
            fresh = touched & (new_pane_of != state.pane_of)
            ids = identity_like(self.identity, state.panes)
            ordered_runs = state.ordered_runs
            if ordered_runs is not None:
                ordered_runs = ordered_runs + jnp.sum(
                    touched.astype(CTRL_DTYPE))

            def fold(tbl, u, ident):
                u = u.reshape((K, P) + u.shape[1:])
                t = jnp.where(_b(fresh, tbl), jnp.asarray(ident, tbl.dtype), tbl)
                m = _b(touched, tbl)
                if self.combine is jnp.add:
                    return jnp.where(m, t + u, t)
                return jnp.where(m, self.combine(t, u), t)

            def fold_structs(panes, upd):
                """``fold`` with the combine on whole structs, the ring's
                partial (the pane's earlier tuples) on the left."""
                t = jax.tree.map(lambda tbl, ident: jnp.where(
                    _b(fresh, tbl), jnp.asarray(ident, tbl.dtype), tbl),
                    panes, ids)
                c = self.combine(t, jax.tree.map(
                    lambda u: u.reshape((K, P) + u.shape[1:]), upd))
                return jax.tree.map(
                    lambda t, c: jnp.where(_b(touched, t), c, t), t, c)

        if not cb:
            with jax.named_scope("keys"):
                # a key's lanes are its slots' counts; a key without one
                # reads -1 or the least int32, at or below any watermark
                counts_add = jnp.sum(cnt_upd.reshape(K, P), axis=1)
                if rides:
                    ts_max = _key_max(batch.key, batch.ts, valid, K)
                else:
                    ts_max = jax.ops.segment_max(
                        jnp.where(valid, batch.ts, -1), batch.key,
                        num_segments=K)
        with jax.named_scope("fold" if cb else "keys"):
            wm_new = jnp.maximum(state.wm, ts_max)
        lat = state.lat_hist
        if lat is not None:
            # per-key TB path: lateness vs the MAX per-key watermark — the
            # cross-key skew measure (a lagging key's tuples land in high
            # buckets even though its own frontier fires late)
            lat = _et.lateness_update(lat, jnp.max(wm_new), batch.ts,
                                      batch.valid)
        with jax.named_scope("fold"):
            if self._ordered():
                panes = fold_structs(state.panes, upd)
            else:
                panes = jax.tree.map(fold, state.panes, upd, ids)
            pane_count = (jnp.where(fresh, 0, state.pane_count)
                          + cnt_upd.reshape(K, P))
        return dataclasses.replace(
            state,
            panes=panes,
            pane_count=pane_count,
            pane_of=new_pane_of,
            count=state.count + counts_add,
            wm=wm_new,
            dropped_old=state.dropped_old + n_dropped,
            lat_hist=lat,
            ring_overruns=ring_overruns,
            fold_fallbacks=fold_counters[0],
            fold_partials=fold_counters[1],
            fold_spill_lanes=fold_counters[2],
            ordered_runs=ordered_runs,
        )

    def _pk_fold(self, state: FFATState, batch: Batch, pane, valid, lifted,
                 first_win, n_over, counters):
        """The per-key time-based fold of integers to add, in
        ``keyed_pane_fold``'s one contraction: ``upd`` (the values' pytree),
        ``cnt_upd``, ``pane_id_upd`` over ``[K*P]`` as the scatters make them,
        bit for bit, and the fold's three counters advanced by its branch.

        The fold's locality test is by pane over the keys of a 1,024-lane
        chunk, which keys whose clocks lie apart fail. A lane's pane relative
        to its key's first unfired one, ``H = next_win * spanes`` (its
        horizon, which follows the key's own watermark), is near every other
        in-order key's: the fold takes that, and is indexed by ``rel % P``
        where the ring is by ``(H + rel) % P``, so each key's row of every
        table turns by ``H % P`` (:func:`_rotate_rows`). A kept lane lies in
        ``[H, H + P)`` unless it overran the ring, so a touched slot ``s``
        holds the pane ``H + (s - H) mod P``; a batch in which a lane
        overran (``n_over``) takes the pane ids' ``segment_max`` behind a
        ``cond`` instead (scope ``overrun``)."""
        from ..ops.histogram import FOLD_PARTIAL, FOLD_WHOLE, keyed_pane_fold
        K, P = self.num_keys, self.P
        base = state.next_win * self.spanes                   # [K]: H
        cnt, folds, branch, spilled = keyed_pane_fold(
            batch.key, pane - first_win * self.spanes, valid, lifted, K, P)
        shift = base % P

        def place(t):                                         # [K, P] -> ring
            return _rotate_rows(t, shift).reshape(K * P)

        def closed(_):
            s = jnp.arange(P, dtype=CTRL_DTYPE)
            return (base[:, None] + (s - base[:, None]) % P).reshape(K * P)

        def scattered(_):
            with jax.named_scope("overrun"):
                seg = jnp.where(valid, batch.key * P + pane % P, K * P)
                return jax.ops.segment_max(pane, seg, num_segments=K * P)

        pane_id_upd = jax.lax.cond(n_over > 0, scattered, closed, None)
        fallbacks, partials, spill = counters
        return (jax.tree.map(place, folds), place(cnt), pane_id_upd,
                (fallbacks + (branch == FOLD_WHOLE).astype(CTRL_DTYPE),
                 partials + (branch == FOLD_PARTIAL).astype(CTRL_DTYPE),
                 spill + spilled))

    def _cb_updates(self, state: FFATState, batch: Batch):
        """The batch's update tables for a count-based window: ``upd``,
        ``cnt_upd``, ``pane_id_upd`` over ``[K*P]`` and ``counts_add``,
        ``ts_max`` per key.

        A tuple's pane is ``(count[key] + rank) // pane_len``, so after one
        stable sort by key every (key, pane) group is one run of neighbouring
        lanes in stream order. ``ops/segment.py::segment_run_fold`` folds the
        runs where they lie and hands back at most ``run_budget`` of them;
        the tables are written run by run (thousands of writes, not a scatter
        per lane) and the per-key count and watermark come from the runs too.
        On one v5e at C = 1,048,576 and K = 512 the sort costs 1.7 ms where
        each of the eight per-lane scatters it replaces cost 4.8-9.2 ms
        (PERF.md section 6, PR 26). Within a batch two runs of a key never
        share a ring slot (``bind_geometry``: P > wpanes + C/pane_len + 2),
        so the writes have unique indices."""
        K, P = self.num_keys, self.P
        lifted = jax.vmap(self.lift)(
            TupleRef(key=batch.key, id=batch.id, ts=batch.ts, data=batch.payload))
        with jax.named_scope("rank"):
            runs = segment_run_fold(
                [(lifted, self.combine, self.identity),
                 (batch.ts, jnp.maximum, -1)],
                batch.key, batch.valid, K, state.count, self.pane_len)
        with jax.named_scope("fold"), jax.named_scope("write"):
            run_vals, run_ts = runs.folded
            seg = jnp.where(runs.live, runs.key * P + runs.chunk % P, K * P)

            def table(fill, rows):
                init = jnp.broadcast_to(jnp.asarray(fill, rows.dtype),
                                        (K * P,) + rows.shape[1:])
                return init.at[seg].set(rows, mode="drop")
            upd = jax.tree.map(lambda rows, ident: table(ident, rows),
                               run_vals, identity_like(self.identity, run_vals))
            cnt_upd = table(0, runs.length)
            pane_id_upd = table(-1, runs.chunk)
            ts_max = jnp.full((K,), -1, run_ts.dtype).at[
                jnp.where(runs.live, runs.key, K)].max(run_ts, mode="drop")
        return upd, cnt_upd, pane_id_upd, runs.key_count, ts_max

    # ------------------------------------------------------------------ fire

    def _emit(self, state: FFATState, W: int, flush: bool):
        """The due windows of every key, key by key, in ``W`` rows (count-based
        and per-key time-based specs). Scopes: ``range`` (the rows' keys and
        window ids: ``enumerate_runs`` and the ``lo`` lookup), ``gather``
        (each window's key's whole ``[P]`` ring row out of ``pane_of`` and
        the partials where :meth:`_emit_reads_rows`, else its ``[wpanes]``
        slots one element each), ``reduce`` (a window's live panes into its
        result; ``reduce/ordered`` where the combine takes structs)."""
        K, P = self.num_keys, self.P
        s = self.spec
        lo = state.next_win
        with jax.named_scope("range"):
            hi = jnp.maximum(self._due_hi(state, flush), lo)
            n_f = hi - lo
            k_safe, i_of, valid_w = enumerate_runs(n_f, W)
            wid = table_lookup(lo, k_safe) + i_of
            # the first W of the list are emitted
            emitted_k = jnp.clip(W - (jnp.cumsum(n_f) - n_f), 0, n_f)

        # gather the wpanes panes of each window and tree-reduce (getResult():
        # wf/flatfat.hpp root read; here a log-depth reduction over the pane axis)
        with jax.named_scope("gather"):
            pane0 = wid * self.spanes
            if self._emit_reads_rows():
                # a slot holds pane p only at p % P: it is live where the pane
                # id it holds lies in the window's range (never-written slots
                # hold -1, fired panes' older ids); the offset is compared,
                # since the range's end may pass int32's
                def gat(tbl):
                    return jnp.take(tbl, k_safe, axis=0)         # [W, P, ...]
                off = gat(state.pane_of) - pane0[:, None]
                live = (off >= 0) & (off < self.wpanes)
            else:
                pane_ids = pane0[:, None] + jnp.arange(
                    self.wpanes, dtype=CTRL_DTYPE)[None, :]
                slot = pane_ids % P
                gflat = k_safe[:, None] * P + slot               # [W, wpanes]

                def gat(tbl):
                    return jnp.take(tbl.reshape((K * P,) + tbl.shape[2:]),
                                    gflat, axis=0)
                live = jnp.take(state.pane_of.reshape(K * P), gflat) == pane_ids
            live &= valid_w[:, None]

        def gat_live(tbl, ident):
            with jax.named_scope("gather"):
                g = gat(tbl)
            with jax.named_scope("reduce"):
                return jnp.where(_b(live, g), g, jnp.asarray(ident, g.dtype))

        g = jax.tree.map(gat_live, state.panes,
                         identity_like(self.identity, state.panes))
        with jax.named_scope("reduce"):
            if self.combine is jnp.add:
                results = jax.tree.map(lambda x: jnp.sum(x, axis=1), g)
            else:
                results = self._reduce_panes(g, axis=1)
        res_ts = (wid * s.slide + s.win_len - 1 if not s.is_cb
                  else jnp.zeros_like(wid))
        out = Batch(key=k_safe, id=wid, ts=jnp.asarray(res_ts, CTRL_DTYPE),
                    payload=results, valid=valid_w)
        return dataclasses.replace(state, next_win=lo + emitted_k), out

    # ------------------------------------------------------------------ operator API

    def _due_hi(self, state, flush: bool):
        """One past the last window id that is due (scalar on the global-time
        path, per key otherwise): whole windows behind the watermark or the
        count, every window with a tuple at EOS. May lie below ``next_win``."""
        s = self.spec
        if self.global_time:
            return (state.wm // s.slide + 1 if flush
                    else (state.wm - s.delay - s.win_len) // s.slide + 1)
        if s.is_cb:
            return (jnp.where(state.count > 0, (state.count - 1) // s.slide + 1, 0)
                    if flush else
                    jnp.maximum(0, (state.count - s.win_len) // s.slide + 1))
        return (jnp.where(state.count > 0, state.wm // s.slide + 1, 0)
                if flush else
                jnp.maximum(0, (state.wm - s.delay - s.win_len) // s.slide + 1))

    def _resolve_w(self, capacity):
        if self.max_wins is not None:
            return self.max_wins
        if self.global_time:
            # windows drainable per step, bounded by what the pane ring can hold
            return max(4, (self.P - self.wpanes) // self.spanes)
        W = max(16, -(-capacity // self.spec.slide) + 64)
        if W * self.wpanes > (1 << 22):
            # same adversarial-slide guard as Win_Seq._resolve_w: a window
            # combines wpanes pane partials, so the default budget implies a
            # [W, wpanes] gather per batch — force an explicit budget
            raise ValueError(
                f"{self.name}: default fired-window budget W={W} with "
                f"{self.wpanes} panes/window implies a [{W}, {self.wpanes}] "
                f"gather per batch; pass max_wins= to bound it")
        return W

    def apply(self, state, batch: Batch):
        """One scope per phase (``insert``, ``emit``), under the operator's own
        scope that the chain opens, and below them ``insert/rank`` and
        ``insert/fold`` (count-based windows), ``insert/lookup``,
        ``insert/fold`` and ``insert/keys`` (per-key time-based windows),
        ``emit/range``, ``emit/gather`` and ``emit/reduce`` (both, in the step
        and in the EOS flush), ``insert/hist``, ``insert/fold``,
        ``emit/gather``, ``emit/reduce`` and ``emit/clear`` (the global-time
        path, in the step and in the EOS flush), and where the combine takes
        whole structs ``insert/fold/ordered`` (time-based; ``sort``, ``scan``
        and ``place`` below it) and ``emit/reduce/ordered`` (every path): a
        profile's device operations say which part of the engine they
        belong to."""
        W = self._resolve_w(batch.capacity)
        self._w = W
        insert, emit = ((self._g_insert, self._g_emit) if self.global_time
                        else (self._insert, self._emit))
        with jax.named_scope("insert"):
            state = insert(state, batch)
        with jax.named_scope("emit"):
            return emit(state, W, flush=False)

    def flush_capacity(self, in_capacity: int) -> int:
        """``flush``'s batches are a step's: ``out_capacity``."""
        return self.out_capacity(in_capacity)

    def flush(self, state):
        """One batch of up to W open windows (W a key on the global-time path),
        None once none is left: the drivers call until None
        (``CompiledChain.flush``). A time-based window without a tuple is
        never delivered, so a pass of such windows alone is passed over, not
        taken for the end. Publishes ``windows_undelivered_at_eos``: the window
        ids still open after the call (a key's summed over the keys; one count
        for all keys on the global-time path), 0 once flushed until None."""
        W = self._w or self._resolve_w(256)
        if not hasattr(self, "_flush_jit"):
            emit = self._g_emit if self.global_time else self._emit

            def flush_emit(st):
                with jax.named_scope(self.scope_name()), \
                        jax.named_scope("emit"):
                    st, out = emit(st, W, flush=True)
                    left = jnp.sum(jnp.maximum(
                        self._due_hi(st, True) - st.next_win, 0))
                    return st, out, jnp.any(out.valid), left
            self._flush_jit = jax.jit(flush_emit)
        while True:
            state, out, any_valid, left = self._flush_jit(state)
            any_valid, left = bool(any_valid), int(left)
            if any_valid or left == 0:
                break
        self.collect_stats(state)
        self._publish_stage_counters({**self.stage_counters(),
                                      "windows_undelivered_at_eos": left})
        return state, (out if any_valid else None)

    def collect_stats(self, state=None) -> None:
        """Sync the device-resident counters into the Stats_Record and the stage
        counters (monitoring snapshot / EOS — scalar D2H reads, off the hot
        path): ``old_drops``; ``ffat_ring_overruns`` on the per-key
        time-based path and on the global-time one where the fold counts
        them (a lift that reads the tuple: the count-lift branch folds no
        value by slot and publishes none); on the per-key time-based path
        ``ffat_key_clock_spread``; ``ffat_fold_fallbacks``,
        ``ffat_fold_partials`` and ``ffat_fold_spill_lanes`` on the
        global-time path (every batch's counts take ``keyed_pane_fold``) and
        on the per-key one where the value fold rides its contraction; on
        the global-time one ``ffat_late_lanes`` where the spec allows
        lateness; ``ffat_ordered_runs`` where the combine takes structs;
        for time-based specs the fired-window budget once it is settled."""
        if state is None or not hasattr(state, "dropped_old"):
            return
        import numpy as np
        old = int(np.asarray(state.dropped_old))
        self._stats[0].tuples_dropped_old = old
        counters = {**self.stage_counters(), "old_drops": old}
        if not self.spec.is_cb:
            counters.update(self._fired_budget_gauge())
        counters.update(self._per_key_gauges())
        per_key_time = not (self.global_time or self.spec.is_cb)
        global_traced = self.global_time and self.count_lift is not None
        if per_key_time or (global_traced and not self._counts_are_values()):
            counters["ffat_ring_overruns"] = int(
                np.asarray(state.ring_overruns))
        if per_key_time:
            wm = np.asarray(state.wm)[np.asarray(state.count) > 0]
            counters["ffat_key_clock_spread"] = (
                int(wm.max()) - int(wm.min()) if wm.size else 0)
        if global_traced or self._fold_rides:
            for name in ("fold_fallbacks", "fold_partials",
                         "fold_spill_lanes"):
                counters["ffat_" + name] = int(np.asarray(getattr(state, name)))
        if getattr(state, "late_lanes", None) is not None:
            counters["ffat_late_lanes"] = int(np.asarray(state.late_lanes))
        if getattr(state, "ordered_runs", None) is not None:
            counters["ffat_ordered_runs"] = int(np.asarray(state.ordered_runs))
        self._publish_stage_counters(counters)

    def drop_counters(self, state=None) -> dict:
        if state is None or not hasattr(state, "dropped_old"):
            return {}
        import numpy as np
        return {"old_drops": int(np.asarray(state.dropped_old))}

    def event_time_stats(self, state=None):
        """Watermark-map section (TB specs): the event-time frontier, the
        fired-window horizon, arrived-but-unfired lag, OLD drops, and the
        observed-lateness histogram whose ``recommend_delay`` names the
        smallest ``delay=`` that would have kept the recorded stragglers."""
        if state is None or self.spec.is_cb:
            return None
        import numpy as np
        wm = int(np.asarray(state.wm).max())
        nxt = int(np.asarray(state.next_win).max())
        frontier = nxt * self.spec.slide
        out = {
            "watermark_ts": wm,
            "fire_frontier_ts": frontier,
            "lag": max(wm - frontier + 1, 0) if wm >= 0 else 0,
            "delay": self.spec.delay,
            "old_drops": int(np.asarray(state.dropped_old)),
        }
        counts = _et.read_hist(getattr(state, "lat_hist", None))
        if counts is not None:
            out["lateness"] = {"in": _et.summarize(counts)}
        return out


def _detect_count_lift(lift, batch) -> bool:
    """True iff ``lift`` provably returns the constant scalar 1 for every tuple:
    its jaxpr output must not depend on the input vars, and its value on a zero
    tuple must be 1. Conservative: a constant that cannot be made concrete
    (ConcretizationTypeError) is not a proof; any other error in the user's
    lift propagates."""
    import numpy as np
    dummy = TupleRef(
        key=jax.ShapeDtypeStruct((), CTRL_DTYPE),
        id=jax.ShapeDtypeStruct((), CTRL_DTYPE),
        ts=jax.ShapeDtypeStruct((), CTRL_DTYPE),
        data=jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                          batch.payload))
    from jax.extend.core import Literal
    jaxpr = jax.make_jaxpr(lift)(dummy).jaxpr
    tainted = {id(v) for v in jaxpr.invars}
    for eqn in jaxpr.eqns:
        if any(not isinstance(v, Literal) and id(v) in tainted
               for v in eqn.invars):
            tainted |= {id(v) for v in eqn.outvars}
    if any(not isinstance(v, Literal) and id(v) in tainted
           for v in jaxpr.outvars):
        return False
    zero = TupleRef(
        key=np.zeros((), np.int32), id=np.zeros((), np.int32),
        ts=np.zeros((), np.int32),
        data=jax.tree.map(lambda l: np.zeros(l.shape[1:], l.dtype),
                          batch.payload))
    try:
        # Detection runs INSIDE the chain's jit trace, where every jnp op —
        # even a constant like jnp.ones(()) — returns a tracer of the ambient
        # trace; compile-time eval makes the constant concrete. (Without it
        # the windowed-count chain took the serialized segment-sum path for
        # a whole round unnoticed; tests/test_ysb.py pins it.)
        with jax.ensure_compile_time_eval():
            out = jax.tree.leaves(lift(zero))
            return (len(out) == 1 and np.shape(out[0]) == ()
                    and float(out[0]) == 1.0)
    except jax.errors.ConcretizationTypeError:
        # the constant is built from a value of an enclosing trace (a lift
        # closing over a traced array): not provably 1
        return False


def _b(mask, v):
    return mask.reshape(mask.shape + (1,) * (v.ndim - mask.ndim))


def _rotate_rows(tbl, shift):
    """Each row ``k`` of ``tbl`` ``[K, P]`` turned right by ``shift[k]`` (in
    ``[0, P)``): ``out[k, s] = tbl[k, (s - shift[k]) % P]``. A barrel shift,
    one static ``jnp.roll`` a bit of the shift, kept where the key's bit is
    set: elementwise, so exact in any dtype, where a take of single elements
    would be serialized (``ELEMENT_TAKE_NS``)."""
    P = tbl.shape[1]
    for b in range((P - 1).bit_length()):
        on = ((shift >> b) & 1) == 1
        tbl = jnp.where(on[:, None], jnp.roll(tbl, 1 << b, axis=1), tbl)
    return tbl


def _key_max(key, ts, valid, K):
    """Each key's largest ``ts`` over the ``valid`` lanes, -1 for a key with
    none: a select-reduce over the ``[C, K]`` key one-hot, ``table_lookup``'s
    form with the reduction over the lanes. The TPU compiler fuses the
    compare, the select and the reduction into one elementwise pass, where a
    ``segment_max`` over the lanes is serialized lane by lane."""
    hit = (key[:, None] == jnp.arange(K, dtype=key.dtype)) & valid[:, None]
    return jnp.max(jnp.where(hit, ts[:, None], -1), axis=0)


def _next_pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p
