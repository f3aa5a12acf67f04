"""Win_Seq — THE sequential window engine, vectorized.

Counterpart of ``wf/win_seq.hpp:56-567`` (svc ``:304-465``, EOS flush ``:468-529``)
with ``StreamArchive`` (``wf/stream_archive.hpp``) fused in: per-key archives live as
HBM ring buffers ``[K, A]``; each micro-batch (1) sorts its tuples by key once and
moves them into the rings as whole rows of ``run_len`` slots (``_insert``: no scatter
or gather with an index per lane), (2) advances per-key counts/watermarks from the
sorted order's key boundaries, (3) computes the FIRED window range per key
with batch-level triggerer arithmetic (``window.py``), (4) gathers up to ``max_wins``
fired windows as rows ``[W, L]`` and (5) applies the user window function across the
window axis with ``vmap`` — the direct TPU generalization of the reference GPU engine's
one-thread-per-window ``ComputeBatch_Kernel`` (``wf/win_seq_gpu.hpp:57-82,352-560``),
with the whole archive resident on device (no H2D flattening step at all).

User function flavours (``wf/meta.hpp`` window families):
- non-incremental: ``f(wid, iterable) -> result_payload`` over an :class:`Iterable`;
- incremental (fold): ``f(wid, t, acc) -> acc`` via ``lax.scan`` across the window axis
  (``winupdate_func`` semantics, ``wf/win_seq.hpp:389-397``).

CB windows index per-key *arrival position* (the reference's TS_RENUMBERING-style
progressive ids, ``wf/basic.hpp:129``); TB windows index timestamps with per-key
watermarks and ``delay`` lateness. Windows whose turn exceeds the per-batch ``max_wins``
budget defer to the next batch (``next_win`` only advances past emitted windows).

Budgets. A time-based ring holds, per key, the tuples of the open windows plus one
batch's: how many that is depends on the stream's rate and key spread, which the
engine cannot see, so a deployment passes ``tb_capacity`` (slots per key) and
``max_wins`` (fired windows a batch); the default ``2 * batch`` slots per key is the
worst case of a whole batch on one key. A pattern that feeds the engine its own results
does know their spacing and says so (``ts_stride``: ``Pane_Farm``'s WLQ gets one pane
result a pane at most), and the defaults then follow the batch as the count-based ones
do. A ring too small overwrites tuples that an
unfired window still needs: the state counts them (``overwrites``), and the OLD drops
(``dropped_old``), and ``collect_stats`` publishes both with the two budgets
(``archive_overwrites``, ``old_drops``, ``archive_slots``, ``fired_window_budget``);
``flush`` adds ``windows_undelivered_at_eos``. The insert's row geometry follows the
shapes (``_row_geometry``: batch capacity, keys, ring slots) and is published with
them: ``archive_run_len``, ``archive_run_rows``, ``archive_run_groups`` (the gathers
of the sorted columns a pass issues, one slice a row each), ``owner_compare_cells``
(how the listed rows and the fired windows find their key: ``enumerate_runs``), and
``archive_runs_written`` counts the rows the inserts wrote per table (beside the
tuples they archived).

Emission order is per-key ascending window id — the ordered-collector guarantee of
``WF_Collector`` (``wf/wf_nodes.hpp:253-318``) by construction.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t, role_t, DEFAULT_MAX_KEYS
from ..batch import Batch, CTRL_DTYPE, TupleRef
from ..meta import classify_window, classify_winupdate
from ..ops.lookup import table_lookup
from ..ops.segment import (SLICE_GBPS, SLICE_US, enumerate_runs,
                           owner_compare_cells, range_max, sort_segments,
                           take_windows, window_groups)
from .base import Basic_Operator
from .window import Iterable, WindowSpec


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WinSeqState:
    arch_payload: Any     # pytree [K, A, ...]
    arch_id: jax.Array    # i32[K, A] global tuple id of each slot
    arch_ts: jax.Array    # i32[K, A]
    arch_pos: jax.Array   # i32[K, A] arrival position held by slot (-1 = empty)
    count: jax.Array      # i32[K] tuples archived per key
    wm: jax.Array         # i32[K] per-key max ts seen
    next_win: jax.Array   # i32[K] next window id to fire
    overwrites: jax.Array   # i32[] live slots (an unfired window's) overwritten
    dropped_old: jax.Array  # i32[] TB tuples dropped as OLD (behind the horizon)
    runs_written: jax.Array  # i32[] ring rows the inserts wrote, per table


class Win_Seq(Basic_Operator):
    routing = routing_modes_t.KEYBY

    def __init__(self, win_fn: Callable, spec: WindowSpec, *,
                 incremental: Optional[bool] = None, init_acc: Any = None,
                 num_keys: int = DEFAULT_MAX_KEYS, archive_capacity: int = None,
                 max_wins: int = None, tb_capacity: int = None,
                 ts_stride: int = None,
                 name: str = "win_seq", parallelism: int = 1,
                 role: role_t = role_t.SEQ, context=None):
        super().__init__(name, parallelism)
        self.win_fn = win_fn
        self.spec = spec
        if incremental is None:
            # flavour deduced from the callable, like the reference's static
            # dispatch between Iterable and winupdate signatures (wf/meta.hpp
            # window families; catalogue /root/reference/API KEY_FARM/WIN_FARM)
            from ..meta import classify_window_flavour
            incremental, self.is_rich = classify_window_flavour(win_fn)
        elif incremental:
            self.is_rich = classify_winupdate(win_fn)
        else:
            self.is_rich = classify_window(win_fn)
        self.incremental = incremental
        self.init_acc = init_acc
        if incremental and init_acc is None:
            from ..meta import RICH_PARAM_NAMES
            raise ValueError(
                f"{name}: incremental window function f(wid, t, acc) -> acc "
                f"requires init_acc. (If this callable is actually a rich "
                f"NON-incremental f(wid, iterable, ctx), name its context "
                f"parameter one of {RICH_PARAM_NAMES} or pass incremental=False "
                f"— 3-positional-arg flavours are separated by the trailing "
                f"parameter's name.)")
        from ..context import RuntimeContext
        self.context = context or RuntimeContext(parallelism, 0)
        # resolve the rich flavour once: downstream code always calls self._fn
        # with the plain arity (wf/meta.hpp rich variants bind RuntimeContext)
        if self.is_rich and incremental:
            self._fn = lambda w, t, a: win_fn(w, t, a, self.context)
        elif self.is_rich:
            self._fn = lambda w, it: win_fn(w, it, self.context)
        else:
            self._fn = win_fn
        self.num_keys = int(num_keys)
        self.role = role
        self._archive_capacity = archive_capacity
        self._tb_capacity = tb_capacity
        #: time-based only: a key's tuples are known to lie at least this many
        #: ticks apart (a pattern's pane results, one a pane). A window then
        #: holds ``win_len // ts_stride`` lanes and a slide ``slide //
        #: ts_stride``, and the default budgets follow the batch as the
        #: count-based ones do, not the ``2 * batch`` of an unknown rate.
        self._ts_stride = ts_stride
        self.A = None                  # resolved in bind_geometry
        self.max_wins = max_wins       # resolved at first apply if None
        self._w = None
        self._run_groups = None        # settled by the first _insert
        self._wshard = None            # (mesh, axis): shard the fired-window W axis
        #: the operator whose ``Class:name`` scope the chain opens around this
        #: engine's ``apply``: itself, or the pattern that owns it; and the
        #: scopes that pattern opens between its own and the engine's phases
        #: (``Pane_Farm``: ``plq`` or ``wlq``)
        self.scope_op = self
        self.scope_stage = ()
        self.bind_geometry(256)        # provisional; compiler re-binds with real C

    def bind_geometry(self, batch_capacity: int) -> None:
        L = self.spec.win_len
        if self._archive_capacity is not None:
            self.A = _next_pow2(self._archive_capacity)
        elif self.spec.is_cb:
            # ring must survive one whole batch landing on a single key before the
            # fire phase runs, plus the open-window span
            self.A = _next_pow2(L + batch_capacity)
        elif self._tb_capacity is None and self._ts_stride:
            self.A = _next_pow2(L // self._ts_stride + batch_capacity)
        else:
            self.A = _next_pow2(self._tb_capacity or 2 * batch_capacity)
        self.run_len, self.run_rows = self._row_geometry(batch_capacity)
        self._publish_stage_counters({**self.stage_counters(),
                                      **self._budget_gauges()})

    def _row_geometry(self, capacity: int):
        """How ``_insert`` cuts a batch of ``capacity`` lanes: ``(T, rows)``.
        The rings move as rows of ``T`` slots, the power of two up to ``A``
        at which a batch's two passes cost least (the longer where two cost
        the same). A pass pays by the row, a head row a key and the ``rows``
        that bound the runs after a key's first (each key has at most ``n //
        T + 1`` of them, and fewer than ``n``): one ``take_windows`` slice
        (``SLICE_US``, whatever it holds) and the row's ``T`` lanes of id, ts,
        position and a payload word at ``SLICE_GBPS``. A small ring so moves
        as whole rings (``T = A``: 64 slots cost what 8 do), a large one in
        rows about as long as a slice's price buys."""
        K = self.num_keys

        def rows(T):
            return min(capacity, capacity // T + min(K, capacity))

        def cost_us(T):
            return (K + rows(T)) * (SLICE_US + T * 16 / (SLICE_GBPS * 1e3))

        T = min((1 << e for e in range(self.A.bit_length())),
                key=lambda T: (cost_us(T), -T))
        return T, rows(T)

    # ------------------------------------------------------------------ state

    def init_state(self, payload_spec: Any):
        K, A = self.num_keys, self.A
        def mk(s):
            return jnp.zeros((K, A) + tuple(s.shape), s.dtype)
        return WinSeqState(
            arch_payload=jax.tree.map(mk, payload_spec),
            arch_id=jnp.zeros((K, A), CTRL_DTYPE),
            arch_ts=jnp.zeros((K, A), CTRL_DTYPE),
            arch_pos=jnp.full((K, A), -1, CTRL_DTYPE),
            count=jnp.zeros((K,), CTRL_DTYPE),
            wm=jnp.full((K,), -1, CTRL_DTYPE),
            next_win=jnp.zeros((K,), CTRL_DTYPE),
            overwrites=jnp.zeros((), CTRL_DTYPE),
            dropped_old=jnp.zeros((), CTRL_DTYPE),
            runs_written=jnp.zeros((), CTRL_DTYPE),
        )

    def out_spec(self, payload_spec: Any) -> Any:
        L = self.spec.win_len if self.spec.is_cb else self.A
        it = Iterable(
            data=jax.tree.map(lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype),
                              payload_spec),
            ids=jax.ShapeDtypeStruct((L,), CTRL_DTYPE),
            ts=jax.ShapeDtypeStruct((L,), CTRL_DTYPE),
            mask=jax.ShapeDtypeStruct((L,), jnp.bool_),
        )
        wid = jax.ShapeDtypeStruct((), CTRL_DTYPE)
        if not self.incremental:
            return jax.eval_shape(self._fn, wid, it)
        t = TupleRef(key=wid, id=wid, ts=wid,
                     data=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                                       payload_spec))
        acc = jax.eval_shape(lambda: jax.tree.map(jnp.asarray, self.init_acc))
        return jax.eval_shape(self._fn, wid, t, acc)

    # ------------------------------------------------------------------ insert

    def _insert(self, state: WinSeqState, batch: Batch) -> WinSeqState:
        """Archive a batch in the order of one stable sort by key.

        A key's live lanes of one batch take consecutive arrival positions,
        ``count[key]`` onward, so in sorted order they are one contiguous lane
        range that fills consecutive ring slots. Cut the positions every
        ``T = run_len`` slots: each (key, chunk) run is then one aligned row of
        the ring viewed as ``[K * A / T, T]`` and one ``T``-wide window of the
        sorted column. Per table the touched rows are read, each slot takes its
        lane where "the slot's position is written by this batch" holds, and
        the rows go back: R- and K-sized index arrays, never one entry a lane
        (on one v5e a 1 M-lane scatter costs 4.8-9.2 ms, the sort of the same
        lanes about 2: PERF.md section 6, PR 26 and 28).

        Only the last ``A`` lanes of a key are written (``overwrites`` counts
        the rest), so the written positions ``[begin, end)`` cover at most
        ``A / T + 1`` chunks. The chunks after the first are distinct ring rows
        (the body, ``enumerate_runs``); the first may share its row with the
        last (and always does where ``T = A``: a key's ring is one row), so it
        is written in a pass of its own (the head, one row a key): no pass
        holds a ring row twice, and the two write disjoint slots. A pass reads
        each row's window of the sorted columns once for all the columns that
        can share a buffer (``take_windows``; ``archive_run_groups`` such
        gathers a pass, 1 where payload, id and ts are all 32-bit columns),
        because what a window costs there does not depend on what it holds."""
        K, A = self.num_keys, self.A
        T, body_rows = self._row_geometry(batch.capacity)
        per_key = A // T                                 # ring rows a key
        valid = batch.valid
        dropped_old = state.dropped_old
        with jax.named_scope("rank"):
            if not self.spec.is_cb:
                # drop OLD tuples: they precede the purge horizon (already-fired
                # windows)
                horizon = table_lookup(state.next_win, batch.key) * self.spec.slide
                fresh = valid & (batch.ts >= horizon)
                dropped_old = dropped_old + jnp.sum(valid & ~fresh, dtype=CTRL_DTYPE)
                valid = fresh
            with jax.named_scope("sort"):
                columns, first, n = sort_segments(
                    (batch.payload, batch.id, batch.ts), batch.key, valid, K)
            with jax.named_scope("runs"):
                count = state.count + n
                begin = jnp.maximum(state.count, count - A)
                head = begin // T
                n_body = jnp.where(n > 0, (count - 1) // T - head, 0)
                body_key, body_i, body_live = enumerate_runs(n_body, body_rows)
                passes = (
                    (jnp.arange(K, dtype=CTRL_DTYPE), head, n > 0),
                    (body_key, jnp.take(head, body_key) + 1 + body_i, body_live))
                # a window may start up to T - 1 lanes before a key's first
                # lane and end as many after its last: pad, do not clamp
                padded = jax.tree.map(
                    lambda c: jnp.pad(c, [(T, T)] + [(0, 0)] * (c.ndim - 1)),
                    columns)
                lane0 = first - state.count + T          # padded lane of position 0

        with jax.named_scope("count"):
            # the per-key watermark is over all n lanes, the overwritten ones too
            ts_max = range_max(columns[2], first, n, -1)
            # this batch writes over every slot that holds a position below
            # count - A. One that an unfired window still needs (at or past the
            # purge horizon, by position for CB and by ts for TB) is a lost tuple,
            # and so is a tuple of this batch that a later one of it overwrites:
            # K*A- and K-sized work, no per-lane read of the ring.
            stamp = state.arch_pos if self.spec.is_cb else state.arch_ts
            lost = ((state.arch_pos >= 0)
                    & (stamp >= (state.next_win * self.spec.slide)[:, None])
                    & (state.arch_pos < (count - A)[:, None]))
            overwrites = (state.overwrites + jnp.sum(lost, dtype=CTRL_DTYPE)
                          + jnp.sum(jnp.maximum(n - A, 0)))
            runs_written = (state.runs_written
                            + jnp.sum(n > 0, dtype=CTRL_DTYPE)
                            + jnp.sum(n_body, dtype=CTRL_DTYPE))

        def fill(tables, key, chunk, live):
            """One pass: the rows (key, chunk) of every table, read, filled
            with the batch's lanes and written back."""
            row = jnp.where(live, key * per_key + chunk % per_key, K * per_key)
            pos = chunk[:, None] * T + jnp.arange(T, dtype=CTRL_DTYPE)[None, :]
            written = (live[:, None] & (pos >= jnp.take(begin, key)[:, None])
                       & (pos < jnp.take(count, key)[:, None]))
            lane = jnp.take(lane0, key) + chunk * T

            def put(tbl, new):
                ring = tbl.reshape((K * per_key, T) + tbl.shape[2:])
                rows = jnp.where(
                    written.reshape(written.shape + (1,) * (new.ndim - 2)),
                    new, jnp.take(ring, row, axis=0, mode="clip"))
                return ring.at[row].set(rows, mode="drop").reshape(tbl.shape)

            return (*jax.tree.map(put, tables[:3],
                                  take_windows(padded, lane, T)),
                    put(tables[3], pos))

        with jax.named_scope("write"):
            self._run_groups = len(window_groups(jax.tree.leaves(padded)))
            tables = (state.arch_payload, state.arch_id, state.arch_ts,
                      state.arch_pos)
            for key, chunk, live in passes:
                tables = fill(tables, key, chunk, live)
            arch_payload, arch_id, arch_ts, arch_pos = tables
            return dataclasses.replace(
                state,
                arch_payload=arch_payload,
                arch_id=arch_id,
                arch_ts=arch_ts,
                arch_pos=arch_pos,
                count=count,
                wm=jnp.maximum(state.wm, ts_max),
                overwrites=overwrites,
                dropped_old=dropped_old,
                runs_written=runs_written,
            )

    # ------------------------------------------------------------------ fire

    def _resolve_w(self, capacity: int) -> int:
        if self.max_wins is not None:
            return self.max_wins
        slide = self.spec.slide
        if not self.spec.is_cb and self._ts_stride:
            slide = max(1, slide // self._ts_stride)      # in lanes, as CB's
        W = max(16, -(-capacity // slide) + 64)
        L = self.spec.win_len if self.spec.is_cb else self.A
        if W * L > (1 << 22):
            # adversarial slide (e.g. slide=1 at large batch) would imply a [W, L]
            # gather per batch per payload leaf — force an explicit budget instead
            # of silently allocating it (the reference sizes this with batch_len,
            # wf/win_seq_gpu.hpp tuples_per_batch)
            raise ValueError(
                f"{self.name}: default fired-window budget W={W} with window row "
                f"length L={L} implies a [{W}, {L}] gather per batch "
                f"({W * L} elements per payload leaf); pass max_wins= to bound the "
                f"per-batch fired-window budget")
        return W

    def set_window_sharding(self, mesh, axis: str) -> None:
        """Cross-chip window parallelism (Win_Farm's distribution,
        ``wf/wf_nodes.hpp:157-204`` / ``wf/win_farm.hpp:165-175``): partition the
        fired-window [W] axis over mesh axis ``axis``. The archive stays replicated
        (every chip sees every tuple — the WF_Emitter multicast as a sharding rule);
        each chip gathers and computes only its W/p window rows."""
        self._wshard = (mesh, axis)

    def _wsc(self, a):
        """Constrain the leading (window) axis of ``a`` to the window mesh axis."""
        if self._wshard is None:
            return a
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, axis = self._wshard
        spec = P(axis, *([None] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    def _fired_range(self, state: WinSeqState, flush: bool):
        s = self.spec
        if s.is_cb:
            hi = s.flush_hi_cb(state.count) if flush else s.fired_hi_cb(state.count)
        else:
            hi = (s.flush_hi_tb(state.wm, state.count > 0) if flush
                  else s.fired_hi_tb(state.wm))
        return state.next_win, jnp.maximum(hi, state.next_win)

    def _emit(self, state: WinSeqState, W: int, flush: bool):
        """Emit up to W fired windows (per-key ascending wid). Returns (state, Batch)."""
        K, A = self.num_keys, self.A
        s = self.spec
        with jax.named_scope("range"):
            lo, hi = self._fired_range(state, flush)
            n_f = hi - lo
            k_safe, i_of, valid_w = map(self._wsc, enumerate_runs(n_f, W))
            # what a fired window reads of its key's K-sized tables comes by
            # one select-reduce over both (exact: one term a sum), not by two
            # gathers of an element a window (0.067 ms each at 8,704 windows).
            # Stacked, because as two lookups they cost a ring table its place
            # in the chip's fast memory and a row gather below 0.27 ms more
            # (PERF.md section 6, PR 37)
            at_key = table_lookup(jnp.stack([lo, state.count], axis=1), k_safe)
            wid = self._wsc(at_key[:, 0] + i_of)
            count_w = at_key[:, 1:]

            # advance next_win past emitted windows: the first W of the list
            csum = jnp.cumsum(n_f)
            new_next = lo + jnp.clip(W - (csum - n_f), 0, n_f)

        with jax.named_scope("gather"):
            if s.is_cb:
                L = s.win_len
                p = wid[:, None] * s.slide + jnp.arange(L, dtype=CTRL_DTYPE)[None, :]
                slot = p % A
                gflat = k_safe[:, None] * A + slot                         # [W, L]
                def gat(tbl):
                    return jnp.take(tbl.reshape((K * A,) + tbl.shape[2:]), gflat, axis=0)
                content_mask = (p < count_w) & valid_w[:, None]
                # stale-slot guard: the slot must actually hold position p
                content_mask &= gat(state.arch_pos) == p
                data = jax.tree.map(gat, state.arch_payload)
                ids, tss = gat(state.arch_id), gat(state.arch_ts)
                res_ts = jnp.max(jnp.where(content_mask, tss, -1), axis=1)
            else:
                # TB: full-ring rows masked by ts-in-range
                def gat(tbl):
                    return jnp.take(tbl, k_safe, axis=0)                   # [W, A, ...]
                tss = gat(state.arch_ts)
                poss = gat(state.arch_pos)
                # (the window's end may lie past int32's: compare the offset)
                w_start = (wid * s.slide)[:, None]
                content_mask = ((poss >= 0) & (tss >= w_start)
                                & (tss - w_start < s.win_len) & valid_w[:, None])
                # ring-overwrite guard: slot must hold a live (not yet overwritten) pos
                content_mask &= poss >= jnp.maximum(0, count_w - A)
                data = jax.tree.map(gat, state.arch_payload)
                ids = gat(state.arch_id)
                # the window's last tick, or int32's last where it ends later
                res_ts = (jnp.minimum(wid * s.slide, _TS_MAX - (s.win_len - 1))
                          + (s.win_len - 1))

            if not s.is_cb:
                # TB: a window with no content never fires in the reference (Triggerer_TB
                # only triggers on tuples); filter empty windows from the emission
                valid_w = valid_w & jnp.any(content_mask, axis=1)

        it = Iterable(data=jax.tree.map(self._wsc, data), ids=self._wsc(ids),
                      ts=self._wsc(tss), mask=self._wsc(content_mask))
        if self.incremental:
            results = _fold_windows(self._fn, wid, it, self.init_acc)
        else:
            results = jax.vmap(self._fn)(wid, it)

        out = Batch(key=k_safe, id=wid,
                    ts=self._wsc(res_ts if s.is_cb
                                 else jnp.asarray(res_ts, CTRL_DTYPE)),
                    payload=jax.tree.map(self._wsc, results), valid=valid_w)
        return dataclasses.replace(state, next_win=new_next), out

    # ------------------------------------------------------------------ operator API

    def out_capacity(self, in_capacity: int) -> int:
        return self._resolve_w(in_capacity)

    def apply(self, state: WinSeqState, batch: Batch):
        """One scope per phase, as ``Win_SeqFFAT.apply`` has them: ``insert``
        (``rank`` with ``sort`` and ``runs``, ``count``, ``write``) and ``emit``
        (``range``, ``gather``, then the window function), directly under the
        scope the chain opened for the operator (a pattern built on this engine
        opens none for it)."""
        W = self._resolve_w(batch.capacity)
        self._w = W
        with jax.named_scope("insert"):
            state = self._insert(state, batch)
        with jax.named_scope("emit"):
            return self._emit(state, W, flush=False)

    def flush(self, state: WinSeqState):
        """One batch of up to W open windows, None once none is left: the drivers
        call until None (``CompiledChain.flush``). Windows a TB key skipped are
        empty and never delivered, so a batch of them alone is passed over, not
        taken for the end."""
        W = self._w or self._resolve_w(256)
        if not hasattr(self, "_flush_jit"):
            def flush_emit(st):
                with contextlib.ExitStack() as scopes:
                    for scope in (self.scope_op.scope_name(),
                                  *self.scope_stage, "emit"):
                        scopes.enter_context(jax.named_scope(scope))
                    st, out = self._emit(st, W, flush=True)
                    lo, hi = self._fired_range(st, True)
                    return st, out, jnp.any(out.valid), jnp.sum(hi - lo)
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

    def _budget_gauges(self) -> dict:
        """The static budgets: ring slots per key, the insert's row length and
        the rows one batch may write per table (a head row a key and the
        listed ones), the ``take_windows`` gathers a pass issues (each of
        them one slice a row; once the first ``_insert`` has seen the
        payload), fired windows a batch (once ``max_wins`` or the first
        ``apply`` has settled it) and with them the rows x keys cells a step
        compares to find the key of every listed ring row and fired window
        (``enumerate_runs``; 0 where both lists kept the binary search)."""
        W = self.max_wins if self.max_wins is not None else self._w
        K = self.num_keys
        return {"archive_slots": self.A, "archive_run_len": self.run_len,
                "archive_run_rows": K + self.run_rows,
                **({} if self._run_groups is None
                   else {"archive_run_groups": self._run_groups}),
                **({} if W is None else {
                    "fired_window_budget": W,
                    "owner_compare_cells": owner_compare_cells(W, K)
                    + owner_compare_cells(self.run_rows, K)})}

    def collect_stats(self, state=None) -> None:
        """Sync the device-resident counters into the stage counters (monitoring
        snapshot / EOS: three scalar D2H reads, off the hot path)."""
        if state is None or not hasattr(state, "overwrites"):
            return
        import numpy as np
        old = int(np.asarray(state.dropped_old))
        self._stats[0].tuples_dropped_old = old
        self._publish_stage_counters({
            **self.stage_counters(), **self._budget_gauges(),
            "archive_overwrites": int(np.asarray(state.overwrites)),
            "archive_runs_written": int(np.asarray(state.runs_written)),
            "old_drops": old})

    def drop_counters(self, state=None) -> dict:
        if state is None or not hasattr(state, "dropped_old"):
            return {}
        import numpy as np
        return {"old_drops": int(np.asarray(state.dropped_old))}


def _fold_windows(fn, wids, it: Iterable, init_acc):
    """Incremental path: lax.scan the user fold over the window axis, vmapped over
    windows. Absent slots (mask False) skip the fold (wf/win_seq.hpp:389-397)."""
    def one(wid, data, ids, ts, mask):
        acc0 = jax.tree.map(jnp.asarray, init_acc)

        def step(acc, x):
            d, i, t, m = x
            tref = TupleRef(key=wid, id=i, ts=t, data=d)
            new = fn(wid, tref, acc)
            acc = jax.tree.map(lambda a, n: jnp.where(m, n, a), acc, new)
            return acc, None

        acc, _ = jax.lax.scan(step, acc0, (data, ids, ts, mask))
        return acc

    return jax.vmap(one)(wids, it.data, it.ids, it.ts, it.mask)


_TS_MAX = int(jnp.iinfo(CTRL_DTYPE).max)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
