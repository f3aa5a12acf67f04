"""Session windows — data-dependent-gap firing (``WindowSpec.session``).

The third firing family beside the CB/TB triggerer lattice of
``operators/window.py``: a per-key session stays open while consecutive
events arrive within ``gap`` of each other and FIRES when the gap is
exceeded — the firing bound is a function of the *observed inter-arrival
gaps* (:meth:`WindowSpec.fired_session`), so there is no static window-id
grid to enumerate. The batched formulation keeps everything one fixed-shape
device program, masked like the TB path:

1. lanes sort by ``(key, ts, id)`` (one fused multi-operand ``lax.sort``);
2. in-batch session *fragments* fall out of a vectorized gap/boundary scan
   (``first | gap-break`` flags -> dense fragment ids -> ``segment_reduce``
   per-fragment aggregates in event-time order);
3. each key's first fragment merges with its carried open session where the
   gap chains; every non-final fragment is closed by in-batch evidence (a
   successor fragment *is* the observed gap);
4. each key's final fragment becomes/extends the carried open session; the
   ``fired_session`` triggerer then closes carried sessions the watermark
   has proven complete (``wm - delay > last + gap``) — evaluated over the
   whole ``[K]`` open-session table at once.

Ordering contract: arrival is assumed **event-time ordered per key**
(cross-key skew is fine — that is what the ``delay`` lateness allowance and
the watermark triggerer absorb; within one batch, intra-key disorder is
fully repaired by the sort). An in-batch successor fragment beyond the gap
is therefore *proof* the predecessor session ended, and closes it
immediately regardless of ``delay`` — keeping exactly ONE open session per
key in the ``[K]`` state. The cost of that bound: an intra-key straggler
that violates the contract *across batches* (its session already closed)
is OLD and dropped on device — the ``Win_SeqFFAT`` straggler convention,
surfaced through the same ``tuples_dropped_old`` stats field. Emission rows carry ``(key, session
ordinal, end ts)`` control fields and payload ``{"agg", "start", "end",
"n"}``. State is a plain pytree — checkpoint/restore and supervised replay
carry it unchanged.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t, DEFAULT_MAX_KEYS
from ..batch import Batch, CTRL_DTYPE, TupleRef, tuple_refs
from ..observability import event_time as _et
from ..ops.lookup import table_lookup
from ..ops.segment import leafwise, segment_reduce
from .base import Basic_Operator
from .window import WindowSpec

_IMIN = -(1 << 31)
_IMAX = (1 << 31) - 1


def _ref_spec(payload_spec):
    s = jax.ShapeDtypeStruct((), CTRL_DTYPE)
    return TupleRef(key=s, id=s, ts=s, data=payload_spec)


class SessionWindow(Basic_Operator):
    """Per-key session aggregation under a :meth:`WindowSpec.session` spec.

    ``value_fn(t) -> pytree`` extracts the per-event contribution;
    ``combine`` folds contributions in event-time order (associative;
    default add). One output row per CLOSED session: ``key`` = key slot,
    ``id`` = per-key session ordinal, ``ts`` = session end, payload
    ``{"agg": <folded pytree>, "start", "end", "n"}``.

    Requires per-key event-time-ordered arrival (see the module docstring):
    ``spec.delay`` buys lateness against *cross-key* watermark skew; an
    intra-key straggler landing after its session closed drops as OLD."""

    routing = routing_modes_t.KEYBY

    def __init__(self, value_fn: Callable, spec: WindowSpec, *,
                 combine: Callable = None, identity: Any = 0,
                 num_keys: int = DEFAULT_MAX_KEYS, tiered=None,
                 name: str = "session", parallelism: int = 1):
        super().__init__(name, parallelism)
        if not spec.is_session:
            raise ValueError(
                f"{name}: SessionWindow needs a session spec — build it with "
                f"WindowSpec.session(gap, delay), got {spec.wtype}")
        self.value_fn = value_fn
        self.spec = spec
        self.combine = combine
        self.identity = identity
        self.num_keys = int(num_keys)
        self._cap: Optional[int] = None
        self._old_synced = 0
        self._closed_synced = 0
        # tiered keyed state: a key -> hot-slot directory in front of the
        # direct-indexed session table. OPEN sessions are PINNED hot (they
        # must fire through the in-graph triggerer); only closed keys'
        # floors/ordinals spill, and the watermark retires floors the
        # lateness contract proves can never flag an OLD again
        from ..state import TierConfig
        self._tier_cfg = TierConfig.resolve(tiered)
        self._tier = None
        self._slots = (int(self._tier_cfg.hot_capacity or num_keys)
                       if self._tier_cfg is not None else self.num_keys)

    # -- geometry / specs -------------------------------------------------

    def bind_geometry(self, batch_capacity: int) -> None:
        self._cap = int(batch_capacity)

    def out_capacity(self, in_capacity: int) -> int:
        # in-batch evidence closes (<= 2 row groups of C) + watermark closes
        return 2 * in_capacity + self._slots

    def _val_spec(self, payload_spec):
        return jax.eval_shape(self.value_fn, _ref_spec(payload_spec))

    def out_spec(self, payload_spec: Any) -> Any:
        i = jax.ShapeDtypeStruct((), CTRL_DTYPE)
        return {"agg": self._val_spec(payload_spec),
                "start": i, "end": i, "n": i}

    def init_state(self, payload_spec: Any):
        K = self._slots
        vspec = self._val_spec(payload_spec)
        acc = jax.tree.map(
            lambda s: jnp.zeros((K,) + tuple(s.shape), s.dtype), vspec)
        z = lambda fill=0: jnp.full((K,), fill, jnp.int32)
        state = {"open": jnp.zeros((K,), jnp.bool_),
                 "start": z(), "last": z(), "cnt": z(), "sid": z(),
                 "acc": acc, "floor": z(_IMIN),
                 "wm": jnp.asarray(_IMIN, jnp.int32),
                 "closed": jnp.asarray(0, jnp.int32),
                 "old": jnp.asarray(0, jnp.int32),
                 "eos": jnp.asarray(0, jnp.int32)}
        if self._tier_cfg is not None:
            from ..state.tiered import SlotTableTier, slot_directory_init
            cap = self._cap or DEFAULT_MAX_KEYS
            self._hot_target = max(1, K - min(cap, K - 1))
            outbox = int(self._tier_cfg.outbox or 4 * cap)
            state.update(slot_directory_init(K, outbox, {
                "ofloor": lambda s: jnp.full((s,), _IMIN, jnp.int32),
                "osid": lambda s: jnp.zeros((s,), jnp.int32)}))
            state["ovf"] = jnp.asarray(0, jnp.int32)
            gap, delay = self.spec.gap, self.spec.delay
            self._tier = SlotTableTier(
                self.name,
                {"floor": (jnp.int32, ()), "sid": (jnp.int32, ())},
                self._tier_cfg, count_key="ocnt",
                col_keys=["okey", "otick", "ofloor", "osid"],
                state_to_store=lambda n, host: (
                    host["okey"], host["otick"],
                    {"floor": host["ofloor"], "sid": host["osid"]}),
                # retire floors once no admissible arrival can be OLD:
                # floor + gap < wm - delay  =>  ts > floor + gap for every
                # future tuple the lateness contract admits
                compact_col="floor",
                compact_bound=lambda wm: wm - delay - gap,
                wm_key="wm")
        if self._event_time:
            # observed-lateness histogram (event-time monitoring only —
            # absent otherwise, so the off program is unchanged)
            state["lat_hist"] = _et.lateness_init()
        return state

    def tier_controllers(self):
        return (self._tier.controller,) if self._tier is not None else ()

    # -- the batched session step -----------------------------------------

    def _fold(self, a, b):
        fn = self.combine or jnp.add
        return jax.tree.map(fn, a, b)

    def apply(self, state, batch: Batch):
        if self._tier is None:
            return self._apply_core(state, batch)
        from ..ops.lookup import count_drops
        from ..state.tiered import slot_directory_evict, \
            slot_directory_resolve
        K = self._slots
        state, slot, live = slot_directory_resolve(
            state, batch.key, batch.valid, self._tier.lookup_cb,
            self._host_shapes, self._admit_write)
        # a lane whose key found no hot slot (directory saturated with
        # OPEN sessions) drops, counted — the untiered table would have
        # silently mangled any key >= num_keys
        state = dict(state, ovf=count_drops(
            state["ovf"], "overflow_drops",
            jnp.sum((batch.valid & ~live).astype(jnp.int32))))
        b2 = batch.replace(key=jnp.where(live, slot, 0), valid=live)
        state, out = self._apply_core(state, b2)
        out = out.replace(key=jnp.where(
            out.valid, jnp.take(state["hkey"],
                                jnp.clip(out.key, 0, K - 1)), out.key))
        # OPEN sessions are pinned hot — only closed keys' floors spill;
        # floors with nothing to remember free without outbox space
        state = slot_directory_evict(
            state, self._hot_target,
            evictable=~state["open"],
            discardable=state["floor"] == _IMIN,
            pack_write=self._pack_write)
        return state, out

    def _host_shapes(self, r):
        return [jax.ShapeDtypeStruct((r,), jnp.bool_),
                jax.ShapeDtypeStruct((r,), jnp.int32),
                jax.ShapeDtypeStruct((r,), jnp.int32)]

    def _admit_write(self, out, widx, got, in_ob, oidx, host_res):
        """Write admitted keys' carried fields: the cold (floor, session
        ordinal) pair — outbox beats host — or the fresh (_IMIN, 0)."""
        _found, h_floor, h_sid = host_res
        cold = in_ob | _found
        floor = jnp.where(in_ob, jnp.take(out["ofloor"], oidx), h_floor)
        sid = jnp.where(in_ob, jnp.take(out["osid"], oidx), h_sid)
        floor = jnp.where(cold, floor, _IMIN)
        sid = jnp.where(cold, sid, 0)
        out["floor"] = out["floor"].at[widx].set(floor, mode="drop")
        out["sid"] = out["sid"].at[widx].set(sid, mode="drop")
        # an admitted slot starts closed (stale open slots are never
        # evicted, so open is already False here by construction)
        out["open"] = out["open"].at[widx].set(False, mode="drop")
        return out

    def _pack_write(self, out, opos, perm, spill):
        out["ofloor"] = out["ofloor"].at[opos].set(
            jnp.take(out["floor"], perm), mode="drop")
        out["osid"] = out["osid"].at[opos].set(
            jnp.take(out["sid"], perm), mode="drop")
        return out

    def _apply_core(self, state, batch: Batch):
        K, C = self._slots, batch.capacity
        gap = self.spec.gap
        refs = tuple_refs(batch)
        vals = jax.vmap(self.value_fn)(refs)
        # OLD: the event predates (within gap of) the key's last closed end
        floor_k = table_lookup(state["floor"], batch.key)
        old = batch.valid & (floor_k > _IMIN) & (batch.ts <= floor_k + gap)
        live = batch.valid & ~old
        # one fused sort puts lanes in (key, event-time, id) order
        iota = jnp.arange(C, dtype=jnp.int32)
        skeys, sts, sids, orig = jax.lax.sort(
            (jnp.where(live, batch.key, _IMAX), batch.ts, batch.id, iota),
            num_keys=3, is_stable=True)
        sv = skeys != _IMAX
        sk = jnp.where(sv, skeys, 0)
        svals = jax.tree.map(lambda a: jnp.take(a, orig, axis=0), vals)
        first = sv & jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                      skeys[1:] != skeys[:-1]])
        prev_ts = jnp.concatenate([jnp.zeros((1,), sts.dtype), sts[:-1]])
        brk = sv & ~first & (sts - prev_ts > gap)
        open_k = table_lookup(state["open"].astype(jnp.int32), sk) > 0
        last_k = table_lookup(state["last"], sk)
        cont = first & open_k & (sts - last_k <= gap)
        # dense fragment ids + per-fragment aggregates (event-time order)
        seg = jnp.maximum(jnp.cumsum((first | brk).astype(jnp.int32)) - 1, 0)
        red = lambda v, comb, ident: segment_reduce(
            v, seg, sv, C, combine=comb, identity=ident)
        fkey = red(sk, jnp.maximum, 0)
        fmin = red(sts, jnp.minimum, _IMAX)
        fmax = red(sts, jnp.maximum, _IMIN)
        fcnt = red(jnp.ones((C,), jnp.int32), None, 0)
        facc = segment_reduce(svals, seg, sv, C, combine=leafwise(self.combine),
                              identity=self.identity)
        ffirst = red(first.astype(jnp.int32), jnp.maximum, 0) > 0
        fcont = red(cont.astype(jnp.int32), jnp.maximum, 0) > 0
        fvalid = fcnt > 0
        # carried open-session fields, per fragment key
        c_start = table_lookup(state["start"], fkey)
        c_last = table_lookup(state["last"], fkey)
        c_cnt = table_lookup(state["cnt"], fkey)
        c_acc = jax.tree.map(lambda t: table_lookup(t, fkey), state["acc"])
        c_open = table_lookup(state["open"].astype(jnp.int32), fkey) > 0
        mrg = lambda m, a, b: jnp.where(m, a, b)
        m_start = mrg(fcont, jnp.minimum(c_start, fmin), fmin)
        m_last = mrg(fcont, jnp.maximum(c_last, fmax), fmax)
        m_cnt = mrg(fcont, c_cnt + fcnt, fcnt)
        m_acc = jax.tree.map(
            lambda f, m: jnp.where(
                fcont.reshape(fcont.shape + (1,) * (f.ndim - 1)), m, f),
            facc, self._fold(c_acc, facc))
        # fragment topology per key
        minseg = segment_reduce(seg, sk, sv, K, combine=jnp.minimum,
                                identity=_IMAX)
        maxseg = segment_reduce(seg, sk, sv, K, combine=jnp.maximum,
                                identity=-1)
        frag_rank = iota - table_lookup(minseg, fkey)
        flast = fvalid & (iota == table_lookup(maxseg, fkey))
        # group 1: carried sessions closed by in-batch evidence (first
        # fragment of the key does NOT chain into the open session)
        g1 = fvalid & ffirst & ~fcont & c_open
        g1_id = table_lookup(state["sid"], fkey)
        # group 2: every non-final fragment is a closed session
        g2 = fvalid & ~flast
        n1 = segment_reduce(g1.astype(jnp.int32), fkey, fvalid, K)
        g2_id = (table_lookup(state["sid"], fkey) + table_lookup(n1, fkey)
                 + frag_rank)
        nclosed = segment_reduce(g2.astype(jnp.int32), fkey, fvalid, K)
        sid2 = state["sid"] + n1 + nclosed
        # floor: newest closed end per key
        ends1 = segment_reduce(jnp.where(g1, c_last, _IMIN), fkey, fvalid,
                               K, combine=jnp.maximum, identity=_IMIN)
        ends2 = segment_reduce(jnp.where(g2, m_last, _IMIN), fkey, fvalid,
                               K, combine=jnp.maximum, identity=_IMIN)
        floor2 = jnp.maximum(state["floor"], jnp.maximum(ends1, ends2))
        # final fragments become/extend the carried open session
        upd = jnp.where(flast, fkey, K)
        open2 = state["open"].at[upd].set(True, mode="drop")
        start2 = state["start"].at[upd].set(m_start, mode="drop")
        last2 = state["last"].at[upd].set(m_last, mode="drop")
        cnt2 = state["cnt"].at[upd].set(m_cnt, mode="drop")
        acc2 = jax.tree.map(lambda t, v: t.at[upd].set(v, mode="drop"),
                            state["acc"], m_acc)
        # group 3: the data-dependent triggerer over the [K] open table
        wm2 = jnp.maximum(state["wm"],
                          jnp.max(jnp.where(batch.valid, batch.ts, _IMIN)))
        g3 = open2 & self.spec.fired_session(last2, wm2)
        open3 = open2 & ~g3
        floor3 = jnp.where(g3, jnp.maximum(floor2, last2), floor2)
        sid3 = sid2 + g3.astype(jnp.int32)
        out = self._emit_rows(
            C, K,
            (g1, fkey, g1_id, c_last, c_start, c_cnt, c_acc),
            (g2, fkey, g2_id, m_last, m_start, m_cnt, m_acc),
            (g3, sid2, last2, start2, cnt2, acc2))
        from ..ops.lookup import count_drops
        new_state = dict(
            state, open=open3, start=start2, last=last2,
            cnt=cnt2, sid=sid3, acc=acc2, floor=floor3,
            wm=wm2,
            closed=state["closed"] + jnp.sum(g1.astype(jnp.int32))
            + jnp.sum(g2.astype(jnp.int32))
            + jnp.sum(g3.astype(jnp.int32)),
            old=count_drops(state["old"], "old_drops",
                            jnp.sum(old.astype(jnp.int32))))
        if self._event_time:
            # arrival lateness vs the post-batch watermark: one masked
            # reduction, state-only (results untouched).  delay >= the
            # recorded quantile keeps that fraction of arrivals inside their
            # session's lateness allowance.
            new_state["lat_hist"] = _et.lateness_update(
                state["lat_hist"], wm2, batch.ts, batch.valid)
        return new_state, out

    def _emit_rows(self, C, K, g1, g2, g3):
        """Assemble the [2C + K] output batch from the three close groups."""
        m1, k1, i1, e1, s1, n1, a1 = g1
        m2, k2, i2, e2, s2, n2, a2 = g2
        m3, i3, e3, s3, n3, a3 = g3
        kk = jnp.arange(K, dtype=jnp.int32)
        cat = lambda a, b, c: jnp.concatenate([a, b, c], axis=0)
        payload = {
            "agg": jax.tree.map(cat, a1, a2, a3),
            "start": cat(s1, s2, s3), "end": cat(e1, e2, e3),
            "n": cat(n1, n2, n3)}
        return Batch(key=cat(k1, k2, kk), id=cat(i1, i2, i3),
                     ts=cat(e1, e2, e3), payload=payload,
                     valid=cat(m1, m2, m3))

    def flush(self, state):
        """EOS fires every open session regardless of watermark (the
        ``flush_hi`` convention of the CB/TB paths)."""
        import numpy as np
        if state is None or int(np.asarray(state["eos"])):
            return state, None
        K = self._slots
        C = self._cap or K
        g3 = state["open"]
        z = jnp.zeros((C,), jnp.int32)
        zb = jnp.zeros((C,), jnp.bool_)
        zacc = jax.tree.map(
            lambda t: jnp.zeros((C,) + t.shape[1:], t.dtype), state["acc"])
        out = self._emit_rows(
            C, K,
            (zb, z, z, z, z, z, zacc), (zb, z, z, z, z, z, zacc),
            (g3, state["sid"], state["last"], state["start"], state["cnt"],
             state["acc"]))
        if self._tier is not None:
            # open sessions are pinned hot, so the EOS fire covers every
            # live session — emitted slot ids remap to their true keys
            out = out.replace(key=jnp.where(
                out.valid, jnp.take(state["hkey"],
                                    jnp.clip(out.key, 0, K - 1)), out.key))
        state = dict(state)
        state["closed"] = state["closed"] + jnp.sum(g3.astype(jnp.int32))
        state["sid"] = state["sid"] + g3.astype(jnp.int32)
        state["open"] = jnp.zeros_like(state["open"])
        state["eos"] = jnp.asarray(1, jnp.int32)
        self.collect_stats(state)
        return state, out

    def collect_stats(self, state: Any = None) -> None:
        if state is None:
            return
        import numpy as np
        from ..control import _state as _cstate
        old = int(np.asarray(state["old"]))
        self._stats[0].tuples_dropped_old = old
        closed = int(np.asarray(state["closed"]))
        if closed > self._closed_synced:
            _cstate.bump("sessions_closed", closed - self._closed_synced)
            self._closed_synced = closed
        counters = {"sessions_closed": closed, "old_drops": old}
        if self._tier is not None:
            from .join import _tier_counters
            counters.update(_tier_counters(state, self._tier))
            counters["overflow_drops"] = int(np.asarray(state["ovf"]))
        self._publish_stage_counters(counters)

    def drop_counters(self, state: Any = None) -> dict:
        if state is None:
            return {}
        import numpy as np
        out = {"old_drops": int(np.asarray(state["old"]))}
        if self._tier is not None:
            out["overflow_drops"] = int(np.asarray(state["ovf"]))
        return out

    def event_time_stats(self, state: Any = None):
        """Watermark-map section: open-session pressure (count + oldest-open
        age vs the watermark), close/drop totals, and the arrival-lateness
        histogram with its ``recommend_delay`` advice."""
        if state is None:
            return None
        import numpy as np
        wm = int(np.asarray(state["wm"]))
        open_mask = np.asarray(state["open"])
        n_open = int(open_mask.sum())
        out = {
            "watermark_ts": wm,
            "gap": self.spec.gap,
            "delay": self.spec.delay,
            "open_sessions": n_open,
            "key_slots": self._slots,
            "occupancy_pct": round(100.0 * n_open / self._slots, 2),
            "sessions_closed": int(np.asarray(state["closed"])),
            "old_drops": int(np.asarray(state["old"])),
        }
        if self._tier is not None:
            from ..state.tiered import slot_directory_stats
            out["tier"] = {**slot_directory_stats(state),
                           **self._tier.controller.stats()}
            out["overflow_drops"] = int(np.asarray(state["ovf"]))
        if n_open:
            # age of the longest-open session: how much event time the
            # watermark has advanced past its first event
            start = np.asarray(state["start"])
            out["oldest_open_age"] = max(0, wm - int(start[open_mask].min()))
        counts = _et.read_hist(state.get("lat_hist"))
        if counts is not None:
            out["lateness"] = {"in": _et.summarize(counts)}
        return out
