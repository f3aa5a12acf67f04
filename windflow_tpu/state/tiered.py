"""Tiered keyed state — the host-side controller of the two-tier layer.

``TieredTable`` supervises ONE device-resident table's cold tier: the
operator's ``apply`` packs eviction candidates into a bounded **outbox**
inside the compiled program (a deterministic pure function of watermark,
per-key last-access position, and occupancy — never wall clock), and this
controller moves them to the :class:`~windflow_tpu.state.host_store.
HostStore` with the PR 7 ordering-readback discipline: ``copy_to_host_async``
started right after a push, consumed at the next maintenance point — no
synchronous D2H on the hot path.

The spill protocol (each :meth:`maintain` call = one push boundary, so the
cadence is a pure function of stream position and supervised replay re-walks
it exactly):

1. a *count probe* (one async-copied scalar) discovers whether the outbox
   holds anything;
2. when it does, a *full copy* of the outbox columns (+ the watermark
   scalar) is started asynchronously;
3. the next maintain **applies** the copied prefix to the host store and
   **clears** exactly that prefix from the device outbox (one tiny jitted
   shift program) — entries leave the outbox only *after* they are in the
   store, so the union (device table ∪ outbox ∪ host store) always covers
   every key and the in-graph miss-resolution (which probes the outbox
   before falling back to the host ``io_callback``) can never lose a row.

``settle()`` forces the pipeline synchronously (supervised snapshots settle
first; a checkpoint therefore captures a consistent (state, store) pair and
a restore just discards whatever async copies were in flight — replay
re-derives them). Watermark compaction runs on a maintain-count cadence with
the async-copied watermark as its frontier hint: a stale hint only retains
rows longer, never retires one early, so compaction is semantics-free.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, List, Optional, Union

import numpy as np

from ..observability import journal as _journal
from .host_store import HostStore


@dataclasses.dataclass
class TierConfig:
    """Resolved tiered-state settings for one stateful operator.

    The ``tiered=`` kwarg / ``WF_STATE_TIERED`` env of the stateful
    operators (``StreamTableJoin``/``Distinct``/``SessionWindow``/``TopN``/
    ``IntervalJoin``) — off by default; the OFF path is byte-for-byte
    today's state pytrees and compiled programs."""

    #: device-resident hot-table slots (None = the operator's own
    #: ``num_slots``/``num_keys`` — today's geometry). ``WF_STATE_HOT_CAPACITY``
    #: overrides for every tiered operator (the WF_TRACE_SAMPLE convention).
    hot_capacity: Optional[int] = None
    #: spill-outbox slots (None = auto: 4x the operator's per-batch
    #: admission bound, absorbing the 3-phase async drain latency)
    outbox: Optional[int] = None
    #: interval-join re-admission: max cold rows matched per probing lane
    #: per batch (bounded candidate growth; truncation is deterministic)
    readmit_rows: int = 8
    #: maintains between host-store watermark compactions
    compact_every: int = 64
    #: optional cold-tier TTL in event-time ticks for the JoinTable-backed
    #: operators (None = dimension-table semantics, rows live forever);
    #: a row is retired once its version ts < watermark - ttl
    ttl: Optional[int] = None

    def __post_init__(self):
        if self.hot_capacity is not None and int(self.hot_capacity) < 2:
            raise ValueError("tiered hot_capacity must be >= 2")
        if self.outbox is not None and int(self.outbox) < 1:
            raise ValueError("tiered outbox must be >= 1")
        if int(self.readmit_rows) < 1:
            raise ValueError("tiered readmit_rows must be >= 1")
        if int(self.compact_every) < 1:
            raise ValueError("tiered compact_every must be >= 1")

    @classmethod
    def resolve(cls, tiered: Union[None, bool, str, dict, "TierConfig"]
                ) -> Optional["TierConfig"]:
        """Normalize the user-facing ``tiered=`` argument; None when off.
        ``None`` consults ``WF_STATE_TIERED`` (``''``/``'0'`` = off,
        ``'1'`` = defaults, inline JSON object / JSON file path = field
        overrides); ``WF_STATE_HOT_CAPACITY`` overrides the hot-table size
        whenever tiering is on. Read at operator construction —
        geometry-binding (the WF_MONITORING_EVENT_TIME convention): the
        tier fields live in the state pytree, so toggling after
        construction needs a fresh operator."""
        cfg = None
        if isinstance(tiered, TierConfig):
            cfg = tiered
        elif isinstance(tiered, dict):
            cfg = cls(**tiered)
        elif tiered is None:
            env = os.environ.get("WF_STATE_TIERED", "")
            if env not in ("", "0", "false", "False"):
                if env == "1" or env.lower() == "true":
                    cfg = cls()
                elif env.lstrip().startswith("{"):
                    cfg = cls(**json.loads(env))
                elif os.path.exists(env):
                    with open(env, encoding="utf-8") as f:
                        cfg = cls(**json.load(f))
                else:
                    raise ValueError(
                        f"WF_STATE_TIERED={env!r} is neither a toggle, "
                        f"inline JSON, nor a readable JSON file")
        elif tiered:
            cfg = cls()
        if cfg is not None:
            hot = os.environ.get("WF_STATE_HOT_CAPACITY", "")
            if hot:
                cfg = dataclasses.replace(cfg, hot_capacity=int(hot))
        return cfg


def _np_tree(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a), tree)


def _slice_tree(tree, n):
    import jax
    return jax.tree.map(lambda a: a[:n], tree)


# the controller's async-pipeline fields (_cnt/_full/_wm_hint/...) are
# confined to the ONE thread driving the owning chain (the pipeline driver,
# or a segment thread of the threaded driver); the JAX callback threads
# only ever touch the lock-guarded HostStore, never this controller —
# checked by the thread-role annotations on maintain/settle below
class TieredTable:  # wf-lint: single-writer[driver, stage]
    """Host-side supervisor of one device table's spill outbox + cold tier.

    ``col_keys`` name the outbox fields inside the operator's state dict
    (each may itself be a pytree); ``count_key`` the live-entry count
    scalar; ``apply_fn(store, n, cols)`` turns ``n`` copied outbox rows
    into host-store writes; ``compact_fn(store, wm)`` (optional) applies
    the operator's watermark retention bound."""

    def __init__(self, name: str, store: HostStore, count_key: str,
                 col_keys: List[str],
                 apply_fn: Callable[[HostStore, int, dict], int], *,
                 wm_key: Optional[str] = None,
                 compact_fn: Optional[Callable[[HostStore, int], int]] = None,
                 compact_every: int = 64):
        self.name = name
        self.store = store
        self.count_key = count_key
        self.col_keys = list(col_keys)
        self.apply_fn = apply_fn
        self.wm_key = wm_key
        self.compact_fn = compact_fn
        self.compact_every = max(1, int(compact_every))
        self._maintains = 0
        self._cnt = None       # async count probe (phase 1)
        self._full = None      # (count, cols, wm) async full copy (phase 2)
        self._wm_hint = None   # last copied watermark (compaction frontier)
        self._clear_fn = None  # jitted prefix-shift, built lazily
        self._journal_synced = {"state_spills": 0, "state_readmits": 0,
                                "state_compactions": 0}

    # -- jitted outbox clear ----------------------------------------------

    def _clear(self, state, c0: int):
        """Shift the first ``c0`` outbox entries out of ``state`` (they are
        in the host store now) — ONE cached executable, ``c0`` traced."""
        import jax
        import jax.numpy as jnp
        if self._clear_fn is None:
            count_key, col_keys = self.count_key, tuple(self.col_keys)

            def clear(st, c):
                out = dict(st)
                for k in col_keys:
                    out[k] = jax.tree.map(
                        lambda a: jnp.take(
                            a, jnp.arange(a.shape[0]) + c, axis=0,
                            mode="fill", fill_value=0), st[k])
                out[count_key] = jnp.maximum(st[count_key] - c, 0)
                return out
            self._clear_fn = jax.jit(clear)
        return self._clear_fn(state, np.int32(c0))

    # -- the per-push maintenance point -----------------------------------

    def maintain(self, state):  # wf-lint: thread-role[driver, stage]
        """One push boundary: advance the 3-phase async spill pipeline +
        the compaction cadence. Pure host work; the only device interaction
        is starting async copies and (when a prefix settled) one cached
        clear executable.

        OWNING-THREAD ONLY — statically checked: the ``thread-role``
        annotation restricts maintenance to the chain's driving thread
        (driver, or the owning segment thread); WF261 fails the gate if a
        reporter/watchdog/pool/JAX-callback thread ever reaches it."""
        self._maintains += 1
        if self._full is not None:
            cnt, cols, wm = self._full
            self._full = None
            c0 = int(np.asarray(cnt))
            if wm is not None:
                self._wm_hint = int(np.asarray(wm))
            if c0 > 0:
                # barrier BEFORE touching the store: the just-dispatched
                # push may still be executing, and its re-admission
                # callbacks read the store — applying rows their in-graph
                # state still holds in the outbox would let one probe see a
                # row in BOTH tiers (a duplicate match). Blocking on the
                # producing push's state settles it (ordered io_callbacks
                # complete with the program), exactly the PR 7 settling
                # discipline; the copies themselves stayed async.
                import jax
                jax.block_until_ready(state[self.count_key])
                host = {k: _slice_tree(_np_tree(v), c0)
                        for k, v in cols.items()}
                self.apply_fn(self.store, c0, host)
                state = self._clear(state, c0)
        elif self._cnt is not None:
            cnt, wm = self._cnt
            self._cnt = None
            if wm is not None:
                self._wm_hint = int(np.asarray(wm))
            if int(np.asarray(cnt)) > 0:
                self._full = self._start_copy(state, full=True)
        if self._full is None and self._cnt is None:
            self._cnt = self._start_copy(state, full=False)
        if (self.compact_fn is not None and self._wm_hint is not None
                and self._maintains % self.compact_every == 0):
            self.compact_fn(self.store, self._wm_hint)
        self._journal_deltas()
        return state

    def _start_copy(self, state, full: bool):
        cnt = state[self.count_key]
        wm = state[self.wm_key] if self.wm_key is not None else None
        for a in ([cnt] + ([wm] if wm is not None else [])):
            if hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()
        if not full:
            return (cnt, wm)
        import jax
        cols = {k: state[k] for k in self.col_keys}
        for leaf in jax.tree.leaves(cols):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        return (cnt, cols, wm)

    def settle(self, state):  # wf-lint: thread-role[driver, stage]
        """Synchronously drain the outbox into the host store (one blocking
        readback) and drop the async pipeline — the pre-snapshot barrier:
        after settle, (state, store) is a consistent pair and nothing is in
        flight.  Owning-thread only (the maintain contract, statically
        checked via the thread-role annotation)."""
        self._cnt = None
        self._full = None
        c0 = int(np.asarray(state[self.count_key]))
        if self.wm_key is not None:
            self._wm_hint = int(np.asarray(state[self.wm_key]))
        if c0 > 0:
            host = {k: _slice_tree(_np_tree(state[k]), c0)
                    for k in self.col_keys}
            self.apply_fn(self.store, c0, host)
            state = self._clear(state, c0)
        self._journal_deltas()
        return state

    def discard_inflight(self) -> None:
        """Restore path: drop async copies from the failed attempt — the
        restored state still holds the entries in its outbox, so replay
        re-derives the spill."""
        self._cnt = None
        self._full = None

    # -- durability / telemetry -------------------------------------------

    def manifest(self) -> Dict[str, np.ndarray]:
        return self.store.manifest()

    def restore(self, manifest: Dict[str, np.ndarray]) -> None:
        self.discard_inflight()
        self.store.restore(manifest)
        self._journal_synced = dict(self.store.counters())

    def counters(self) -> dict:
        return self.store.counters()

    def stats(self) -> dict:
        """The ``tier`` section of the operator's event-time snapshot row:
        cold-tier size + movement counters (host side; the device-side
        outbox depth/occupancy ride the operator's own section)."""
        out = {"cold_keys": self.store.key_count(),
               "cold_rows": len(self.store)}
        out.update(self.store.counters())
        return out

    def _journal_deltas(self) -> None:
        """Emit ``spill``/``readmit`` journal events for counter movement
        since the last maintenance point.  Runs only under maintain/settle
        (whose thread-role annotations keep the callback threads out — so
        the JAX callback threads never touch the journal)."""
        if _journal.get_active() is None:
            return
        cur = self.store.counters()
        for kind, event in (("state_spills", "spill"),
                            ("state_readmits", "readmit")):
            delta = cur[kind] - self._journal_synced[kind]
            if delta > 0:
                _journal.record(event, table=self.name, n=delta,
                                total=cur[kind])
        self._journal_synced.update(
            {k: cur[k] for k in ("state_spills", "state_readmits")})
        # compactions are quieter: counted, not journaled per event
        self._journal_synced["state_compactions"] = cur["state_compactions"]


# ------------------------------------------------- per-table-shape runtimes


class JoinTableTier:
    """Cold tier + controller + host callback for one versioned JoinTable
    (``ops/lookup.py`` ``join_table_*`` — StreamTableJoin and Distinct).
    Row schema: the table's value columns + the ``(ver, vid, vseq)`` LWW
    version triplet (so cross-tier last-writer-wins is exactly the device
    table's never-roll-back rule)."""

    def __init__(self, name: str, val_spec, cfg: TierConfig):
        import jax
        self.cfg = cfg
        self._leaves = jax.tree.leaves(val_spec)
        cols = {f"v{i}": np.dtype(getattr(leaf, "dtype", np.int32))
                for i, leaf in enumerate(self._leaves)}
        self.store = HostStore(name, cols, unique=True)

        def apply_fn(store, n, host):
            import jax as _jax
            leaves = _jax.tree.leaves(host["oval"])
            return store.upsert(
                host["okey"], host["over"], host["ovid"], host["ovseq"],
                {f"v{i}": leaf for i, leaf in enumerate(leaves)})

        compact_fn = None
        if cfg.ttl is not None:
            ttl = int(cfg.ttl)

            def compact_fn(store, wm):     # noqa: F811 — the optional hook
                return store.compact_below("m0", wm - ttl)

        self.controller = TieredTable(
            name, self.store, "ocnt",
            ["okey", "oval", "over", "ovid", "ovseq"],
            apply_fn, wm_key="wm", compact_fn=compact_fn,
            compact_every=cfg.compact_every)

    def lookup_cb(self, keys, want):
        """The ordered-``io_callback`` target: probe the cold tier for the
        wanted keys. Zero-mask calls are host no-ops (the ``warm()``
        contract)."""
        found, meta, cols = self.store.lookup(keys, want)
        out = [found, meta[:, 0].astype(np.int32),
               meta[:, 1].astype(np.int32), meta[:, 2].astype(np.int32)]
        for i, leaf in enumerate(self._leaves):
            out.append(cols[f"v{i}"].astype(
                np.dtype(getattr(leaf, "dtype", np.int32))))
        return tuple(out)


class ArchiveTier:
    """Cold tier + controller for ONE side of an interval-join archive — a
    MULTIMAP: every spilled row (an archived tuple the ring overwrote while
    still inside its match window) is retained until the watermark frontier
    retires it. Re-admission is read-only (``fetch_multi``): cold rows are
    matched as extra candidates and stay probeable by later arrivals —
    removal would lose pairs, duplication is impossible because a row lives
    in exactly one tier (archive XOR outbox XOR here)."""

    def __init__(self, name: str, payload_spec, cfg: TierConfig, side: str,
                 compact_bound):
        import jax
        self.cfg = cfg
        self.side = side
        self._leaves = jax.tree.leaves(payload_spec)
        cols = {"ts": np.int32, "id": np.int32}
        shapes = {"ts": (), "id": ()}
        for i, leaf in enumerate(self._leaves):
            cols[f"p{i}"] = np.dtype(getattr(leaf, "dtype", np.int32))
            shapes[f"p{i}"] = tuple(getattr(leaf, "shape", ()))
        self.store = HostStore(f"{name}.{side}", cols, shapes, unique=False)

        def apply_fn(store, n, host):
            import jax as _jax
            leaves = _jax.tree.leaves(host[f"{side}opay"])
            rows = {"ts": host[f"{side}ots"], "id": host[f"{side}oid"]}
            rows.update({f"p{i}": leaf for i, leaf in enumerate(leaves)})
            z = np.zeros(n, np.int64)
            return store.append(host[f"{side}okey"], z, z, z, rows)

        def compact_fn(store, wm):
            return store.compact_below("ts", compact_bound(wm))

        self.controller = TieredTable(
            f"{name}.{side}", self.store, f"{side}ocnt",
            [f"{side}okey", f"{side}ots", f"{side}oid", f"{side}opay"],
            apply_fn, wm_key="wm", compact_fn=compact_fn,
            compact_every=cfg.compact_every)

    def fetch_cb(self, keys, want):
        """Ordered-``io_callback`` target: up to ``readmit_rows`` cold rows
        per probing lane's key — ``(mask [C, M], ts, id, *pay leaves)``."""
        mask, _meta, cols = self.store.fetch_multi(keys, want,
                                                   self.cfg.readmit_rows)
        out = [mask, cols["ts"].astype(np.int32),
               cols["id"].astype(np.int32)]
        for i, leaf in enumerate(self._leaves):
            out.append(cols[f"p{i}"].astype(
                np.dtype(getattr(leaf, "dtype", np.int32))))
        return tuple(out)


# --------------------------------------- in-graph slot-directory primitives
#
# The session/top-N tables are DIRECT-indexed (the tuple key IS the slot);
# tiering them needs a key -> hot-slot directory in front of the existing
# table math. These primitives are the directory: pure jnp, fixed shapes,
# the same deterministic cumsum fresh-slot discipline as the JoinTable.

_KEY_SENTINEL = -(1 << 31)


def slot_lookup(hkey, hused, keys, ok):
    """``(hit [R], slot [R])`` of each wanted key in the hot directory."""
    import jax.numpy as jnp
    tk = jnp.where(hused, hkey, _KEY_SENTINEL)
    eq = keys[:, None] == tk[None, :]
    hit = jnp.any(eq, axis=1) & ok & (keys != _KEY_SENTINEL)
    return hit, jnp.argmax(eq, axis=1)


def slot_alloc(hused, adm):
    """Deterministic fresh slots: the r-th admitted lane claims the r-th
    free slot (ascending). ``(got [R], slot [R])``."""
    import jax.numpy as jnp
    rank = jnp.cumsum(adm.astype(jnp.int32)) - 1
    free = ~hused
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    oh = free[None, :] & (free_rank[None, :] == rank[:, None])
    got = jnp.any(oh, axis=1) & adm
    return got, jnp.argmax(oh, axis=1)


def outbox_find_last(okey, ocnt, keys, need):
    """Newest outbox entry per wanted key: ``(found [R], idx [R])``."""
    import jax.numpy as jnp
    S = okey.shape[0]
    olive = jnp.arange(S, dtype=jnp.int32) < ocnt
    eq = (keys[:, None] == okey[None, :]) & olive[None, :]
    idx = jnp.max(jnp.where(eq, jnp.arange(S, dtype=jnp.int32)[None, :], -1),
                  axis=1)
    return need & (idx >= 0), jnp.maximum(idx, 0)


def slot_directory_resolve(state, keys, ok, lookup_cb, host_shapes,
                           admit_write):
    """Generic key -> hot-slot resolution for a direct-indexed table:
    touch hot hits, then for missing keys search the spill outbox (newest
    entry), then the host store (ONE ordered ``io_callback``), and admit
    EVERY missing first-occurrence key — readmitted with its cold row,
    or fresh — through the deterministic cumsum fresh-slot discipline.
    ``admit_write(out, widx, got, in_ob, oidx, host_res)`` writes the
    operator's own columns for the admitted slots. Returns ``(state,
    slot [R], live [R])`` — ``live`` excludes lanes whose key could not
    get a slot (hot directory saturated; the caller counts those as
    overflow drops through ``count_drops``)."""
    import jax.numpy as jnp
    from jax.experimental import io_callback
    from ..ops.segment import segment_rank
    H = state["hkey"].shape[0]
    keys = keys.astype(jnp.int32)
    ok = ok.astype(jnp.bool_) & (keys != _KEY_SENTINEL)
    tick = state["tick"]
    hit, slot = slot_lookup(state["hkey"], state["hused"], keys, ok)
    lap = state["lap"].at[jnp.where(hit, slot, H)].set(tick, mode="drop")
    need = ok & ~hit
    in_ob, oidx = outbox_find_last(state["okey"], state["ocnt"], keys, need)
    need_host = need & ~in_ob
    shapes = (host_shapes(keys.shape[0]) if callable(host_shapes)
              else host_shapes)
    host_res = io_callback(lookup_cb, shapes, keys, need_host,
                           ordered=True)
    host_found = host_res[0] & need_host
    adm = need & (segment_rank(keys, need) == 0)
    got, snew = slot_alloc(state["hused"], adm)
    widx = jnp.where(got, snew, H)
    out = dict(state)
    out["hkey"] = state["hkey"].at[widx].set(keys, mode="drop")
    out["hused"] = state["hused"].at[widx].set(True, mode="drop")
    out["lap"] = lap.at[widx].set(tick, mode="drop")
    out = admit_write(out, widx, got, in_ob, oidx, host_res)
    out["readmits"] = state["readmits"] + jnp.sum(
        (got & (in_ob | host_found)).astype(jnp.int32))
    hit2, slot2 = slot_lookup(out["hkey"], out["hused"], keys, ok)
    return out, slot2, ok & hit2


def slot_directory_evict(state, hot_target, evictable, discardable,
                         pack_write):
    """Generic pressure eviction over a hot directory: free the coldest
    ``used - hot_target`` evictable slots. Rows with nothing worth
    remembering (``discardable``) are freed outright; the rest pack into
    the spill outbox (``okey``/``otick`` here, the operator's columns via
    ``pack_write(out, opos, perm, spill)``), bounded by outbox space —
    a full outbox simply defers those evictions. Pure function of
    (occupancy, last-access) — the deterministic-policy contract — and
    closes the batch by advancing ``tick``."""
    import jax.numpy as jnp
    imax = jnp.iinfo(jnp.int32).max
    H = state["hkey"].shape[0]
    S = state["okey"].shape[0]
    used = state["hused"]
    used_n = jnp.sum(used.astype(jnp.int32))
    need = jnp.maximum(used_n - jnp.asarray(int(hot_target), jnp.int32), 0)
    cand = used & evictable
    sortkey = jnp.where(cand, state["lap"], imax)
    perm = jnp.lexsort((jnp.arange(H, dtype=jnp.int32), sortkey))
    r = jnp.arange(H, dtype=jnp.int32)
    sel = (r < need) & jnp.take(cand, perm)
    disc = jnp.take(discardable, perm)
    spill = sel & ~disc
    srank = jnp.cumsum(spill.astype(jnp.int32)) - 1
    fits = spill & (state["ocnt"] + srank < S)
    evict = sel & (disc | fits)
    opos = jnp.where(fits, state["ocnt"] + srank, S)
    out = dict(state)
    out["okey"] = state["okey"].at[opos].set(jnp.take(state["hkey"], perm),
                                             mode="drop")
    out["otick"] = state["otick"].at[opos].set(state["tick"], mode="drop")
    out = pack_write(out, opos, perm, fits)
    cleared = jnp.where(evict, perm, H)
    out["hused"] = used.at[cleared].set(False, mode="drop")
    out["hkey"] = out["hkey"].at[cleared].set(_KEY_SENTINEL, mode="drop")
    n = jnp.sum(fits.astype(jnp.int32))
    out["ocnt"] = state["ocnt"] + n
    out["spills"] = state["spills"] + n
    out["tick"] = state["tick"] + 1
    return out


def slot_directory_init(hot: int, outbox: int, extra_outbox_cols):
    """The directory + outbox state fields shared by every slot-directory
    tier (``extra_outbox_cols``: name -> zero array factory over [S])."""
    import jax.numpy as jnp
    H, S = int(hot), int(outbox)
    out = {
        "hkey": jnp.full((H,), _KEY_SENTINEL, jnp.int32),
        "hused": jnp.zeros((H,), jnp.bool_),
        "lap": jnp.zeros((H,), jnp.int32),
        "tick": jnp.asarray(0, jnp.int32),
        "okey": jnp.full((S,), _KEY_SENTINEL, jnp.int32),
        "otick": jnp.zeros((S,), jnp.int32),
        "ocnt": jnp.asarray(0, jnp.int32),
        "spills": jnp.asarray(0, jnp.int32),
        "readmits": jnp.asarray(0, jnp.int32),
    }
    for name, factory in extra_outbox_cols.items():
        out[name] = factory(S)
    return out


def slot_directory_stats(state) -> dict:
    """Device-side tier numbers of a slot directory (snapshot time only)."""
    H = int(state["hkey"].shape[0])
    S = int(state["okey"].shape[0])
    used = int(np.asarray(state["hused"]).sum())
    return {
        "hot_slots": H,
        "hot_used": used,
        "hot_pct": round(100.0 * used / H, 2),
        "outbox_slots": S,
        "outbox_depth": int(np.asarray(state["ocnt"])),
        "state_spills": int(np.asarray(state["spills"])),
        "state_readmits": int(np.asarray(state["readmits"])),
    }


class SlotTableTier:
    """Cold tier + controller for a direct-indexed keyed table behind a
    slot directory (SessionWindow floors, TopN leaderboards). Row schema =
    ``cols`` (name -> (dtype, trailing shape)); LWW meta is the spill tick
    (chronological — a later spill of the same key always wins)."""

    def __init__(self, name: str, cols, cfg: TierConfig, *,
                 count_key: str, col_keys, state_to_store,
                 compact_col: Optional[str] = None,
                 compact_bound=None, wm_key: Optional[str] = "wm"):
        self.cfg = cfg
        self._cols = {k: np.dtype(d) for k, (d, _s) in cols.items()}
        self._shapes = {k: tuple(s) for k, (_d, s) in cols.items()}
        self.store = HostStore(name, self._cols, self._shapes, unique=True)
        self._state_to_store = state_to_store
        compact_fn = None
        if compact_col is not None and compact_bound is not None:
            def compact_fn(store, wm):  # noqa: F811 — optional hook:
                # retire rows the operator's retention arithmetic proves
                # unreachable (the fired_hi_tb family; a stale wm hint
                # only RETAINS longer, never retires early)
                return store.compact_below(compact_col, compact_bound(wm))
        self.controller = TieredTable(
            name, self.store, count_key, list(col_keys),
            self._apply, wm_key=wm_key, compact_fn=compact_fn,
            compact_every=cfg.compact_every)

    def _apply(self, store, n, host):
        keys, tick, cols = self._state_to_store(n, host)
        return store.upsert(keys, tick, np.zeros(n, np.int64),
                            np.zeros(n, np.int64), cols)

    def lookup_cb(self, keys, want):
        found, _meta, cols = self.store.lookup(keys, want)
        return (found,) + tuple(
            cols[k].astype(self._cols[k]) for k in sorted(self._cols))

