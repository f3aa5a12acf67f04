"""Parallel window patterns: Win_Farm, Key_Farm, Key_FFAT, Pane_Farm, Win_MapReduce.

The reference implements each pattern as a distinct thread topology around ``Win_Seq``
workers (``wf/win_farm.hpp``, ``wf/key_farm.hpp``, ``wf/key_ffat.hpp``,
``wf/pane_farm.hpp``, ``wf/win_mapreduce.hpp``). On TPU the *batched window axis* plays
the role of the worker pool — every fired window is a row processed in parallel by one
compiled program — so each pattern reduces to a configuration/composition of the
vectorized engines plus a sharding recipe for multi-chip (``parallel/sharding.py``):

- **Win_Farm** (``wf/win_farm.hpp:65-666``): N replicas each own every N-th window
  (private slide = slide*N, ``:165-175``), fed by a multicast WF_Emitter
  (``wf/wf_nodes.hpp:110-204``). Here: windows are already independent rows of the
  [W] axis — "ownership" is row index; multi-chip shards the W axis (window w on
  device w % p — the emitter arithmetic as a sharding rule). No tuple multicast
  exists because the archive is shared in HBM rather than copied per replica.
- **Key_Farm** (``wf/key_farm.hpp:68-641``): whole keys routed to replicas
  (KF_Emitter, ``wf/kf_nodes.hpp:43-111``). Here: the [K] state axis; multi-chip
  shards the key-state tables (key k on device hash(k) % p).
- **Key_FFAT** (``wf/key_ffat.hpp:65-246``): Key_Farm whose workers are Win_SeqFFAT —
  directly ``Win_SeqFFAT`` with key-axis sharding.
- **Pane_Farm** (``wf/pane_farm.hpp:66-1012``): pane decomposition, PLQ computes
  pane partials (pane_len = gcd(win, slide), ``:175``), WLQ combines pane results
  per window. Here: PLQ = tumbling Win_Seq over panes, WLQ = Win_Seq over the pane
  result stream — two engines fused in one compiled program (the LEVEL2 flattening,
  ``:222-260``, is the default and only mode).
- **Win_MapReduce** (``wf/win_mapreduce.hpp:63-1002``): each window's content is
  round-robin partitioned across ``map_parallelism`` workers (WinMap_Emitter,
  ``wf/wm_nodes.hpp:45-181``), partials reduced. Here: gather the window row [L],
  reshape to [M, L/M] partitions, vmap MAP over partitions, tree-reduce with REDUCE —
  all inside the window-axis vmap; multi-chip shards the M axis with a psum-style
  combine over ICI.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t, role_t, pattern_t, DEFAULT_MAX_KEYS
from ..batch import Batch, CTRL_DTYPE, TupleRef
from ..observability.names import PANE_STAGES
from .base import Basic_Operator
from .window import Iterable, WindowSpec
from .win_seq import Win_Seq
from .win_seqffat import Win_SeqFFAT


def _check_nesting_args(outer: str, args, kw) -> None:
    """The nesting ctors take only parallelism/name — the window geometry and key
    capacity belong to the inner pattern (as in the reference, where the outer farm
    replicates the inner pattern verbatim, ``wf/win_farm.hpp:266-355``). Reject
    anything else rather than silently ignoring it."""
    extra = [repr(a) for a in args] + [k for k in kw if k not in ("parallelism", "name")]
    if extra:
        raise TypeError(
            f"{outer}(inner_pattern, ...): nesting accepts only parallelism= and "
            f"name= — the window spec / num_keys come from the inner pattern; got "
            f"extra argument(s): {', '.join(extra)}")


class Win_Farm(Win_Seq):
    """Keyless (or keyed) window parallelism. ``parallelism`` declares the number of
    window-axis shards for multi-chip; single-chip, the [W] axis is already the farm.
    The reference's emitter math (window w owned by replica (hash(key)%p + w) % p,
    ``wf/wf_nodes.hpp:182-204``) becomes the sharding rule of the W axis.

    Nesting (``wf/win_farm.hpp:266-355``): pass a :class:`Pane_Farm` or
    :class:`Win_MapReduce` instance as the first argument to replicate that whole
    pattern as the worker — ``Win_Farm(Pane_Farm(...))``."""

    pattern = pattern_t.WF_CPU
    shard_axis = "window"

    def __new__(cls, win_fn=None, *args, **kw):
        if isinstance(win_fn, (Pane_Farm, Win_MapReduce)):
            _check_nesting_args(cls.__name__, args, kw)
            return Nested_Farm(win_fn, shard_axis="window", pattern=pattern_t.WF_CPU,
                               parallelism=kw.get("parallelism", 1),
                               name=kw.get("name", f"win_farm[{win_fn.name}]"))
        return super().__new__(cls)

    def __init__(self, win_fn, spec: WindowSpec, *, parallelism: int = 1,
                 num_keys: int = 1, name: str = "win_farm", **kw):
        super().__init__(win_fn, spec, num_keys=num_keys, name=name,
                         parallelism=parallelism, **kw)
        self.routing = routing_modes_t.COMPLEX


class Key_Farm(Win_Seq):
    """Keyed window parallelism: keys partitioned over replicas, each key's windows
    computed sequentially in order (``wf/key_farm.hpp``). The [K] state axis is the
    farm; multi-chip shards it.

    Nesting (``wf/key_farm.hpp:155-167`` worker variants): pass a
    :class:`Pane_Farm` or :class:`Win_MapReduce` instance as the first argument."""

    pattern = pattern_t.KF_CPU
    shard_axis = "key"

    def __new__(cls, win_fn=None, *args, **kw):
        if isinstance(win_fn, (Pane_Farm, Win_MapReduce)):
            _check_nesting_args(cls.__name__, args, kw)
            return Nested_Farm(win_fn, shard_axis="key", pattern=pattern_t.KF_CPU,
                               parallelism=kw.get("parallelism", 1),
                               name=kw.get("name", f"key_farm[{win_fn.name}]"))
        return super().__new__(cls)

    def __init__(self, win_fn, spec: WindowSpec, *, parallelism: int = 1,
                 num_keys: int = DEFAULT_MAX_KEYS, name: str = "key_farm", **kw):
        super().__init__(win_fn, spec, num_keys=num_keys, name=name,
                         parallelism=parallelism, **kw)


class Key_FFAT(Win_SeqFFAT):
    """Key_Farm with FlatFAT-style associative incremental workers
    (``wf/key_ffat.hpp:65-246``): pane-partial sharing + key-axis sharding."""

    pattern = pattern_t.KFF_CPU
    shard_axis = "key"

    def __init__(self, lift, combine, *, spec: WindowSpec, parallelism: int = 1,
                 num_keys: int = DEFAULT_MAX_KEYS, name: str = "key_ffat", **kw):
        super().__init__(lift, combine, spec=spec, num_keys=num_keys, name=name,
                         parallelism=parallelism, **kw)


class Nested_Farm(Basic_Operator):
    """Composition of an outer distribution pattern (Win_Farm / Key_Farm) with an
    inner computation pattern (Pane_Farm / Win_MapReduce) — the reference's nesting
    ctors replicate the whole inner pattern as the farm worker
    (``wf/win_farm.hpp:266-355``, ``wf/key_farm.hpp:155-167``; flattened by
    ``optimize_*`` LEVEL2 into one network, ``wf/win_farm.hpp:188-230``).

    Here flattening is inherent: the inner pattern's batched window axis IS the
    worker pool, and the outer pattern contributes only the multi-chip shard axis
    ("window" for WF, "key" for KF) plus parallelism metadata."""

    def __init__(self, inner, *, shard_axis: str, pattern, parallelism: int = 1,
                 name: str | None = None):
        super().__init__(name or f"nested[{inner.name}]", parallelism)
        self.inner = inner
        self.shard_axis = shard_axis
        self.pattern = pattern
        self.routing = inner.routing
        self.spec = inner.spec
        self.num_keys = getattr(inner, "num_keys", None)

    def bind_geometry(self, batch_capacity: int) -> None:
        self.inner.bind_geometry(batch_capacity)

    def out_capacity(self, in_capacity: int) -> int:
        return self.inner.out_capacity(in_capacity)

    def init_state(self, payload_spec: Any):
        return self.inner.init_state(payload_spec)

    def out_spec(self, payload_spec: Any) -> Any:
        return self.inner.out_spec(payload_spec)

    def apply(self, state, batch: Batch):
        return self.inner.apply(state, batch)

    def flush(self, state):
        return self.inner.flush(state)

    def collect_stats(self, state=None) -> None:
        self.inner.collect_stats(state)

    def stage_counters(self) -> dict:
        return self.inner.stage_counters()

    def set_window_sharding(self, mesh, axis: str) -> None:
        if hasattr(self.inner, "set_window_sharding"):
            self.inner.set_window_sharding(mesh, axis)


class Pane_Farm(Basic_Operator):
    """Pane decomposition (Li et al. SIGMOD'05; ``wf/pane_farm.hpp``).

    ``plq_fn(pane_id, iterable) -> pane_result`` runs once per pane;
    ``wlq_fn(wid, iterable_of_pane_results) -> result`` combines the panes of each
    window. Sliding windows only (slide < win_len, enforced like ``:170-173``).
    Composed of two vectorized engines executing in the same program, each under
    its own scope (``plq`` / ``wlq``) inside the pattern's.

    Budgets, one pair a stage and one form for count- and time-based panes:
    ``plq_slots`` / ``wlq_slots`` are ring slots per key (tuples of an open pane
    plus a batch's share; pane results of an open window plus a batch's),
    ``plq_max_wins`` / ``wlq_max_wins`` the panes and windows one batch may fire
    over all keys. Left out, the PLQ takes ``Win_Seq``'s defaults (and its own
    ``max_wins=`` / ``archive_capacity=`` / ``tb_capacity=``, which ``**kw``
    still hands it with ``incremental=`` and the rest) and the WLQ sizes itself
    from the pane results a batch can bring, ``plq_max_wins`` of them: the panes
    of a window plus all of those on one key, and a window fired for every
    slide's worth. At a large batch both defaults exceed ``Win_Seq``'s ``[W, L]``
    gather guard, which raises and asks for the stage's budget."""

    routing = routing_modes_t.KEYBY
    pattern = pattern_t.PF_CPU
    #: the two engines, as ``stage_counters`` prefixes and as scopes
    STAGES = PANE_STAGES

    def __init__(self, plq_fn: Callable, wlq_fn: Callable, spec: WindowSpec, *,
                 num_keys: int = DEFAULT_MAX_KEYS, name: str = "pane_farm",
                 plq_parallelism: int = 1, wlq_parallelism: int = 1,
                 plq_slots: int = None, plq_max_wins: int = None,
                 wlq_slots: int = None, wlq_max_wins: int = None, **kw):
        import math
        super().__init__(name, max(plq_parallelism, wlq_parallelism))
        if spec.slide >= spec.win_len:
            raise ValueError("Pane_Farm requires sliding windows (slide < win_len), "
                             "wf/pane_farm.hpp:170-173")
        for stage_kw, value, engine_kws in (
                ("plq_slots", plq_slots, ("archive_capacity", "tb_capacity")),
                ("plq_max_wins", plq_max_wins, ("max_wins",))):
            if value is None:
                continue
            for engine_kw in engine_kws:
                if engine_kw in kw:
                    raise TypeError(f"{name}: the PLQ's budget is given twice "
                                    f"({stage_kw}= and {engine_kw}=)")
            kw[engine_kws[0]] = value
        self.spec = spec
        self.num_keys = num_keys
        self.shard_axis = "key"
        self.pane_len = math.gcd(spec.win_len, spec.slide)
        self.wpanes = spec.win_len // self.pane_len
        self.spanes = spec.slide // self.pane_len
        # PLQ: tumbling windows of one pane, same window type as the outer spec
        plq_spec = WindowSpec(self.pane_len, self.pane_len, spec.wtype, spec.delay)
        self.plq = Win_Seq(plq_fn, plq_spec, num_keys=num_keys, role=role_t.PLQ,
                           name=f"{name}_plq", **kw)
        # WLQ consumes the pane-result stream: CB windows counted in pane results
        # (panes arrive per key in ascending order without gaps for CB; for TB, pane
        # results carry ts = pane end time and WLQ windows stay time-based: one
        # result a pane at most, which is what sizes the WLQ's default budgets)
        if spec.is_cb:
            wlq_spec, stride = WindowSpec(self.wpanes, self.spanes), None
        else:
            wlq_spec = WindowSpec(spec.win_len, spec.slide, spec.wtype)
            stride = self.pane_len
        self.wlq = Win_Seq(wlq_fn, wlq_spec, num_keys=num_keys, role=role_t.WLQ,
                           name=f"{name}_wlq", archive_capacity=wlq_slots,
                           max_wins=wlq_max_wins, ts_stride=stride)
        for stage, engine in self.engines():
            engine.scope_op, engine.scope_stage = self, (stage,)

        def cascade(st_w, panes):
            with jax.named_scope(self.scope_name()), jax.named_scope("wlq"):
                return self.wlq.apply(st_w, panes)
        #: a batch of the PLQ's EOS panes through the WLQ: outside the chain's
        #: step, so compiled and scoped here
        self._cascade = jax.jit(cascade)

    def engines(self):
        """``(stage, engine)`` in the order a batch passes them."""
        return zip(self.STAGES, (self.plq, self.wlq))

    def _fired_budget(self, stage: str, engine: Win_Seq, capacity: int) -> int:
        try:
            return engine.out_capacity(capacity)
        except ValueError as e:
            raise ValueError(f"{e} (this engine is {self.name}'s {stage.upper()}: "
                             f"{stage}_max_wins= is that budget and {stage}_slots= "
                             f"bounds a time-based L)") from None

    def bind_geometry(self, batch_capacity: int) -> None:
        self.plq.bind_geometry(batch_capacity)
        self.wlq.bind_geometry(
            self._fired_budget("plq", self.plq, batch_capacity))

    def out_capacity(self, in_capacity: int) -> int:
        return self._fired_budget(
            "wlq", self.wlq, self._fired_budget("plq", self.plq, in_capacity))

    def init_state(self, payload_spec: Any):
        return {"plq": self.plq.init_state(payload_spec),
                "wlq": self.wlq.init_state(self.plq.out_spec(payload_spec))}

    def out_spec(self, payload_spec: Any) -> Any:
        return self.wlq.out_spec(self.plq.out_spec(payload_spec))

    def set_window_sharding(self, mesh, axis: str) -> None:
        self.plq.set_window_sharding(mesh, axis)
        self.wlq.set_window_sharding(mesh, axis)

    # Pane results enter WLQ directly: Win_Seq already stamps TB pane results
    # with the pane close time, so no ts fix-up is needed between the stages.

    def apply(self, state, batch: Batch):
        with jax.named_scope("plq"):
            st_p, panes = self.plq.apply(state["plq"], batch)
        with jax.named_scope("wlq"):
            st_w, out = self.wlq.apply(state["wlq"], panes)
        return {"plq": st_p, "wlq": st_w}, out

    def flush(self, state):
        """One batch a call, None at the end (``CompiledChain.flush`` calls until
        then): first the PLQ's open panes, partial, each batch of them through
        the WLQ as a batch of any other panes; then the WLQ's open windows."""
        st_p, panes = self.plq.flush(state["plq"])
        if panes is None:
            st_w, out = self.wlq.flush(state["wlq"])
            return {"plq": st_p, "wlq": st_w}, out
        st_w, out = self._cascade(state["wlq"], panes)
        return {"plq": st_p, "wlq": st_w}, out

    # both engines' device counters and budgets are this operator's, each under
    # its stage's prefix (``plq_archive_overwrites``, ``wlq_fired_window_budget``)
    def collect_stats(self, state=None) -> None:
        if state is None:
            return
        for stage, engine in self.engines():
            engine.collect_stats(state[stage])
        self._stats[0].tuples_dropped_old = sum(
            engine.get_StatsRecords()[0].tuples_dropped_old
            for _, engine in self.engines())

    def stage_counters(self) -> dict:
        return {f"{stage}_{name}": value for stage, engine in self.engines()
                for name, value in engine.stage_counters().items()}

    def drop_counters(self, state=None) -> dict:
        if state is None:
            return {}
        return {f"{stage}_{name}": value for stage, engine in self.engines()
                for name, value in engine.drop_counters(state[stage]).items()}


class Win_MapReduce(Basic_Operator):
    """Window partitioning: each window's content is split round-robin across
    ``map_parallelism`` partitions, MAP computes per-partition partials, REDUCE
    combines them (``wf/win_mapreduce.hpp:63-230``, emitters ``wf/wm_nodes.hpp``).

    ``map_fn(wid, iterable) -> partial`` per partition;
    ``reduce_fn(wid, iterable_of_partials) -> result`` over the M partials.
    Supports CB and TB windows: partitioning is round-robin by window-row position
    (the reference scatters by arrival order, ``wf/wm_nodes.hpp:45-181``; its TB
    nesting case broadcasts + drops to the same effect, ``wf/pipegraph.hpp:1922-1930``
    — here the mask-aware row makes both cases the same reshape)."""

    routing = routing_modes_t.KEYBY
    pattern = pattern_t.WMR_CPU

    def __init__(self, map_fn: Callable, reduce_fn: Callable, spec: WindowSpec, *,
                 map_parallelism: int = 2, num_keys: int = DEFAULT_MAX_KEYS,
                 name: str = "win_mapreduce", **kw):
        super().__init__(name, map_parallelism)
        if map_parallelism < 2:
            raise ValueError("Win_MapReduce requires map_parallelism >= 2 "
                             "(wf/win_mapreduce.hpp:160-166)")
        self.spec = spec
        self.M = int(map_parallelism)
        self.map_fn = map_fn
        self.reduce_fn = reduce_fn
        self.num_keys = num_keys
        self.shard_axis = "key"
        # the underlying archive/firing machinery is a Win_Seq whose window function
        # does partition-map + reduce inside the per-window vmap
        self.engine = Win_Seq(self._window_fn, spec, num_keys=num_keys,
                              name=f"{name}_engine", role=role_t.MAP, **kw)
        self.engine.scope_op = self

    def _window_fn(self, wid, it: Iterable):
        M = self.M
        L = it.mask.shape[0]                  # static row length (win_len for CB,
        P = -(-L // M)                        # archive ring for TB); pad to P*M
        def part(a):
            pad = [(0, P * M - L)] + [(0, 0)] * (a.ndim - 1)
            a = jnp.pad(a, pad) if P * M != L else a
            # round-robin: partition p gets positions p, p+M, p+2M, ...
            # (WinMap_Emitter scatter): reshape [PM] -> [P, M] -> transpose [M, P]
            return jnp.swapaxes(a.reshape((P, M) + a.shape[1:]), 0, 1)
        with jax.named_scope("map"):
            sub = Iterable(data=jax.tree.map(part, it.data), ids=part(it.ids),
                           ts=part(it.ts), mask=part(it.mask))
            partials = jax.vmap(lambda s: self.map_fn(wid, s))(sub)
        # REDUCE over the M partials (CB window of length M in the reference,
        # wf/win_mapreduce.hpp:180-230). A partition that received no tuples
        # contributes no partial — mask it out so identity values (e.g. 0 from an
        # empty sum) can't poison non-sum reduces like min.
        with jax.named_scope("reduce"):
            red_it = Iterable(
                data=partials,
                ids=jnp.arange(M, dtype=CTRL_DTYPE),
                ts=jnp.broadcast_to(jnp.asarray(0, CTRL_DTYPE), (M,)),
                mask=jnp.any(part(it.mask), axis=1))
            return self.reduce_fn(wid, red_it)

    def bind_geometry(self, batch_capacity: int) -> None:
        self.engine.bind_geometry(batch_capacity)

    def out_capacity(self, in_capacity: int) -> int:
        return self.engine.out_capacity(in_capacity)

    def init_state(self, payload_spec: Any):
        return self.engine.init_state(payload_spec)

    def out_spec(self, payload_spec: Any) -> Any:
        return self.engine.out_spec(payload_spec)

    def set_window_sharding(self, mesh, axis: str) -> None:
        self.engine.set_window_sharding(mesh, axis)

    def apply(self, state, batch: Batch):
        return self.engine.apply(state, batch)

    def flush(self, state):
        return self.engine.flush(state)

    # the engine's device counters and budgets are this operator's
    def collect_stats(self, state=None) -> None:
        self.engine.collect_stats(state)
        self._stats[0].tuples_dropped_old = \
            self.engine.get_StatsRecords()[0].tuples_dropped_old

    def stage_counters(self) -> dict:
        return self.engine.stage_counters()

    def drop_counters(self, state=None) -> dict:
        return self.engine.drop_counters(state)
