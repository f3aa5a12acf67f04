"""Hermetic perf gate — Pillar 3 of the static-analysis layer.

Measuring the headline requires hardware; this module keeps a device-free
COUNT of what the compiled programs cost (never a speed — ROADMAP C6). Two
instruments, no accelerator:

1. **XLA cost-analysis pins.** The compiled YSB and mp-matrix chains are
   AOT-lowered on the CPU backend and XLA's own cost model
   (``compiled.cost_analysis()``: FLOPs / bytes accessed per step) is
   compared against a checked-in baseline
   (``analysis/perfgate_baseline.json``). The numbers are *logical* program
   costs — deterministic for a given source tree + jax version, identical
   on a laptop and in CI — so a change that bloats the compiled chain
   (a fusion break, an accidental f64 promotion, a gather that became a
   scalar loop) fails tier-1 the day it lands, chip or no chip.

   Ratchet-down semantics (the ``analysis/baseline.json`` discipline):
   cost ABOVE the pin (beyond ``rtol``) is a **regression** finding; cost
   BELOW the pin is a **stale-pin** finding — the improvement must be
   banked with ``--update-baseline`` so the gate guards the new, better
   number. Workloads missing a pin, and pins whose workload no longer
   exists, also fail: silence is never evidence.

2. **CPU-proxy microbenchmarks.** Every kernel family in
   ``observability/names.py::KERNELS`` is timed on the CPU backend (small
   shapes, min-of-reps). Wall-clock on shared CI boxes is noisy, so these
   are ADVISORY by default: recorded in the gate report, compared against
   the baseline only under ``--strict-proxy`` with a generous factor.

CLI: ``scripts/wf_perfgate.py`` (exit 0 clean / 1 findings / 2 internal
error — the ``wf_lint.py`` contract). Baseline override:
``WF_PERFGATE_BASELINE`` env or ``--baseline``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

#: default location of the pinned baseline (checked in, ratchet-down)
BASELINE_REL = os.path.join("windflow_tpu", "analysis",
                            "perfgate_baseline.json")
#: relative tolerance around a cost pin: above = regression, below = stale
DEFAULT_RTOL = 0.02
#: advisory proxy-microbench regression factor (strict mode only)
PROXY_FACTOR = 3.0

#: compile capacities per workload — small enough that the CPU-backend AOT
#: compile stays test-budget friendly, pinned in the baseline for honesty
WORKLOAD_CAPACITY = {"ysb": 2048, "mp_matrix": 1024,
                     "nexmark_join": 512, "nexmark_session": 512,
                     "nexmark_topn": 512,
                     # the tiered-state miss->readmit->reprobe round: the
                     # Nexmark join chain with tiered= on (resolve + probe
                     # fallback + eviction compiled into the step; the
                     # io_callback lowers to a host custom-call)
                     "tiered_probe_miss": 512}


# ------------------------------------------------------------- workloads


def _build_ysb():
    """The YSB chain exactly as ``bench.py::bench_ysb`` builds it, at the
    gate capacity."""
    from ..benchmarks import ysb, device_cursor_step
    from ..runtime.pipeline import CompiledChain
    cap = WORKLOAD_CAPACITY["ysb"]
    panes_per_batch = cap // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN) + 1
    src = ysb.make_source(total=16 * cap)
    ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                       max_wins=panes_per_batch + 64)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=cap,
                          event_time=False)
    step = device_cursor_step(chain, src, cap)
    return chain, step, cap


def _build_mp_matrix():
    """A representative mp-matrix chain (the ``kf_ffat`` + chaining shape of
    ``tests/test_mp_matrix.py``): stateless map/filter fused ahead of a
    keyed TB FFAT window — the fold path the segment/pane-fold kernels
    serve."""
    import jax.numpy as jnp
    from ..basic import win_type_t
    from ..benchmarks import device_cursor_step
    from ..operators.filter import Filter
    from ..operators.map import Map
    from ..operators.win_patterns import Key_FFAT
    from ..operators.window import WindowSpec
    from ..operators.source import DeviceSource
    from ..runtime.pipeline import CompiledChain
    cap = WORKLOAD_CAPACITY["mp_matrix"]
    src = DeviceSource(lambda i: {"v": ((i * 13) % 23).astype(jnp.float32)},
                       total=16 * cap, num_keys=8)
    ops = [Map(lambda t: {"v": t.v + 1.0}),
           Filter(lambda t: t.v > 2.0),
           Key_FFAT(lambda t: t.v, jnp.add,
                    spec=WindowSpec(40, 20, win_type_t.TB), num_keys=8)]
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=cap,
                          event_time=False)
    step = device_cursor_step(chain, src, cap)
    return chain, step, cap


def _build_nexmark(query: str, cap: int):
    """One Nexmark query chain at the gate capacity (the ``bench.py::
    bench_nexmark`` construction): the join pin covers the versioned
    JoinTable upsert + registry probe, the session pin the data-dependent
    triggerer path, the top-N pin the bitonic rank merge."""
    from ..nexmark import make_query
    from ..runtime.pipeline import CompiledChain
    from ..benchmarks import device_cursor_step
    src, ops = make_query(query, total=16 * cap)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=cap,
                          event_time=False)
    step = device_cursor_step(chain, src, cap)
    return chain, step, cap


def _build_nexmark_join():
    return _build_nexmark("q3_enrich_join", WORKLOAD_CAPACITY["nexmark_join"])


def _build_nexmark_session():
    return _build_nexmark("q5_session", WORKLOAD_CAPACITY["nexmark_session"])


def _build_nexmark_topn():
    return _build_nexmark("q6_topn", WORKLOAD_CAPACITY["nexmark_topn"])


def _build_tiered_probe_miss():
    """The q3 join chain with tiered state ON (``windflow_tpu/state``):
    the pin covers the in-graph tier machinery — miss-resolution probes
    (hot + outbox), the deterministic fresh-slot re-admission, the probe
    fallback chain, and the pressure-eviction pack — compiled into the
    SAME step as the join. Hot capacity clears the admission reserve
    (WF114's sizing rule) at a 100x key space, so the compiled shape is
    the acceptance workload's."""
    from ..nexmark import make_query
    from ..runtime.pipeline import CompiledChain
    from ..benchmarks import device_cursor_step
    cap = WORKLOAD_CAPACITY["tiered_probe_miss"]
    src, ops = make_query("q3_enrich_join", 16 * cap,
                          n_auctions=100 * 16, num_slots=2048,
                          tiered=dict())
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=cap,
                          event_time=False)
    step = device_cursor_step(chain, src, cap)
    return chain, step, cap


WORKLOADS: Dict[str, Callable] = {
    "ysb": _build_ysb,
    "mp_matrix": _build_mp_matrix,
    "nexmark_join": _build_nexmark_join,
    "nexmark_session": _build_nexmark_session,
    "nexmark_topn": _build_nexmark_topn,
    "tiered_probe_miss": _build_tiered_probe_miss,
}


# ------------------------------------------------------------ cost model


def _cost_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def _arg_specs(args):
    import jax
    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if hasattr(a, "shape") else a), args)


def workload_cost(name: str) -> Dict[str, float]:
    """Compile one gate workload AOT (zero execution) and read XLA's logical
    cost model for the full chain step."""
    import jax
    import jax.numpy as jnp
    chain, step, cap = WORKLOADS[name]()
    specs = _arg_specs((tuple(chain.states),
                        jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = step.lower(*specs).compile()
    out = _cost_of(compiled)
    out["capacity"] = cap
    return out


#: reshard_pack pin geometry: one batch split into N masked per-shard
#: sub-batches (``parallel/sharding.py::ShardAssignment.split_fn`` — the
#: only per-batch program the sharded supervisors add, and the pack step of
#: the re-sharding handoff)
RESHARD_PACK_CAPACITY = 2048
RESHARD_PACK_SHARDS = 4


def reshard_pack_cost() -> Dict[str, float]:
    """AOT cost of the shard splitter at the pinned geometry — zero
    execution, CPU backend. The pin guards the claim that sharding's
    per-batch overhead is ONE masked split (a change that sneaks a gather,
    sort, or device round trip into the splitter moves this number)."""
    import jax
    import jax.numpy as jnp
    from ..batch import Batch
    from ..parallel.sharding import ShardAssignment
    cap = RESHARD_PACK_CAPACITY
    assign = ShardAssignment(RESHARD_PACK_SHARDS)
    bspec = jax.eval_shape(
        lambda: Batch.empty(cap, {"v": jnp.zeros((), jnp.float32)}))
    compiled = assign.split_fn().lower(bspec).compile()
    out = _cost_of(compiled)
    out["capacity"] = cap
    out["shards"] = RESHARD_PACK_SHARDS
    return out


def stage_costs(chain, capacity: int) -> List[dict]:
    """Per-operator cost-analysis of a built chain: each op's ``apply``
    lowered in isolation with the chain's own specs — the per-stage
    flops/bytes column ``bench.py`` attaches next to its metrics snapshots
    (so BENCH_r*.json carry *which stage* grew, not just that the chain
    did). Isolated lowering loses cross-op fusion, so the rows are an upper
    bound that localizes changes; the whole-chain number is the pin."""
    import jax
    from ..batch import Batch
    out = []
    cap = capacity
    for i, op in enumerate(chain.ops):
        row = {"op": op.getName(), "capacity": int(cap) if cap else None}
        try:
            bspec = jax.eval_shape(
                lambda c=cap, s=chain.specs[i]: Batch.empty(c, s))
            sspec = _arg_specs(chain.states[i])
            compiled = jax.jit(op.apply).lower(sspec, bspec).compile()
            row.update(_cost_of(compiled))
        except Exception as e:  # noqa: BLE001 — a stage that refuses abstract
            #               lowering (host callbacks etc.) reports, not raises
            row["error"] = f"{type(e).__name__}: {e}"
        if cap is not None:
            try:
                cap = op.out_capacity(cap)
            except Exception:  # noqa: BLE001 — capacity flow is best-effort
                cap = None
        out.append(row)
    return out


# --------------------------------------------------------- proxy benches


def _bench_one(fn, *args, reps: int = 3) -> float:
    """Min-of-reps wall time of a jitted call on the current backend."""
    import jax
    jax.block_until_ready(fn(*args))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def proxy_microbench(reps: int = 3) -> Dict[str, dict]:
    """CPU-proxy timings for every registry kernel family (reference impls —
    the trend instrument, not a TPU prediction). Keyed by
    ``names.py::KERNELS`` so a newly registered kernel without a proxy row
    fails the gate's coverage check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..ops.bitonic import merge_network
    from ..ops.histogram import keyed_pane_fold
    from ..ops.lookup import join_probe, table_lookup
    from ..ops.segment import segment_fold

    rng = np.random.default_rng(0)
    out: Dict[str, dict] = {}

    C, K, P = 8192, 100, 256
    key = jnp.asarray(rng.integers(0, K, C).astype(np.int32))
    pane = jnp.asarray((np.arange(C) // 200).astype(np.int32))
    ok = jnp.asarray(rng.random(C) < 0.9)
    f = jax.jit(lambda a, b, c: keyed_pane_fold(a, b, c, (), K, P))
    out["pane_fold_counts"] = {"elems": C, "seconds": _bench_one(
        f, key, pane, ok, reps=reps)}

    KT, CT = 1000, 8192
    table = jnp.asarray(rng.integers(0, 1 << 12, KT).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, KT, CT).astype(np.int32))
    f = jax.jit(table_lookup)
    out["lookup"] = {"elems": CT, "seconds": _bench_one(f, table, idx,
                                                        reps=reps)}

    n = 8192
    prim = jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32))
    zero = jnp.zeros((n,), jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    f = jax.jit(merge_network)
    out["ordering_merge"] = {"elems": n,
                             "seconds": _bench_one(f, prim, zero, zero, iota,
                                                   reps=reps)}

    S = 512
    vals = jnp.asarray(rng.integers(-100, 100, C).astype(np.int32))
    seg = jnp.asarray(rng.integers(0, S, C).astype(np.int32))
    f = jax.jit(lambda v, s, o: segment_fold(v, s, o, S))
    out["segment_fold"] = {"elems": C, "seconds": _bench_one(f, vals, seg, ok,
                                                             reps=reps)}

    KJ = 512
    tk = jnp.asarray(rng.permutation(1 << 16)[:KJ].astype(np.int32))
    tv = jnp.asarray(rng.integers(0, 1 << 12, KJ).astype(np.int32))
    probe = jnp.asarray(rng.integers(0, 1 << 16, C).astype(np.int32))
    f = jax.jit(join_probe)
    out["join_probe"] = {"elems": C, "seconds": _bench_one(f, tk, tv, probe,
                                                           ok, reps=reps)}

    # join: one full versioned-JoinTable step — upsert (pending ring +
    # LWW dominance + slot allocation) then probe through the registry's
    # join_probe kernel. The probe kernels keep their microbench through
    # this family (PERF_PROXY_FAMILIES coverage) even if the raw
    # "join_probe" row ever moves.
    CJ, KJ2, PJ = 1024, 256, 2048
    from ..ops.lookup import join_table_init, join_table_probe, \
        join_table_upsert
    jt = join_table_init(KJ2, PJ, {"v": jnp.zeros((), jnp.int32)})
    jk = jnp.asarray(rng.integers(0, KJ2, CJ).astype(np.int32))
    jv = {"v": jnp.asarray(rng.integers(0, 1 << 20, CJ).astype(np.int32))}
    jts = jnp.asarray(np.arange(CJ, dtype=np.int32))
    jid = jnp.asarray(np.arange(CJ, dtype=np.int32))
    jok = jnp.asarray(rng.random(CJ) < 0.5)

    def join_step(st):
        st = join_table_upsert(st, jk, jv, jts, jid, jok, delay=0)
        vals, hit = join_table_probe(st, jk, ~jok)
        return st, vals["v"], hit
    f = jax.jit(join_step)
    out["join"] = {"elems": CJ, "seconds": _bench_one(f, jt, reps=reps)}

    # spill: the tiered-state eviction/pack path (ops/lookup.py
    # join_table_tier_evict: the deterministic coldness sort + outbox pack
    # + slot clear) over a fully-loaded hot table — the device-side cost of
    # moving one batch's worth of cold keys toward the host tier
    from ..ops.lookup import (JOIN_KEY_SENTINEL, join_table_init,
                              join_table_tier_evict, join_table_tier_init)
    KT2, ST2 = 2048, 1024
    vspec = {"v": jnp.zeros((), jnp.int32)}
    ts0 = join_table_init(KT2, 8, vspec)
    ts0 = join_table_tier_init(ts0, ST2, vspec)
    ts0["key"] = jnp.asarray(rng.permutation(1 << 20)[:KT2].astype(np.int32))
    ts0["used"] = jnp.ones((KT2,), jnp.bool_)
    ts0["lap"] = jnp.asarray(rng.integers(0, 1 << 16, KT2).astype(np.int32))
    ts0["tick"] = jnp.asarray(1 << 16, jnp.int32)
    f = jax.jit(lambda s: join_table_tier_evict(s, KT2 // 2))
    out["spill"] = {"elems": KT2, "seconds": _bench_one(f, ts0, reps=reps)}

    # shard: the sharded supervisors' key-ownership splitter (one batch ->
    # N masked sub-batches, parallel/sharding.py) — the only per-batch cost
    # shard-local supervision adds; also the pack step of a reshard handoff
    from ..batch import Batch
    from ..parallel.sharding import ShardAssignment
    CS, NS = 8192, 4
    assign = ShardAssignment(NS)
    sb = Batch.of({"v": jnp.asarray(rng.random(CS).astype(np.float32))},
                  key=jnp.asarray(rng.integers(0, 64, CS).astype(np.int32)))
    out["shard"] = {"elems": CS,
                    "seconds": _bench_one(assign.split_fn(), sb, reps=reps)}

    for row in out.values():
        row["ns_per_elem"] = round(row.pop("seconds") / row["elems"] * 1e9, 3)
    return out


# --------------------------------------------------------------- baseline


def baseline_path(root: str = ".") -> str:
    override = os.environ.get("WF_PERFGATE_BASELINE", "")
    if override:
        return override if os.path.isabs(override) \
            else os.path.join(root, override)
    return os.path.join(root, BASELINE_REL)


def load_baseline(path: str) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def save_baseline(path: str, report: dict) -> None:
    payload = {
        "comment": "hermetic perf-gate pins (XLA logical cost model per "
                   "compiled workload step, CPU backend; proxy rows are "
                   "advisory). Regenerate with scripts/wf_perfgate.py "
                   "--update-baseline after an INTENTIONAL cost change — "
                   "the gate ratchets down: improvements must be banked "
                   "here or they fail as stale pins.",
        "workloads": report["workloads"],
        "proxy": report.get("proxy", {}),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def measure(skip_proxy: bool = False, reps: int = 3) -> dict:
    """The gate's current measurement: cost pins for every workload (+
    advisory proxy timings)."""
    report = {"workloads": {name: workload_cost(name) for name in WORKLOADS}}
    report["workloads"]["reshard_pack"] = reshard_pack_cost()
    if not skip_proxy:
        report["proxy"] = proxy_microbench(reps=reps)
    return report


def compare(current: dict, baseline: Optional[dict],
            rtol: float = DEFAULT_RTOL, strict_proxy: bool = False,
            proxy_factor: float = PROXY_FACTOR) -> List[dict]:
    """Findings between a measurement and the pinned baseline (empty =
    clean). Kinds: ``regression`` / ``stale-pin`` / ``unpinned`` /
    ``stale-workload`` / ``capacity-drift`` / ``proxy-regression`` /
    ``proxy-coverage``."""
    out: List[dict] = []
    if baseline is None:
        for name in current["workloads"]:
            out.append({"kind": "unpinned", "workload": name,
                        "message": f"workload {name!r} has no baseline — "
                                   f"run --update-baseline to pin it"})
        return out
    pinned = baseline.get("workloads", {})
    for name, cur in current["workloads"].items():
        pin = pinned.get(name)
        if pin is None:
            out.append({"kind": "unpinned", "workload": name,
                        "message": f"workload {name!r} has no baseline pin "
                                   f"— run --update-baseline"})
            continue
        if int(pin.get("capacity", -1)) != int(cur.get("capacity", -2)):
            out.append({"kind": "capacity-drift", "workload": name,
                        "message": f"{name}: gate capacity changed "
                                   f"({pin.get('capacity')} -> "
                                   f"{cur.get('capacity')}); costs are not "
                                   f"comparable — re-pin with "
                                   f"--update-baseline"})
            continue
        for metric in ("flops", "bytes_accessed"):
            c, p = float(cur.get(metric, 0.0)), float(pin.get(metric, 0.0))
            if p <= 0.0:
                continue
            if c > p * (1.0 + rtol):
                out.append({
                    "kind": "regression", "workload": name, "metric": metric,
                    "current": c, "pinned": p,
                    "message": f"{name}.{metric} regressed: {c:.4g} vs "
                               f"pinned {p:.4g} (+{(c / p - 1) * 100:.1f}%, "
                               f"rtol {rtol:g}) — the compiled chain got "
                               f"more expensive"})
            elif c < p * (1.0 - rtol):
                out.append({
                    "kind": "stale-pin", "workload": name, "metric": metric,
                    "current": c, "pinned": p,
                    "message": f"{name}.{metric} improved: {c:.4g} vs "
                               f"pinned {p:.4g} "
                               f"({(1 - c / p) * 100:.1f}% below) — bank it "
                               f"with --update-baseline (ratchet-down: the "
                               f"gate must guard the better number)"})
    for name in pinned:
        if name not in current["workloads"]:
            out.append({"kind": "stale-workload", "workload": name,
                        "message": f"baseline pins workload {name!r} which "
                                   f"the gate no longer measures — remove "
                                   f"via --update-baseline"})
    # proxy coverage: every registry kernel family + every extra gate family
    # (names.py::PERF_PROXY_FAMILIES) must have a proxy microbenchmark
    if "proxy" in current:
        from ..observability.names import KERNELS, PERF_PROXY_FAMILIES
        for k in KERNELS + PERF_PROXY_FAMILIES:
            if k not in current["proxy"]:
                out.append({"kind": "proxy-coverage", "workload": k,
                            "message": f"family {k!r} (names.py::KERNELS / "
                                       f"PERF_PROXY_FAMILIES) has no proxy "
                                       f"microbenchmark"})
        if strict_proxy:
            for k, cur in current["proxy"].items():
                pin = baseline.get("proxy", {}).get(k)
                if not pin:
                    continue
                c, p = float(cur["ns_per_elem"]), float(pin["ns_per_elem"])
                if p > 0 and c > p * proxy_factor:
                    out.append({
                        "kind": "proxy-regression", "workload": k,
                        "current": c, "pinned": p,
                        "message": f"proxy {k}: {c:g} ns/elem vs pinned "
                                   f"{p:g} (>{proxy_factor:g}x)"})
    return out


def run_gate(root: str = ".", rtol: float = DEFAULT_RTOL,
             skip_proxy: bool = False, strict_proxy: bool = False,
             reps: int = 3) -> Tuple[dict, List[dict]]:
    """Measure + compare against the resolved baseline. Returns
    ``(measurement report, findings)`` — empty findings = gate clean."""
    path = baseline_path(root)
    if os.environ.get("WF_PERFGATE_BASELINE", "") \
            and not os.path.exists(path):
        # an EXPLICIT override pointing nowhere must fail loudly (exit 2),
        # never read as "no baseline yet" (the wf_lint.py contract)
        raise FileNotFoundError(
            f"WF_PERFGATE_BASELINE points at a missing baseline: {path}")
    current = measure(skip_proxy=skip_proxy, reps=reps)
    findings = compare(current, load_baseline(path), rtol=rtol,
                       strict_proxy=strict_proxy)
    return current, findings
