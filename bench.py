"""Benchmark driver — one process holds the chip and runs every row.

Headline: Yahoo Streaming Benchmark (YSB) throughput in tuples/sec on one chip —
the north-star metric of BASELINE.json. The pipeline is the full YSB chain
(event source -> filter(1/3) -> campaign join -> keyed tumbling TB window count ->
device reduce sink) compiled as ONE XLA program per micro-batch, with event
generation fused on device (the reference replays an in-memory dataset from its
source threads; data never leaves the chip here either).

vs_baseline compares against the reference CUDA backend's best published number,
16.6 M tuples/s stateless MapGPU (BASELINE.md; the keyed-stateful CUDA peak is
11.8 M t/s) — the bar the TPU backend must beat.

Output: one JSON object per row on stdout, each naming the device it ran on
(platform, device_kind, device_count); the last line is the headline. A run
that finds no TPU exits 2 before measuring anything; a row that raises is
named in ``failed_rows`` and makes the exit code 1. ``WF_BENCH_ALL=1`` adds
the secondary rows. A chip belongs to one process, so nothing here starts a
child that needs the device.
"""

import json
import os
import sys
import time
import traceback

BATCH = int(os.environ.get("WF_BENCH_BATCH", 1 << 20))
STEPS = int(os.environ.get("WF_BENCH_STEPS", 40))
BASELINE_TPS = 16.6e6

#: roofline peaks by ``device_kind``, each with the source of its figures; a
#: device that is not here is an error, never a default
PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0, "bf16_tflops": 197.0,
        "source": "Google Cloud documentation, 'TPU v5e': 819 GB/s HBM "
                  "bandwidth, 197 TFLOP/s bf16 per chip",
    },
}


def device_info() -> dict:
    """What JAX reports for the default device; exits 2 unless it is a TPU
    (a number from another backend is never printed under a device name)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
    if info["platform"] != "tpu":
        print(f"bench.py measures the TPU; JAX found {info} — not measuring",
              file=sys.stderr)
        sys.exit(2)
    return info


def _peaks() -> dict:
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(f"no peak figures for device_kind {kind!r}: add a "
                       f"sourced row to bench.PEAKS")
    return PEAKS[kind]


def _arg_specs(args):
    """ShapeDtypeStruct skeleton of ``args`` — captured BEFORE a donating loop
    runs (metadata only), usable for lowering AFTER it. One implementation,
    shared with the hermetic perf gate."""
    from windflow_tpu.analysis.perfgate import _arg_specs as impl
    return impl(args)


def _roofline(step_jitted, args, step_s):
    """Roofline utilization for one compiled step: XLA's own cost model
    (``compiled.cost_analysis()``) supplies bytes accessed + FLOPs per step;
    divided by the measured step time and the device's peaks (``PEAKS``) that
    yields achieved GB/s / GFLOP/s and utilization percentages. Called after
    the timed loop, with ``_arg_specs`` captured beforehand (the loop donates
    its arguments)."""
    from windflow_tpu.analysis.perfgate import _cost_of
    peaks = _peaks()
    cost = _cost_of(step_jitted.lower(*args).compile())
    flops, bts = cost["flops"], cost["bytes_accessed"]
    gbps = bts / step_s / 1e9
    gfls = flops / step_s / 1e9
    out = {
        "bytes_per_step": bts,
        "flops_per_step": flops,
        "achieved_hbm_gbps": round(gbps, 2),
        "hbm_utilization_pct": round(100 * gbps / peaks["hbm_gbps"], 2),
        "achieved_gflops": round(gfls, 2),
        "mxu_utilization_pct": round(100 * gfls / (peaks["bf16_tflops"] * 1e3),
                                     3),
        "peaks": peaks,
    }
    if gbps > peaks["hbm_gbps"]:
        # cost_analysis() counts LOGICAL tensor traffic; when the step is fast
        # enough that the implied bandwidth exceeds the physical peak, most of
        # that traffic stayed in VMEM/fused registers and never touched HBM.
        # Flag it so nobody publishes a >100% "utilization" as a measurement.
        out["model_overcount"] = ("bytes-accessed is XLA's logical cost model; "
                                  "implied bandwidth exceeds the HBM peak, so "
                                  "the working set is VMEM-resident/fused — "
                                  "not a bandwidth measurement")
    return out


def _cursor_bench(chain, src, batch: int = None):
    """The one recipe for a timed chain bench: shared device-cursor step +
    lowering specs (a ShapeDtypeStruct cursor spec — no device array is
    materialized over the flaky link just to read a shape)."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.benchmarks import device_cursor_step
    step = device_cursor_step(chain, src, batch or BATCH)
    specs = _arg_specs((tuple(chain.states),
                        jax.ShapeDtypeStruct((), jnp.int32)))
    return step, specs


def _bench_loop(step, states, n_steps, reps: int = 1):
    """Time ``n_steps`` async-dispatched steps of a device-cursor step
    (``step(states, cur) -> (states, cur + batch, out)`` — see
    ``windflow_tpu.benchmarks.device_cursor_step``); with ``reps`` > 1 return
    the median rep (dispatch-pipelining jitter is large when steps are
    fast). The caller's source must cover reps*n_steps+1
    batches. The cursor stays on device, so no bench row carries a per-step
    host-scalar upload."""
    import jax
    import jax.numpy as jnp
    cur = jnp.asarray(0, jnp.int32)
    # warmup/compile
    states, cur, out = step(states, cur)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            states, cur, out = step(states, cur)
            # async dispatch: the host enqueues step i+1 while the device runs i
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2], states


def bench_ysb():
    import jax
    import jax.numpy as jnp
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.runtime.pipeline import CompiledChain

    # pane ring: one batch spans BATCH/EVENTS_PER_TICK time units =
    # BATCH/(EVENTS_PER_TICK*WIN_LEN) panes; hold 2 batches + the window span
    panes_per_batch = BATCH // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN) + 1
    src = ysb.make_source(total=(STEPS + 2) * BATCH)
    ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                       max_wins=panes_per_batch + 64)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH,
                          event_time=False)

    step, specs = _cursor_bench(chain, src)
    dt, _ = _bench_loop(step, tuple(chain.states), STEPS)
    roof = _roofline(step, specs, dt / STEPS)
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "roofline": roof}


def bench_ysb_wmr(map_parallelism: int = 4):
    """YSB with the Win_MapReduce window stage — the reference's other
    headline YSB pipeline (``src/yahoo_test_cpu/test_ysb_wmr.cpp``: each
    window's content partitioned over MAP workers, partial counts combined by
    REDUCE). Same source/filter/join prefix as bench_ysb.

    Geometry is WMR-appropriate, not Key_FFAT's: Win_MapReduce rides the
    gather-based Win_Seq engine whose TB emission gathers the FULL per-key
    ring per fired window (L = tb_capacity) and whose fired-window budget W is
    SHARED across all keys — at the FFAT bench's win_len=100 that is ~105k
    fired windows x the ring per batch, infeasible by design (WMR is the
    reference's pattern for FEW, LARGE windows; per-pane counting is what
    Key_FFAT is for). win_len = 1000 ticks gives ~1 window/key/batch:
    W = num_keys * (windows/batch + margin), ring = 8192 > per-key window
    span (~3.3k tuples) + one batch of arrivals (~3.5k).

    The run self-checks exactness: the summed window counts must cover the
    views of every COMPLETED window; a mis-sized budget (deferral collapse or
    ring overwrite) undercounts and raises instead of reporting a degenerate
    pipeline's throughput."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.operators.sink import ReduceSink
    from windflow_tpu.runtime.pipeline import CompiledChain

    WIN_LEN = 1000                       # ticks; 10x the FFAT bench's windows
    wins_per_batch = BATCH // (ysb.EVENTS_PER_TICK * WIN_LEN) + 1
    src = ysb.make_source(total=(STEPS + 2) * BATCH)
    ops = ysb.make_ops_wmr(win_len=WIN_LEN,
                           map_parallelism=map_parallelism,
                           max_wins=ysb.N_CAMPAIGNS * (wins_per_batch + 2),
                           tb_capacity=8192)
    ops.append(ReduceSink(lambda t: t.data, name="wmr_total"))
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH,
                          event_time=False)

    step, specs = _cursor_bench(chain, src)
    dt, states = _bench_loop(step, tuple(chain.states), STEPS)
    # exactness self-check: every window whose span is fully delivered AND
    # past the flush horizon must have fired with its full count. After
    # n_batches = STEPS+1 (incl. warmup), ticks delivered = n*BATCH/RATE;
    # completed windows cover ticks [0, floor(.../WIN_LEN)*WIN_LEN); views in
    # that range = ceil(ticks*RATE/3) (every 3rd global index is a view).
    total = int(np.asarray(jax.tree.leaves(states[-1])[0]))
    ticks = (STEPS + 1) * BATCH // ysb.EVENTS_PER_TICK
    complete_ticks = (ticks // WIN_LEN - 1) * WIN_LEN   # -1: delay horizon
    expect_min = (complete_ticks * ysb.EVENTS_PER_TICK + 2) // 3
    if total < expect_min:
        raise RuntimeError(
            f"bench_ysb_wmr undercounted: {total} < {expect_min} views over "
            f"completed windows — budget/ring mis-sized, refusing to report "
            f"a degenerate pipeline")
    roof = _roofline(step, specs, dt / STEPS)
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "roofline": roof}


def bench_nexmark(batch: int = None, steps: int = None):
    """The Nexmark-class query suite (``windflow_tpu/nexmark``): tuples/s
    per query over the names.py::NEXMARK_QUERIES registry, each chain
    compiled + driven with the same device-cursor step discipline as
    bench_ysb. Smaller default batch than the headline: the join/session
    state machinery is [C, A]-quadratic in places, and the suite's job is
    the per-query trend, not a memory-bandwidth headline. ``WF_BENCH_NEXMARK_EVENTS`` overrides the
    per-query event budget."""
    import jax
    from windflow_tpu.benchmarks import device_cursor_step
    from windflow_tpu.nexmark import QUERIES, make_query
    from windflow_tpu.runtime.pipeline import CompiledChain

    batch = int(batch or min(BATCH, 1 << 14))
    steps = int(steps or min(STEPS, 20))
    budget = os.environ.get("WF_BENCH_NEXMARK_EVENTS", "")
    total = int(budget) if budget else (steps + 2) * batch
    rows = {}
    for name in QUERIES:
        src, ops = make_query(name, total)
        chain = CompiledChain(ops, src.payload_spec(), batch_capacity=batch,
                              event_time=False)
        step = device_cursor_step(chain, src, batch)
        dt, _ = _bench_loop(step, tuple(chain.states), steps)
        rows[name] = {"tps": steps * batch / dt, "step_s": dt / steps,
                      "batch": batch}
        # e2e event-time p99 per query: a SHORT separate pass with the
        # event-time histograms compiled in (the timed row above stays the
        # exact monitoring-off program) — the max per-(operator, stream)
        # observed-lateness p99, in event-time ticks.
        rows[name]["event_time_p99"] = _nexmark_event_time_p99(
            name, total, batch, min(steps, 5))
    # the tiered-state acceptance row: the q3 stream-table join at 100x the
    # per-batch key space with a FIXED hot table (windflow_tpu/state two-tier
    # layer) — the ROADMAP-3 claim measured: overflow_drops stays 0 while
    # cold keys spill to host and re-admit on probe miss, with a bounded
    # per-step p99 (the drive loop runs chain.push so the async spill
    # maintenance runs exactly as in production)
    rows["q3_enrich_join_100x"] = _bench_nexmark_tiered_100x(batch, steps)
    return rows


def _bench_nexmark_tiered_100x(batch: int, steps: int) -> dict:
    import time as _time
    import jax
    import numpy as np
    from windflow_tpu.nexmark import make_query
    from windflow_tpu.runtime.pipeline import CompiledChain
    b = min(int(batch), 1024)       # the [R, K] resolve compare is quadratic
    hot = 4 * b                     # clears the WF114 admission reserve (3b)
    keys = 100 * b                  # 100x the per-batch working set
    n_steps = max(4, min(steps, 12))
    total = keys + n_steps * b      # definition prefix + probe traffic
    src, ops = make_query("q3_enrich_join", total, n_auctions=keys,
                          num_slots=hot, tiered=dict())
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=b,
                          event_time=False)
    times = []
    for bt in src.batches(b):
        t0 = _time.perf_counter()
        out = chain.push(bt)
        jax.block_until_ready(out)
        times.append(_time.perf_counter() - t0)
    st = chain.states[0]
    timed = sorted(times[1:])       # drop the compile step
    p99 = timed[min(len(timed) - 1, int(0.99 * len(timed)))]
    n = len(times)
    spills = int(np.asarray(st["spills"]))
    readmits = int(np.asarray(st["readmits"]))
    return {
        "tps": n * b / sum(times),
        "step_s": sum(timed) / max(1, len(timed)),
        "p99_step_s": p99,
        "batch": b, "keys": keys, "hot_capacity": hot, "batches": n,
        "overflow_drops": int(np.asarray(st["dropped"])),
        "state_spills": spills, "state_readmits": readmits,
        "spills_per_step": round(spills / n, 2),
        "readmits_per_step": round(readmits / n, 2),
        "cold_keys": ops[0]._tier.store.key_count(),
    }


def _nexmark_event_time_p99(name, total, batch, steps):
    """Max observed-lateness p99 (ticks) across one query's stateful
    operators after ``steps`` batches with event-time monitoring compiled
    in; None when the query has no lateness surface."""
    from windflow_tpu.benchmarks import device_cursor_step
    from windflow_tpu.nexmark import make_query
    from windflow_tpu.runtime.pipeline import CompiledChain
    src, ops = make_query(name, total)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=batch,
                          event_time=True)
    step = device_cursor_step(chain, src, batch)
    states = tuple(chain.states)
    import jax.numpy as jnp
    cur = jnp.asarray(0, jnp.int32)
    for _ in range(int(steps)):
        states, cur, _out = step(states, cur)
    chain.states = list(states)
    p99 = None
    for op, st in zip(chain.ops, chain.states):
        sec = op.event_time_stats(st)
        for summ in ((sec or {}).get("lateness") or {}).values():
            if summ.get("total"):
                p99 = max(p99 or 0, summ["p99"])
    return p99


def bench_stateless():
    """Config 2 of BASELINE.json: Source->Map->Filter->Sink micro-batch."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.operators.map import Map
    from windflow_tpu.operators.filter import Filter
    from windflow_tpu.operators.sink import ReduceSink
    from windflow_tpu.operators.source import DeviceSource
    from windflow_tpu.runtime.pipeline import CompiledChain

    src = DeviceSource(lambda i: {"v": (i % 1000).astype(jnp.float32)},
                       total=(STEPS + 2) * BATCH, num_keys=512)
    ops = [Map(lambda t: {"v": t.v * 2.0 + 1.0}),
           Filter(lambda t: t.v > 100.0),
           ReduceSink(lambda t: t.v)]
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH,
                          event_time=False)

    step, specs = _cursor_bench(chain, src)
    dt, _ = _bench_loop(step, tuple(chain.states), STEPS)
    roof = _roofline(step, specs, dt / STEPS)
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "roofline": roof}


def bench_keyed_cb():
    """Config 3: Key_Farm/Win_SeqFFAT keyed count-based sliding-window sum."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.operators.source import DeviceSource
    from windflow_tpu.operators.win_patterns import Key_FFAT
    from windflow_tpu.operators.window import WindowSpec
    from windflow_tpu.runtime.pipeline import CompiledChain

    K = 512
    reps = 3
    src = DeviceSource(lambda i: {"v": (i % 97).astype(jnp.float32)},
                       total=(reps * STEPS + 2) * BATCH, num_keys=K)
    op = Key_FFAT(lambda t: t.v, jnp.add,
                  spec=WindowSpec(1024, 512), num_keys=K)
    chain = CompiledChain([op], src.payload_spec(), batch_capacity=BATCH,
                          event_time=False)

    step, specs = _cursor_bench(chain, src)
    dt, _ = _bench_loop(step, tuple(chain.states), STEPS, reps=reps)
    roof = _roofline(step, specs, dt / STEPS)
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "roofline": roof}


def measure_floor():
    """The host<->device synchronization floor of THIS environment, measured so
    latency numbers decompose honestly: the round trip of a tiny jitted step
    and the D2H rate of a 4 MB array. Every latency the curves report
    includes this floor — the device-side component is (raw - rtt)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.zeros((16,))
    _ = np.asarray(x)                     # enter real-transfer mode
    f = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(f(x))
    rtt = []
    for _ in range(20):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        rtt.append(time.perf_counter() - t0)
    rtt.sort()
    big = jax.device_put(np.zeros(1 << 20, np.float32))
    jax.block_until_ready(big)
    t0 = time.perf_counter()
    _ = np.asarray(big)
    d2h_s = time.perf_counter() - t0
    return {"sync_rtt_ms": rtt[len(rtt) // 2] * 1e3,
            "d2h_mbps": 4.0 / d2h_s}


def bench_latency_curve(batches=(4096, 16384, 65536, 262144), steps: int = 80,
                        depth: int = 2):
    """Per-window-result latency, measured the reference's way
    (``ysb_nodes.hpp:200-216``): emission timestamp -> host receipt, per result.

    A batch's tuples are "emitted" when the batch is submitted (ship_time); its
    window results are received when their async D2H copy lands on the host
    (receipt_time). The loop runs PIPELINED with ``depth`` batches in flight
    (bounded-queue backpressure — the reference's FF_BOUNDED_BUFFER role): the
    device computes batch i while results of batch i-depth are harvested, so
    latency ~= depth * step_time + transfer, not a blocking sync per batch.
    Window results ship as ONE packed [4, W] i32 array (key, wid, count, valid)
    to cost a single transfer per batch."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.runtime.async_sink import AsyncResultShipper
    from windflow_tpu.runtime.pipeline import CompiledChain

    out_rows = []
    for batch in batches:
        panes_per_batch = max(batch // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN), 1) + 1
        src = ysb.make_source(total=(steps + 4) * batch)
        ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                           max_wins=panes_per_batch + 64)
        chain = CompiledChain(ops, src.payload_spec(), batch_capacity=batch,
                              event_time=False)

        # device-resident cursor, advanced in-program: a per-step host-scalar
        # upload would sit INSIDE every latency sample and under-pipeline
        # the curve
        from windflow_tpu.benchmarks import device_cursor_step
        step = device_cursor_step(
            chain, src, batch,
            out_fn=lambda b: jnp.stack([b.key, b.id,
                                        jnp.asarray(b.payload, jnp.int32),
                                        b.valid.astype(jnp.int32)]))
        states = tuple(chain.states)
        cur = jnp.asarray(0, jnp.int32)
        states, cur, packed = step(states, cur)
        jax.block_until_ready(packed)                     # compile outside timing

        shipper = AsyncResultShipper(depth=depth)
        lat = []
        n_results = 0
        t_wall0 = time.perf_counter()
        for i in range(1, steps + 1):
            states, cur, packed = step(states, cur)       # async dispatch
            shipper.ship(packed, tag=i)
            for rec in shipper.harvest():                 # blocks only past depth
                lat.append(rec.receipt_time - rec.ship_time)
                n_results += int((rec.value[3] > 0).sum())
        for rec in shipper.drain():
            lat.append(rec.receipt_time - rec.ship_time)
            n_results += int((rec.value[3] > 0).sum())
        t_wall = time.perf_counter() - t_wall0
        lat.sort()
        out_rows.append({
            "batch": batch,
            "p50_ms": lat[len(lat) // 2] * 1e3,
            "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3,
            "tput_mtps": steps * batch / t_wall / 1e6,
            "step_ms": t_wall / steps * 1e3,
            "results": n_results,
        })
    return {"depth": depth, "rows": out_rows}


def bench_adaptive(total_batches: int = 240, base_batch: int = None):
    """Closed-loop capacity autotuning through the real Pipeline driver: a
    stateless map+filter chain starts at ``base_batch`` and the control
    plane's hill-climber converges on the ladder rung this device actually
    sustains best. Returns end-to-end tuples/s, the chosen capacity, and the
    controller's decision counters — the closed-loop convergence evidence."""
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu import control as wfcontrol
    from windflow_tpu.operators.source import DeviceSource

    import tempfile
    base = base_batch or max(BATCH // 4, 1 << 12)
    src = DeviceSource(lambda i: {"v": (i % 1000).astype(jnp.float32)},
                       total=total_batches * base, num_keys=512)
    with tempfile.TemporaryDirectory(prefix="wf_bench_tuning_") as tmp:
        cfg = wf.ControlConfig(autotune=True, ladder_up=2, ladder_down=2,
                               decide_every=6, settle_batches=2,
                               cache_path=os.path.join(tmp, "tuning.json"))
        pipe = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v * 2.0 + 1.0}),
                                 wf.Filter(lambda t: t.v > 100.0),
                                 wf.ReduceSink(lambda t: t.v)],
                           batch_size=base, control=cfg)
        t0 = time.perf_counter()
        pipe.run()
        dt = time.perf_counter() - t0
    ctl = wfcontrol.counters()
    return {
        "tps": total_batches * base / dt,
        "base_capacity": base,
        "chosen_capacity": wfcontrol.gauges().get("chosen_capacity"),
        "capacity_switches": ctl["capacity_switches"],
        "tuning_decisions": ctl["tuning_decisions"],
    }


def bench_keyed_stateful(num_keys: int):
    """MapGPU-stateful analogue (BASELINE.md rows 3-5): keyed map with a per-key
    running state folded in stream order (the reference keeps a per-key device
    scratch, wf/map_gpu_node.hpp:216-222). Sweep num_keys to reproduce the
    1-key serialization floor / 500-key peak / 10k-key curve."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.operators.accumulator import Accumulator
    from windflow_tpu.operators.sink import ReduceSink
    from windflow_tpu.operators.source import DeviceSource
    from windflow_tpu.runtime.pipeline import CompiledChain

    reps = 3
    src = DeviceSource(lambda i: {"v": (i % 1000).astype(jnp.float32)},
                       total=(reps * STEPS + 2) * BATCH, num_keys=num_keys)
    # per-key running state folded in stream order: the associative formulation
    # (segmented prefix scan + HBM carry table) — the TPU-native equivalent of the
    # reference's sequential per-key scratch update; no serialization floor at K=1
    ops = [Accumulator(lambda t: t.data["v"], init_value=0.0,
                       num_keys=max(num_keys, 8)),
           ReduceSink(lambda t: t.data)]
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH,
                          event_time=False)

    step, _ = _cursor_bench(chain, src)
    dt, _ = _bench_loop(step, tuple(chain.states), STEPS, reps=reps)
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "num_keys": num_keys}


def bench_scatter(fanout: int, variant: str = "sort"):
    """Keyed-scatter emitter analogue (BASELINE.md row 9, scattering study):
    partition each batch into per-destination sub-batches on device. Two
    formulations, A/B'd like the reference's own scattering study
    (``src/GPU_Tests/scattering``): ``sort`` = stable argsort grouping,
    ``onehot`` = sort-free one-hot-cumsum ranks."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops.compaction import (partition_by_destination,
                                             partition_by_destination_onehot)

    part = (partition_by_destination if variant == "sort"
            else partition_by_destination_onehot)
    cap = 2 * BATCH // fanout

    @jax.jit
    def step(carry, start):
        i = start + jnp.arange(BATCH, dtype=jnp.int32)
        key = (i.astype(jnp.uint32) * jnp.uint32(2654435761) % 10007).astype(jnp.int32)
        dest = key % fanout
        valid = jnp.ones((BATCH,), jnp.bool_)
        gather_idx, out_valid = part(dest, valid, fanout, cap)
        v = (i % 1000).astype(jnp.float32)
        sub = jnp.take(v, gather_idx)              # [fanout, cap] sub-batch payloads
        # carry the sum so step N+1 data-depends on step N: the final
        # block_until_ready then bounds ALL steps, not just the last
        return carry + jnp.sum(jnp.where(out_valid, sub, 0.0))

    carry = step(jnp.float32(0), 0)
    jax.block_until_ready(carry)
    times = []
    pos = 1
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            carry = step(carry, pos * BATCH)
            pos += 1
        jax.block_until_ready(carry)
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    return {"tps": STEPS * BATCH / dt, "step_s": dt / STEPS, "batch": BATCH,
            "fanout": fanout, "variant": variant}


def bench_ordering_overhead(total: int = 200_000, batch: int = 4096):
    """DETERMINISTIC-vs-DEFAULT merge throughput (the Ordering_Node's hot-path
    cost — reference inserts an Ordering_Node before each replica in
    DETERMINISTIC mode, ``wf/pipegraph.hpp:1197-1199``). Two sources -> merge ->
    map -> reduce, identical streams, both modes."""
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.basic import Mode
    from windflow_tpu.runtime.pipegraph import PipeGraph

    def run(mode):
        g = PipeGraph("ord", mode=mode, batch_size=batch)
        sa = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=total,
                       num_keys=8, ts_fn=lambda i: 2 * i, name="a")
        sb = wf.Source(lambda i: {"v": -i.astype(jnp.float32)}, total=total,
                       num_keys=8, ts_fn=lambda i: 2 * i + 1, name="b")
        pa, pb = g.add_source(sa), g.add_source(sb)
        m = pa.merge(pb)
        m.add(wf.Map(lambda t: {"v": t.v * 2.0}))
        m.add(wf.ReduceSink(lambda t: t.v, name="out"))
        t0 = time.perf_counter()
        res = g.run()
        dt = time.perf_counter() - t0
        return 2 * total / dt, float(res["out"])

    # warm BOTH modes' compile caches (the Ordering_Node's jitted cores are
    # module-level and shared across instances, so a warmup graph's traces
    # carry over to the timed run)
    run(Mode.DEFAULT)
    run(Mode.DETERMINISTIC)
    d_tps, d_sum = run(Mode.DEFAULT)
    o_tps, o_sum = run(Mode.DETERMINISTIC)
    if d_sum != o_sum:                       # ordering must not change the sum
        raise RuntimeError(f"DETERMINISTIC sum {o_sum} != DEFAULT sum {d_sum}")
    return {"default_tps": d_tps, "deterministic_tps": o_tps,
            "ratio": o_tps / d_tps, "batch": batch}


def measure_h2d_bandwidth(mb: int = 64, streams: int = 4):
    """Aggregate host->device transfer bandwidth (MB/s): ``streams`` concurrent
    device_put transfers, the way the prefetch path issues them. Random
    payload, so that nothing on the way can compress it."""
    import jax
    import numpy as np
    rng = np.random.default_rng(7)
    bufs = [rng.random(((mb // streams) << 18,), np.float32)
            for _ in range(2 * streams)]
    jax.block_until_ready([jax.device_put(b) for b in bufs[:streams]])  # warm path
    t0 = time.perf_counter()
    jax.block_until_ready([jax.device_put(b) for b in bufs[streams:]])
    n_bytes = sum(b.nbytes for b in bufs[streams:])
    return n_bytes / 1e6 / (time.perf_counter() - t0)     # MB/s (1e6 bytes)


def bench_ingest():
    """Ingest-inclusive YSB: host-resident numpy events -> prefetch thread with
    overlapped device_put (double buffering, the reference GPU path's pinned
    cudaMemcpyAsync protocol) -> full YSB chain. The reference's cost model is
    per-tuple host ingest (``wf/source.hpp:184``); its in-memory dataset replay is
    mirrored by pre-generated host chunks. ``transport_ceiling_tps`` is derived
    from the H2D bandwidth measured in the same run."""
    import jax
    import numpy as np
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.operators.source import GeneratorSource
    from windflow_tpu.runtime.pipeline import CompiledChain

    B = 1 << 18
    steps = 24
    # host event chunks: ad_id/event_type payload + campaign key + event ts
    chunks = []
    for s in range(steps):
        i = np.arange(s * B, (s + 1) * B, dtype=np.int64)
        chunks.append((
            {"ad_id": ((i * 7919) % ysb.N_ADS).astype(np.int32),
             "event_type": (i % 3).astype(np.int32)},
            ((i * 7919) % ysb.N_ADS % ysb.N_CAMPAIGNS).astype(np.int32),
            (i // ysb.EVENTS_PER_TICK).astype(np.int32)))
    bytes_per_tuple = 4 + 4 + 4 + 4 + 4 + 1      # payload + key + ts + id + valid

    src = GeneratorSource(lambda: iter(chunks),
                          {"ad_id": jax.ShapeDtypeStruct((), "int32"),
                           "event_type": jax.ShapeDtypeStruct((), "int32")},
                          name="ysb_host_source")
    panes_per_batch = B // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN) + 1
    ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                       max_wins=panes_per_batch + 64)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=B,
                          event_time=False)

    # warmup/compile on the first chunk
    warm = next(iter(src.batches(B)))
    jax.block_until_ready(chain.push(warm).valid)

    t0 = time.perf_counter()
    out = None
    for b in src.batches_prefetched(B, depth=4):
        out = chain.push(b)
    jax.block_until_ready(out.valid)
    dt = time.perf_counter() - t0
    h2d_mbps = measure_h2d_bandwidth()
    return {"tps": steps * B / dt, "step_s": dt / steps, "batch": B,
            "h2d_mbps": h2d_mbps, "bytes_per_tuple": bytes_per_tuple,
            "transport_ceiling_tps": h2d_mbps * 1e6 / bytes_per_tuple}


def bench_ingest_decomposition(n: int = 1 << 20, reps: int = 7):
    """Split the ingest path into separately-measured terms so the ingest story
    is arithmetic over constants, not an assertion:

    1. host framing — AoS record buffer -> SoA columns (``wf_unpack_records``)
       and key hashing (``wf_hash_int_keys``), in ns/tuple and GB/s; this is
       the reference's per-tuple Source cost model (``wf/source.hpp:184``) paid
       once per batch instead of per tuple;
    2. transfer — ``device_put`` of the framed columns on THIS backend;
    3. chain — the on-device compute, measured separately by bench_ysb.

    The ingest-inclusive ceiling is min(framing, transfer) by construction
    (prefetch overlaps them); the returned dict carries each term."""
    import jax
    import numpy as np
    from windflow_tpu.native import hash_keys_native, unpack_records

    rec_dt = np.dtype([("ad_id", "<i4"), ("event_type", "<i4"), ("ts", "<i4")])
    rng = np.random.default_rng(3)
    buf = np.empty(n, rec_dt)
    buf["ad_id"] = rng.integers(0, 100000, n, dtype=np.int32)
    buf["event_type"] = rng.integers(0, 3, n, dtype=np.int32)
    buf["ts"] = np.arange(n, dtype=np.int32)

    def _median(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    frame_s = _median(lambda: unpack_records(buf))
    cols = unpack_records(buf)
    hash_s = _median(lambda: hash_keys_native(cols["ad_id"], 10007))

    # transfer: the framed columns, H2D, this backend
    put = lambda: jax.block_until_ready(
        [jax.device_put(c) for c in cols.values()])
    put()                                         # warm the path
    xfer_s = _median(put)
    col_bytes = sum(c.nbytes for c in cols.values())

    framing_tps = n / (frame_s + hash_s)
    xfer_tps = n / xfer_s
    return {
        "framing_ns_per_tuple": frame_s / n * 1e9,
        "framing_gbps": buf.nbytes / frame_s / 1e9,
        "hash_ns_per_tuple": hash_s / n * 1e9,
        "transfer_mbps": col_bytes / xfer_s / 1e6,
        "bytes_per_tuple": buf.nbytes // n,
        "host_framing_tps": framing_tps,
        "transfer_tps": xfer_tps,
        "ingest_ceiling_tps": min(framing_tps, xfer_tps),
    }


def bench_drive_loop(batches=(4096, 262144, 1 << 20),
                     total_tuples: int = 1 << 22):
    """Host-side cost of the Python drive loop, per batch.

    Every fresh PipeGraph re-traces its user lambdas, so timing one run times
    compilation. Instead each batch size runs the SAME graph shape at two
    stream lengths N1 < N2: both pay the identical compile cost C, so the
    steady-state per-batch driver wall time is (t2-t1)/(N2-N1), compile
    cancelled. Subtracting the bare pre-jitted step loop's per-batch time
    (device dispatch only, measured warm) leaves ``driver_us_per_batch`` — the
    Python loop's own cost. Rows feed ROADMAP A2's decision on moving the
    steady-state loop behind the native layer (SURVEY §7: Python as toolchain,
    not data path)."""
    import jax
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.operators.source import DeviceSource
    from windflow_tpu.runtime.pipeline import CompiledChain
    from windflow_tpu.runtime.pipegraph import PipeGraph

    rows = []
    for B in batches:
        n1 = max(total_tuples // B // 4, 4)
        n2 = max(total_tuples // B, 4 * n1)

        def run_graph(n_batches):
            g = PipeGraph("drv", batch_size=B)
            (g.add_source(wf.Source(lambda i: {"v": (i % 97).astype(jnp.float32)},
                                    total=n_batches * B, num_keys=8))
             .add(wf.Map(lambda t: {"v": t.v * 2.0 + 1.0}))
             .add(wf.ReduceSink(lambda t: t.v, name="out")))
            t0 = time.perf_counter()
            g.run()
            return time.perf_counter() - t0

        run_graph(4)                          # warm the process-wide caches
        t1 = min(run_graph(n1) for _ in range(2))
        t2 = min(run_graph(n2) for _ in range(2))
        per_batch_s = max(t2 - t1, 0.0) / (n2 - n1)

        # bare loop: same ops, pre-jitted, no driver
        src = DeviceSource(lambda i: {"v": (i % 97).astype(jnp.float32)},
                           total=(n2 + 2) * B, num_keys=8)
        ops = [wf.Map(lambda t: {"v": t.v * 2.0 + 1.0}),
               wf.ReduceSink(lambda t: t.v, name="out")]
        chain = CompiledChain(ops, src.payload_spec(), batch_capacity=B,
                              event_time=False)

        # bare loop carries a DEVICE cursor exactly like the driven path
        # (operators/source.py::batches) — if it uploaded a host int per step
        # the ~0.1 ms H2D would no longer cancel in the subtraction and
        # driver_us_per_batch would read low by that amount
        from windflow_tpu.benchmarks import device_cursor_step
        step = device_cursor_step(chain, src, B)
        states_b = tuple(chain.states)
        cur = jnp.asarray(0, jnp.int32)
        states_b, cur, out = step(states_b, cur)      # warm/compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n2 - n1):
            states_b, cur, out = step(states_b, cur)
        jax.block_until_ready(out)
        bare_s = time.perf_counter() - t0

        step_us = bare_s / (n2 - n1) * 1e6
        drv_us = per_batch_s * 1e6 - step_us
        rows.append({
            "batch": B, "n1": n1, "n2": n2,
            "driver_wall_us_per_batch": round(per_batch_s * 1e6, 1),
            "step_us_per_batch": round(step_us, 1),
            "driver_us_per_batch": round(max(drv_us, 0.0), 1),
            "driver_overhead_pct": round(100 * max(drv_us, 0.0)
                                         / max(step_us, 1e-9), 1),
        })
    return {"rows": rows}


def bench_framing_scaling(n: int = 1 << 22, workers=(1, 2, 4, 8), reps: int = 5):
    """Multi-core host framing sweep: sharded AoS->SoA
    transpose (``parallel_unpack``) vs worker count — the reference's 1-14
    source-thread sweep applied to framing. On a single-core container the
    curve is flat by construction; the row set records the container's core
    count so the number reads honestly."""
    import numpy as np
    from windflow_tpu.native import hardware_concurrency, parallel_unpack

    rec_dt = np.dtype([("ad_id", "<i4"), ("event_type", "<i4"), ("ts", "<i4")])
    rng = np.random.default_rng(5)
    buf = np.empty(n, rec_dt)
    buf["ad_id"] = rng.integers(0, 100000, n, dtype=np.int32)
    buf["event_type"] = rng.integers(0, 3, n, dtype=np.int32)
    buf["ts"] = np.arange(n, dtype=np.int32)

    rows = []
    for w in workers:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            parallel_unpack(buf, workers=w)
            ts.append(time.perf_counter() - t0)
        dt = sorted(ts)[len(ts) // 2]
        rows.append({"workers": w, "ns_per_tuple": round(dt / n * 1e9, 2),
                     "tps": round(n / dt), "gbps": round(buf.nbytes / dt / 1e9, 2)})
    return {"host_cores": hardware_concurrency(),
            "rows": rows,
            "speedup_at_max": round(rows[-1]["tps"] / rows[0]["tps"], 2)}


def bench_pallas_ab(shapes=((4096, 512), (1024, 1024), (8192, 256)),
                    iters: int = 30):
    """A/B the Pallas masked window reduce (ops/pallas_kernels.py — the
    ComputeBatch_Kernel analogue's inner aggregation) against the XLA
    formulation at fired-window-batch shapes [W, L]. The winner belongs in
    the data path; the loser's existence is only justified by this number.
    A Mosaic failure raises (the row fails), it is not recorded as a cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from windflow_tpu.ops import pallas_kernels as pk

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6

    rows = []
    for W, L in shapes:
        vals = jnp.asarray(np.random.default_rng(0).random((W, L), np.float32))
        mask = jnp.asarray(np.random.default_rng(1).random((W, L)) < 0.7)
        rows.append({"W": W, "L": L,
                     "xla_us": timed(jax.jit(pk._xla_masked_sum), vals, mask),
                     "pallas_us": timed(pk._pallas_masked_sum, vals, mask)})
    return {"rows": rows}


def bench_native_ring(n: int = 200_000, capacity: int = 1024):
    """Host-side SPSC ring throughput (tokens/s) across two pinned threads —
    the FastFlow-role substrate under the threaded driver
    (``native/spsc_queue.cpp``; reference L0, lock-free SPSC queues). Each
    token stands for a micro-batch handle, so sustaining ~1M tokens/s carries
    ~1T tuples/s of stream at 1M-tuple batches — the ring is never the
    bottleneck. Runs entirely on the host (no device needed)."""
    import threading
    from windflow_tpu.native import SPSCQueue, pin_thread

    q = SPSCQueue(capacity)
    sentinel = object()

    def producer():
        pin_thread(0)
        for i in range(n):
            q.push(i)
        q.push(sentinel)

    got = []

    def consumer():
        pin_thread(1)
        c = 0
        while True:
            ok, item = q.pop(spin=1024)
            if not ok:
                continue
            if item is sentinel:
                break
            c += 1
        got.append(c)

    t0 = time.perf_counter()
    tp = threading.Thread(target=producer)
    tc = threading.Thread(target=consumer)
    tc.start(); tp.start(); tp.join(); tc.join()
    dt = time.perf_counter() - t0
    if got[0] != n:
        raise RuntimeError(f"ring delivered {got[0]} of {n} tokens")
    return {"python_binding_tokens_per_s": n / dt}


def bench_native_ring_raw():
    """Raw ring throughput measured entirely in C across two threads
    (``wf_queue_selfbench``) — each token is a micro-batch handle."""
    from windflow_tpu.native import hardware_concurrency, queue_selfbench
    return {"tokens_per_s": queue_selfbench(),
            "host_cores": hardware_concurrency()}


#: (row name, callable returning a JSON-serializable dict). Every row runs in
#: THIS process, one after another: a warmed executable's step time was
#: checked on the chip to be unchanged by building and running another chain
#: in the same process (CHANGES.md, PR 21), so rows need no isolation.
DEFAULT_ROWS = [
    ("ysb", bench_ysb),
    ("stateless", bench_stateless),
    ("nexmark", bench_nexmark),
    ("keyed_cb", bench_keyed_cb),
    ("native_ring", bench_native_ring_raw),
    ("pallas_ab", bench_pallas_ab),
    ("floor", measure_floor),
    ("latency_curve_depth2", lambda: bench_latency_curve(depth=2)),
    ("latency_curve_depth12", lambda: bench_latency_curve(depth=12)),
]

#: added by WF_BENCH_ALL=1
SECONDARY_ROWS = [
    ("native_ring_python", bench_native_ring),
    *[(f"keyed_stateful_k{k}", lambda k=k: bench_keyed_stateful(k))
      for k in (1, 500, 10000)],
    ("adaptive", bench_adaptive),
    ("ysb_wmr", bench_ysb_wmr),
    ("ordering_overhead", bench_ordering_overhead),
    *[(f"scatter_fanout{n}_{v}", lambda n=n, v=v: bench_scatter(n, v))
      for n in (2, 4, 8, 16) for v in ("sort", "onehot")],
    ("ingest", bench_ingest),
    ("ingest_decomposition", bench_ingest_decomposition),
    ("framing_scaling", bench_framing_scaling),
    ("drive_loop", bench_drive_loop),
]


def main() -> int:
    device = device_info()
    from windflow_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = list(DEFAULT_ROWS)
    if os.environ.get("WF_BENCH_ALL"):
        rows += SECONDARY_ROWS
    results, failed = {}, []
    for name, fn in rows:
        try:
            results[name] = fn()
        except Exception:  # noqa: BLE001 — row boundary: the failure is
            # printed, named in the headline and fails the run; later rows
            # still get their turn
            traceback.print_exc()
            failed.append(name)
            print(json.dumps({"row": name, **device, "failed": True}),
                  flush=True)
            continue
        print(json.dumps({"row": name, **device, **results[name]}), flush=True)
    headline = {"metric": "YSB tuples/sec/chip", "unit": "tuples/s", **device,
                "failed_rows": failed}
    if "ysb" in results:
        ysb = results["ysb"]
        headline["value"] = round(ysb["tps"])
        headline["vs_baseline"] = round(ysb["tps"] / BASELINE_TPS, 3)
        headline["cost"] = {
            "flops_per_step": ysb["roofline"]["flops_per_step"],
            "bytes_per_step": ysb["roofline"]["bytes_per_step"]}
    print(json.dumps(headline))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
