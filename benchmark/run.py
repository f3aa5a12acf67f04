"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``benchmark/workloads/<cell>.json``, its configuration
``benchmark/configs/<config>.json`` + ``.py``, each per-layer metric
``benchmark/layer_metrics/<metric>.py``; ``BENCHMARK.json`` says which metrics
a cell reports. See ``benchmark/README.md``. Without a TPU the run fails;
``--rehearsal`` runs the cell's tiny sizes on any backend, tags every line
REHEARSAL and prints no metric.

Standard output carries one line, the result; everything else goes to standard
error, the numbers compared last.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: glibc adapts its mmap and trim thresholds to what the process has freed so
#: far, and the program allocates every column of every batch afresh. So the
#: same ysb.backlog window read 25 M tuples/s in a process that loaded its
#: programs from the compile cache, 33 M in one that had compiled them, and
#: 5.5 M with the thresholds fixed at their initial 128 KiB (PERF.md, section
#: 2). Fixed high values (mallopt's M_MMAP_THRESHOLD at its maximum,
#: M_TRIM_THRESHOLD, M_TOP_PAD) take that history out of every run.
for _param, _value in ((-3, 32 << 20), (-1, 1 << 30), (-2, 256 << 20)):
    if ctypes.CDLL(None).mallopt(_param, _value) != 1:
        raise RuntimeError(f"mallopt({_param}, {_value}) refused")

SPAN_NAMES = ("ingest_wait", "push", "sink_consume")
SLICE_NAME = "bench_slice"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name, rehearsal):
    """(cell entry of BENCHMARK.json, traffic, configuration, its module)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
    wl = load_json(HERE, "workloads", name + ".json")
    cfg = load_json(HERE, "configs", cells[0]["config"] + ".json")
    mod = load_module(os.path.join(HERE, "configs",
                                   cells[0]["config"] + ".py"))
    traffic = dict(wl["traffic"])
    if rehearsal:
        traffic.update(wl.get("rehearsal", {}))
        cfg.update(cfg.get("rehearsal", {}))
    if traffic["pool_batches"] < traffic["queue_depth"] + 4:
        raise ValueError("the pool must outnumber the batches in flight")
    return bench, cells[0], traffic, cfg, mod


def metrics_of(bench, cell_name, kind, end_to_end_reported=None):
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``)."""
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if (kind == "per_layer" and cells is None
                and m["moves"] not in end_to_end_reported):
            continue
        out.append(m)
    return out


class Spans:
    """Host-clock spans around the calls into each layer, one row per call,
    each also a ``TraceAnnotation`` so that device gaps can be attributed."""

    def __init__(self):
        self.rows = {name: [] for name in SPAN_NAMES}   # (batch, t0, t1)

    def call(self, name, batch, fn, *args, **kw):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kw)
        finally:
            self.rows[name].append((batch, t0, time.perf_counter()))


class CompileWatch:
    """Compile requests and persistent-cache hits, as JAX's own monitoring
    reports them, and the program's compile ledger: what the window saw."""

    def __init__(self):
        import jax.monitoring
        from windflow_tpu.observability import device_health
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        self.ledger = device_health.HealthLedger(cost_analysis=False)
        device_health.set_active(self.ledger)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        from windflow_tpu.observability import device_health
        device_health.set_active(None)

    def mark(self):
        return (self.requests, self.hits, self.ledger.traces)

    def since(self, mark):
        req, hit = self.requests - mark[0], self.hits - mark[1]
        return {"compile_requests": req, "fresh_compiles": req - hit,
                "chain_traces": self.ledger.traces - mark[2]}


def drive(mod, cfg, traffic, records, feed=None, trace_dir=None, watch=None):
    """One ``wf.Pipeline.run()`` over ``records``: the entry the window
    drives. With ``feed`` it is the measured pipeline (warm prefix, window,
    EOS flush); without, the throw-away that fills the compile cache."""
    import jax
    import numpy as np
    import windflow_tpu as wf

    batch = traffic["batch"]
    src = wf.RecordSource(records, mod.RECORD, key_field=mod.KEY_FIELD,
                          ts_field=mod.TS_FIELD, name="bench_records")
    ops = mod.build_ops(cfg, batch)
    deliveries = []

    def deliver(view):
        if view is not None:
            deliveries.append((time.perf_counter(), view["key"], view["id"],
                               np.asarray(view["payload"])))

    sink = wf.Sink(deliver, name="bench_sink")
    pipe = wf.Pipeline(src, ops, sink, batch_size=batch,
                       prefetch=traffic["prefetch"])
    spans = Spans()
    out = {"deliveries": deliveries, "spans": spans, "ops": ops,
           "out_devices": set(), "slice": None, "window_mark": None}
    prefix = feed.prefix if feed is not None else 0
    first, count = traffic["trace_start_batch"], traffic["trace_batches"]
    # "annotation" is the open slice's TraceAnnotation while the profiler runs
    state = {"pulled": 0, "pushed": 0, "consumed": 0, "annotation": None}

    prefetched = src.batches_prefetched

    def spanned_batches(*a, **kw):
        it = prefetched(*a, **kw)
        while True:
            try:
                b = spans.call("ingest_wait", state["pulled"], next, it)
            except StopIteration:
                return
            state["pulled"] += 1
            yield b
    src.batches_prefetched = spanned_batches

    chain_push = pipe.chain.push

    def push(b, from_op=0):
        if from_op:
            return chain_push(b, from_op=from_op)
        i = state["pushed"]
        state["pushed"] += 1
        return spans.call("push", i, chain_push, b)
    pipe.chain.push = push

    consume = sink.consume

    def consume_spanned(b):
        if b is None:
            return consume(b)
        i = state["consumed"]
        state["consumed"] += 1
        out["out_devices"].update(b.valid.devices())
        spans.call("sink_consume", i, consume, b)
        if feed is None:
            return
        if i + 1 == prefix:
            if watch is not None:
                out["window_mark"] = watch.mark()
            feed.prefix_delivered.set()
        if trace_dir is None:
            return
        # the profiler is open for ``count`` whole drive cycles in mid-window
        if i + 1 == prefix + first:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["annotation"] = jax.profiler.TraceAnnotation(SLICE_NAME)
            state["annotation"].__enter__()
            out["slice"] = [i + 1, None, time.perf_counter(), None]
        elif state["annotation"] and i + 1 == prefix + first + count:
            stop_slice()

    def stop_slice():
        state["annotation"].__exit__(None, None, None)
        state["annotation"] = None
        out["slice"][1] = state["consumed"]
        out["slice"][3] = time.perf_counter()
        jax.profiler.stop_trace()
    sink.consume = consume_spanned

    try:
        pipe.run()
    finally:
        if state["annotation"]:     # the stream ended inside the slice
            stop_slice()
    out["state_devices"] = set()
    for leaf in jax.tree.leaves(pipe.chain.states):
        out["state_devices"].update(leaf.devices())
    return out


def describe_window(say, feed, run, in_window, t_close, t_end_run):
    """The earlier lines: what to read before trusting or blaming a number
    (a starved generator, a compile, a stall and the layer it sat in)."""
    import numpy as np
    prefix, t_open = feed.prefix, feed.t_open
    n_timed, window_s = len(feed.t_created) - prefix, t_close - t_open
    say(f"window: {n_timed} batches of {feed.batch} in {window_s:.4f} s after "
        f"a warm prefix of {prefix}; last batch to last delivery "
        f"{t_close - feed.t_created[-1]:.4f} s; run() returned "
        f"{t_end_run - t_close:.4f} s later")
    say(f"generator ({feed.mode}): {feed.empty_pulls} of {n_timed} timed "
        f"pulls found the queue empty (the pull that opens the window is not "
        f"counted)")
    say(f"compile ledger inside the window: {in_window}")
    rows = run["spans"].rows
    timed_ms = {n: sum(t1 - t0 for i, t0, t1 in r if i >= prefix) * 1e3
                / n_timed for n, r in rows.items()}
    say(f"host spans over the window, ms per batch: {timed_ms}")
    stamps = np.asarray([d[0] for d in run["deliveries"] if d[0] >= t_open])
    gaps = np.diff(stamps)
    if not len(gaps):
        return
    # a stall shows as a long wait between deliveries; the drive thread's
    # span that covers it says which layer the wait sat in
    say(f"waits between deliveries: median {np.median(gaps) * 1e3:.1f} ms; "
        f"the longest, by the drive thread's spans:")
    for i in np.argsort(gaps)[::-1][:3]:
        lo, hi = stamps[i], stamps[i + 1]
        inside = {n: sum(max(0.0, min(t1, hi) - max(t0, lo))
                         for _, t0, t1 in r) * 1e3 for n, r in rows.items()}
        say(f"  {gaps[i] * 1e3:.1f} ms at {lo - t_open:.2f} s: " + ", ".join(
            f"{n} {v:.1f}" for n, v in inside.items()))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on any backend; no metric is printed")
    return ap.parse_args(argv)


def run_cell(args, control=None):
    """One run of one cell: set-up, window, reference, metrics. Returns the
    result line's object. ``control`` (``control.py``, ``selfcheck.py``) puts
    other results in the place of what the pipeline delivered, just before
    they are judged."""
    tag = "REHEARSAL " if args.rehearsal else ""

    def say(msg):
        print(f"{tag}{msg}", file=sys.stderr, flush=True)

    bench, cell, traffic, cfg, mod = load_cell(args.workload, args.rehearsal)

    from windflow_tpu.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import numpy as np
    from peaks import device_info, peaks_for
    import judge
    import trace_reduce
    import traffic as traffic_gen

    t_imports = time.perf_counter()
    device = device_info(cell["chips"], rehearsal=args.rehearsal)
    dev0 = jax.devices()[0]
    t_device = time.perf_counter()
    say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} jax={jax.__version__} device={device} "
        f"compile cache at {cache_dir}")
    from windflow_tpu import native
    if not native.native_available():
        raise RuntimeError("native framing library unavailable")

    # ---- set-up: pool, compile cache, warm prefix ------------------------
    batch = traffic["batch"]
    pool = traffic_gen.make_pool(mod, cfg, traffic, args.seed)
    t_pool = time.perf_counter()
    watch = CompileWatch()

    def throwaway():
        for j in range(traffic["warmup_batches"]):
            mod.stamp(cfg, pool[j], j * batch)
            yield pool[j]
    drive(mod, cfg, traffic, throwaway)
    t_warm = time.perf_counter()
    say(f"set-up: imports {t_imports - T_PROCESS_START:.3f} s, TPU runtime "
        f"start {t_device - t_imports:.3f} s, native library and pool "
        f"{t_pool - t_device:.3f} s, throw-away pipeline "
        f"{t_warm - t_pool:.3f} s, compiles so far {watch.since((0, 0, 0))}")

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    feed = traffic_gen.Feed(mod, cfg, traffic, pool, args.seconds)
    feed.thread.start()
    try:
        run = drive(mod, cfg, traffic, feed.records, feed=feed,
                    trace_dir=trace_dir, watch=watch)
    finally:
        feed.close()
        watch.close()
    t_end_run = time.perf_counter()

    # ---- the window ------------------------------------------------------
    prefix = feed.prefix
    n_batches = len(feed.t_created)
    n_timed = n_batches - prefix
    if n_timed <= 0 or feed.t_open is None:
        raise RuntimeError("the window fed no batch")
    t_open = feed.t_open
    deliveries = run["deliveries"]
    t_close = deliveries[-1][0]
    window_s = t_close - t_open
    setup_s = t_open - T_PROCESS_START
    in_window = watch.since(run["window_mark"])
    stats = dev0.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    describe_window(say, feed, run, in_window, t_close, t_end_run)
    if feed.capped:
        raise RuntimeError(
            f"the stream reached {traffic_gen.MAX_RECORDS} records, the end "
            f"of RecordSource's int32 tuple index, before --seconds ran out")

    # ---- correct: the reference, after the window, state freed -----------
    numbers = dict(mod.program_checks(cfg, run["ops"]))
    on_device = {dev0}
    numbers["results_off_device"] = (
        0 if run["out_devices"] == on_device else 1, 0)
    numbers["state_off_device"] = (
        0 if run["state_devices"] == on_device else 1, 0)
    numbers["fresh_compiles_in_window"] = (in_window["fresh_compiles"], 0)
    numbers["chain_traces_in_window"] = (in_window["chain_traces"], 0)
    spans = run["spans"].rows
    trace_slice = run["slice"]
    del run
    t_ref = time.perf_counter()
    expected = mod.reference(cfg, pool, n_batches, batch)
    key, wid, val, t, burst = judge.gather(deliveries)
    if control is not None:
        key, wid, val = control(mod, cfg, pool, n_batches, batch)
        t, burst = np.full(len(key), t_close), np.zeros(len(key), np.int64)
    numbers.update(judge.compare(expected, key, wid, val,
                                 cfg["guarantees"]["ordered_per_key"]))
    lat, moments = judge.latencies_ms(expected, key, wid, t, burst,
                                      feed.t_created, t_open, prefix)
    say(f"reference: {expected['value'].size} cells, {len(key)} results "
        f"delivered, compared in {time.perf_counter() - t_ref:.3f} s")
    say(f"latency: {len(lat)} samples at {moments} distinct delivery moments")
    correct = all(v <= limit for v, limit in numbers.values())
    attempted = int(np.count_nonzero(expected["must_deliver"]))
    failed = sum(numbers[k][0] for k in ("results_wrong", "results_missing",
                                         "results_twice", "results_unknown"))

    # ---- metrics ---------------------------------------------------------
    values = {
        "tuples_per_s": n_timed * batch / window_s,
        "result_latency_p50_ms": float(np.quantile(lat, 0.50)) if len(lat)
        else None,
        "result_latency_p95_ms": float(np.quantile(lat, 0.95)) if len(lat)
        else None,
        "setup_s": setup_s,
    }
    e2e = metrics_of(bench, cell["name"], "end_to_end")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": {},
              "device": dict(device, memory_peak_bytes=memory_peak)}
    if args.trace:
        if trace_slice is None or trace_slice[1] is None:
            raise RuntimeError("the window ended before the traced slice did")
        lo, hi = trace_slice[0], trace_slice[1]
        path = trace_reduce.find_xplane(trace_dir)
        size = os.path.getsize(path)
        reduced = trace_reduce.reduce(path, SLICE_NAME, SPAN_NAMES)
        say(f"trace: batches {lo}..{hi - 1} of the stream, "
            f"{trace_slice[3] - trace_slice[2]:.4f} s by the host clock, "
            f"{reduced['window_s']:.4f} s in the trace, {size} bytes at {path}")
        ctx = {
            "spans": {n: [r for r in rows if lo <= r[0] < hi]
                      for n, rows in spans.items()},
            "slice_batches": hi - lo,
            "trace": reduced,
            "trace_path": path,
            "min_bytes_per_batch": mod.min_bytes_per_batch(cfg, batch),
            "peaks": None if args.rehearsal else peaks_for(device["kind"]),
        }
        for m in metrics_of(bench, cell["name"], "per_layer",
                            {e["name"] for e in e2e}):
            reader = load_module(os.path.join(HERE, "layer_metrics",
                                              m["name"] + ".py"))
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        for m in e2e:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    say(f"counts: {n_timed * batch} tuples in the window, {len(lat)} latency "
        f"samples, {len(key)} results")
    result["compared"] = {k: {"value": v, "limit": limit}
                          for k, (v, limit) in numbers.items()}
    for k, (v, limit) in numbers.items():
        say(f"compared {k}: {v} (limit {limit})")
    return result


def main(argv=None):
    args = parse_args(argv)
    result = run_cell(args)
    if args.rehearsal:
        print("REHEARSAL " + json.dumps(
            {k: result[k] for k in ("correct", "attempted", "failed",
                                    "compared")}), file=sys.stderr)
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
