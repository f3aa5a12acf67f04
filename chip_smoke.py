"""chip_smoke.py — the quickest proof that windflow-tpu still starts on the chip.

One process, no network, no git; run from the root of a checkout:

    python chip_smoke.py                  # TPU only: anything else exits 1
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal    # tiny sizes, CPU

Legs (each raises on failure; exit 0 only if every leg passed):

  A  the served Yahoo Streaming Benchmark path: host records (numpy structured
     array made from --seed) -> wf.RecordSource (native framing) -> prefetch to
     HBM -> ysb.make_ops() -> host wf.Sink, driven by wf.Pipeline.run(); every
     (campaign, window) count is compared with a numpy bincount oracle.
  B  the README's PipeGraph example (Source -> Filter -> KeyFFAT CB 1000/500
     -> Sink) against wf.Win_Seq on the same stream at another batch size.
  C  every registered kernel, XLA and Pallas forms, compiled for this device
     and compared with a numpy reference on inputs that leave bf16's 8 bits.

The wall times printed are a smoke's set-up and run time, not a throughput.
The last stdout line of a passing chip run is one JSON object naming the device.
"""

import argparse
import json
import sys
import time
import traceback

import numpy as np

#: (real, tiny) defaults per size argument; --rehearsal picks the tiny column
SIZES = {
    "batch": (1 << 20, 1 << 14),        # leg A batch capacity (bench.py's own)
    "batches": (16, 4),                 # leg A batches
    "legb_total": (1 << 23, 1 << 16),   # leg B tuples
    "legb_batch": (1 << 16, 1 << 12),   # leg B batch (the README's)
    "lanes": (1 << 20, 1 << 12),        # leg C lanes per kernel call
    "join_keys": (2048, 256),           # leg C join_probe table rows
    "merge_lanes": (8192, 1024),        # leg C ordering-merge network width
}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="allow a non-TPU backend; tiny default sizes; every "
                         "output line is tagged REHEARSAL")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legs", default="ABC",
                    help="subset of legs to run, e.g. 'C' (default: ABC)")
    for name, (real, _tiny) in SIZES.items():
        ap.add_argument(f"--{name.replace('_', '-')}", type=int, default=None,
                        help=f"default {real} (tiny in --rehearsal)")
    args = ap.parse_args(argv)
    for name, (real, tiny) in SIZES.items():
        if getattr(args, name) is None:
            setattr(args, name, tiny if args.rehearsal else real)
    return args


# --------------------------------------------------------------------- leg A

YSB_RECORD = np.dtype([("ad_id", "<i4"), ("event_type", "<i4"),
                       ("ts", "<i4"), ("key", "<i4")])


def leg_a(say, args, device):
    import jax
    import windflow_tpu as wf
    from windflow_tpu import native
    from windflow_tpu.benchmarks import ysb

    B, n_batches = args.batch, args.batches
    n = B * n_batches
    rng = np.random.default_rng(args.seed)
    recs = np.empty(n, YSB_RECORD)
    recs["ad_id"] = rng.integers(0, ysb.N_ADS, n, dtype=np.int32)
    recs["event_type"] = rng.integers(0, 3, n, dtype=np.int32)
    recs["ts"] = np.arange(n, dtype=np.int64) // ysb.EVENTS_PER_TICK
    recs["key"] = recs["ad_id"] % ysb.N_CAMPAIGNS

    # the oracle, independent of ysb.oracle_totals: views per (campaign, window)
    n_win = int(recs["ts"][-1]) // ysb.WIN_LEN + 1
    view = recs["event_type"] == 0
    cell = ((recs["ad_id"][view] // ysb.ADS_PER_CAMPAIGN).astype(np.int64)
            * n_win + recs["ts"][view] // ysb.WIN_LEN)
    want = np.bincount(cell, minlength=ysb.N_CAMPAIGNS * n_win)

    if not native.native_available():
        raise RuntimeError("native framing library unavailable")
    src = wf.RecordSource(
        lambda: (recs[i:i + B] for i in range(0, n, B)), YSB_RECORD,
        key_field="key", ts_field="ts", name="ysb_records")
    panes_per_batch = B // (ysb.EVENTS_PER_TICK * ysb.WIN_LEN) + 1
    ops = ysb.make_ops(pane_capacity=2 * panes_per_batch + 2,
                       max_wins=panes_per_batch + 64)
    window = ops[-1]

    got_cells, got_counts, stamps = [], [], []

    def deliver(view):
        if view is None:
            return
        stamps.append(time.perf_counter())
        got_cells.append(view["key"].astype(np.int64) * n_win + view["id"])
        got_counts.append(np.asarray(view["payload"]))

    sink = wf.Sink(deliver, name="ysb_sink")
    out_devices = set()
    consume = sink.consume

    def consume_and_note_device(batch):
        if batch is not None:
            out_devices.update(batch.valid.devices())
        consume(batch)
    sink.consume = consume_and_note_device

    pipe = wf.Pipeline(src, ops, sink, batch_size=B, prefetch=2)
    t0 = time.perf_counter()
    pipe.run()
    t1 = time.perf_counter()

    cells = np.concatenate(got_cells)
    counts = np.concatenate(got_counts)
    if len(np.unique(cells)) != len(cells):
        raise AssertionError("a (campaign, window) result was delivered twice")
    got = np.zeros_like(want)
    got[cells] = counts
    if not np.array_equal(got, want):
        bad = np.flatnonzero(got != want)
        raise AssertionError(
            f"{len(bad)} of {len(want)} (campaign, window) counts differ from "
            f"the numpy oracle; first: cell {bad[0]} got {got[bad[0]]} "
            f"want {want[bad[0]]}")
    if window.count_lift is not True:
        raise AssertionError(
            f"window.count_lift is {window.count_lift!r}: the window stage "
            f"took the segment-sum fallback, not the count fold")
    state_devices = set()
    for leaf in jax.tree.leaves(pipe.chain.states):
        state_devices.update(leaf.devices())
    if out_devices != {device} or state_devices != {device}:
        raise AssertionError(
            f"results on {out_devices}, state on {state_devices}; "
            f"expected only {device}")
    say(f"leg A: {n} events in {n_batches} batches of {B}: "
        f"{len(cells)} window results == numpy oracle "
        f"({int(want.sum())} views), count_lift=True, native framing, "
        f"results+state on {device}")
    say(f"leg A: first batch (compile included) {stamps[0] - t0:.2f} s, "
        f"remaining {n_batches - 1} batches + EOS flush {t1 - stamps[0]:.2f} s")


# --------------------------------------------------------------------- leg B

def leg_b(say, args):
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t

    total, batch = args.legb_total, args.legb_batch
    keys, win_len, slide = 64, 1000, 500

    def source():
        return (wf.Source_Builder(lambda i: {"v": (i % 7).astype(jnp.float32)})
                .withName("src").withTotal(total).withKeys(keys).build())

    def collector(out):
        def cb(view):
            if view is not None:
                out.extend(zip(view["key"].tolist(), view["id"].tolist(),
                               np.asarray(view["payload"]).tolist()))
        return cb

    # the README example, payload (i % 7) so f32 sums are order-independent
    got = []
    filt = wf.Filter_Builder(lambda t: t.v > 2).withName("filter").build()
    kfft = (wf.KeyFFAT_Builder(lambda t: t.v, jnp.add)
            .withCBWindows(win_len=win_len, slide=slide).withKeys(keys).build())
    graph = wf.PipeGraph("example", batch_size=batch)
    graph.add_source(source()).chain(filt).add(kfft).add_sink(
        wf.Sink_Builder(collector(got)).build())
    t0 = time.perf_counter()
    graph.run()
    t1 = time.perf_counter()

    # the repo's result-invariance oracle: Win_Seq, another batch size
    want = []
    wf.Pipeline(
        source(),
        [wf.Filter(lambda t: t.v > 2),
         wf.Win_Seq(lambda wid, it: it.sum("v"),
                    wf.WindowSpec(win_len, slide, win_type_t.CB),
                    num_keys=keys)],
        wf.Sink(collector(want)), batch_size=batch // 2).run()
    t2 = time.perf_counter()
    if not got or sorted(got) != sorted(want):
        raise AssertionError(
            f"PipeGraph KeyFFAT delivered {len(got)} windows, Win_Seq oracle "
            f"{len(want)}; contents differ")
    say(f"leg B: {total} tuples, PipeGraph KeyFFAT CB {win_len}/{slide} x "
        f"{keys} keys @ batch {batch}: {len(got)} windows == Win_Seq "
        f"@ batch {batch // 2} ({t1 - t0:.2f} s + oracle {t2 - t1:.2f} s)")


# --------------------------------------------------------------------- leg C

def _case_segment_fold(args, rng):
    """Values across the whole int32 range: sums wrap like XLA's segment_sum."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops.segment import FOLD_MAX_SEGMENTS, segment_fold
    C, S = args.lanes, FOLD_MAX_SEGMENTS
    vals = rng.integers(-(1 << 31), 1 << 31, C, dtype=np.int64).astype(np.int32)
    seg = rng.integers(0, S, C, dtype=np.int32)
    ok = rng.random(C) < 0.9
    ref = np.zeros(S, np.int64)
    np.add.at(ref, seg[ok], vals[ok].astype(np.int64))
    dev = tuple(map(jnp.asarray, (vals, seg, ok)))
    return (f"segment_fold[C={C},S={S},i32 full range]",
            {impl: (lambda impl=impl: jax.jit(
                lambda v, s, k: segment_fold(v, s, k, S, impl=impl))(*dev))
             for impl in ("xla", "pallas")},
            ref.astype(np.int32))


def _case_pane_counts(args, rng):
    """``keyed_pane_fold`` with no value leaf (the count lift) at the YSB
    chain's geometry; the first half of the batch is a one-key stream: ~1000
    counts per (key, pane, chunk), far beyond bf16's 256."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.ops.histogram import FOLD_FAST, keyed_pane_fold
    from windflow_tpu.operators.win_seqffat import _next_pow2
    C, K = args.lanes, ysb.N_CAMPAIGNS
    per_pane = ysb.EVENTS_PER_TICK * ysb.WIN_LEN
    P = max(8, _next_pow2(2 * (C // per_pane + 1) + 2))   # the chain's ring
    lane = np.arange(C)
    key = np.where(lane < C // 2, 0, rng.integers(0, K, C)).astype(np.int32)
    pane = (P - 3 + lane // per_pane).astype(np.int32)     # wraps the ring
    ok = rng.random(C) < 0.97
    ref = np.zeros((K, P), np.int32)
    np.add.at(ref, (key[ok], pane[ok] % P), 1)
    dev = tuple(map(jnp.asarray, (key, pane, ok)))
    return (f"pane_counts[C={C},K={K},P={P},one-key half]",
            {"xla": lambda: jax.jit(
                lambda k, p, v: keyed_pane_fold(k, p, v, (), K, P))(*dev)},
            (ref, (), np.int32(FOLD_FAST), np.int32(0)))


def _case_pane_fold(args, rng):
    """``kff``'s geometry with values across the whole int32 range (the
    cell's own lie in [0, 96]: one limb); the first quarter of the batch is
    one key's one pane, so a limb column of that cell passes 2^24."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops.histogram import FOLD_FAST, keyed_pane_fold
    C, K, P = args.lanes, 512, 256
    lane = np.arange(C)
    key = np.where(lane < C // 4, 7, lane % K).astype(np.int32)
    pane = (P - 3 + lane // max(256, C // 64)).astype(np.int32)  # wraps
    pane[:C // 4] = P - 3
    vals = rng.integers(-(1 << 31), 1 << 31, C, dtype=np.int64).astype(np.int32)
    ok = rng.random(C) < 0.97
    counts = np.zeros((K, P), np.int32)
    np.add.at(counts, (key[ok], pane[ok] % P), 1)
    sums = np.zeros((K, P), np.int64)
    np.add.at(sums, (key[ok], pane[ok] % P), vals[ok].astype(np.int64))
    dev = tuple(map(jnp.asarray, (key, pane, ok, vals)))
    return (f"pane_fold[C={C},K={K},P={P},i32 full range,one-cell quarter]",
            {"xla": lambda: jax.jit(
                lambda k, p, v, x: keyed_pane_fold(k, p, v, x, K, P))(*dev)},
            (counts, sums.astype(np.int32), np.int32(FOLD_FAST),
             np.int32(0)))


def _case_pane_fold_late(args, rng):
    """``kff_late``'s stream shape: a tenth of the lanes up to 19.2 panes
    late (the partial branch: the stragglers behind each chunk's window are
    compacted and scattered), values across the whole int32 range."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops.histogram import (DEFAULT_CHUNK, DEFAULT_L,
                                            FOLD_PARTIAL, keyed_pane_fold)
    C, K, P = args.lanes, 512, 256
    pane_len = max(256, C // 64)
    lane = np.arange(C) + 40 * pane_len
    late = rng.random(C) < 0.1
    ts = lane - np.where(late, rng.integers(1, int(19.2 * pane_len), C), 0)
    pane = (ts // pane_len).astype(np.int32)
    key = (lane % K).astype(np.int32)
    vals = rng.integers(-(1 << 31), 1 << 31, C, dtype=np.int64).astype(np.int32)
    ok = rng.random(C) < 0.97
    counts = np.zeros((K, P), np.int32)
    np.add.at(counts, (key[ok], pane[ok] % P), 1)
    sums = np.zeros((K, P), np.int64)
    np.add.at(sums, (key[ok], pane[ok] % P), vals[ok].astype(np.int64))
    rows = np.where(ok, pane, np.iinfo(np.int32).min).reshape(-1, DEFAULT_CHUNK)
    behind = (ok.reshape(rows.shape)
              & (rows <= rows.max(axis=1, keepdims=True) - DEFAULT_L))
    dev = tuple(map(jnp.asarray, (key, pane, ok, vals)))
    return (f"pane_fold[C={C},K={K},P={P},i32 full range,a tenth late]",
            {"xla": lambda: jax.jit(
                lambda k, p, v, x: keyed_pane_fold(k, p, v, x, K, P))(*dev)},
            (counts, sums.astype(np.int32), np.int32(FOLD_PARTIAL),
             np.int32(behind.sum())))


def _cases_lookup(args, rng):
    """The YSB join's table size, values over the documented exact domain."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.benchmarks import ysb
    from windflow_tpu.ops.lookup import table_lookup
    C, rows = args.lanes, ysb.N_ADS
    idx = rng.integers(0, rows, C, dtype=np.int32)
    didx = jnp.asarray(idx)
    tables = {
        "i32<=2^16": rng.integers(0, (1 << 16) + 1, rows).astype(np.int32),
        "i32<2^24": rng.integers(-(1 << 24) + 1, 1 << 24, rows).astype(np.int32),
        "f32": rng.standard_normal(rows).astype(np.float32) * 1e3,
    }
    tables["i32<=2^16"][:2] = (1 << 16, (1 << 16) - 1)
    for tag, table in tables.items():
        dtab = jnp.asarray(table)
        yield (f"lookup[C={C},K={rows},{tag}]",
               {impl: (lambda impl=impl, dtab=dtab: jax.jit(
                   lambda i: table_lookup(dtab, i, impl=impl))(didx))
                for impl in ("xla", "pallas")},
               table[idx])


def _case_join_probe(args, rng):
    """Sparse unique keys, values over the whole int32 range."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops.lookup import join_probe
    C, K = args.lanes, args.join_keys
    tk = rng.choice(1 << 30, K, replace=False).astype(np.int32)
    tv = rng.integers(-(1 << 31), 1 << 31, K, dtype=np.int64).astype(np.int32)
    probe = np.where(rng.random(C) < 0.7, tk[rng.integers(0, K, C)],
                     rng.integers(0, 1 << 30, C)).astype(np.int32)
    ok = rng.random(C) < 0.95
    order = np.argsort(tk)
    pos = np.clip(np.searchsorted(tk[order], probe), 0, K - 1)
    hit = (tk[order][pos] == probe) & ok
    dev = tuple(map(jnp.asarray, (tk, tv, probe, ok)))
    return (f"join_probe[C={C},K={K}]",
            {impl: (lambda impl=impl: jax.jit(
                lambda k, v, p, o: join_probe(k, v, p, o, impl=impl))(*dev))
             for impl in ("xla", "pallas")},
            (np.where(hit, tv[order][pos], 0).astype(np.int32), hit))


def _case_ordering_merge(args, rng):
    """A bitonic (ascending ++ descending) run of 4-lane composite keys."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops import bitonic
    from windflow_tpu.ops.registry import resolve_impl
    n = args.merge_lanes
    comp = np.stack([rng.integers(0, 64, n), rng.integers(0, 4, n),
                     rng.integers(0, 2, n), rng.permutation(n)]).astype(np.int32)
    up = np.lexsort(comp[::-1, :n // 2])
    down = np.lexsort(comp[::-1, n // 2:])[::-1] + n // 2
    comp = comp[:, np.concatenate([up, down])]
    dev = tuple(jnp.asarray(r) for r in comp)
    forms = {"xla": bitonic.merge_network,
             "pallas": bitonic.merge_network_pallas}

    def run(impl):
        # selection goes through the registry, as Ordering_Node's does
        return jax.jit(forms[resolve_impl(
            "ordering_merge", impl=impl, record=False)])(*dev)
    return (f"ordering_merge[n={n}]",
            {impl: (lambda impl=impl: run(impl)) for impl in forms},
            tuple(comp[:, np.lexsort(comp[::-1])]))


def _case_masked_window_reduce(args, rng):
    """Small integers in f32: sums are exact under any order."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.ops import pallas_kernels as pk
    W, L = (4096, 512) if args.lanes >= (1 << 20) else (pk.ROW_TILE, 128)
    vals = rng.integers(0, 8, (W, L)).astype(np.float32)
    mask = rng.random((W, L)) < 0.7
    dev = (jnp.asarray(vals), jnp.asarray(mask))
    return (f"masked_window_reduce[W={W},L={L}]",
            {"xla": lambda: jax.jit(pk._xla_masked_sum)(*dev),
             "pallas": lambda: jax.jit(pk.masked_window_reduce)(*dev)},
            np.where(mask, vals, 0).sum(axis=1))


def _kernel_cases(args, rng):
    """(label, {impl: thunk}, numpy reference) per kernel case; a thunk
    compiles and runs one form on the default device."""
    yield _case_segment_fold(args, rng)
    yield _case_pane_counts(args, rng)
    yield _case_pane_fold(args, rng)
    yield _case_pane_fold_late(args, rng)
    yield from _cases_lookup(args, rng)
    yield _case_join_probe(args, rng)
    yield _case_ordering_merge(args, rng)
    yield _case_masked_window_reduce(args, rng)


def _same(got, ref) -> bool:
    import jax
    got, ref = jax.tree.leaves(got), jax.tree.leaves(ref)
    return len(got) == len(ref) and all(
        np.asarray(g).dtype == r.dtype and np.array_equal(np.asarray(g), r)
        for g, r in zip(got, ref))


def leg_c(say, args):
    import jax
    from windflow_tpu.ops.registry import KernelRefused, pallas_interpret

    ran = "interpreted" if pallas_interpret() else "compiled"
    failures = []
    for label, impls, ref in _kernel_cases(
            args, np.random.default_rng(args.seed + 1)):
        for impl, thunk in impls.items():
            how = "compiled" if impl == "xla" else ran
            t0 = time.perf_counter()
            try:
                out = jax.block_until_ready(thunk())
            except KernelRefused as e:
                # a refusal the registry owns up to: selecting the impl on
                # this device raises, nothing quietly swaps in XLA
                say(f"leg C: {label} {impl}: refused: "
                    f"{str(e).splitlines()[0]}")
                continue
            except Exception as e:  # noqa: BLE001 — row boundary: a Pallas
                # form's refusal is printed and fails the leg below
                if impl == "xla":
                    raise                              # XLA forms must compile
                traceback.print_exc()
                first = (str(e).strip().splitlines() or [type(e).__name__])[0]
                say(f"leg C: {label} {impl}: {type(e).__name__}: {first}")
                failures.append(f"{label} {impl}: unregistered refusal")
                continue
            took = time.perf_counter() - t0
            verdict = "exact" if _same(out, ref) else "WRONG"
            say(f"leg C: {label} {impl}: {how}+{verdict} "
                f"(compile + first run {took:.1f} s)")
            if verdict == "WRONG":
                failures.append(f"{label} {impl}: WRONG")
    if failures:
        raise AssertionError("leg C failed: " + "; ".join(failures))


# ----------------------------------------------------------------------- main

def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    devices = jax.devices()
    dev = devices[0]
    tag = f"REHEARSAL on {dev.platform}: " if args.rehearsal else ""

    def say(msg):
        print(f"{tag}{msg}", flush=True)

    say(f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' "
              f"(--rehearsal runs the tiny sizes elsewhere)", file=sys.stderr)
        return 1

    from windflow_tpu.runtime.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if "A" in args.legs:
        leg_a(say, args, dev)
    if "B" in args.legs:
        leg_b(say, args)
    if "C" in args.legs:
        leg_c(say, args)
    say(f"legs {args.legs} passed in {time.perf_counter() - t0:.1f} s")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
