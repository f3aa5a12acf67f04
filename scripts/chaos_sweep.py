#!/usr/bin/env python
"""Chaos sweep: run N seeded fault plans through all three drivers and report
any divergence from the fault-free baseline.

For each seed a probabilistic FaultPlan (errors on source.next / chain.step /
sink.consume for the supervised drivers, stalls on queue.stall for the
threaded driver) is injected via runtime/faults.py; the run's outputs must be
byte-identical to the fault-free oracle (exactly-once under injection).
Exit code 0 = no divergence, 1 = at least one.

--controller additionally runs every driver with the adaptive control plane
active (deterministic positional admission on the supervised drivers — shed
decisions are part of the replayed stream, so faulted runs must still match
the fault-free controlled baseline byte-for-byte; backpressure governor on
the threaded driver). Controller + injection must neither diverge nor
livelock the supervisor's backoff.

The graph_det driver (DETERMINISTIC merge) keeps the Ordering_Node's async
counts readback in every sweep.

--shards N runs the two SUPERVISED drivers (pipeline + graph) through the
shard-local supervision layer (N ShardSupervisor units) and widens each
seed's plan with shard-kill and torn reshard-handoff injection; the
fault-free baselines stay UNSHARDED, so every seed asserts shard-count
invariance AND shard-local recovery byte-identity at once. The sharded
pipeline run additionally carries a mid-stream N -> 2N live reshard.

--remediate closes the loop: the supervised PIPELINE runs (baseline AND
chaos) carry barrier remediation (``remediation=True`` + deterministic
positional admission) — decisions are part of the replayed stream, so the
faulted remediated runs must match the remediated baseline byte-for-byte.
It then adds one LIVE threaded leg under queue.stall chaos riding the full
self-driving loop — OK -> PAGE (drop_ratio burn) -> shed_harder actuation ->
recovery back to OK — asserting the loop shape and that the incident bundle
recorded the actions (lossy by design: admission sheds, so THIS leg asserts
recovery, not byte-identity).

--serve runs ONLY the serving closed-loop legs (one per seed): a
ServingRuntime ingesting two tenants over a real loopback socket, with a
seeded peer kill mid-stream (abrupt close, torn frame), garbage-byte
injection, a full reconnect re-send (the dedup overlap), and a live
graph hot-swap to a registered twin graph mid-stream — the outputs must
be byte-identical to a RecordSource oracle fed the same chunks, with
zero dropped committed tuples, >= 1 torn frame resync'd and >= 1
duplicate frame deduped (the peer-kill-degrades-to-replay contract).

    JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --seeds 5 --total 400
    JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --seeds 5 --controller
    JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --seeds 5 --shards 4
    JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --seeds 3 --remediate
    JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --seeds 3 --serve
"""

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np                                        # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

import windflow_tpu as wf                                 # noqa: E402
from windflow_tpu.basic import win_type_t                 # noqa: E402
from windflow_tpu.operators.window import WindowSpec      # noqa: E402
from windflow_tpu.runtime import faults as faults_mod     # noqa: E402
from windflow_tpu.runtime.faults import (FaultInjector,   # noqa: E402
                                         FaultPlan, FaultSpec)
from windflow_tpu.runtime.pipegraph import PipeGraph      # noqa: E402
from windflow_tpu.runtime.supervisor import SupervisedPipeline  # noqa: E402
from windflow_tpu.runtime.threaded import ThreadedPipeline      # noqa: E402
from windflow_tpu.control import ControlConfig                  # noqa: E402


def sup_control(batch):
    # deterministic positional bucket: ~80% admitted, replay-stable
    return ControlConfig(autotune=False, backpressure=False, admission=True,
                         refill_per_batch=0.8 * batch, burst_tuples=2 * batch)


def thr_control():
    # governor only: throttling delays, never drops — results must not change
    return ControlConfig(autotune=False, backpressure=True,
                         high_watermark=0.5, low_watermark=0.25)


def collect(acc):
    def cb(view):
        if view is None:
            return
        acc.extend(zip(view["key"].tolist(), view["id"].tolist(),
                       np.asarray(view["payload"]).tolist()))
    return cb


def run_pipeline(total, batch, faults=None, controller=False,
                 shards=0, remediate=False):
    got = []
    src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                    total=total, num_keys=4)
    op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                    WindowSpec(10, 10, win_type_t.TB), num_keys=4)
    SupervisedPipeline(src, [op], wf.Sink(collect(got)), batch_size=batch,
                       checkpoint_every=3, max_restarts=8,
                       backoff_base=0.001, backoff_cap=0.01,
                       faults=faults, shards=shards or 1,
                       # sharded runs also cross a live N -> 2N reshard at
                       # the first barrier past 1/3 of the stream — chaos
                       # seeds then hit shard kills AND torn handoffs
                       reshard=({"new_shards": shards * 2,
                                 "at_pos": max(1, total // batch // 3)}
                                if shards else False),
                       # --remediate: barrier remediation over the owned
                       # actuators (admission always; reshard when sharded)
                       # — decisions are replayed state, so byte-identity
                       # against the remediated baseline still holds
                       remediation=True if remediate else None,
                       control=(sup_control(batch)
                                if (controller or remediate) else False)
                       ).run()
    return sorted(got)


def run_graph(total, batch, faults=None, controller=False,
              mode=None, shards=0):
    from windflow_tpu.basic import Mode
    got = []
    g = PipeGraph("sweep", batch_size=batch, mode=mode or Mode.DEFAULT)
    a = g.add_source(wf.Source(lambda i: {"v": (i % 9).astype(jnp.float32)},
                               total=total, num_keys=3, name="a"))
    b = g.add_source(wf.Source(lambda i: {"v": (i % 7).astype(jnp.float32)},
                               total=total // 2, num_keys=3, name="b"))
    (a.merge(b)
     .add(wf.Win_Seq(lambda wid, it: it.sum("v"),
                     WindowSpec(12, 12, win_type_t.CB), num_keys=3))
     .add_sink(wf.Sink(collect(got))))
    g.run_supervised(checkpoint_every=3, max_restarts=8,
                     backoff_base=0.001, backoff_cap=0.01, faults=faults,
                     shards=shards or 1,
                     # hermetic: the graph runs never reshard in the sweep —
                     # a caller's WF_RESHARD must not diverge them from the
                     # unsharded baselines (run_pipeline pins its own plan)
                     reshard=False,
                     control=sup_control(batch) if controller else False)
    return sorted(got)


def run_graph_det(total, batch, faults=None, controller=False, shards=0):
    # DETERMINISTIC merge: every root push drives the Ordering_Node's
    # async [n_released, n_kept] readback — the sync-free hot path under
    # chaos
    from windflow_tpu.basic import Mode
    return run_graph(total, batch, faults=faults, controller=controller,
                     mode=Mode.DETERMINISTIC, shards=shards)


def run_threaded(total, batch, faults=None, controller=False):
    got = []
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=total)
    ThreadedPipeline(src, [[wf.Map(lambda t: {"v": t.v * 3})],
                           [wf.Map(lambda t: {"v": t.v + 1})]],
                     wf.Sink(lambda v: got.extend(
                         zip(v["id"].tolist(),
                             np.asarray(v["payload"]["v"]).tolist()))
                         if v is not None else None),
                     batch_size=batch, pin=False, heartbeat_timeout=0.25,
                     faults=faults,
                     control=thr_control() if controller else False).run()
    return sorted(got)


def run_closed_loop(seed):
    """The headline --remediate acceptance: a LIVE threaded run under
    queue.stall chaos rides the full self-driving loop — OK -> PAGE
    (drop_ratio burn) -> shed_harder actuation -> recovery back to OK —
    with the incident bundle recording the actions the page triggered.
    Lossy by design (admission sheds during the flood), so this leg
    asserts the loop shape, not byte-identity.  Returns (problems,
    n_applies, n_faults)."""
    import json
    import shutil
    import tempfile

    from windflow_tpu.control import RemediationAction, RemediationPolicy
    from windflow_tpu.observability import MonitoringConfig

    mon_dir = tempfile.mkdtemp(prefix="wf_chaos_remediate_")
    batch, total = 32, 6000
    got = []

    def sink(view):
        # host-side pacing (the sink is a plain callback, never traced):
        # ~4ms/batch keeps the run alive long past the bounded stall burst,
        # so the burn windows get clean post-incident ticks to decay over
        if view is not None:
            got.extend(view["id"].tolist())
        time.sleep(0.004)

    # the admission rate is astronomically high: shed_harder's actuation is
    # REAL (the setpoint halves, journaled, gauged) but never actually
    # sheds, so the closed-loop leg also asserts zero tuple loss
    policy = RemediationPolicy((RemediationAction(
        name="shed_harder", slo="latency", actuator="admission_rate",
        factor=0.5, floor=1.0, window=2, max_applies=2),))
    mon = MonitoringConfig(
        slo=json.dumps([{"name": "latency", "signal": "e2e_p99_ms",
                         "target": 150.0, "objective": 0.5,
                         "fast_window": 2, "slow_window": 4,
                         "warn_burn": 0.5, "page_burn": 1.0}]),
        remediation=policy, interval_s=0.05, remediation_cooldown_s=0.05,
        out_dir=mon_dir)
    # a bounded burst of queue stalls: each holds a ring op ~0.5s, so the
    # delayed batches blow the per-tick e2e p99 past target (OK -> PAGE);
    # max_fires bounds the incident, so the tail of the run recovers
    inj = FaultInjector(FaultPlan([FaultSpec("queue.stall", kind="stall",
                                             p=0.25, stall_s=0.5,
                                             max_fires=4)], seed=seed))
    src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                    total=total, num_keys=4)
    ThreadedPipeline(src, [[wf.Map(lambda t: {"v": t.v + 1.0})]],
                     wf.Sink(sink),
                     batch_size=batch, pin=False, heartbeat_timeout=0.25,
                     faults=inj,
                     control=ControlConfig(autotune=False,
                                           backpressure=False,
                                           admission=True, rate_tps=1e9),
                     monitoring=mon).run()

    snaps = [json.loads(line)
             for line in open(os.path.join(mon_dir, "snapshots.jsonl"))]
    events = [json.loads(line)
              for line in open(os.path.join(mon_dir, "events.jsonl"))]
    applies = [e for e in events if e.get("event") == "remediation_apply"]
    paged = any((s.get("slo") or {}).get("latency", {}).get("state")
                == "page" for s in snaps)
    final = (snaps[-1].get("slo") or {}).get("latency", {}).get("state")
    inc_dir = os.path.join(mon_dir, "incidents")
    bundles = sorted(os.listdir(inc_dir)) if os.path.isdir(inc_dir) else []
    with_rem = [b for b in bundles if os.path.exists(
        os.path.join(inc_dir, b, "remediation.json"))]
    problems = []
    if not paged:
        problems.append("the latency SLO never paged")
    if not applies:
        problems.append("no remediation_apply journaled")
    if final != "ok":
        problems.append(f"final state {final!r} — did not recover to ok")
    if not bundles:
        problems.append("no incident bundle captured for the page")
    elif not with_rem:
        problems.append("no incident bundle recorded remediation.json")
    if sorted(got) != list(range(total)):
        problems.append(f"tuple loss: {len(got)}/{total} delivered")
    shutil.rmtree(mon_dir, ignore_errors=True)
    return problems, len(applies), len(inj.fired)


def run_serve_loop(seed, total=2000, chunk=50):
    """The --serve acceptance: a ServingRuntime fed two tenants over a
    real loopback socket, with a seeded mid-stream peer kill (abrupt
    close), garbage injection, a full re-send on reconnect (the dedup
    overlap), and a live hot-swap to a registered twin graph — outputs
    must be byte-identical to a RecordSource oracle over the same chunks.
    Returns (problems, counters)."""
    import json
    import shutil
    import tempfile

    from windflow_tpu.serving import (RecordClient, ServingRuntime,
                                      SocketSource)

    rng = np.random.RandomState(seed)
    dt = np.dtype([("key", np.int32), ("ts", np.int64), ("v", np.float32)])
    recs = np.zeros(total, dtype=dt)
    recs["key"] = rng.randint(0, 8, total)
    recs["ts"] = np.arange(total)
    recs["v"] = rng.rand(total).astype(np.float32)
    chunks = [recs[i:i + chunk] for i in range(0, total, chunk)]
    # even chunks ride tenant "a", odd ones "b" — both unlimited, so the
    # byte-identity claim covers the multi-tenant path with zero shedding
    tenant_of = ["a" if i % 2 == 0 else "b" for i in range(len(chunks))]

    def make_ops():
        return [wf.Map(lambda t: {"v": t.v * 2.0 + 1.0})]

    def collect_out(acc):
        def cb(view):
            if view is not None:
                acc.extend(zip(view["id"].tolist(),
                               np.asarray(view["payload"]["v"]).tolist()))
        return cb

    # oracle: the same chunks through a plain RecordSource pipeline
    oracle = []
    wf.Pipeline(wf.RecordSource(lambda: iter(chunks), dt, key_field="key",
                                ts_field="ts", num_keys=8),
                make_ops(), wf.Sink(collect_out(oracle)),
                batch_size=chunk).run()

    mon_dir = tempfile.mkdtemp(prefix="wf_chaos_serve_")
    got = []
    src = SocketSource("tcp://127.0.0.1:0", dt, key_field="key",
                       ts_field="ts", num_keys=8, replay=len(chunks) + 8)
    rt = ServingRuntime(
        src, make_ops(), wf.Sink(collect_out(got)), batch_size=chunk,
        serving={"tenants": [{"id": "a"}, {"id": "b"}]},
        monitoring=mon_dir)
    rt.register_graph("twin", make_ops())
    src.start()                      # bind now: the client needs the port
    thread = rt.run_background()

    def decoded_stable():
        # wait for the ingest side to drain a killed connection's kernel
        # buffer before the overlap re-send, so chunk admission order
        # stays the wire send order (the id-identity precondition)
        last = -1
        for _ in range(100):
            cur = src.frames_decoded + src.frames_torn + src.frames_dup
            if cur == last:
                return
            last = cur
            time.sleep(0.05)

    client = RecordClient(src.endpoint)
    kill_at = int(rng.randint(len(chunks) // 4, 3 * len(chunks) // 4))
    swap_at = kill_at // 2           # always before the kill: the swap
    #                                  frame must survive the peer death
    sent = {}                        # tenant -> [(seq, chunk_bytes)]
    for i, c in enumerate(chunks[:kill_at]):
        t = tenant_of[i]
        seq = client.send(c.tobytes(), tenant=t)
        sent.setdefault(t, []).append((seq, c.tobytes()))
        if i == swap_at:
            client.send_swap("twin")
    client.send_garbage(b"TORN BYTES IN FLIGHT " * 3)
    client.kill()                    # abrupt peer death, no EOS
    decoded_stable()
    client.reconnect()
    # the client has no ack channel, so re-send EVERYTHING already sent
    # (original seqs): the server drops the overlap as dup and admits only
    # what the kill actually lost — replay, never loss or duplication
    for t, frames in sent.items():
        for seq, blob in frames:
            client.send(blob, tenant=t, seq=seq)
    for i in range(kill_at, len(chunks)):
        t = tenant_of[i]
        client.send(chunks[i].tobytes(), tenant=t)
    client.send_eos("a")             # default eos policy: first eos ends it
    client.close()
    thread.join(timeout=60.0)

    problems = []
    if thread.is_alive():
        problems.append("serving drive thread did not reach EOS")
    if rt.background_error is not None:
        problems.append(f"serving run raised "
                        f"{type(rt.background_error).__name__}: "
                        f"{rt.background_error}")
    if sorted(got) != sorted(oracle):
        missing = set(map(tuple, oracle)) - set(map(tuple, got))
        extra = set(map(tuple, got)) - set(map(tuple, oracle))
        problems.append(f"DIVERGED from the RecordSource oracle: "
                        f"missing={len(missing)} extra={len(extra)}")
    if src.frames_torn < 1:
        problems.append("no torn frame — the garbage/kill injection never "
                        "exercised resync")
    if src.frames_dup < 1:
        problems.append("no duplicate frame — the reconnect overlap never "
                        "exercised dedup")
    if rt.swaps_applied != 1:
        problems.append(f"swaps_applied={rt.swaps_applied}, want 1 (the "
                        f"wire-driven hot swap)")
    if rt.graph_label != "twin":
        problems.append(f"live graph is {rt.graph_label!r}, want 'twin'")
    try:
        with open(os.path.join(mon_dir, "snapshot.json")) as f:
            snap = json.load(f)
        srv = snap.get("serving") or {}
        if srv.get("graph") != "twin":
            problems.append("snapshot serving.graph did not record the swap")
        tenants = srv.get("tenants") or {}
        for t in ("a", "b"):
            if t not in tenants:
                problems.append(f"snapshot serving.tenants missing {t!r}")
            elif tenants[t].get("shed", 0):
                problems.append(f"tenant {t!r} shed "
                                f"{tenants[t]['shed']} batch(es) — "
                                f"unlimited tenants must never shed")
    except (OSError, ValueError) as e:
        problems.append(f"cannot read the serving snapshot: {e}")
    counters = {"torn": src.frames_torn, "dup": src.frames_dup,
                "decoded": src.frames_decoded, "kill_at": kill_at}
    src.close()
    shutil.rmtree(mon_dir, ignore_errors=True)
    return problems, counters


def plan_for(seed, threaded=False, shards=0):
    if threaded:
        # the threaded driver has no replay machinery: stalls only (delay,
        # never drop) — the watchdog must notice, results must not change
        return FaultPlan([FaultSpec("queue.stall", kind="stall", p=0.15,
                                    stall_s=0.4)], seed=seed)
    specs = [FaultSpec("source.next", p=0.06),
             FaultSpec("chain.step", p=0.08),
             FaultSpec("sink.consume", p=0.10)]
    if shards:
        # shard-local drills: random shard step kills (each recovers by
        # replaying ONLY that shard's key range) + a torn handoff against
        # the mid-stream live reshard (the seal must be discarded and the
        # move re-derived at the same barrier)
        specs += [FaultSpec("shard.kill", p=0.05),
                  FaultSpec("reshard.handoff", kind="torn", p=0.25,
                            max_fires=1)]
    return FaultPlan(specs, seed=seed)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--total", type=int, default=400)
    ap.add_argument("--batch", type=int, default=40)
    ap.add_argument("--controller", action="store_true",
                    help="run every driver with the adaptive control plane "
                    "active (admission/backpressure; baselines use the same "
                    "controller, so shedding must stay deterministic)")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="run the supervised drivers (pipeline + graph) "
                    "through N-way shard-local supervision (plus a live "
                    "N->2N reshard on the pipeline driver) with shard-kill "
                    "and torn-handoff injection added to each seed's plan; "
                    "baselines stay unsharded, so every seed asserts "
                    "shard-count invariance and shard-local recovery at "
                    "once")
    ap.add_argument("--remediate", action="store_true",
                    help="supervised pipeline runs (baselines AND chaos) "
                    "carry barrier remediation + deterministic admission "
                    "(byte-identity must still hold), plus one live "
                    "threaded closed-loop leg under queue.stall asserting "
                    "OK -> PAGE -> actuate -> recovery to OK with the "
                    "incident bundle recording the actions")
    ap.add_argument("--serve", action="store_true",
                    help="run ONLY the serving closed-loop legs (one per "
                    "seed): two tenants over a real loopback socket, a "
                    "seeded peer kill mid-stream + garbage + reconnect "
                    "overlap + a live graph hot-swap — outputs must be "
                    "byte-identical to a RecordSource oracle (zero loss, "
                    "torn frames resync'd, overlap deduped)")
    args = ap.parse_args()
    if args.serve:
        failures = 0
        for seed in range(args.seeds):
            t0 = time.time()
            problems, ctr = run_serve_loop(seed)
            ok = not problems
            print(f"[seed {seed}] serve: kill@chunk {ctr['kill_at']}, "
                  f"{ctr['decoded']} decoded / {ctr['torn']} torn / "
                  f"{ctr['dup']} dup, {'OK' if ok else 'FAILED'} "
                  f"({time.time() - t0:.1f}s)")
            for p in problems:
                print(f"            {p}")
            failures += bool(problems)
        if failures:
            print(f"FAIL: {failures} divergent serving run(s)")
            return 1
        print("PASS: all serving chaos runs byte-identical to the "
              "RecordSource oracle")
        return 0

    #: drivers that route through the sharded supervisors under --shards
    sharded_drivers = {"pipeline", "graph", "graph_det"}
    drivers = {"pipeline": run_pipeline, "graph": run_graph,
               "graph_det": run_graph_det, "threaded": run_threaded}
    baselines = {}
    for name, fn in drivers.items():
        t0 = time.time()
        kw = ({"remediate": True}
              if (args.remediate and name == "pipeline") else {})
        baselines[name] = fn(args.total, args.batch,
                             controller=args.controller, **kw)
        print(f"[baseline] {name}: {len(baselines[name])} results "
              f"({time.time() - t0:.1f}s)")

    divergences = 0
    for seed in range(args.seeds):
        for name, fn in drivers.items():
            n_shards = args.shards if name in sharded_drivers else 0
            inj = FaultInjector(plan_for(seed, threaded=(name == "threaded"),
                                         shards=n_shards))
            t0 = time.time()
            try:
                kw = {"shards": n_shards} if n_shards else {}
                if args.remediate and name == "pipeline":
                    kw["remediate"] = True
                out = fn(args.total, args.batch, faults=inj,
                         controller=args.controller, **kw)
            except Exception as e:          # noqa: BLE001
                print(f"[seed {seed}] {name}: RUN FAILED {type(e).__name__}: "
                      f"{e} ({len(inj.fired)} faults injected)")
                divergences += 1
                continue
            ok = out == baselines[name]
            print(f"[seed {seed}] {name}: {len(inj.fired)} faults injected, "
                  f"{'OK' if ok else 'DIVERGED'} ({time.time() - t0:.1f}s)")
            if not ok:
                divergences += 1
                missing = set(baselines[name]) - set(out)
                extra = set(out) - set(baselines[name])
                print(f"            missing={len(missing)} extra={len(extra)}")
    if args.remediate:
        t0 = time.time()
        problems, n_applies, n_faults = run_closed_loop(seed=0)
        ok = not problems
        print(f"[closed-loop] threaded: {n_faults} faults injected, "
              f"{n_applies} remediation action(s), "
              f"{'OK' if ok else 'FAILED'} ({time.time() - t0:.1f}s)")
        if not ok:
            for p in problems:
                print(f"            {p}")
            divergences += 1
    ctr = faults_mod.counters()
    print(f"\ncounters: {ctr}")
    if divergences:
        print(f"FAIL: {divergences} divergent run(s)")
        return 1
    print("PASS: all chaos runs byte-identical to the fault-free baselines")
    return 0


if __name__ == "__main__":
    sys.exit(main())
