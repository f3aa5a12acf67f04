"""The benchmark's checks of itself, runnable without a chip:

    python3 benchmark/selfcheck.py

1. the trace reduction gives known numbers on the small recorded trace in
   ``testdata/`` (taken on the chip, see ``testdata/README.md``);
2. the generator's open-loop mode offers batches at the rate and counts
   latency from the due time;
3. every cell of ``BENCHMARK.json`` runs ``--rehearsal`` (tiny sizes, the
   pool cycled more than twice, EOS flush included) and is correct;
4. the control, the reference in bfloat16 in the program's place, is NOT
   correct in any cell;
5. with the timed path broken underneath (an answer altered where it is
   produced, a result dropped, a batch of results delivered twice) the rest
   of a run sees ``correct`` come out false.

Not collected by ``pytest tests/``. Exit code 0 when every check holds.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
import control as bench_control  # noqa: E402
import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
FAILURES = []


def check(name, ok, detail=""):
    print(f"{'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        FAILURES.append(name)


def check_trace_reduction():
    with open(os.path.join(HERE, "testdata", "expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce(os.path.join(HERE, "testdata", want["file"]),
                              bench_run.SLICE_NAME, bench_run.SPAN_NAMES)
    for k in ("window_s", "busy_s"):
        check(f"trace {k}", abs(got[k] - want[k]) <= 1e-9 * want[k],
              f"{got[k]!r} want {want[k]!r}")
    check("trace top operation", got["device_ops"][0][0] == want["top_op"],
          got["device_ops"][0][0])
    check("trace longest gap's label",
          got["idle_gaps"][0][0] == want["top_gap_label"],
          got["idle_gaps"][0][0])
    # a figure the reduction did not make: the slice's executions on the
    # trace's 'XLA Modules' line, read by hand (expected.json says how)
    devices, _ = trace_reduce.read_planes(
        os.path.join(HERE, "testdata", want["file"]), line="XLA Modules")
    (modules,) = devices.values()
    modules_s = sum(e - s for _, s, e in modules) / 1e9
    check("trace modules, by hand", len(modules) == want["modules"]
          and abs(modules_s - want["modules_s"]) < 1e-12, f"{modules_s!r}")
    check("trace busy_s within the modules' time, at least 98 % of it",
          0.98 * want["modules_s"] <= got["busy_s"] <= want["modules_s"])
    # the union counts overlapping operations once
    check("interval union", trace_reduce.union(
        [(0, 4), (2, 6), (8, 9), (9, 10)]) == [[0, 6], [8, 10]])


def check_open_loop():
    """The generator's open-loop mode, without a pipeline: batches fall due
    at the rate whatever the consumer does, and ``t_created`` is the due
    time, so a slow consumer's wait counts."""
    import time
    import types
    import traffic
    mod = types.SimpleNamespace(stamp=lambda cfg, recs, first: None)
    feed = traffic.Feed(mod, {}, {"mode": "open_loop", "batch": 1000,
                                  "rate_tuples_per_s": 50_000,
                                  "warm_prefix_batches": 2}, list(range(32)),
                        seconds=0.4)
    feed.thread.start()
    pulled = []
    for i, _ in enumerate(feed.records()):
        if i + 1 == feed.prefix:
            feed.prefix_delivered.set()
        if i == feed.prefix + 5:
            time.sleep(0.1)             # the consumer stalls for 5 periods
        pulled.append(time.perf_counter())
    feed.close()
    due = feed.t_created[feed.prefix:]
    steps = [b - a for a, b in zip(due, due[1:])]
    check("open loop: 20 timed batches in 0.4 s at 50 batches/s",
          len(due) == 20, str(len(due)))
    check("open loop: t_created advances by one period exactly",
          all(abs(d - 0.02) < 1e-9 for d in steps))
    check("open loop: no batch is pulled before it is due",
          all(p >= d for p, d in zip(pulled[feed.prefix:], due)))
    waits = [p - d for p, d in zip(pulled[feed.prefix:], due)]
    check("open loop: the consumer's stall shows as a wait from due time",
          max(waits) >= 0.09, f"{max(waits):.3f} s")


def rehearse(cell, seed, control=None):
    args = bench_run.parse_args(["--workload", cell, "--seed", str(seed),
                                 "--seconds", "1", "--trace", "0",
                                 "--rehearsal"])
    return bench_run.run_cell(args, control=control)


class broken_push:
    """Break ``CompiledChain.push`` underneath the harness, once, in the
    first timed batch that carries a result: ``fault(out) -> out``."""

    def __init__(self, fault, warm_pushes=7):
        from windflow_tpu.runtime.pipeline import CompiledChain
        self.cls, self.fault, self.warm = CompiledChain, fault, warm_pushes
        self.sound = CompiledChain.push

    def __enter__(self):
        sound, fault, warm, calls = self.sound, self.fault, self.warm, [0]

        def push(chain, batch, from_op=0):
            out = sound(chain, batch, from_op=from_op)
            calls[0] += 1
            # pushes 1-3 are the throw-away's, 4-7 the warm prefix
            if calls[0] > warm and bool(out.valid.any()):
                calls[0] = -10 ** 9
                return fault(out)
            return out
        self.cls.push = push

    def __exit__(self, *exc):
        self.cls.push = self.sound


def altered(out):
    import jax.numpy as jnp
    first = jnp.argmax(out.valid)
    return out.replace(payload=out.payload.at[first].add(1))


def dropped(out):
    import jax.numpy as jnp
    return out.replace(valid=out.valid.at[jnp.argmax(out.valid)].set(False))


class delivered_twice:
    """The sink sees one batch of results a second time."""

    def __enter__(self):
        from windflow_tpu.operators.sink import Sink
        self.cls, self.sound, calls = Sink, Sink.consume, [0]

        def consume(sink, batch):
            self.sound(sink, batch)
            calls[0] += 1
            # consumes 1-3 are the throw-away's, 4-7 the warm prefix
            if calls[0] > 7 and batch is not None and bool(batch.valid.any()):
                calls[0] = -10 ** 9
                self.sound(sink, batch)
        Sink.consume = consume

    def __exit__(self, *exc):
        self.cls.consume = self.sound


def main():
    check_trace_reduction()
    check_open_loop()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for i, cell in enumerate(cells):
        seed = 2 ** 31 + 17 + i
        r = rehearse(cell, seed)
        check(f"{cell} rehearsal correct", r["correct"], str(r["compared"]))
        r = rehearse(cell, seed, control=bench_control.lower_precision_results)
        check(f"{cell} control (bfloat16 reference) not correct",
              not r["correct"] and r["compared"]["results_wrong"]["value"] > 0,
              f"results_wrong {r['compared']['results_wrong']['value']} of "
              f"{r['attempted']}")
        for name, fault, number in (
                ("answer altered", broken_push(altered), "results_wrong"),
                ("result dropped", broken_push(dropped), "results_missing"),
                ("results delivered twice", delivered_twice(),
                 "results_twice")):
            with fault:
                r = rehearse(cell, seed)
            check(f"{cell} {name}: not correct",
                  not r["correct"] and r["compared"][number]["value"] > 0,
                  f"{number} {r['compared'][number]['value']}")
    print("selfcheck:", "FAILED " + ", ".join(FAILURES) if FAILURES
          else "all checks hold")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
