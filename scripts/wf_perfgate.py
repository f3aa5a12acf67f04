#!/usr/bin/env python3
"""wf_perfgate — the hermetic perf gate over this repository.

Compiles the gate workloads (YSB + mp-matrix chains) AOT on the CPU backend,
reads XLA's logical cost model (FLOPs / bytes accessed per step), and
compares against the checked-in ratchet-down baseline
(``windflow_tpu/analysis/perfgate_baseline.json``); CPU-proxy kernel
microbenchmarks ride along as advisory trend rows. Zero device access — the
whole gate runs on a laptop or a CI box without an accelerator.

    JAX_PLATFORMS=cpu python scripts/wf_perfgate.py            # text report
    python scripts/wf_perfgate.py --format=json                # machine-readable
    python scripts/wf_perfgate.py --update-baseline            # bank current costs

Exit codes (the wf_lint.py contract): 0 = clean, 1 = findings (regressions,
stale pins, unpinned workloads), 2 = internal error / explicit-but-missing
baseline — a broken gate must never masquerade as a clean one.

Baseline override: ``--baseline`` or the ``WF_PERFGATE_BASELINE`` env var.
``--update-baseline`` rewrites the resolved baseline from the current
measurement (do this ONLY for intentional cost changes; the ratchet exists
so improvements are banked and regressions cannot hide behind old pins).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="wf_perfgate",
        description="windflow_tpu hermetic perf gate (XLA cost-analysis "
                    "pins + CPU-proxy microbenchmarks, no device)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=None,
                    help="baseline file overriding analysis/"
                         "perfgate_baseline.json (WF_PERFGATE_BASELINE env "
                         "does the same)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current measurement "
                         "and exit 0")
    ap.add_argument("--rtol", type=float, default=None,
                    help="relative tolerance around each cost pin "
                         "(default 0.02)")
    ap.add_argument("--skip-proxy", action="store_true",
                    help="skip the CPU-proxy microbenchmarks (cost pins "
                         "only)")
    ap.add_argument("--strict-proxy", action="store_true",
                    help="fail on proxy timings beyond the advisory factor "
                         "(noisy boxes: leave off)")
    ap.add_argument("--reps", type=int, default=3,
                    help="proxy microbenchmark repetitions (min taken)")
    args = ap.parse_args(argv)

    try:
        sys.path.insert(0, REPO)
        # the gate is hermetic BY CONSTRUCTION: pin the CPU backend before
        # jax initializes so an accelerator can neither be touched nor held
        # by the gate — an unconditional overwrite, NOT setdefault
        os.environ["JAX_PLATFORMS"] = "cpu"
        from windflow_tpu.analysis import perfgate

        if args.baseline:
            # resolve against the INVOKER's cwd (the wf_lint.py convention)
            os.environ["WF_PERFGATE_BASELINE"] = \
                os.path.abspath(args.baseline)
        bpath = perfgate.baseline_path(REPO)
        if args.update_baseline:
            report = perfgate.measure(skip_proxy=args.skip_proxy,
                                      reps=args.reps)
            perfgate.save_baseline(bpath, report)
            print(f"wf_perfgate: pinned {len(report['workloads'])} "
                  f"workload(s) to {bpath}")
            return 0
        report, findings = perfgate.run_gate(
            REPO, rtol=(args.rtol if args.rtol is not None
                        else perfgate.DEFAULT_RTOL),
            skip_proxy=args.skip_proxy, strict_proxy=args.strict_proxy,
            reps=args.reps)
    except Exception as e:  # noqa: BLE001 — a broken gate must exit 2,
        #                     never masquerade as clean (0) or dirty (1)
        print(f"wf_perfgate: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.format == "json":
        print(json.dumps({"report": report, "findings": findings}, indent=1))
    else:
        for w, row in sorted(report["workloads"].items()):
            print(f"{w}@{row['capacity']}: flops={row['flops']:.6g} "
                  f"bytes={row['bytes_accessed']:.6g}")
        for k, row in sorted(report.get("proxy", {}).items()):
            print(f"proxy {k}: {row['ns_per_elem']:g} ns/elem "
                  f"({row['elems']} elems)")
        for x in findings:
            print(f"FINDING [{x['kind']}] {x['message']}")
        print(f"wf_perfgate: {len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
