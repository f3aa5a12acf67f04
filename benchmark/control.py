"""The control of ``correct``: the reference in the precision below the one
the configuration states, put in the program's place. It has to come out as
not correct. The benchmark's own runs never run this.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

runs each seed's window on the chip at the cell's own size, then judges the
bfloat16 reference's results over that same stream in place of the
pipeline's. Exit code 0 means every seed's control failed the comparison, as
it must. ``selfcheck.py`` keeps the same control at a size a CPU holds.
"""

import argparse
import sys

import run as bench_run


def lower_precision_results(mod, cfg, pool, n_batches, batch):
    """The configuration's own reference, accumulating in bfloat16: what a
    program that carried its counts and sums through a TPU's default
    one-pass matmul precision would deliver."""
    import ml_dtypes
    import numpy as np
    exp = mod.reference(cfg, pool, n_batches, batch,
                        acc_dtype=ml_dtypes.bfloat16)
    key, wid = np.nonzero(exp["must_deliver"])
    # window-id order within a key, as the guarantees ask
    return key, wid, exp["value"][key, wid].astype(np.float64)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    own, rest = ap.parse_known_args(argv)
    passed_as_correct = 0
    for seed in own.seeds.split(","):
        args = bench_run.parse_args(rest + ["--seed", seed, "--trace", "0"])
        result = bench_run.run_cell(args, control=lower_precision_results)
        wrong = result["compared"]["results_wrong"]["value"]
        print(f"control seed={seed}: correct={result['correct']} "
              f"results_wrong={wrong} of {result['attempted']}", flush=True)
        passed_as_correct += bool(result["correct"])
    return 1 if passed_as_correct else 0


if __name__ == "__main__":
    sys.exit(main())
