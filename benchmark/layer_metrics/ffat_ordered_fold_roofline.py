"""``ffat_ordered_fold_roofline``: the least time any ordered fold of a batch
could take on the chip, its needed bytes over the chip's peak memory
bandwidth, as a share of the device time per batch under ``Key_FFAT``'s
``insert/fold/ordered`` (as ``ffat_ordered_fold_device_ms`` reads it). Bound
by bandwidth: the fold compares and subtracts, it multiplies nothing.

The needed bytes (``ordered_fold_min_bytes``) come from the configuration's
shapes: the columns the query reads, 4 bytes each as the device holds them,
read once, and every leaf of the ``[keys, ring slots]`` partials read and
written once. The cell is the one whose trace this is (``run.py`` keeps a
cell's under ``.bench_trace/<cell>/``, found as ``ffat_fold_roofline.py``
finds it). None without a trace, without the chip's peaks, or where the
program scopes no ordered fold."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ordered_roofline_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _next_pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def ordered_fold_min_bytes(mod, cfg, batch):
    """Bytes one batch's ordered fold must move: ``QUERY_COLUMNS`` read
    once, and each of the configuration's ``PARTIAL_BYTES / 4 - 1`` int32
    leaves (the partials without the count, which is not the ordered fold's)
    of ``[n_keys, P]`` read and written once, ``P`` the deployment's ring
    (``engine_budgets``, a power of two)."""
    ring = _next_pow2(mod.engine_budgets(cfg, batch)[0])
    leaves = mod.PARTIAL_BYTES // 4 - 1
    return (batch * 4 * len(mod.QUERY_COLUMNS)
            + 2 * leaves * cfg["n_keys"] * ring * 4)


def read(run):
    if not run.get("trace_path") or run.get("peaks") is None:
        return None
    fold_ms = _load("ffat_ordered_fold_device_ms").read(run)
    found = _load("ffat_fold_roofline").cell_of(run["trace_path"])
    if not fold_ms or found is None:
        return None
    least_s = ordered_fold_min_bytes(*found) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (fold_ms / 1e3)
