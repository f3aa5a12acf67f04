"""``ffat_fold_roofline``: the least time any fold of a batch into its pane
partials could take on the chip, its needed bytes over the chip's peak
memory bandwidth, as a share of the device time per batch under
``Key_FFAT``'s ``insert/fold`` (as ``ffat_fold_device_ms`` reads it). Bound
by bandwidth: the fold adds, it multiplies nothing it must.

The needed bytes (``fold_min_bytes``) come from the configuration's shapes:
the columns the query reads, 4 bytes each as the device holds them, read
once, and the ``[keys, ring slots]`` tables of partials and counts read and
written once. The cell is the one whose trace this is (``run.py`` keeps a
cell's under ``.bench_trace/<cell>/``). None without a trace, without the
chip's peaks, or where the program scopes no fold."""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TRACE_DIR = ".bench_trace"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _next_pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def fold_min_bytes(mod, cfg, batch):
    """Bytes one batch's fold must move: ``QUERY_COLUMNS`` read once, and
    the partials and the counts, ``[n_keys, P]`` int32 each with ``P`` the
    deployment's ring (``engine_budgets``, a power of two), read and written
    once."""
    ring = _next_pow2(mod.engine_budgets(cfg, batch)[0])
    return (batch * 4 * len(mod.QUERY_COLUMNS)
            + 2 * 2 * cfg["n_keys"] * ring * 4)


def cell_of(trace_path):
    """(configuration module, configuration, batch) of the cell whose traced
    slice ``trace_path`` holds, or None."""
    parts = os.path.normpath(trace_path).split(os.sep)
    if TRACE_DIR not in parts[:-1]:
        return None
    cell = parts[parts.index(TRACE_DIR) + 1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w for w in json.load(f)["workloads"] if w["name"] == cell]
    if not cells:
        return None
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        batch = json.load(f)["traffic"]["batch"]
    base = os.path.join(BENCH, "configs", cells[0]["config"])
    with open(base + ".json") as f:
        cfg = json.load(f)
    mod = _load(base + ".py", "roofline_cfg_" + cells[0]["config"])
    return mod, cfg, batch


def read(run):
    if not run.get("trace_path") or run.get("peaks") is None:
        return None
    fold = _load(os.path.join(HERE, "ffat_fold_device_ms.py"),
                 "roofline_fold_reader")
    fold_ms = fold.read(run)
    found = cell_of(run["trace_path"])
    if not fold_ms or found is None:
        return None
    least_s = fold_min_bytes(*found) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (fold_ms / 1e3)
