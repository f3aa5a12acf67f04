"""Hermetic perf gate (analysis/perfgate.py + scripts/wf_perfgate.py):
the repo gate is green against the checked-in cost pins, the ratchet-down
compare semantics (regression AND stale pins fail), the 0/1/2 CLI exit
contract, proxy coverage over every registered kernel, and the per-stage
cost rows bench.py attaches to captures. Device-free by construction —
everything here runs on the CPU backend."""

import copy
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from windflow_tpu.analysis import perfgate  # noqa: E402


@pytest.fixture(scope="module")
def measurement():
    """ONE AOT measurement shared by the module (compiles both workloads;
    proxy reps kept minimal for CI wall time)."""
    return perfgate.measure(reps=1)


def _cli_main(argv):
    """scripts/wf_perfgate.py main() in-process (no subprocess: one jax
    import per tier-1 run, not one per exit-code case)."""
    path = os.path.join(ROOT, "scripts", "wf_perfgate.py")
    spec = importlib.util.spec_from_file_location("wf_perfgate_cli", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["wf_perfgate_cli"] = mod
    spec.loader.exec_module(mod)
    return mod.main(argv)


# ------------------------------------------------------------ the repo gate


def test_repo_gate_green_against_checked_in_baseline(measurement):
    """THE tier-1 perf gate: current cost-analysis of the compiled YSB +
    mp-matrix chains matches the pinned baseline within rtol — a fusion
    break / dtype promotion / gather blowup fails here with zero device
    access."""
    findings = perfgate.compare(
        measurement, perfgate.load_baseline(perfgate.baseline_path(ROOT)))
    assert findings == [], json.dumps(findings, indent=1)


def test_measurement_shape(measurement):
    for name in perfgate.WORKLOADS:
        row = measurement["workloads"][name]
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert row["capacity"] == perfgate.WORKLOAD_CAPACITY[name]


def test_proxy_covers_every_registered_kernel(measurement):
    """CPU-proxy microbenchmarks exist (and measured a positive time) for
    every kernel family in names.py::KERNELS — a newly registered kernel
    without a proxy row fails the gate's coverage finding too."""
    from windflow_tpu.observability.names import KERNELS
    for k in KERNELS:
        assert k in measurement["proxy"], k
        assert measurement["proxy"][k]["ns_per_elem"] > 0
    assert perfgate.compare(measurement, {"workloads":
                                          measurement["workloads"],
                                          "proxy": measurement["proxy"]}
                            ) == []


# -------------------------------------------------- compare() semantics


def _synth():
    current = {"workloads": {"ysb": {"flops": 1000.0,
                                     "bytes_accessed": 500.0,
                                     "capacity": 2048}}}
    baseline = copy.deepcopy(current)
    return current, baseline


def test_compare_clean_within_rtol():
    current, baseline = _synth()
    current["workloads"]["ysb"]["flops"] *= 1.01      # inside rtol=0.02
    assert perfgate.compare(current, baseline) == []


def test_compare_regression_fails():
    current, baseline = _synth()
    current["workloads"]["ysb"]["flops"] *= 1.10
    [f] = perfgate.compare(current, baseline)
    assert f["kind"] == "regression" and f["metric"] == "flops"


def test_compare_stale_pin_fails_ratchet_down():
    """An IMPROVEMENT beyond rtol is also a finding: the better number must
    be banked with --update-baseline or the gate would let it erode back."""
    current, baseline = _synth()
    current["workloads"]["ysb"]["bytes_accessed"] *= 0.80
    [f] = perfgate.compare(current, baseline)
    assert f["kind"] == "stale-pin" and "update-baseline" in f["message"]


def test_compare_unpinned_and_stale_workloads_fail():
    current, baseline = _synth()
    current["workloads"]["nexmark"] = {"flops": 1.0, "bytes_accessed": 1.0,
                                       "capacity": 64}
    del baseline["workloads"]["ysb"]
    baseline["workloads"]["retired"] = {"flops": 2.0, "bytes_accessed": 2.0,
                                        "capacity": 64}
    kinds = sorted(f["kind"] for f in perfgate.compare(current, baseline))
    assert kinds == ["stale-workload", "unpinned", "unpinned"]


def test_compare_capacity_drift_fails():
    current, baseline = _synth()
    current["workloads"]["ysb"]["capacity"] = 4096
    [f] = perfgate.compare(current, baseline)
    assert f["kind"] == "capacity-drift"


def test_compare_no_baseline_means_unpinned():
    current, _ = _synth()
    [f] = perfgate.compare(current, None)
    assert f["kind"] == "unpinned"


def test_compare_proxy_advisory_vs_strict():
    current, baseline = _synth()
    from windflow_tpu.observability.names import KERNELS, PERF_PROXY_FAMILIES
    current["proxy"] = {k: {"ns_per_elem": 100.0, "elems": 1}
                        for k in KERNELS + PERF_PROXY_FAMILIES}
    baseline["proxy"] = {"lookup": {"ns_per_elem": 10.0}}
    # default: proxy timings never fail the gate (noisy CI boxes)
    assert perfgate.compare(current, baseline) == []
    strict = perfgate.compare(current, baseline, strict_proxy=True)
    assert [f["kind"] for f in strict] == ["proxy-regression"]


# --------------------------------------------------------- CLI contract


def test_cli_update_baseline_then_green_then_regression(tmp_path,
                                                        monkeypatch,
                                                        capsys):
    """Exit-code contract on a scratch baseline: --update-baseline (0) ->
    clean gate (0) -> doctored pin (1)."""
    bpath = tmp_path / "perfgate_baseline.json"
    monkeypatch.setenv("WF_PERFGATE_BASELINE", str(bpath))
    assert _cli_main(["--update-baseline", "--skip-proxy", "--reps", "1"]) \
        == 0
    assert _cli_main(["--skip-proxy", "--reps", "1"]) == 0
    doc = json.loads(bpath.read_text())
    for row in doc["workloads"].values():
        row["flops"] *= 0.5               # current is now a 2x "regression"
    bpath.write_text(json.dumps(doc))
    assert _cli_main(["--skip-proxy", "--reps", "1"]) == 1
    out = capsys.readouterr().out
    assert "regression" in out


def test_cli_exit_2_on_missing_explicit_baseline(tmp_path, monkeypatch,
                                                 capsys):
    """An explicit WF_PERFGATE_BASELINE pointing nowhere is a BROKEN gate
    (exit 2) — never 'no baseline yet' (the wf_lint.py contract)."""
    monkeypatch.setenv("WF_PERFGATE_BASELINE", str(tmp_path / "typo.json"))
    assert _cli_main(["--skip-proxy"]) == 2
    assert "internal error" in capsys.readouterr().err


# ------------------------------------------------------ per-stage costs


def test_stage_costs_rows_per_operator():
    """analysis/perfgate.py::stage_costs — the rows bench.py attaches next
    to each capture's metrics snapshot: one row per op, flops/bytes
    present, capacities flowed through out_capacity."""
    chain, _step, cap = perfgate.WORKLOADS["mp_matrix"]()
    rows = perfgate.stage_costs(chain, cap)
    assert len(rows) == len(chain.ops)
    for row in rows:
        assert "error" not in row, row
        assert row["flops"] >= 0 and row["bytes_accessed"] > 0
    assert rows[0]["capacity"] == cap


# ----------------------------------------------- proxy family coverage


def test_proxy_family_row_and_coverage(measurement):
    """A non-kernel proxy family (names.py::PERF_PROXY_FAMILIES) is
    measured — and dropping it is a coverage finding, the KERNELS
    convention."""
    row = measurement["proxy"]["join"]
    assert row["ns_per_elem"] > 0
    pruned = {"workloads": measurement["workloads"],
              "proxy": {k: v for k, v in measurement["proxy"].items()
                        if k != "join"}}
    findings = perfgate.compare(pruned, pruned)
    assert any(f["kind"] == "proxy-coverage" and f["workload"] == "join"
               for f in findings)
