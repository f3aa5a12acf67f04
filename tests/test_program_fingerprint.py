"""The toggle-OFF program-identity gate (ISSUE 19 satellite): every
host-side observability/serving toggle must leave the compiled step program
*structurally identical* — not "results equal", the PROGRAM equal — across
the whole Nexmark query set.

One table-driven test replaces the per-PR ad-hoc HLO-text pins
(test_device_health/test_fleet/test_slo ``test_off_path_hlo_identical``):
each toggle row builds the same chain under its env set and asserts
``program_fingerprint`` equality against the no-env baseline.  The
fingerprint is the canonical structural hash of the traced jaxpr
(``analysis/progcheck.py``) — stable across processes, so these pins are
comparable between CI runs, not just within one.

``event_time`` is the one GEOMETRY-BINDING toggle (ON adds lateness
histograms to operator state, changing the program by design); its row
pins the OFF resolution under ``WF_MONITORING=1`` — the regression that
actually bites (monitoring on silently flipping event-time state in)."""

import pytest
import jax.numpy as jnp

import windflow_tpu as wf
from windflow_tpu.analysis import progcheck as pc
from windflow_tpu.nexmark import queries as q
from windflow_tpu.observability import device_health as dh

#: every env var any toggle row touches — cleared for the baseline build
_TOGGLE_ENVS = ("WF_MONITORING", "WF_MONITORING_HEALTH",
                "WF_MONITORING_EVENT_TIME", "WF_SLO", "WF_TELEMETRY",
                "WF_REMEDIATION", "WF_SERVE", "WF_PROFILE")

#: toggle -> env set; ``health`` additionally activates a live
#: HealthLedger around build+trace (the ledger hooks chain tracing)
TOGGLES = {
    "monitoring": {"WF_MONITORING": "1"},
    "health": {"WF_MONITORING": "1", "WF_MONITORING_HEALTH": "1"},
    "event_time": {"WF_MONITORING": "1", "WF_MONITORING_EVENT_TIME": "0"},
    "slo": {"WF_MONITORING": "1", "WF_SLO": "1"},
    "telemetry": {"WF_MONITORING": "1",
                  "WF_TELEMETRY": "tcp://127.0.0.1:9"},
    "remediation": {"WF_MONITORING": "1", "WF_SLO": "1",
                    "WF_REMEDIATION": "1"},
    "serving": {"WF_MONITORING": "1", "WF_SERVE": "1"},
    "profile": {"WF_MONITORING": "1", "WF_SLO": "1", "WF_PROFILE": "1"},
}


def _fingerprint(query: str) -> str:
    """Build the query's chain UNDER THE CURRENT ENV (CompiledChain
    consults the monitoring envs at construction) and fingerprint its
    per-push step program."""
    src, ops = q.make_query(query, total=512)
    chain = pc._mk_chain(src, ops, 64)
    return pc.step_fingerprint(chain, 64)


@pytest.mark.parametrize("query", sorted(q.QUERIES))
def test_toggles_off_program_identical(query, monkeypatch):
    for env in _TOGGLE_ENVS:
        monkeypatch.delenv(env, raising=False)
    base = _fingerprint(query)
    for name, envs in TOGGLES.items():
        for env in _TOGGLE_ENVS:
            monkeypatch.delenv(env, raising=False)
        for k, v in envs.items():
            monkeypatch.setenv(k, v)
        if name == "health":
            # a LIVE ledger during build+trace: its trace hooks ride the
            # jit path, the abstract trace here must stay untouched either
            # way (the ledger-observes-jit pin lives in test_device_health)
            led = dh.HealthLedger(cost_analysis=False)
            dh.set_active(led)
            try:
                fp = _fingerprint(query)
            finally:
                dh.set_active(None)
        else:
            fp = _fingerprint(query)
        assert fp == base, (
            f"{query}: toggle {name!r} changed the compiled step program "
            f"(fingerprint {fp[:16]} != baseline {base[:16]}) — host-side "
            f"toggles must be byte-for-byte OFF the device path")


def test_event_time_on_changes_program(monkeypatch):
    """The counter-pin that keeps the gate honest: event_time ON is
    geometry-binding (lateness histograms enter operator state), so its
    fingerprint MUST differ — if it ever stops differing, the gate above
    is vacuous."""
    for env in _TOGGLE_ENVS:
        monkeypatch.delenv(env, raising=False)
    base = _fingerprint("q5_session")
    monkeypatch.setenv("WF_MONITORING", "1")
    monkeypatch.setenv("WF_MONITORING_EVENT_TIME", "1")
    assert _fingerprint("q5_session") != base
