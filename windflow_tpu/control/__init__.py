"""Adaptive control plane — closed-loop micro-batch autotuning, backpressure,
and load-shedding admission control.

The reference WindFlow fixes batch size and queue capacities at
graph-construction time and hand-searches them offline (the committed
{batch} x {sources} x {keys} sweep in ``src/GPU_Tests/new_tests/
run_tests.py``); PR 1's observability layer exposed exactly the signals a
controller needs (service percentiles, queue-depth gauges, watermark lag) but
nothing consumed them. This package closes the loop:

- ``autotune.py`` — :class:`CapacityAutotuner`: a power-of-two capacity
  ladder hill-climbed on measured tuples/s, switching among *cached* compiled
  executables (capacity is a static trace shape — ``CompiledChain.warm``
  pre-compiles every rung; the hot path never retraces), with a JSON
  :class:`TuningCache` keyed by (chain signature, payload spec, device kind)
  for warm starts. Actuated by the ``Pipeline`` driver via a
  :class:`Rebatcher` at the ingest boundary.
- ``governor.py`` — :class:`BackpressureGovernor`: per-edge high/low
  watermarks over the SPSC ring depths; throttles the source loop and pauses
  ``prefetch_to_device`` when a downstream stage falls behind. Actuated by
  ``ThreadedPipeline`` and ``PipeGraph._run_threaded``.
- ``admission.py`` — :class:`AdmissionController`: token-bucket rate
  limiting (:class:`TokenBucket` wall-clock / :class:`PositionBucket`
  deterministic-for-replay) + pluggable shed policy (``drop_newest`` /
  ``drop_oldest_ts``) at every driver's ingest boundary.
- ``remediation.py`` — :class:`RemediationPolicy`/:class:`RemediationEngine`:
  self-driving remediation mapping SLO burn signatures to these actuators —
  live on the Reporter tick, or as the deterministic
  :class:`BarrierRemediation` at supervised commit barriers (checkpointed
  decision state, byte-identical replay). Behind ``remediation=`` /
  ``WF_REMEDIATION``.

Everything is **off by default** and enabled per driver via ``control=``
(True, a dict of :class:`ControlConfig` fields, a config object) or
process-wide via ``WF_CONTROL`` — the ``monitoring=``/``faults=`` convention.
Every decision is counted (``MetricsRegistry`` snapshot section ``control``,
Prometheus ``windflow_control_*`` series) and journaled (``shed`` /
``throttle`` / ``capacity_switch`` / ``tuning_converged`` events).
"""

from ._state import bump, counters, gauges, reset, set_gauge
from .admission import (AdmissionController, PositionBucket, TokenBucket,
                        admission_from_config, admission_group,
                        bucket_from_config)
from .autotune import (CapacityAutotuner, Rebatcher, TuningCache,
                       build_ladder, chain_signature, device_kind,
                       payload_signature, tuning_key)
from .config import ControlConfig
from .governor import BackpressureGovernor, governor_from_config
from .remediation import (ACTUATORS, BarrierRemediation, RemediationAction,
                          RemediationEngine, RemediationPolicy,
                          barrier_policy_problems, default_barrier_policy,
                          default_policy, resolve_barrier_policy,
                          resolve_policy)

__all__ = [
    "ControlConfig", "AdmissionController", "TokenBucket", "PositionBucket",
    "BackpressureGovernor", "CapacityAutotuner", "Rebatcher", "TuningCache",
    "build_ladder", "chain_signature", "payload_signature", "device_kind",
    "tuning_key", "admission_from_config",
    "admission_group", "bucket_from_config", "governor_from_config",
    "RemediationAction", "RemediationPolicy", "RemediationEngine",
    "BarrierRemediation", "ACTUATORS", "default_policy", "resolve_policy",
    "default_barrier_policy", "resolve_barrier_policy",
    "barrier_policy_problems",
    "counters", "gauges", "reset", "bump", "set_gauge",
]
