"""Device time by the stage of a two-engine window pattern.

``Pane_Farm`` runs each of its engines under a scope of its own, ``plq`` or
``wlq``, directly inside the pattern's ``Class:name`` scope, with the
engine's ``insert`` / ``emit`` phases below it. ``stage_ms`` adds up the self
time of the device operations under one stage, from the reduction the other
scope readers share (``span_reduce.for_run``). A program that opens no such
scope (a parent commit, another pattern) gives None.
"""

import span_reduce


def stage_of(scope):
    """The element right after the first ``Class:name`` of a scope path."""
    parts = (scope or "").split("/")
    for i, part in enumerate(parts[:-1]):
        if span_reduce.OPERATOR.match(part):
            return parts[i + 1]
    return None


def stage_ms(run, stage):
    """ms of device time per batch of the slice under ``<operator>/<stage>``."""
    red = span_reduce.for_run(run)
    if red is None:
        return None
    rows = [r for r in red["device_ops"] if stage_of(r["scope"]) == stage]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["slice_batches"]
