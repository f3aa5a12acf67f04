"""``ffat_fold_device_ms``: device time per batch of the traced slice under
``insert/fold`` of ``Key_FFAT``'s global-time path (the lift, the segment
fold of a batch's values into its (key, pane) partials: the ``segment_fold``
call site, and the add into the ring), self time by the ``XLA Ops`` line.
None where the program opens no ``fold`` scope right under an operator's
``insert``."""

import span_reduce

BELOW_OPERATOR = ("insert", "fold")


def under(scope):
    """Whether a scope path has ``insert/fold`` right after its first
    ``Class:name``."""
    parts = (scope or "").split("/")
    for i, part in enumerate(parts):
        if span_reduce.OPERATOR.match(part):
            return tuple(parts[i + 1:i + 3]) == BELOW_OPERATOR
    return False


def read(run):
    red = span_reduce.for_run(run)
    if red is None:
        return None
    rows = [r for r in red["device_ops"] if under(r["scope"])]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["slice_batches"]
