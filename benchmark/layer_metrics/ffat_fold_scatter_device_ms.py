"""``ffat_fold_scatter_device_ms``: device time per batch of the traced slice
under the ``scatter`` scope inside ``Key_FFAT``'s ``insert/fold``: the exact
fallback of ``keyed_pane_fold`` (the two 1 M-lane scatters, counts and
values), which a batch takes when one of its chunks spans more panes than
the one-hot contraction holds. Self time by the ``XLA Ops`` line. None where
no such operation ran (an in-order stream, or a program without the scope)."""

import span_reduce

BELOW_OPERATOR = ("insert", "fold")
SCOPE = "scatter"


def under(scope):
    """Whether a scope path has ``insert/fold`` right after its first
    ``Class:name`` and ``scatter`` among the scopes below it (the last
    element is the operation's own name)."""
    parts = (scope or "").split("/")
    for i, part in enumerate(parts):
        if span_reduce.OPERATOR.match(part):
            return (tuple(parts[i + 1:i + 3]) == BELOW_OPERATOR
                    and SCOPE in parts[i + 3:-1])
    return False


def read(run):
    red = span_reduce.for_run(run)
    if red is None:
        return None
    rows = [r for r in red["device_ops"] if under(r["scope"])]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["slice_batches"]
