"""``ffat_ordered_fold_device_ms``: device time per batch of the traced slice
under ``insert/fold/ordered`` of ``Key_FFAT`` (the ordered fold of a combine
called on whole structs: the stable sort by (key, pane slot), the segmented
scan, the placement of each run's last lane into ``[K*P]`` and the fold into
the ring, the ring's partial first), self time by the ``XLA Ops`` line. None
where the program opens no such scope (an additive combine, or a program
without the ordered fold)."""

import span_reduce

BELOW_OPERATOR = ("insert", "fold", "ordered")


def under(scope, below=BELOW_OPERATOR):
    """Whether a scope path has ``below`` right after its first
    ``Class:name``."""
    parts = (scope or "").split("/")
    for i, part in enumerate(parts):
        if span_reduce.OPERATOR.match(part):
            return tuple(parts[i + 1:i + 1 + len(below)]) == below
    return False


def read(run, below=BELOW_OPERATOR):
    red = span_reduce.for_run(run)
    if red is None:
        return None
    rows = [r for r in red["device_ops"] if under(r["scope"], below)]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["slice_batches"]
