"""``device_unscoped_share``: share of the device's busy time in the traced
slice spent in operations whose ``tf_op`` names no operator (copies, layout
changes; a fusion across operators goes to its root's scope): how far the
per-operator device times can be trusted."""

import span_reduce


def read(run):
    return span_reduce.unscoped_share(run)
