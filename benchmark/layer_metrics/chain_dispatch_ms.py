"""``chain_dispatch_ms``: the duration of ``wf.chain.dispatch``, the ``jit`` call
alone inside ``CompiledChain._push`` (argument flattening, PjRt ``Execute``).
Median over the batches of the traced slice (``timeline_reduce.py``); None
under 8 rows, and for a program without the span."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "chain_dispatch_ms")
