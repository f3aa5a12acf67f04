"""``chain_push_ms``: time per batch of the traced slice inside the program's
``wf.chain.push`` span, read from the profiler's file:
``CompiledChain.push``: the inside twin of the harness's ``push_ms``."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.chain.push")
