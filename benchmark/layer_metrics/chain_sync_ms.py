"""``chain_sync_ms``: time per batch of the traced slice inside the program's
``wf.chain.sync`` span, read from the profiler's file: the sampled
``block_until_ready`` inside ``push`` (one push in
``SERVICE_SAMPLE_EVERY``); what is left of ``chain_push_ms`` is dispatch."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.chain.sync")
