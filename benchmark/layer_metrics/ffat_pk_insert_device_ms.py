"""``ffat_pk_insert_device_ms``: device time per batch of the traced slice
under ``Key_FFAT``'s ``insert`` phase on the per-key time-based path: each
lane's key's horizon (``insert/lookup``), the three ``[K*P]`` reductions and
the fold into the ring (``insert/fold``), the per-key count and watermark
(``insert/keys``); self time by the ``XLA Ops`` line. None where the program
scopes no such phase."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "insert")
