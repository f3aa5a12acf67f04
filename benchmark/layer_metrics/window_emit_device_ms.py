"""``window_emit_device_ms``: the part of ``window_device_ms`` under the
window engine's ``emit`` phase."""

import span_reduce


def read(run):
    return span_reduce.window_ms(run, "emit")
