"""``device_busy_ms``: union of the device-operation intervals in the traced
slice, per batch."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"] or not run["slice_batches"]:
        return None
    return trace["busy_s"] * 1e3 / run["slice_batches"]
