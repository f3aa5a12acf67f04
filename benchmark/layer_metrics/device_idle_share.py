"""``device_idle_share``: the part of the traced slice in which no operation
ran on the device."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
