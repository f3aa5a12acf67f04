"""``chain_roofline``: the least time the chip could take for one batch, its
needed bytes (the configuration's ``min_bytes_per_batch``, from shapes) over
the chip's peak memory bandwidth, as a share of the device's busy time per
batch. Bound by bandwidth: the chains count and add, they multiply nothing."""


def read(run):
    trace = run["trace"]
    if (not trace or not trace["busy_s"] or not run["slice_batches"]
            or run["peaks"] is None):
        return None
    least_s = run["min_bytes_per_batch"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace["busy_s"] / run["slice_batches"])
