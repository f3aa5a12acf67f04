"""``sink_wait_ms``: host-clock time per batch in the harness's ``sink_consume`` span,
over the batches of the traced slice."""


def read(run):
    rows = run["spans"]["sink_consume"]
    if not rows or not run["slice_batches"]:
        return None
    return sum(t1 - t0 for _, t0, t1 in rows) * 1e3 / run["slice_batches"]
