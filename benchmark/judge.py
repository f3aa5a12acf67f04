"""The comparison that decides ``correct``, and the latency arithmetic.

What is compared is what the timed pipeline's sink received, warm prefix and
EOS flush included, against the configuration's plain reference over the same
logical stream. Every comparison is exact: each number has the limit 0.
"""

import numpy as np


def gather(deliveries):
    """Sink callbacks ``(t, key, id, payload)`` as flat arrays, in order."""
    if not deliveries:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0), np.zeros(0), z
    key = np.concatenate([d[1] for d in deliveries]).astype(np.int64)
    wid = np.concatenate([d[2] for d in deliveries]).astype(np.int64)
    val = np.concatenate([np.asarray(d[3]).reshape(-1) for d in deliveries])
    t = np.concatenate([np.full(len(d[1]), d[0]) for d in deliveries])
    burst = np.concatenate([np.full(len(d[1]), i, np.int64)
                            for i, d in enumerate(deliveries)])
    return key, wid, val.astype(np.float64), t, burst


def compare(expected, key, wid, val, ordered_per_key):
    """Numbers compared, each ``name: (value, limit)``; all limits are 0."""
    want = expected["value"]
    n_keys, n_wid = want.shape
    inside = (key >= 0) & (key < n_keys) & (wid >= 0) & (wid < n_wid)
    cell = key[inside] * n_wid + wid[inside]
    seen = np.bincount(cell, minlength=n_keys * n_wid).reshape(want.shape)
    got = np.zeros(want.shape, np.float64)
    got[key[inside], wid[inside]] = val[inside]
    numbers = {
        "results_wrong": int(np.count_nonzero(got != want)),
        "results_missing": int(np.count_nonzero(
            expected["must_deliver"] & (seen == 0))),
        "results_twice": int(np.count_nonzero(seen > 1)),
        "results_unknown": int(np.count_nonzero(~inside)),
    }
    if ordered_per_key:
        # stable sort by key keeps delivery order within a key
        order = np.argsort(key, kind="stable")
        k, w = key[order], wid[order]
        numbers["results_out_of_order"] = int(np.count_nonzero(
            (k[1:] == k[:-1]) & (w[1:] <= w[:-1])))
    return {name: (v, 0) for name, v in numbers.items()}


def latencies_ms(expected, key, wid, t, burst, t_created, t_open, prefix):
    """Per result delivered in the window: delivery time minus ``t_created``
    of the batch holding its last contributing event. Results delivered before
    the window opened, or whose last event lies in the warm prefix, are not
    samples. Returns (latencies, number of distinct delivery moments)."""
    n_keys, n_wid = expected["value"].shape
    ok = (key >= 0) & (key < n_keys) & (wid >= 0) & (wid < n_wid) \
        & (t >= t_open)
    last = np.full(len(key), -1, np.int64)
    last[ok] = expected["last_batch"][key[ok], wid[ok]]
    ok &= (last >= prefix) & (last < len(t_created))
    lat = (t[ok] - np.asarray(t_created)[last[ok]]) * 1e3
    return lat, len(np.unique(burst[ok]))
