"""Configuration ``kcb``: the keyed count-based sliding-window sum.

The sizes live in ``kcb.json``; this file turns them into records, into the
system's operator chain, and into the plain reference the results are held to.
The reference is numpy on the logical stream and imports nothing of the
program.
"""

import numpy as np

#: upstream's ``tuple_t`` (``src/mp_test_gpu/mp_common_gpu.hpp``):
#: ``size_t key; uint64_t id; uint64_t ts; int64_t value``, 32 bytes
RECORD = np.dtype([("key", "<u8"), ("id", "<u8"), ("ts", "<u8"),
                   ("value", "<i8")])
KEY_FIELD = "key"
TS_FIELD = "ts"
#: what the query reads of a tuple, 4 bytes each as the device holds them
QUERY_COLUMNS = ("key", "value")


def _check_shapes(cfg, batch):
    per_key = batch // cfg["n_keys"]
    if (batch % cfg["n_keys"] or per_key % cfg["slide"]
            or cfg["win_len"] % cfg["slide"]):
        raise ValueError("kcb's reference wants whole slides per key and batch")
    return per_key


def make_pool(cfg, rng, batch, n_pool):
    _check_shapes(cfg, batch)
    pool = []
    for _ in range(n_pool):
        recs = np.zeros(batch, RECORD)
        recs["key"] = np.arange(batch, dtype=np.int64) % cfg["n_keys"]
        recs["value"] = rng.integers(0, cfg["v_max"] + 1, batch)
        pool.append(recs)
    return pool


def stamp(cfg, recs, first_index):
    """Records ``first_index ...`` of the logical stream: the progressive id
    within the key and a timestamp that advances by one a tuple, as the
    upstream test source sets them. Count-based windows read neither."""
    idx = np.arange(first_index, first_index + len(recs), dtype=np.uint64)
    recs["id"] = idx // cfg["n_keys"]
    recs["ts"] = idx


def build_ops(cfg, batch):
    import jax.numpy as jnp
    import windflow_tpu as wf
    return [wf.Key_FFAT(lambda t: t.value, jnp.add,
                        spec=wf.WindowSpec(cfg["win_len"], cfg["slide"]),
                        num_keys=cfg["n_keys"], name="kcb_window")]


def program_checks(cfg, ops):
    return {}


def reference(cfg, pool, n_batches, batch, acc_dtype=np.float64):
    """Sum of ``value`` per (key, window) over the first ``n_batches`` batches,
    window ``w`` of a key covering its tuples ``[w*slide, w*slide + win_len)``,
    the last one partial (end of stream), added up slide by slide in
    ``acc_dtype`` (float64: exact, the values are small integers; a lower
    precision is the control), and the batch that holds each window's last
    tuple."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    per_key = _check_shapes(cfg, batch)
    spb = per_key // slide                      # slides per key per batch
    # a pool batch as [position, key]; its slide sums as [slide, key]
    slide_sums = []
    for recs in pool:
        v = recs["value"].reshape(spb, slide, n_k)
        acc = np.zeros((spb, n_k), acc_dtype)
        for r in range(slide):                  # tuple by tuple, in acc_dtype
            acc = (acc + v[:, r].astype(acc_dtype)).astype(acc_dtype)
        slide_sums.append(acc)
    s = np.concatenate([slide_sums[j % len(pool)] for j in range(n_batches)])
    n_slides = len(s)                           # windows 0 .. n_slides - 1
    value = s.copy()
    for k in range(1, win // slide):
        value[:n_slides - k] = (value[:n_slides - k] + s[k:]).astype(acc_dtype)
    # window w's last tuple is position w*slide + win_len - 1 of its key,
    # or the key's last tuple where the stream ends first
    last_pos = np.minimum(np.arange(n_slides) * slide + win - 1,
                          n_batches * per_key - 1)
    last_batch = np.broadcast_to(last_pos // per_key, (n_k, n_slides))
    return {"value": value.T.astype(np.float64), "last_batch": last_batch,
            "must_deliver": np.ones((n_k, n_slides), bool)}


def min_bytes_per_batch(cfg, batch):
    """The least traffic one batch needs, whatever implements it: the columns
    the query reads (``QUERY_COLUMNS``) read once, each key's open slide sums
    read and written once, each result (key, window id, ts, sum) written
    once."""
    state = 2 * cfg["n_keys"] * (cfg["win_len"] // cfg["slide"]) * 4
    results = (batch // cfg["slide"]) * 16
    return batch * 4 * len(QUERY_COLUMNS) + state + results
