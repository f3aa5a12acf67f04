"""Benchmark harness utilities shared by the sweep, bench.py, and probes."""

from typing import Callable, Optional

import jax


def device_cursor_step(chain, src, batch: int,
                       out_fn: Optional[Callable] = None):
    """Build the canonical jitted bench step with a DEVICE-RESIDENT cursor:
    ``step(states, cur) -> (states, cur + batch, out_fn(b))``.

    One host->device scalar upload at open, zero per step — the same
    discipline as ``operators/source.py::batches`` (a per-step host-int
    argument costs a 4 B H2D on every dispatch and sits inside every
    latency sample). ``out_fn``
    picks the step output to hang timing/data-dependence on (default: the
    batch's valid mask)."""
    if out_fn is None:
        out_fn = lambda b: b.valid  # noqa: E731

    def step(states, cur):
        b = src.make_batch(cur, batch)
        states = list(states)
        for j, op in enumerate(chain.ops):
            states[j], b = op.apply(states[j], b)
        return tuple(states), cur + batch, out_fn(b)

    return jax.jit(step, donate_argnums=(0, 1))
