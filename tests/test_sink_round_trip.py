"""A result batch crosses to the host in one round trip: ``Batch.to_host``
starts every leaf's copy before it reads any, and every fetch of a result
batch (``Sink.consume``, the supervisors' commit buffer) goes through it."""

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.batch import Batch
from windflow_tpu.observability import tracing
from windflow_tpu.runtime.supervisor import _CommitBufferSink

C = 8
NAMES = ("key", "id", "ts", "v", "valid")


class FakeLeaf:
    """A device array as the sink sees one: a copy that can be started, and a
    read; both write to the shared ``log``."""

    def __init__(self, name, value, log):
        self.name, self.value, self.log = name, np.asarray(value), log
        self.shape, self.nbytes = self.value.shape, self.value.nbytes

    def copy_to_host_async(self):
        self.log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.name))
        return self.value


def fake_batch(log, host_leaves=()):
    cols = {"key": np.arange(C, dtype=np.int32) % 2,
            "id": np.arange(C, dtype=np.int32),
            "ts": np.arange(C, dtype=np.int32) * 10,
            "v": np.arange(C, dtype=np.int32) * 3,
            "valid": np.arange(C) % 4 != 3}
    leaf = {n: (a if n in host_leaves else FakeLeaf(n, a, log))
            for n, a in cols.items()}
    return Batch(key=leaf["key"], id=leaf["id"], ts=leaf["ts"],
                 payload={"v": leaf["v"]}, valid=leaf["valid"]), cols


@pytest.fixture
def spans(monkeypatch):
    """Every ``tracing.span`` of the test, as (name, counts)."""
    seen = []

    class Recorded:
        def __init__(self, name, **counts):
            seen.append((name, counts))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_annotation", Recorded)
    return seen


def d2h(spans):
    return [counts for name, counts in spans if name == "wf.sink.d2h"]


def fetch_to_host(batch):
    return batch.to_host()


def fetch_sink(batch):
    views = []
    wf.Sink(views.append).consume(batch)
    (view,) = views
    return view


def fetch_commit_buffer(batch):
    buffer = _CommitBufferSink(wf.Sink(lambda view: None))
    buffer.consume(batch)
    (host,) = buffer.pending
    return host


@pytest.mark.parametrize("host_leaves", [(), NAMES, ("ts", "valid")],
                         ids=["device_leaves", "numpy_leaves", "mixed_leaves"])
@pytest.mark.parametrize("fetch", [fetch_to_host, fetch_sink,
                                   fetch_commit_buffer],
                         ids=["to_host", "sink_consume", "commit_buffer"])
def test_every_copy_starts_before_any_read(fetch, host_leaves):
    log = []
    batch, cols = fake_batch(log, host_leaves)
    got = fetch(batch)
    device = sorted(n for n in NAMES if n not in host_leaves)
    # each device leaf started once and read once, every start before the
    # first read; a numpy leaf is neither
    kinds = [kind for kind, _ in log]
    assert kinds == ["start"] * len(device) + ["read"] * len(device)
    assert sorted(n for kind, n in log if kind == "start") == device
    assert sorted(n for kind, n in log if kind == "read") == device
    if fetch is fetch_sink:             # the callback's view: live lanes only
        live = cols["valid"]
        for name in ("key", "id", "ts"):
            np.testing.assert_array_equal(got[name], cols[name][live])
        np.testing.assert_array_equal(got["payload"]["v"], cols["v"][live])
    else:                               # the whole batch, as numpy
        for name in ("key", "id", "ts", "valid"):
            leaf = getattr(got, name)
            assert type(leaf) is np.ndarray
            np.testing.assert_array_equal(leaf, cols[name])
        np.testing.assert_array_equal(got.payload["v"], cols["v"])


def test_none_passes_through(spans):
    views = []
    sink = wf.Sink(views.append)
    sink.consume(None)
    assert views == [None]
    assert d2h(spans) == []


def run_pipeline(async_depth=0, total=200, batch=32):
    src = wf.Source(lambda i: {"v": (i % 9).astype(jnp.float32)}, total=total,
                    num_keys=2)
    got = []

    def cb(view):
        got.append(None if view is None
                   else (view["id"].tolist(), view["payload"]["v"].tolist()))

    sink = wf.Sink(cb, async_depth=async_depth)
    pipe = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v * 3})], sink,
                       batch_size=batch)
    pipe.run()
    return got, sink


def test_pipeline_run_marks_one_d2h_per_batch(spans):
    got, sink = run_pipeline()
    n_batches = -(-200 // 32)
    counts = d2h(spans)
    assert [c["pos"] for c in counts] == list(range(n_batches))
    # key, id, ts, the payload leaf, valid of a 32-lane batch
    assert {c["bytes"] for c in counts} == {32 * 17}
    assert sink.get_StatsRecords()[0].bytes_copied_dh == n_batches * 32 * 17
    assert got[-1] is None and len(got) == n_batches + 1
    ids = [i for ids, _ in got[:-1] for i in ids]
    assert ids == list(range(200))
    assert [v for _, vs in got[:-1] for v in vs] == [
        float(i % 9 * 3) for i in range(200)]


def test_consume_of_device_batches_by_hand(spans):
    """What ``pipegraph``, ``threaded`` and ``serving`` do: ``consume`` per
    batch, in order, delivered before it returns."""
    views = []
    sink = wf.Sink(views.append)
    for start in (0, C):
        ids = np.arange(start, start + C, dtype=np.int32)
        sink.consume(Batch.of({"v": jnp.asarray(ids * 2)}, id=ids, ts=ids))
        assert len(views) == start // C + 1
    sink.consume(None)
    assert len(d2h(spans)) == 2
    assert [v["payload"]["v"].tolist() for v in views[:2]] == [
        list(range(0, 2 * C, 2)), list(range(2 * C, 4 * C, 2))]
    assert views[2] is None


@pytest.mark.parametrize("async_depth", [1, 3])
def test_async_depth_behaves_as_before(spans, async_depth):
    sync, _ = run_pipeline()
    n_sync = len(d2h(spans))
    got, _ = run_pipeline(async_depth=async_depth)
    assert got == sync                  # same views, same order, EOS last
    # the shipper's path: no synchronous copy
    assert len(d2h(spans)) == n_sync == len(sync) - 1
