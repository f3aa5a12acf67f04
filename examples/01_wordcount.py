"""Streaming word-count: the hello-world of stream processing.

FlatMap (line -> words, static max_fanout) -> per-key rolling count
(Accumulator, KEYBY routing) -> host sink. Runs on CPU or TPU unchanged.

Counterpart of the reference's basic graph tests (src/graph_test) in spirit:
a tiny end-to-end PipeGraph with a self-checking result.
"""
import _common
_common.bootstrap()

import jax.numpy as jnp
import numpy as np
import windflow_tpu as wf

# synthetic "documents": each source item i carries 3 word ids drawn from a
# zipf-ish table; the FlatMap ships one tuple per word
VOCAB = 50

def make_words(i):
    return {"w": jnp.stack([(i * 7) % VOCAB, (i * 13) % VOCAB, (i * 29) % VOCAB])}

def split_words(t, shipper):
    for j in range(3):
        shipper.push({"word": t.w[j]})

counts = {}

def sink(view):
    if view is None:
        return
    for k, v in zip(view["key"].tolist(), np.asarray(view["payload"]).tolist()):
        counts[k] = v            # rolling count per word id

TOTAL = 3000
g = wf.PipeGraph("wordcount", batch_size=256)
(g.add_source(wf.Source(make_words, total=TOTAL))
 .add(wf.FlatMap(split_words, max_fanout=3))
 .add(wf.Map(lambda t: {"one": jnp.ones((), jnp.int32), "word": t.word}))
 .add(wf.KeyBy(lambda t: t.word, num_keys=VOCAB))
 .add(wf.Accumulator(lambda t: t.data["one"], init_value=0, num_keys=VOCAB))
 .add_sink(wf.Sink(sink)))
g.run()

expect = {}
for i in range(TOTAL):
    for w in ((i * 7) % VOCAB, (i * 13) % VOCAB, (i * 29) % VOCAB):
        expect[w] = expect.get(w, 0) + 1
got = {k: int(v) for k, v in counts.items()}
assert got == expect, "word counts diverge from the oracle"
print(f"wordcount OK: {len(got)} words, {sum(got.values())} total")
