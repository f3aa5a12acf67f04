"""The Yahoo Streaming Benchmark pipeline at example scale.

EventSource -> Filter(view events) -> campaign join (device table lookup) ->
KeyBy(campaign) -> per-campaign tumbling time window counting views -> sink.
The flagship macro-benchmark (bench.py runs it at 1M-tuple batches on TPU);
this example runs it small and checks the window counts against an oracle.
"""
import _common
_common.bootstrap()

import numpy as np
import windflow_tpu as wf
from windflow_tpu.benchmarks import ysb

TOTAL = 40_000
results = []

def sink(view):
    if view is None:
        return
    results.extend(zip(view["key"].tolist(), view["id"].tolist(),
                       np.asarray(view["payload"]).tolist()))

src = ysb.make_source(total=TOTAL)
wf.Pipeline(src, ysb.make_ops(), wf.Sink(sink), batch_size=4096).run()

# oracle: replay the generator's arithmetic on the host
views = [i for i in range(TOTAL) if (i % 3) == 0]
expect = {}
for i in views:
    camp = (i * 7919) % ysb.N_ADS // ysb.ADS_PER_CAMPAIGN
    win = (i // ysb.EVENTS_PER_TICK) // ysb.WIN_LEN
    expect[(camp, win)] = expect.get((camp, win), 0) + 1
got = {(k, w): int(c) for k, w, c in results}
assert got == expect, "window counts diverge from the oracle"
print(f"YSB example OK: {len(got)} windows over {len(set(k for k,_ in got))} campaigns")
