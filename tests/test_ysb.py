"""YSB (flagship macro-benchmark) correctness: the sum of all emitted window counts
must equal the number of view events in the stream (reference oracle: the sink
accumulates per-window counts, src/yahoo_test_cpu/test_ysb_kf.cpp), invariant under
batch size and across the KF (Key_FFAT) and WMR (Win_MapReduce) window variants."""

import re
import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.benchmarks import ysb

TOTAL = 3000        # 300 time units = 3 windows per campaign


def run_variant(make_ops_fn, batch_size, **kw):
    src = ysb.make_source(TOTAL)
    ops = make_ops_fn(**kw)
    results = []

    def cb(view):
        if view is None:
            return
        for k, w, c in zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()):
            results.append((int(k), int(w), int(c)))

    wf.Pipeline(src, ops, wf.Sink(cb), batch_size=batch_size).run()
    return sorted(results)


@pytest.mark.parametrize("batch_size", [256, 1000, TOTAL])
def test_ysb_kf_totals_match_oracle(batch_size):
    res = run_variant(ysb.make_ops, batch_size)
    assert res, "no window results emitted"
    assert sum(c for _, _, c in res) == ysb.oracle_totals(TOTAL)


def test_ysb_wmr_matches_kf_windows():
    kf = run_variant(ysb.make_ops, 500)
    wmr = run_variant(ysb.make_ops_wmr, 500, map_parallelism=2)
    assert kf == wmr
    wmr3 = run_variant(ysb.make_ops_wmr, 750, map_parallelism=3)
    assert kf == wmr3


def test_ysb_per_window_counts_against_dense_oracle():
    res = run_variant(ysb.make_ops, 512)
    want = {}
    for i in range(TOTAL):
        if i % 3 != 0:                          # filter: views only
            continue
        camp = (i * 7919) % ysb.N_ADS // ysb.ADS_PER_CAMPAIGN
        wid = (i // ysb.EVENTS_PER_TICK) // ysb.WIN_LEN
        want[(camp, wid)] = want.get((camp, wid), 0) + 1
    got = {(k, w): c for k, w, c in res}
    assert got == want


def _chain_step(batch_size, pane_capacity, max_wins, n_batches=4):
    """Shared harness: the YSB op chain compiled as one step function."""
    import jax.numpy as jnp
    from windflow_tpu.runtime.pipeline import CompiledChain

    src = ysb.make_source(total=n_batches * batch_size)
    ops = ysb.make_ops(pane_capacity=pane_capacity, max_wins=max_wins)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=batch_size)

    def step(states, start):
        b = src.make_batch(jnp.asarray(start, jnp.int32), batch_size)
        states = list(states)
        for j, op in enumerate(chain.ops):
            states[j], b = op.apply(states[j], b)
        return tuple(states), jnp.sum(b.valid)

    return src, ops, chain, step


def test_count_lift_detected_inside_chain_trace():
    """Regression: _detect_count_lift runs INSIDE the chain's jit trace, where
    float() on a freshly created jnp constant raises ConcretizationTypeError
    unless evaluated under jax.ensure_compile_time_eval(). When the blanket
    except swallowed that, the YSB windowed-count chain silently took the
    serialized segment-sum fallback for its panes update — ~5.4 ms/step at 1M
    batch on-chip, the whole window-stage anomaly of the r05 ablation."""
    import jax

    _, ops, chain, step = _chain_step(2048, 16, 16)
    win = ops[-1]
    assert win.count_lift is None               # not yet traced
    out = jax.jit(step)(tuple(chain.states), 0)
    jax.block_until_ready(out[1])
    assert win.count_lift is True, \
        "count-lift fast path not detected under an ambient jit trace"


def _reachable_computations(hlo: str):
    """(names reachable from ENTRY via calls=/to_apply=, minus conditional
    branch computations) -> their bodies. Text-level HLO walk."""
    comps = {}
    for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+)[^\n]*\{\n(.*?)^\}", hlo,
                         re.M | re.S):
        comps[m.group(1)] = m.group(2)
    entry_name = next(n for n in comps
                      if re.search(rf"^ENTRY %?{re.escape(n)}\b", hlo, re.M))
    seen, todo = set(), [entry_name]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        body = comps[name]
        branch = set()
        for bm in re.finditer(r"branch_computations=\{([^}]*)\}", body):
            branch |= {b.strip().lstrip("%") for b in bm.group(1).split(",")}
        for cm in re.finditer(r"(?:calls|to_apply)=%([\w.\-]+)", body):
            if cm.group(1) not in branch:
                todo.append(cm.group(1))
    return {n: comps[n] for n in seen}


def test_ysb_chain_unconditional_path_has_no_scatter():
    """Structural lock on the count-lift fast path: no scatter opcode may be
    reachable from the compiled chain's ENTRY outside the locality cond's
    branch computations (where the exact fallback legitimately lives). A
    reachable scatter means the panes update regressed onto the serialized
    fallback (the r05 5.4 ms/step anomaly) — including the fused/renamed form
    a plain 'scatter not in ENTRY-text' check would miss."""
    import jax

    _, _, chain, step = _chain_step(4096, 32, 32)
    txt = (jax.jit(step)
           .lower(tuple(chain.states), 0).compile().as_text())
    offenders = {
        name: [l.strip() for l in body.splitlines() if "scatter(" in l]
        for name, body in _reachable_computations(txt).items()}
    offenders = {n: ls for n, ls in offenders.items() if ls}
    assert not offenders, (
        "scatter reachable outside the locality cond — the windowed-count "
        f"panes update fell off the histogram fast path: {offenders}")
