"""Per-backend kernel registry (ops/registry.py): selection precedence, env
subsumption (incl. the deprecated WF_*_IMPL aliases), TuningCache
warm-starts, WF109 stale-executable detection — and the interpret-mode
parity matrix: every registered kernel family byte-identical to its XLA
reference on CPU, including masked/padded-lane edge cases (the ``_bmask`` /
OLD-straggler-mask conventions of the fold call sites)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from windflow_tpu.ops import bitonic, registry
from windflow_tpu.ops.lookup import join_probe
from windflow_tpu.ops.segment import segment_fold, segment_reduce
from windflow_tpu.observability.names import KERNELS


# ------------------------------------------------------------ selection


def _mini_registry():
    r = registry.KernelRegistry()
    r.register_kernel("lookup", "xla", reference=True, default=True)
    r.register_kernel("lookup", "pallas")
    r.register_kernel("segment_fold", "xla", reference=True, default=True)
    r.register_kernel("segment_fold", "pallas")
    return r


def test_default_is_reference(monkeypatch):
    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    monkeypatch.delenv("WF_LOOKUP_IMPL", raising=False)
    r = _mini_registry()
    assert r.resolve_impl("lookup") == "xla"
    assert r.reference_impl("lookup") == "xla"


def test_explicit_impl_wins_over_env(monkeypatch):
    monkeypatch.setenv("WF_KERNEL_IMPL", "lookup=pallas")
    r = _mini_registry()
    assert r.resolve_impl("lookup", impl="xla") == "xla"


def test_env_per_kernel_beats_global(monkeypatch):
    monkeypatch.setenv("WF_KERNEL_IMPL", "pallas,lookup=xla")
    r = _mini_registry()
    assert r.resolve_impl("lookup") == "xla"
    assert r.resolve_impl("segment_fold") == "pallas"


def test_env_off_values_mean_no_override(monkeypatch):
    for off in ("", "0"):
        monkeypatch.setenv("WF_KERNEL_IMPL", off)
        assert _mini_registry().resolve_impl("lookup") == "xla"


def test_deprecated_alias_still_honored(monkeypatch):
    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    monkeypatch.setenv("WF_LOOKUP_IMPL", "pallas")
    r = _mini_registry()
    assert r.resolve_impl("lookup") == "pallas"
    # WF_KERNEL_IMPL outranks the alias
    monkeypatch.setenv("WF_KERNEL_IMPL", "lookup=xla")
    assert r.resolve_impl("lookup") == "xla"
    # ''/'0' = no override for the aliases too (the repo off convention —
    # a stale WF_LOOKUP_IMPL=0 must not crash a pipeline at trace time)
    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    for off in ("", "0"):
        monkeypatch.setenv("WF_LOOKUP_IMPL", off)
        assert r.resolve_impl("lookup") == "xla"


def test_unknown_kernel_and_impl_raise():
    r = _mini_registry()
    with pytest.raises(ValueError, match="unknown kernel"):
        r.resolve_impl("typo_kernel")
    with pytest.raises(ValueError, match="no impl"):
        r.resolve_impl("lookup", impl="cuda")


def test_tuning_cache_warm_start(tmp_path, monkeypatch):
    """persist_winner -> a FRESH registry attached to the same cache
    resolves the winner without any env (the PR 3 second-run property, for
    kernels)."""
    from windflow_tpu.control.autotune import TuningCache
    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    monkeypatch.delenv("WF_LOOKUP_IMPL", raising=False)
    cache = TuningCache(str(tmp_path / "tuning.json"))
    r = _mini_registry()
    r.attach_tuning_cache(cache)
    r.persist_winner("lookup", "C1024", "pallas", tps=1e8)
    r2 = _mini_registry()
    r2.attach_tuning_cache(cache)
    assert r2.resolve_impl("lookup", spec_key="C1024") == "pallas"
    # other spec keys are unaffected; env still outranks the cache
    assert r2.resolve_impl("lookup", spec_key="C2048") == "xla"
    monkeypatch.setenv("WF_KERNEL_IMPL", "lookup=xla")
    assert r2.resolve_impl("lookup", spec_key="C1024") == "xla"


def test_wf109_stale_selection_surfaces_in_validate(monkeypatch):
    """Resolve under one env, flip the env, validate(): the report carries a
    WF109 naming the kernel — and none after the env is restored."""
    import windflow_tpu as wf
    from windflow_tpu.analysis import validate

    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    monkeypatch.delenv("WF_LOOKUP_IMPL", raising=False)
    src = wf.Source(lambda i: {"v": (i % 7).astype(jnp.float32)},
                    total=64, num_keys=2)
    p = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v + 1.0})],
                    wf.Sink(lambda view: None), batch_size=32)
    registry.REGISTRY.reset_records()   # drop leftovers from earlier tests
    try:
        registry.REGISTRY.resolve_impl("lookup", spec_key="wf109-test")
        monkeypatch.setenv("WF_KERNEL_IMPL", "lookup=pallas")
        report = validate(p)
        hits = [d for d in report.diagnostics if d.code == "WF109"]
        assert hits and "lookup" in hits[0].where, str(report)
        assert report.ok            # warning severity: stale, not broken
        monkeypatch.delenv("WF_KERNEL_IMPL")
        assert "WF109" not in validate(p).codes()
    finally:
        registry.REGISTRY.reset_records()


def test_explicit_impl_not_recorded():
    r = _mini_registry()
    r.resolve_impl("lookup", spec_key="s", impl="pallas")
    assert r.trace_records() == {}
    r.resolve_impl("lookup", spec_key="s")
    assert list(r.trace_records().values()) == [frozenset({"xla"})]


def test_wf109_not_silenced_by_re_resolution(monkeypatch):
    """Records accumulate ALL impls per key: a fresh trace AFTER an env flip
    must not overwrite the pre-flip record — the executable compiled under
    the old impl is still cached, so it stays reported as stale."""
    monkeypatch.delenv("WF_KERNEL_IMPL", raising=False)
    monkeypatch.delenv("WF_LOOKUP_IMPL", raising=False)
    r = _mini_registry()
    r.resolve_impl("lookup", spec_key="s")              # records 'xla'
    monkeypatch.setenv("WF_KERNEL_IMPL", "lookup=pallas")
    r.resolve_impl("lookup", spec_key="s")              # re-records
    [rec] = r.stale_selections()
    assert rec["recorded"] == "xla" and rec["current"] == "pallas"


def test_global_registry_covers_names_registry():
    """Every kernel family in names.py::KERNELS is registered (with its
    reference impl) once the ops package is imported — the WF250/lint and
    perf-gate coverage contract."""
    import windflow_tpu.ops  # noqa: F401 — registration side effect
    for k in KERNELS:
        assert k in registry.REGISTRY.kernels()
        assert registry.REGISTRY.reference_impl(k) is not None


# -------------------------------------------------- parity: ordering merge


def _rand_keys(rng, n, lo=0, hi=1 << 20):
    return rng.integers(lo, hi, n).astype(np.int32)


def test_merge_network_parity_fuzz():
    """Pallas merge kernel byte-identical to the XLA network on bitonic
    inputs (ascending ++ descending), across sizes incl. the invalid-lane
    +max padding the ordering pool uses."""
    rng = np.random.default_rng(11)
    big = np.iinfo(np.int32).max
    for n in (4, 64, 1024, 8192):
        h = n // 2
        asc = np.sort(_rand_keys(rng, h))
        # descending side with +max "invalid lane" padding at the front
        # (after the [::-1] reversal the pads sit at the sequence tail, the
        # merge must sink them last like _push_core's ext() padding)
        desc = np.sort(_rand_keys(rng, h))[::-1].copy()
        desc[: max(1, h // 8)] = big
        prim = np.concatenate([asc, desc])
        sec = _rand_keys(rng, n, 0, 4)
        chan = _rand_keys(rng, n, 0, 3)
        idx = np.arange(n, dtype=np.int32)
        args = [jnp.asarray(a) for a in (prim, sec, chan, idx)]
        a = bitonic.merge_network(*args)
        b = bitonic.merge_network_pallas(*args, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.all(np.diff(np.asarray(a[0]).astype(np.int64)) >= 0)


def test_sort_network_parity_vs_lexsort():
    """The full sort network (both impls) equals the stable lexsort the
    ordering _sort_batch reference uses — the byte-identical-impls property
    the registry promises."""
    rng = np.random.default_rng(12)
    for n in (2, 16, 512, 4096):
        prim = _rand_keys(rng, n, 0, 50)          # heavy ties
        sec = _rand_keys(rng, n, 0, 3)
        chan = _rand_keys(rng, n, 0, 2)
        idx = np.arange(n, dtype=np.int32)
        args = [jnp.asarray(a) for a in (prim, sec, chan, idx)]
        want = np.lexsort((chan, sec, prim)).astype(np.int32)
        got_x = bitonic.sort_network(*args)
        got_p = bitonic.sort_network_pallas(*args, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_x[3]), want)
        for x, y in zip(got_x, got_p):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_ordering_node_pallas_stream_identical(monkeypatch):
    """End-to-end Ordering_Node: the released stream under
    merge_impl='pallas' is byte-identical to the default ('xla') node, push
    by push, including watermark gating and the invalid-lane tail."""
    from windflow_tpu.basic import ordering_mode_t
    from windflow_tpu.batch import Batch

    def mk_batch(rng, base, cap=64):
        ts = np.sort(base + rng.integers(0, 40, cap)).astype(np.int32)
        ids = (base * 100 + np.arange(cap)).astype(np.int32)
        valid = rng.random(cap) < 0.8
        return Batch(key=jnp.asarray(ids % 5), id=jnp.asarray(ids),
                     ts=jnp.asarray(ts),
                     payload={"v": jnp.asarray(ts.astype(np.float32))},
                     valid=jnp.asarray(valid))

    def run(merge_impl):
        from windflow_tpu.parallel.ordering import Ordering_Node
        rng = np.random.default_rng(3)
        node = Ordering_Node(2, ordering_mode_t.TS, merge_impl=merge_impl)
        out = []

        def grab(b):
            if b is None:
                return
            ok = np.asarray(b.valid)
            out.append((np.asarray(b.ts)[ok], np.asarray(b.id)[ok],
                        np.asarray(b.payload["v"])[ok]))
        for step in range(6):
            grab(node.push(step % 2, mk_batch(rng, base=step * 25)))
        for ch in (0, 1):
            grab(node.close_channel(ch))
        grab(node.flush())
        return out

    a, b = run("xla"), run("pallas")
    assert len(a) == len(b)
    for (ta, ia, va), (tb, ib, vb) in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(va, vb)


# ------------------------------------------------- parity: segment fold


def test_segment_fold_parity_masked_and_padded():
    """Pallas fold byte-identical to the segment_sum reference: random
    masks, fully-dead chunks, out-of-range sentinel ids (the K*P 'invalid
    lane' convention of win_seqffat's fold), and the S not divisible by the
    tile width case."""
    rng = np.random.default_rng(21)
    for C, S in ((1024, 16), (4096, 300), (8192, 4096), (2048, 513)):
        v = rng.integers(-1000, 1000, C).astype(np.int32)
        seg = rng.integers(0, S + 1, C).astype(np.int32)   # S = sentinel
        valid = rng.random(C) < 0.7
        valid[:256] = False                                # dead head chunk
        a = segment_fold(jnp.asarray(v), jnp.asarray(seg),
                         jnp.asarray(valid), S, impl="xla")
        b = segment_fold(jnp.asarray(v), jnp.asarray(seg),
                         jnp.asarray(valid), S, impl="pallas",
                         interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_segment_fold_full_int32_domain_exact():
    """The limb-split kernel is byte-identical to segment_sum over the FULL
    int32 domain — huge magnitudes, hot segments whose true sums overflow
    int32 (both impls wrap mod 2^32), and narrow dtypes that wrap earlier."""
    rng = np.random.default_rng(24)
    C, S = 2048, 32
    v = rng.integers(-(1 << 31), 1 << 31, C, dtype=np.int64).astype(np.int32)
    seg = rng.integers(0, S, C).astype(np.int32)
    seg[:512] = 7                                  # hot segment -> overflow
    valid = rng.random(C) < 0.9
    for dt in (np.int32, np.int16, np.int8):
        vv = v.astype(dt)
        a = segment_fold(jnp.asarray(vv), jnp.asarray(seg),
                         jnp.asarray(valid), S, impl="xla")
        b = segment_fold(jnp.asarray(vv), jnp.asarray(seg),
                         jnp.asarray(valid), S, impl="pallas",
                         interpret=True)
        assert a.dtype == b.dtype == dt
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(dt))


def test_segment_fold_float_routes_to_reference():
    """Float values are outside the Pallas exactness envelope — impl=pallas
    must still return the reference result (in-call fallback)."""
    rng = np.random.default_rng(22)
    C, S = 2048, 64
    v = rng.normal(size=C).astype(np.float32)
    seg = rng.integers(0, S, C).astype(np.int32)
    valid = rng.random(C) < 0.5
    a = segment_fold(jnp.asarray(v), jnp.asarray(seg), jnp.asarray(valid), S,
                     impl="xla")
    b = segment_fold(jnp.asarray(v), jnp.asarray(seg), jnp.asarray(valid), S,
                     impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_segment_fold_records_the_form_that_ran(monkeypatch):
    """Under WF_KERNEL_IMPL=segment_fold=pallas a segment space past
    FOLD_MAX_SEGMENTS runs the XLA form, and the trace record says so; one
    the kernel takes records ``pallas``."""
    from windflow_tpu.ops.registry import REGISTRY
    from windflow_tpu.ops.segment import FOLD_MAX_SEGMENTS
    C = 2048
    v = jnp.ones((C,), jnp.int32)
    seg = jnp.zeros((C,), jnp.int32)
    valid = jnp.ones((C,), bool)
    monkeypatch.setenv("WF_KERNEL_IMPL", "segment_fold=pallas")
    REGISTRY.reset_records()
    try:
        for S in (FOLD_MAX_SEGMENTS + 1, 64):
            got = segment_fold(v, seg, valid, S)
            assert int(got[0]) == C and int(got[1:].sum()) == 0
        records = {spec: impls for (kernel, spec, _), impls
                   in REGISTRY.trace_records().items()
                   if kernel == "segment_fold"}
    finally:
        REGISTRY.reset_records()
    assert records == {
        f"C{C}xS{FOLD_MAX_SEGMENTS + 1}:int32": frozenset({"xla"}),
        f"C{C}xS64:int32": frozenset({"pallas"})}


def test_segment_reduce_routes_through_fold(monkeypatch):
    """The Win_SeqFFAT fold call site: segment_reduce's default-add path
    under WF_KERNEL_IMPL=segment_fold=pallas equals the reference — through
    the registry, no code change at the call site."""
    rng = np.random.default_rng(23)
    C, S = 2048, 128
    v = rng.integers(0, 50, C).astype(np.int32)
    keys = rng.integers(0, S, C).astype(np.int32)
    valid = rng.random(C) < 0.8
    base = segment_reduce(jnp.asarray(v), jnp.asarray(keys),
                          jnp.asarray(valid), S)
    monkeypatch.setenv("WF_KERNEL_IMPL", "segment_fold=pallas")
    try:
        got = segment_reduce(jnp.asarray(v), jnp.asarray(keys),
                             jnp.asarray(valid), S)
    finally:
        from windflow_tpu.ops.registry import REGISTRY
        REGISTRY.reset_records()
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


# -------------------------------------------------- parity: join probe


def test_join_probe_parity_hits_misses_masks():
    rng = np.random.default_rng(31)
    for C, K in ((1024, 16), (8192, 512), (2048, 2048)):
        tk = rng.permutation(1 << 16)[:K].astype(np.int32)
        tv = rng.integers(-(1 << 20), 1 << 20, K).astype(np.int32)
        # half the probes hit, half miss; some lanes invalid
        probe = np.where(rng.random(C) < 0.5, rng.choice(tk, C),
                         (1 << 17) + rng.integers(0, 1000, C)).astype(np.int32)
        valid = rng.random(C) < 0.8
        va, ha = join_probe(jnp.asarray(tk), jnp.asarray(tv),
                            jnp.asarray(probe), jnp.asarray(valid),
                            impl="xla")
        vb, hb = join_probe(jnp.asarray(tk), jnp.asarray(tv),
                            jnp.asarray(probe), jnp.asarray(valid),
                            impl="pallas", interpret=True)
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        np.testing.assert_array_equal(np.asarray(ha), np.asarray(hb))
        # oracle
        lut = {int(k): int(x) for k, x in zip(tk, tv)}
        for i in range(0, C, 97):
            if valid[i] and int(probe[i]) in lut:
                assert bool(np.asarray(ha)[i])
                assert int(np.asarray(va)[i]) == lut[int(probe[i])]
            else:
                assert not bool(np.asarray(ha)[i])
                assert int(np.asarray(va)[i]) == 0


def test_join_probe_float_values_exact():
    """Float value tables: at most one match per lane, so the select-reduce
    is exact — impls byte-identical in f32 too."""
    rng = np.random.default_rng(32)
    C, K = 1024, 128
    tk = rng.permutation(1 << 12)[:K].astype(np.int32)
    tv = rng.normal(size=K).astype(np.float32)
    probe = rng.choice(tk, C).astype(np.int32)
    valid = np.ones(C, bool)
    va, ha = join_probe(jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(probe),
                        jnp.asarray(valid), impl="xla")
    vb, hb = join_probe(jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(probe),
                        jnp.asarray(valid), impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    assert bool(np.asarray(ha).all()) and bool(np.asarray(hb).all())


def test_join_probe_oversized_table_falls_back():
    """K beyond the kernel's VMEM envelope: impl='pallas' silently takes the
    reference path (selection is an optimization, never a semantics
    change)."""
    from windflow_tpu.ops.lookup import JOIN_PROBE_MAX_ROWS
    rng = np.random.default_rng(33)
    K = JOIN_PROBE_MAX_ROWS + 8
    C = 256
    tk = rng.permutation(1 << 18)[:K].astype(np.int32)
    tv = rng.integers(0, 100, K).astype(np.int32)
    probe = rng.choice(tk, C).astype(np.int32)
    valid = np.ones(C, bool)
    va, ha = join_probe(jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(probe),
                        jnp.asarray(valid), impl="pallas")
    vb, hb = join_probe(jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(probe),
                        jnp.asarray(valid), impl="xla")
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    np.testing.assert_array_equal(np.asarray(ha), np.asarray(hb))


# ------------------------------------------------ parity: lookup via registry


def test_lookup_parity_through_registry(monkeypatch):
    from windflow_tpu.ops.lookup import table_lookup
    rng = np.random.default_rng(42)
    K, C = 700, 1024
    table = jnp.asarray(rng.integers(0, 1 << 12, K).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, K, C).astype(np.int32))
    want = np.asarray(table)[np.asarray(idx)]
    for impl_env in ("xla", "pallas"):
        monkeypatch.setenv("WF_KERNEL_IMPL", f"lookup={impl_env}")
        got = table_lookup(table, idx)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=impl_env)
    from windflow_tpu.ops.registry import REGISTRY
    REGISTRY.reset_records()


# ------------------------------------------------- the one interpret rule

def test_one_interpret_rule_for_every_backend(monkeypatch):
    """cpu interprets, tpu compiles, anything else is an error — and all
    four kernel modules ask this one function."""
    import inspect
    import jax
    from windflow_tpu.ops import bitonic, lookup, pallas_kernels, segment
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert registry.pallas_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        registry.pallas_interpret()
    for mod in (bitonic, lookup, pallas_kernels, segment):
        src = inspect.getsource(mod)
        assert "pallas_interpret()" in src and "default_backend" not in src


def test_refused_impl_raises_on_tpu_only(monkeypatch):
    """An impl Mosaic refuses stays registered, runs interpreted on cpu, and
    raises with Mosaic's message when selected on tpu — explicitly or through
    the environment; nothing swaps in the XLA form."""
    import jax
    assert registry.resolve_impl("ordering_merge", impl="pallas",
                                 record=False) == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(registry.KernelRefused, match="shape cast"):
        registry.resolve_impl("ordering_merge", impl="pallas", record=False)
    monkeypatch.setenv("WF_KERNEL_IMPL", "ordering_merge=pallas")
    with pytest.raises(registry.KernelRefused, match="shape cast"):
        registry.resolve_impl("ordering_merge", record=False)
    assert registry.resolve_impl("lookup", impl="pallas",
                                 record=False) == "pallas"
