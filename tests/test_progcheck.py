"""Device-program analyzer (analysis/progcheck.py, WF3xx): each rule pinned
by a minimally-broken program fixture plus its clean sibling, the recursive
sub-jaxpr walker, the canonical fingerprint's contract (pure function of the
program, address-free, change-sensitive), the rationale-required baseline
gate, the validate() integration, and the CLI's 0/1/2 exit contract
(including exit 2 WITHOUT a traceback on a box with no JAX — the one wf_*
CLI that genuinely needs it)."""

import json
import os
import subprocess
import sys

import pytest
import jax
import jax.numpy as jnp
from jax.experimental import io_callback

import windflow_tpu as wf
from windflow_tpu.analysis import progcheck as pc
from windflow_tpu.analysis.validate import validate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = jax.ShapeDtypeStruct
F8 = S((8,), jnp.float32)
I8 = S((8,), jnp.int32)


def prog(fn, *args, replay=False, shards=1):
    """A fixture Program: trace ``fn`` abstractly, wrap with the given
    execution context."""
    return pc.Program(target="fx",
                      closed=jax.make_jaxpr(fn)(*args), capacity=8,
                      shards=shards, replay=replay)


def codes(p):
    return [x.code for x in pc.analyze_program(p)]


# ------------------------------------------------------------ the rules


def test_wf300_float_scatter_add_under_replay():
    bad = prog(lambda v, i: jnp.zeros(16, jnp.float32).at[i].add(v),
               F8, I8, replay=True)
    assert codes(bad) == ["WF300"]


def test_wf300_clean_siblings():
    unique = prog(lambda v, i: jnp.zeros(16, jnp.float32)
                  .at[i].add(v, unique_indices=True), F8, I8, replay=True)
    integer = prog(lambda v, i: jnp.zeros(16, jnp.int32).at[i].add(v),
                   I8, I8, replay=True)
    no_replay = prog(lambda v, i: jnp.zeros(16, jnp.float32).at[i].add(v),
                     F8, I8, replay=False)
    assert codes(unique) == []
    assert codes(integer) == []
    assert "WF300" not in codes(no_replay)


def test_wf301_unordered_io_callback():
    def cb(x):
        return x
    bad = prog(lambda x: io_callback(cb, F8, x, ordered=False), F8)
    ok = prog(lambda x: io_callback(cb, F8, x, ordered=True), F8)
    assert codes(bad) == ["WF301"]
    # the ordered sibling clears WF301 but still counts as host-sync
    assert codes(ok) == ["WF302"]


def test_wf301_unordered_debug_callback():
    bad = prog(lambda x: (jax.debug.print("v={v}", v=x[0]), x)[1], F8)
    ok = prog(lambda x: (jax.debug.print("v={v}", v=x[0], ordered=True),
                         x)[1], F8)
    assert codes(bad) == ["WF301"]
    assert codes(ok) == ["WF302"]


def test_wf302_names_the_callback_and_ranks_fusion():
    def resolve_miss(x):
        return x
    p = prog(lambda x: io_callback(resolve_miss, F8, x, ordered=True), F8)
    [f] = pc.analyze_program(p)
    assert f.code == "WF302"
    assert "resolve_miss" in f.message
    assert "dispatch_ratio" in f.message


def test_wf303_weak_typed_program_input():
    bad = pc.Program(target="fx",
                     closed=jax.make_jaxpr(lambda x: x * 2)(3.0),
                     capacity=8)
    ok = prog(lambda x: x * 2, F8)
    assert codes(bad) == ["WF303"]
    assert codes(ok) == []


def test_wf304_donated_input_read_after_donation():
    g = jax.jit(lambda x: x + 1, donate_argnums=0)
    bad = prog(lambda x: g(x) + x, F8)     # x read AFTER g donates it
    ok = prog(lambda x: g(x) * 2, F8)
    assert codes(bad) == ["WF304"]
    assert codes(ok) == []


def test_wf305_float_reduction_under_composition():
    under_shards = prog(lambda v: jnp.sum(v), F8, shards=2)
    integer = prog(lambda v: jnp.sum(v), I8, shards=2)
    solo = prog(lambda v: jnp.sum(v), F8)
    exact_max = prog(lambda v: jnp.max(v), F8, shards=2)
    assert codes(under_shards) == ["WF305"]
    assert codes(integer) == []
    assert codes(solo) == []
    assert codes(exact_max) == []          # max is associative-exact


def test_walker_recurses_into_scan_and_cond():
    """A violation INSIDE a scan body / cond branch is found, and the
    finding's text names the nesting path."""
    def body(c, v):
        return c, jnp.sum(v)               # float reduce inside the scan
    bad = prog(lambda vs: jax.lax.scan(body, 0.0, vs),
               S((4, 8), jnp.float32), shards=2)
    hits = [f for f in pc.analyze_program(bad) if f.code == "WF305"]
    assert hits and any("scan" in f.text for f in hits)

    def branch(x):
        return jnp.sum(x)
    bad2 = prog(lambda p, x: jax.lax.cond(p, branch, lambda x: x[0], x),
                S((), jnp.bool_), F8, shards=2)
    hits2 = [f for f in pc.analyze_program(bad2) if f.code == "WF305"]
    assert hits2 and any("cond" in f.text for f in hits2)


# ------------------------------------------------------- the fingerprint


def _q1_chain():
    from windflow_tpu.nexmark import queries as q
    src, ops = q.make_query("q1_currency", total=512)
    return pc._mk_chain(src, ops, 64)


def test_fingerprint_deterministic_in_process():
    chain = _q1_chain()
    assert pc.step_fingerprint(chain, 64) == pc.step_fingerprint(chain, 64)
    # a fresh identical chain traces to the same program
    assert pc.step_fingerprint(_q1_chain(), 64) == \
        pc.step_fingerprint(chain, 64)


def test_fingerprint_stable_across_processes():
    """The acceptance pin: a pure function of the jaxpr — no ids, no
    addresses — so a second interpreter computes the same hex digest."""
    chain = _q1_chain()
    here = pc.step_fingerprint(chain, 64)
    script = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from windflow_tpu.analysis import progcheck as pc\n"
        "from windflow_tpu.nexmark import queries as q\n"
        "src, ops = q.make_query('q1_currency', total=512)\n"
        "print(pc.step_fingerprint(pc._mk_chain(src, ops, 64), 64))\n")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, cwd=REPO,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == here


def test_fingerprint_sensitive_to_program_change():
    a = pc.program_fingerprint(jax.make_jaxpr(lambda x: x * 2)(F8))
    b = pc.program_fingerprint(jax.make_jaxpr(lambda x: x * 3)(F8))
    c = pc.program_fingerprint(jax.make_jaxpr(lambda x: x + 2)(F8))
    assert len({a, b, c}) == 3


def test_fingerprint_ignores_callback_addresses():
    """Two distinct-but-identical callback closures repr with different
    0x addresses; the canonical form must hash them alike (qualname, not
    identity)."""
    def make(tag):
        def cb(x):
            return x
        return jax.make_jaxpr(
            lambda x: io_callback(cb, F8, x, ordered=True))(F8)
    assert pc.program_fingerprint(make("a")) == \
        pc.program_fingerprint(make("b"))


def test_fingerprint_distinguishes_const_values():
    """Constant VALUES are part of the program: two chains differing only
    in a baked-in table must not collide."""
    t1 = jnp.arange(8, dtype=jnp.float32)
    t2 = jnp.arange(8, dtype=jnp.float32) * 2
    a = pc.program_fingerprint(jax.make_jaxpr(lambda x: x + t1)(F8))
    b = pc.program_fingerprint(jax.make_jaxpr(lambda x: x + t2)(F8))
    assert a != b


# --------------------------------------------------------------- baseline


def test_baseline_requires_rationale(tmp_path):
    path = str(tmp_path / "b.json")
    entry = {"code": "WF305", "path": "fx/step", "text": "t",
             "message": "m", "rationale": ""}
    with open(path, "w") as f:
        json.dump({"findings": [entry]}, f)
    counts, problems = pc.load_baseline(path)
    assert counts == {}                    # an unargued entry suppresses NOTHING
    assert len(problems) == 1
    entry["rationale"] = "per-batch fold, grouping invariant in K"
    with open(path, "w") as f:
        json.dump({"findings": [entry]}, f)
    counts, problems = pc.load_baseline(path)
    assert counts == {("WF305", "fx/step", "t"): 1} and problems == []


def test_update_baseline_preserves_written_rationales(tmp_path):
    path = str(tmp_path / "b.json")
    f1 = pc.Finding("WF305", "warning", "fx/step", 1, "m", "t")
    pc.save_baseline(path, [f1])
    data = json.load(open(path))
    assert data["findings"][0]["rationale"] == ""
    data["findings"][0]["rationale"] = "argued"
    with open(path, "w") as f:
        json.dump(data, f)
    # rewrite with the same finding still present plus a new one
    f2 = pc.Finding("WF300", "error", "fx/step", 2, "m2", "t2")
    pc.save_baseline(path, [f1, f2])
    by_code = {e["code"]: e for e in json.load(open(path))["findings"]}
    assert by_code["WF305"]["rationale"] == "argued"
    assert by_code["WF300"]["rationale"] == ""


def test_repo_baseline_every_entry_has_rationale():
    """The acceptance gate: zero unexplained entries in the checked-in
    baseline."""
    counts, problems = pc.load_baseline(pc.baseline_path())
    assert problems == []
    assert sum(counts.values()) > 0        # the first audit WAS recorded


def test_apply_baseline_is_count_aware():
    f = pc.Finding("WF305", "warning", "fx/step", 1, "m", "t")
    g = pc.Finding("WF305", "warning", "fx/step", 2, "m", "t")
    counts = {("WF305", "fx/step", "t"): 1}
    fresh = pc.apply_baseline([f, g], counts)
    assert len(fresh) == 1                 # the duplicate is NOT masked


# ------------------------------------------------- validate() integration


def _tiered_q3_pipeline():
    from windflow_tpu.nexmark import queries as q
    src, ops = q.q3_enrich_join(512, num_slots=512, tiered=True)
    return wf.Pipeline(src, ops, wf.Sink(lambda v: None), batch_size=64)


def test_validate_surfaces_progcheck_findings():
    """The tiered host exchange (io_callback, ordered) surfaces as WF302
    through validate() — the repo baseline keys on audit-target labels,
    not driver labels, so a driver validation sees it fresh."""
    r = validate(_tiered_q3_pipeline())
    assert "WF302" in r.codes()
    assert r.ok                            # warning, not error


def test_validate_progcheck_kwarg_and_env_gate(monkeypatch):
    p = _tiered_q3_pipeline()
    r = validate(p, progcheck=False)
    assert not any(c.startswith("WF3") for c in r.codes())
    monkeypatch.setenv("WF_PROGCHECK", "0")
    r = validate(p)
    assert not any(c.startswith("WF3") for c in r.codes())


def test_validate_clean_chain_stays_clean():
    src = wf.Source(lambda i: {"v": (i % 97).astype(jnp.int32)}, total=256,
                    num_keys=4)
    p = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v * 2})],
                    wf.Sink(lambda v: None), batch_size=64)
    r = validate(p)
    assert not any(c.startswith("WF3") for c in r.codes())


def _float_sum_window(win_type):
    src = wf.Source(lambda i: {"v": ((i * 13) % 23).astype(jnp.float32)},
                    total=240, num_keys=3)
    from windflow_tpu.operators.window import WindowSpec
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(8, 2, win_type), num_keys=3)
    return wf.Pipeline(src, [op], wf.Sink(lambda v: None), batch_size=48)


def test_validate_supervised_flags_replay_rules():
    """A float scatter-add chain (a time-based float sum: ``segment_sum``
    per pane) under a SUPERVISED validation trips WF300 (replay context),
    and stays quiet under plain pipeline validation."""
    from windflow_tpu.basic import win_type_t
    p = _float_sum_window(win_type_t.TB)
    assert "WF300" in validate(p, supervised=True).codes()
    assert "WF300" not in validate(p).codes()


def test_count_based_float_sum_is_replay_clean():
    """The count-based insert folds float sums with a segmented scan in
    sorted order (``ops/segment.py::segment_run_fold``): no float
    scatter-add is left for a replay to reorder."""
    from windflow_tpu.basic import win_type_t
    p = _float_sum_window(win_type_t.CB)
    assert "WF300" not in validate(p, supervised=True).codes()


# ------------------------------------------------------------- the CLI


def _run_cli(*args, env=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "wf_progcheck.py"),
         *args], capture_output=True, text=True, cwd=REPO, env=e,
        timeout=600)


def _poisoned_jax_dir(tmp_path):
    d = tmp_path / "nojax"
    d.mkdir()
    (d / "jax.py").write_text("raise ImportError('no jax here')\n")
    return str(d)


def test_cli_exit_2_without_jax_no_traceback(tmp_path):
    proc = _run_cli(env={"PYTHONPATH": _poisoned_jax_dir(tmp_path)})
    assert proc.returncode == 2
    assert "Traceback" not in proc.stderr
    assert "JAX is not importable" in proc.stderr


def test_cli_explain_works_without_jax(tmp_path):
    proc = _run_cli("--explain", "WF304",
                    env={"PYTHONPATH": _poisoned_jax_dir(tmp_path)})
    assert proc.returncode == 0
    assert "WF304" in proc.stdout and "donated" in proc.stdout


def test_cli_explain_unknown_code_exit_2():
    proc = _run_cli("--explain", "WF999")
    assert proc.returncode == 2


def test_cli_family_token_and_bad_tokens():
    proc = _run_cli("--select", "WF30x", "--targets", "examples")
    assert proc.returncode == 0, proc.stderr
    for tok in ("WF999", "x", "Wx"):
        proc = _run_cli("--select", tok, "--targets", "examples")
        assert proc.returncode == 2, tok


def test_cli_refuses_partial_baseline_update():
    proc = _run_cli("--update-baseline", "--select", "WF305")
    assert proc.returncode == 2
    assert "refusing" in proc.stderr


def test_cli_unknown_target_exit_2():
    proc = _run_cli("--targets", "nope")
    assert proc.returncode == 2
    assert "unknown audit target" in proc.stderr


def test_cli_gate_clean_and_rationale_gate(tmp_path):
    """The examples family is clean against the repo baseline (exit 0
    with the multichip WF300/WF305 entries suppressed); pointing the gate
    at a rationale-less baseline flips it to exit 1."""
    proc = _run_cli("--targets", "examples", "--format=json")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    out = json.loads(proc.stdout)
    assert out["findings"] == [] and out["baseline_problems"] == []

    stripped = json.load(open(pc.baseline_path()))
    for e in stripped["findings"]:
        e["rationale"] = ""
    bad = tmp_path / "no_rationale.json"
    bad.write_text(json.dumps(stripped))
    proc = _run_cli("--targets", "examples", "--baseline", str(bad))
    assert proc.returncode == 1
    assert "WITHOUT a rationale" in proc.stdout


def test_cli_fingerprints_flag():
    proc = _run_cli("--targets", "examples", "--fingerprints",
                    "--format=json")
    assert proc.returncode == 0, proc.stderr
    rows = json.loads(proc.stdout)["fingerprints"]
    assert rows and all(len(r["fingerprint"]) == 64 for r in rows)


# ------------------------------------------------- audit-surface tracing


@pytest.mark.parametrize("target", sorted(pc.AUDIT_TARGETS))
def test_audit_targets_trace(target):
    """Every registered audit family traces abstractly (zero device) and
    analyzes without error — the CLI's whole-repo run can never rot."""
    programs = pc.AUDIT_TARGETS[target]()
    assert programs
    findings = pc.analyze_programs(programs)
    # every finding the audit produces is suppressed by an ARGUED baseline
    counts, problems = pc.load_baseline(pc.baseline_path())
    assert problems == []
    assert pc.apply_baseline(findings, counts) == []
