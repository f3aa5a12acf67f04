"""Pillar-3 gate: the whole-repo static concurrency analyzer
(``analysis/concurrency.py``, the WF26x family) runs as part of ``run_lint``
in tier-1 and must be clean against the baseline — plus per-rule minimal
fixture negatives for WF260–WF265, the annotation-grammar rejection cases,
role-inference through ``ThreadPoolExecutor.submit`` and an ``io_callback``
lambda, and the CLI contract (``--select``/``--ignore``/``--explain``,
exit codes under a poisoned-jax ``PYTHONPATH``)."""

import json
import os
import subprocess
import sys
import textwrap

from windflow_tpu.analysis import lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
conc = lint.concurrency_module()


# ------------------------------------------------------------ the repo gate


def test_repo_concurrency_pass_is_clean():
    """THE acceptance gate: zero un-baselined WF26x findings over this
    repository — every cross-thread contract is locked, annotated with a
    rationale, or was fixed in this PR."""
    fresh, _suppressed = lint.lint_repo(ROOT)
    mine = [x for x in fresh if x.code.startswith("WF26")]
    assert not mine, "\n".join(x.render() for x in mine)


def test_baselined_wf26x_entries_carry_a_rationale():
    """The audit contract: nothing from the concurrency pass may be banked
    in baseline.json without a written rationale — an entry without one is
    an unexplained suppression, which is exactly the convention debt this
    pass exists to kill."""
    path = lint.baseline_path(lint.LintConfig(root=ROOT))
    data = json.load(open(path)) if os.path.exists(path) else {}
    for e in data.get("findings", ()):
        if e["code"].startswith("WF26"):
            assert e.get("rationale", "").strip(), (
                f"baselined {e['code']} at {e['path']} has no rationale: "
                f"{e}")


def test_driver_only_contracts_are_annotation_enforced():
    """The two formerly docstring-only contracts are now declared in the
    checked annotation grammar (and the inference actually classifies them
    — their inferred roles stay inside the declared set)."""
    roles = conc.inferred_roles(ROOT)

    def roles_of(suffix):
        hits = {q: r for q, r in roles.items() if q.endswith(suffix)}
        assert hits, f"no function matching {suffix}"
        return set().union(*hits.values())

    assert roles_of("Ordering_Node.settle") <= {"driver", "stage"}
    assert roles_of("TieredTable.maintain") <= {"driver", "stage"}
    # and the spawned roles landed where the annotations say they do
    assert "reporter" in roles_of("Reporter._run")
    assert "watchdog" in roles_of("ThreadedPipeline._watchdog_body")
    assert "checkpoint-pool" in roles_of("checkpoint.py::save_states")
    assert "jax-callback" in roles_of("JoinTableTier.lookup_cb")


# ----------------------------------------------------------- rule fixtures


def _fixture(tmp_path, module_src, replay=False):
    """Minimal tree the concurrency pass can run against (it needs only
    ``windflow_tpu/``)."""
    pkg = tmp_path / "windflow_tpu"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "mod.py").write_text(textwrap.dedent(module_src))
    replay_modules = ("windflow_tpu/mod.py",) if replay else ()
    return conc.run_rules(str(tmp_path), ("windflow_tpu",),
                          replay_modules=replay_modules)


def _codes(findings):
    return sorted(d["code"] for d in findings)


_SETTLE_FROM_THREAD = '''
    import threading

    class Node:
        def settle(self):  # wf-lint: thread-role[driver]
            return 0

    class Driver:
        def __init__(self, node: Node):
            self._node = node
        def _body(self):
            self._node.settle()
        def run(self):
            t = threading.Thread(target=self._body)
            t.start()
            t.join()
'''


def test_wf261_settle_from_spawned_thread_fires(tmp_path):
    """THE acceptance fixture: a driver-thread-only settle() called from a
    spawned thread fails with WF261."""
    findings = _fixture(tmp_path, _SETTLE_FROM_THREAD)
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1, findings
    assert "settle" in hits[0]["message"]
    assert "'thread'" in hits[0]["message"]


def test_wf261_annotated_spawn_role_is_allowed(tmp_path):
    """The same shape with the spawn annotated as a driver loan (the
    call_with_timeout pattern) is clean."""
    findings = _fixture(tmp_path, '''
        import threading

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        class Driver:
            def __init__(self, node: Node):
                self._node = node
            def _body(self):
                self._node.settle()
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[driver]
                    target=self._body)
                t.start()
                t.join()
    ''')
    assert "WF261" not in _codes(findings)


def test_wf261_mixed_role_fallback_adds_no_phantom_edge(tmp_path):
    """Two same-named annotated methods with DIFFERENT role sets must not
    resolve by name alone — the union would smear one class's allowed
    roles into the stricter class and fire a spurious WF261 (review
    finding: fallback requires IDENTICAL declared sets)."""
    findings = _fixture(tmp_path, '''
        import threading

        class DriverOnly:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        class StageSafe:
            def settle(self):  # wf-lint: thread-role[driver, stage]
                return 1

        def body(x):
            x.settle()

        def run(x):
            t = threading.Thread(  # wf-lint: thread-role[stage]
                target=body)
            t.start()
            t.join()
    ''')
    assert "WF261" not in _codes(findings)


def test_wf261_constructor_typed_local_resolves_precisely(tmp_path):
    """A local bound from a repo-class constructor resolves obj.m() even
    when the bare-name fallback would bail (multiple unannotated-mixed
    definitions) — review finding: the local-type map must actually feed
    call resolution."""
    findings = _fixture(tmp_path, '''
        import threading

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        class Unrelated:
            def settle(self):
                return 1

        def body():
            n = Node()
            n.settle()

        def run():
            t = threading.Thread(target=body)
            t.start()
            t.join()
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "Node.settle" in hits[0]["message"]


def test_wf261_pool_bound_by_plain_assignment(tmp_path):
    """An executor bound by plain assignment (not with-as) still seeds the
    checkpoint-pool role through .submit (review finding)."""
    findings = _fixture(tmp_path, '''
        from concurrent.futures import ThreadPoolExecutor

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        def step(node):
            return node.settle()

        def save_all(nodes):
            ex = ThreadPoolExecutor(2)
            try:
                return [ex.submit(step, n) for n in nodes]
            finally:
                ex.shutdown()
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "checkpoint-pool" in hits[0]["message"]


def test_wf261_role_inference_through_pool_submit(tmp_path):
    """ThreadPoolExecutor.submit seeds the checkpoint-pool role, and it
    propagates through the call graph into the constrained API."""
    findings = _fixture(tmp_path, '''
        from concurrent.futures import ThreadPoolExecutor

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        def save_one(node):
            return step(node)

        def step(node):
            return node.settle()

        def save_all(nodes):
            with ThreadPoolExecutor(max_workers=2) as ex:
                return list(ex.map(save_one, nodes))
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "checkpoint-pool" in hits[0]["message"]


def test_wf261_role_inference_through_io_callback_lambda(tmp_path):
    """A lambda passed to io_callback gets the jax-callback role; its calls
    propagate it into the constrained API."""
    findings = _fixture(tmp_path, '''
        from jax.experimental import io_callback

        class Tier:
            def fetch(self):  # wf-lint: thread-role[driver]
                return 0

        def probe(tier, shapes, keys):
            return io_callback(lambda k: tier.fetch(), shapes, keys,
                               ordered=True)
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "jax-callback" in hits[0]["message"]


def test_wf260_cross_role_attr_without_lock(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self.items = []
            def _body(self):
                self.items.append(1)
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                return len(self.items)
    ''')
    hits = [d for d in findings if d["code"] == "WF260"]
    assert len(hits) == 1 and "Box.items" in hits[0]["message"]
    assert "stage" in hits[0]["message"]


def test_wf260_consistent_lock_is_clean(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []
            def _body(self):
                with self._lock:
                    self.items.append(1)
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                with self._lock:
                    return len(self.items)
    ''')
    assert "WF260" not in _codes(findings)


def test_wf260_lock_held_by_caller_counts(tmp_path):
    """The must-held analysis: a private helper whose every call site holds
    the lock is treated as running under it."""
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []
            def _append(self, x):
                self.items.append(x)
            def _body(self):
                with self._lock:
                    self._append(1)
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                with self._lock:
                    self._append(2)
    ''')
    assert "WF260" not in _codes(findings)


def test_wf260_single_writer_annotation_suppresses(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                # stage body owns the list; driver reads post-join
                self.items = []          # wf-lint: single-writer[stage]
            def _body(self):
                self.items.append(1)
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                t.join()
                return len(self.items)
    ''')
    assert "WF260" not in _codes(findings)


def test_wf260_class_level_single_writer_covers_all_attrs(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Ring:  # wf-lint: single-writer[stage]
            def __init__(self):
                self.buf = []
                self.idx = 0
            def _body(self):
                self.buf.append(1)
                self.idx += 1
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                return self.idx
    ''')
    assert "WF260" not in _codes(findings)


def test_wf260_threadsafe_primitive_attrs_exempt(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self.stop = threading.Event()
            def _body(self):
                self.stop.set()
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[stage]
                    target=self._body)
                t.start()
                return self.stop.is_set()
    ''')
    assert "WF260" not in _codes(findings)


def test_wf262_unordered_io_callback_in_replay_module(tmp_path):
    findings = _fixture(tmp_path, '''
        from jax.experimental import io_callback

        def cb(k):
            return k

        def probe_missing(shapes, keys):
            return io_callback(cb, shapes, keys)

        def probe_false(shapes, keys):
            return io_callback(cb, shapes, keys, ordered=False)

        def probe_var(shapes, keys, flag):
            return io_callback(cb, shapes, keys, ordered=flag)

        def probe_ok(shapes, keys):
            return io_callback(cb, shapes, keys, ordered=True)

        def probe_allowed(shapes, keys):
            return io_callback(cb, shapes, keys)  # wf-lint: allow[unordered]
    ''', replay=True)
    hits = [d for d in findings if d["code"] == "WF262"]
    assert len(hits) == 3, findings


def test_wf262_unresolvable_callback(tmp_path):
    findings = _fixture(tmp_path, '''
        from jax.experimental import io_callback

        def probe(cb_factory, shapes, keys):
            return io_callback(cb_factory(), shapes, keys, ordered=True)
    ''', replay=True)
    hits = [d for d in findings if d["code"] == "WF262"]
    assert len(hits) == 1 and "resolve" in hits[0]["message"]


def test_wf262_scoped_to_replay_modules(tmp_path):
    findings = _fixture(tmp_path, '''
        from jax.experimental import io_callback

        def cb(k):
            return k

        def probe(shapes, keys):
            return io_callback(cb, shapes, keys)
    ''', replay=False)
    assert "WF262" not in _codes(findings)


def test_wf263_lock_order_cycle(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class AB:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()
            def ab(self):
                with self.lock_a:
                    with self.lock_b:
                        return 1
            def ba(self):
                with self.lock_b:
                    with self.lock_a:
                        return 2
    ''')
    hits = [d for d in findings if d["code"] == "WF263"]
    assert len(hits) == 1 and "cycle" in hits[0]["message"]


def test_wf263_cycle_through_call_edge(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class AB:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()
            def _take_b(self):
                with self.lock_b:
                    return 1
            def ab(self):
                with self.lock_a:
                    return self._take_b()
            def ba(self):
                with self.lock_b:
                    with self.lock_a:
                        return 2
    ''')
    assert "WF263" in _codes(findings)


def test_wf263_multi_item_with_statement_orders_locks(tmp_path):
    """`with self.a, self.b:` acquires a THEN b — the a->b edge must enter
    the graph so an opposite-order nested pair is a cycle (review
    finding)."""
    findings = _fixture(tmp_path, '''
        import threading

        class AB:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()
            def ab(self):
                with self.lock_a, self.lock_b:
                    return 1
            def ba(self):
                with self.lock_b:
                    with self.lock_a:
                        return 2
    ''')
    hits = [d for d in findings if d["code"] == "WF263"]
    assert len(hits) == 1 and "cycle" in hits[0]["message"]


def test_multi_role_spawn_annotation_seeds_every_role(tmp_path):
    """A spawn annotated with two roles seeds BOTH (review finding: the
    tail must not silently drop) — and the spawn record duplication does
    not double-report WF264."""
    findings = _fixture(tmp_path, '''
        import threading

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        class Driver:
            def __init__(self, node: Node):
                self._node = node
            def _body(self):
                self._node.settle()
            def run(self):
                t = threading.Thread(  # wf-lint: thread-role[driver, stage]
                    target=self._body)
                t.start()
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "'stage'" in hits[0]["message"]
    assert len([d for d in findings if d["code"] == "WF264"]) == 1


def test_wf263_nested_order_consistent_is_clean(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class AB:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.lock_b = threading.Lock()
            def ab(self):
                with self.lock_a:
                    with self.lock_b:
                        return 1
            def ab2(self):
                with self.lock_a:
                    with self.lock_b:
                        return 2
    ''')
    assert "WF263" not in _codes(findings)


def test_wf263_cross_function_self_reacquire(tmp_path):
    """Holding a plain Lock and calling a helper that re-takes it is a
    guaranteed deadlock even though the acquire lives in another function
    (review finding: the a==b case the cycle graph drops must be checked
    through the call graph); an RLock is fine."""
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
            def _helper(self):
                with self._lock:
                    return 1
            def outer(self):
                with self._lock:
                    return self._helper()

        class ReBox:
            def __init__(self):
                self._lock = threading.RLock()
            def _helper(self):
                with self._lock:
                    return 1
            def outer(self):
                with self._lock:
                    return self._helper()
    ''')
    hits = [d for d in findings if d["code"] == "WF263"]
    assert len(hits) == 1 and "re-acquires" in hits[0]["message"], findings
    assert "Box._helper" in hits[0]["message"] or "_helper" in \
        hits[0]["message"]


def test_wf261_pool_stored_on_self_attribute(tmp_path):
    """`self._pool = ThreadPoolExecutor(...)` + `self._pool.submit(...)`
    seeds the checkpoint-pool role like the local/with-as forms (review
    finding)."""
    findings = _fixture(tmp_path, '''
        from concurrent.futures import ThreadPoolExecutor

        class Node:
            def settle(self):  # wf-lint: thread-role[driver]
                return 0

        class Saver:
            def __init__(self, node: Node):
                self._pool = ThreadPoolExecutor(2)
                self._node = node
            def work(self):
                return self._node.settle()
            def save(self):
                return self._pool.submit(self.work)
    ''')
    hits = [d for d in findings if d["code"] == "WF261"]
    assert len(hits) == 1 and "checkpoint-pool" in hits[0]["message"]


def test_wf263_self_reacquire_of_plain_lock(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
            def outer(self):
                with self._lock:
                    with self._lock:
                        return 1
    ''')
    hits = [d for d in findings if d["code"] == "WF263"]
    assert len(hits) == 1 and "re-acquiring" in hits[0]["message"]


def test_wf264_unjoined_non_daemon_thread(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        def fire_and_forget(fn):
            t = threading.Thread(target=fn)
            t.start()
    ''')
    hits = [d for d in findings if d["code"] == "WF264"]
    assert len(hits) == 1


def test_wf264_not_suppressed_by_unrelated_join_names(tmp_path):
    """os.path.join / ', '.join are not thread joins — they must not
    satisfy the reachable-join() check (review finding)."""
    findings = _fixture(tmp_path, '''
        import os
        import threading

        def fire_and_forget(fn):
            p = os.path.join("a", "b")
            label = ", ".join(["x", "y"])
            t = threading.Thread(target=fn)
            t.start()
            return p, label
    ''')
    hits = [d for d in findings if d["code"] == "WF264"]
    assert len(hits) == 1, findings


def test_wf264_daemon_join_and_allow_are_clean(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        def daemonized(fn):
            threading.Thread(target=fn, daemon=True).start()

        def joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()

        def joined_in_class_method(fn):
            pass

        def allowed(fn):
            t = threading.Thread(target=fn)  # wf-lint: allow[unjoined]
            t.start()
    ''')
    assert "WF264" not in _codes(findings)


def test_wf265_annotation_grammar_rejection(tmp_path):
    findings = _fixture(tmp_path, '''
        import threading

        class Box:
            def __init__(self):
                self.items = []       # wf-lint: single-writer[gremlin]

            def work(self):  # wf-lint: thread-role[bogus-role]
                return self.items
    ''')
    hits = [d for d in findings if d["code"] == "WF265"]
    assert len(hits) == 2, findings
    assert all("unknown role" in d["message"] for d in hits)


def test_wf265_line_above_annotation_form(tmp_path):
    """The declaration-on-the-line-above form parses for thread-role too."""
    findings = _fixture(tmp_path, '''
        import threading

        class Node:
            # wf-lint: thread-role[driver]
            def settle(self):
                return 0

        class Driver:
            def __init__(self, node: Node):
                self._node = node
            def _body(self):
                self._node.settle()
            def run(self):
                threading.Thread(target=self._body).start()
    ''')
    assert "WF261" in _codes(findings)


def test_run_lint_includes_concurrency_findings(tmp_path):
    """The WF26x family rides run_lint/lint_repo (and therefore the shared
    baseline ratchet), not a separate entry point."""
    pkg = tmp_path / "windflow_tpu"
    (pkg / "observability").mkdir(parents=True)
    (pkg / "analysis").mkdir()
    (tmp_path / "docs").mkdir()
    (pkg / "observability" / "names.py").write_text(
        'JOURNAL_EVENTS = ()\nRECOVERY_COUNTERS = ()\n'
        'CONTROL_COUNTERS = ()\nCONTROL_GAUGES = ()\n')
    (tmp_path / "docs" / "ENV_FLAGS.md").write_text("# flags\n")
    (pkg / "mod.py").write_text(textwrap.dedent('''
        import threading

        def fire_and_forget(fn):
            t = threading.Thread(target=fn)
            t.start()
    '''))
    findings = lint.run_lint(cfg=lint.LintConfig(root=str(tmp_path)))
    assert "WF264" in [x.code for x in findings]
    # and the baseline ratchet suppresses it like any WF2xx finding
    bpath = tmp_path / "b.json"
    lint.save_baseline(str(bpath), findings)
    fresh = lint.apply_baseline(findings, lint.load_baseline(str(bpath)))
    assert fresh == []


# ------------------------------------------------------------- CLI contract


def _poisoned_jax_dir(tmp_path):
    d = tmp_path / "nojax"
    d.mkdir(exist_ok=True)
    (d / "jax.py").write_text("raise ImportError('wf_lint must not "
                              "import jax')\n")
    return str(d)


def _run_cli(*args, env=None):
    e = dict(os.environ)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "wf_lint.py"), *args],
        capture_output=True, text=True, timeout=120, env=e)


def test_cli_runs_concurrency_pass_by_default_without_jax(tmp_path):
    """The default wf_lint invocation includes the WF26x pass and exits 0
    on this repo even when importing jax is poisoned (the loadable-by-path
    contract)."""
    proc = _run_cli(env={"PYTHONPATH": _poisoned_jax_dir(tmp_path)})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_select_family_and_exit_codes(tmp_path):
    """A seeded WF264 fixture exits 1 under --select WF264 (family syntax
    included) and 0 under --ignore WF264."""
    pkg = tmp_path / "fix" / "windflow_tpu"
    (pkg / "observability").mkdir(parents=True)
    (pkg / "observability" / "names.py").write_text(
        'JOURNAL_EVENTS = ()\nRECOVERY_COUNTERS = ()\n'
        'CONTROL_COUNTERS = ()\nCONTROL_GAUGES = ()\n')
    (tmp_path / "fix" / "docs").mkdir()
    (tmp_path / "fix" / "docs" / "ENV_FLAGS.md").write_text("# flags\n")
    (pkg / "mod.py").write_text(textwrap.dedent('''
        import threading
        def fire_and_forget(fn):
            t = threading.Thread(target=fn)
            t.start()
    '''))
    proc = _run_cli("--select", "WF26x", "--no-baseline",
                    "--root", str(tmp_path / "fix"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "WF264" in proc.stdout
    proc = _run_cli("--ignore", "WF264", "--no-baseline",
                    "--root", str(tmp_path / "fix"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_select_unknown_code_is_exit_2():
    proc = _run_cli("--select", "WF999")
    assert proc.returncode == 2, proc.stdout + proc.stderr


def test_cli_overbroad_family_token_is_exit_2():
    """`--ignore x` must not match every rule and turn the gate into a
    silent no-op (review finding: family prefix must be WF+digits)."""
    proc = _run_cli("--ignore", "x")
    assert proc.returncode == 2, proc.stdout + proc.stderr
    proc = _run_cli("--select", "Wx")
    assert proc.returncode == 2, proc.stdout + proc.stderr


def test_cli_refuses_partial_baseline_update():
    proc = _run_cli("--select", "WF26x", "--update-baseline")
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "partial baseline" in proc.stderr


def test_cli_explain_mode(tmp_path):
    proc = _run_cli("--explain", "WF261",
                    env={"PYTHONPATH": _poisoned_jax_dir(tmp_path)})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "WF261" in proc.stdout and "thread-role" in proc.stdout
    proc = _run_cli("--explain", "WF999")
    assert proc.returncode == 2
