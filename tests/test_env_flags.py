"""Env-flag inventory gate — every ``WF_*`` environment variable read
anywhere in the tree must be documented in ``docs/ENV_FLAGS.md`` including
*when* it is read (the footgun: trace-time reads are baked
into cached executables, so an undocumented flag toggled mid-process silently
does nothing).

The scanner itself now lives in the invariant linter
(``windflow_tpu/analysis/lint.py`` — rules WF201/WF202), so the CLI, the
tier-1 lint gate (``tests/test_lint_clean.py``), and this focused test all
share ONE source of truth. This file keeps the inventory's contract pinned
directly: the rule finds real reads, and the known trace-time flags stay
marked."""

import os

from windflow_tpu.analysis import lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = lint.LintConfig(root=ROOT)


def test_scanner_sees_env_reads_at_all():
    """Guard against a silently-broken scanner (regex drift would make the
    gate vacuously green)."""
    read = lint.env_flags_read(ROOT, CFG)
    assert read, "the scanner found no WF_* env reads at all — it is broken"
    # a representative spread: package run-time flag, default-name idiom
    # (FaultPlan.from_env), trace-time flag, and the linter's own override
    for flag in ("WF_MONITORING", "WF_FAULT_PLAN", "WF_LOOKUP_IMPL",
                 "WF_LINT_BASELINE"):
        assert flag in read, f"{flag} read site not found by the scanner"


def test_every_env_flag_read_is_documented_with_read_time():
    """Rules WF201 (undocumented read) + WF202 (row missing the read-time
    cell) over the live tree — add the ENV_FLAGS.md row in the same commit
    that introduces a flag."""
    findings = lint.rule_env_flags(CFG)
    assert not findings, "\n".join(x.render() for x in findings)


def test_known_trace_time_flags_marked():
    """The flags read inside jitted code paths must carry the trace-time
    marking — the footgun the inventory exists to prevent."""
    docs = lint.parse_env_doc(os.path.join(ROOT, CFG.env_doc))
    for flag in ("WF_KERNEL_IMPL", "WF_LOOKUP_IMPL",
                 "WF_ORDERING_SKIP_SORTED"):
        assert flag in docs, f"{flag} missing from ENV_FLAGS.md"
        _lineno, cell = docs[flag]
        assert "trace" in cell.lower(), (
            f"{flag} is read at trace time but ENV_FLAGS.md does not say so")
