#!/usr/bin/env python3
"""wf_progcheck — the device-program analyzer (WF3xx) over this repository.

Traces the closed jaxprs of every registered audit target's step
programs (``windflow_tpu/analysis/progcheck.py`` — zero FLOPs, zero device)
and gates on the WF300-WF305 findings:

    python scripts/wf_progcheck.py                    # the whole audit set
    python scripts/wf_progcheck.py --targets nexmark  # one family
    python scripts/wf_progcheck.py --format=json      # machine-readable
    python scripts/wf_progcheck.py --select WF30x     # family filter
    python scripts/wf_progcheck.py --explain WF305    # what a code means
    python scripts/wf_progcheck.py --update-baseline  # accept, keep rationales
    python scripts/wf_progcheck.py --fingerprints     # per-program hashes

``--select``/``--ignore``/``--explain`` share the wf_lint conventions
(comma-separated codes, a trailing ``x`` matches a family). Exit codes: 0 =
clean, 1 = findings (INCLUDING baseline entries without a written rationale
— a suppression is an argued decision, the WF26x discipline), 2 = broken
invocation or internal error. Unlike every other wf_* CLI this one NEEDS
JAX (program analysis traces real jaxprs); on a box without it, exit 2
with a one-line explanation, never a traceback.

Baseline: ``windflow_tpu/analysis/progcheck_baseline.json`` (override with
``--baseline`` / ``WF_PROGCHECK_BASELINE``). ``--update-baseline`` rewrites
it from the current findings, PRESERVING rationales already written for
entries that still match; new entries get ``"rationale": ""`` for a human
to fill — the gate stays red until they do.
"""

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _jax_missing() -> str:
    """Empty string when jax imports; else the reason (checked BEFORE the
    package import so a jax-less box gets a verdict, not a traceback)."""
    try:
        import jax  # noqa: F401
        return ""
    except Exception as e:  # noqa: BLE001 — any import failure = no jax
        return f"{type(e).__name__}: {e}"


def _load():
    """Package imports (progcheck traces real operator code, so the full
    ``windflow_tpu`` package — and therefore JAX — must be importable)."""
    sys.path.insert(0, REPO)
    from windflow_tpu.analysis import lint, progcheck
    return lint, progcheck


def _parse_codes(rules, text: str):
    """wf_lint's token grammar, verbatim semantics: trailing ``x`` =
    family by prefix, exact tokens must be registered — a typo must break
    the invocation (exit 2), never silently select nothing."""
    import re
    codes = set()
    for tok in [t.strip() for t in text.split(",") if t.strip()]:
        if re.fullmatch(r"WF\d+x", tok):
            fam = [c for c in rules if c.startswith(tok[:-1])]
            if not fam:
                raise ValueError(f"unknown rule family {tok!r}")
            codes.update(fam)
        elif tok in rules:
            codes.add(tok)
        else:
            raise ValueError(
                f"unknown rule code {tok!r} (see --explain, or the RULES "
                f"table in windflow_tpu/analysis/lint.py)")
    return codes


def _explain(code: str) -> int:
    """RULES row + the progcheck docstring block — via lint.py loaded BY
    FILE PATH, so --explain works even on a box without JAX."""
    path = os.path.join(REPO, "windflow_tpu", "analysis", "lint.py")
    spec = importlib.util.spec_from_file_location("wf_analysis_lint", path)
    lint = importlib.util.module_from_spec(spec)
    sys.modules["wf_analysis_lint"] = lint
    spec.loader.exec_module(lint)
    if code not in lint.RULES:
        print(f"wf_progcheck: unknown rule code {code!r}; registered: "
              f"{', '.join(sorted(lint.RULES))}", file=sys.stderr)
        return 2
    severity, summary = lint.RULES[code]
    print(f"{code} [{severity}] {summary}")
    doc = lint.progcheck_doc() if code.startswith("WF30") else \
        (lint.__doc__ or "")
    in_block = False
    for line in doc.splitlines():
        if line.strip().startswith(code):
            in_block = True
        elif in_block and (line.strip().startswith("WF")
                           or line.strip().startswith("=====")):
            break
        if in_block:
            print(line)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="wf_progcheck",
        description="windflow_tpu device-program analyzer (WF3xx)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--root", default=REPO,
                    help="repository root (default: this repo)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file overriding analysis/"
                         "progcheck_baseline.json (WF_PROGCHECK_BASELINE "
                         "env does the same)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings "
                         "(rationales already written are preserved; new "
                         "entries get an empty rationale to fill) and "
                         "exit 0")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--select", default=None, metavar="CODES",
                    help="comma-separated codes/families to run in "
                         "isolation (WF305 or WF30x)")
    ap.add_argument("--ignore", default=None, metavar="CODES",
                    help="comma-separated codes/families to drop")
    ap.add_argument("--explain", default=None, metavar="WFnnn",
                    help="print what a rule code means and exit")
    ap.add_argument("--targets", default=None, metavar="NAMES",
                    help="comma-separated audit-target families to trace "
                         "(default: all registered; see "
                         "progcheck.AUDIT_TARGETS)")
    ap.add_argument("--fingerprints", action="store_true",
                    help="also print each traced program's canonical "
                         "structural fingerprint")
    args = ap.parse_args(argv)

    if args.explain:
        # docstring-only path: must work WITHOUT jax (wf_lint convention)
        try:
            return _explain(args.explain)
        except Exception as e:  # noqa: BLE001 — broken invocation = 2
            print(f"wf_progcheck: internal error: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2

    missing = _jax_missing()
    if missing:
        print("wf_progcheck: JAX is not importable on this box — program "
              "analysis traces real jaxprs and cannot run without it "
              f"({missing})", file=sys.stderr)
        return 2

    try:
        lint, pc = _load()
        if args.update_baseline and (args.select or args.ignore):
            print("wf_progcheck: refusing --update-baseline with "
                  "--select/--ignore (a partial baseline would drop the "
                  "other codes' suppressions)", file=sys.stderr)
            return 2
        keep = _parse_codes(lint.RULES, args.select) if args.select else None
        drop = _parse_codes(lint.RULES, args.ignore) if args.ignore else None
        targets = ([t.strip() for t in args.targets.split(",") if t.strip()]
                   if args.targets else None)
        if args.baseline:
            os.environ["WF_PROGCHECK_BASELINE"] = \
                os.path.abspath(args.baseline)

        programs = []
        for name in (targets or sorted(pc.AUDIT_TARGETS)):
            if name not in pc.AUDIT_TARGETS:
                raise ValueError(
                    f"unknown audit target {name!r}; registered: "
                    f"{', '.join(sorted(pc.AUDIT_TARGETS))}")
            programs += pc.AUDIT_TARGETS[name]()
        findings = pc.analyze_programs(programs)
        if keep is not None:
            findings = [x for x in findings if x.code in keep]
        if drop is not None:
            findings = [x for x in findings if x.code not in drop]
        bpath = pc.baseline_path(args.root)
        if args.update_baseline:
            pc.save_baseline(bpath, findings)
            empty = sum(1 for e in json.load(open(bpath))["findings"]
                        if not e["rationale"].strip())
            print(f"wf_progcheck: wrote {len(findings)} finding(s) to "
                  f"{bpath}"
                  + (f" — {empty} without a rationale: fill them or the "
                     f"gate stays red" if empty else ""))
            return 0
        if args.no_baseline:
            fresh, suppressed, problems = findings, [], []
        else:
            counts, problems = pc.load_baseline(bpath)
            fresh = pc.apply_baseline(findings, counts)
            fresh_ids = {id(x) for x in fresh}
            suppressed = [x for x in findings if id(x) not in fresh_ids]
        fps = ([{"target": p.target, "shards": p.shards, "capacity": p.capacity,
                 "fingerprint": pc.program_fingerprint(p.closed)}
                for p in programs] if args.fingerprints else None)
    except Exception as e:  # noqa: BLE001 — a broken analyzer must exit 2,
        #                     never masquerade as a clean (0) or dirty (1) run
        print(f"wf_progcheck: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.format == "json":
        print(json.dumps({
            "findings": [x.to_dict() for x in fresh],
            "suppressed": len(suppressed),
            "baseline_problems": problems,
            "programs": len(programs),
            **({"fingerprints": fps} if fps is not None else {}),
        }, indent=1))
    else:
        if fps is not None:
            for row in fps:
                print(f"{row['target']}/step "
                      f"shards={row['shards']} cap={row['capacity']}  "
                      f"{row['fingerprint']}")
        for x in fresh:
            print(x.render())
        for p in problems:
            print(f"wf_progcheck: baseline entry WITHOUT a rationale: {p} "
                  f"— a suppression is an argued decision; write one")
        print(f"wf_progcheck: {len(fresh)} finding(s) "
              f"({len(suppressed)} baselined, {len(programs)} programs"
              + (f", {len(problems)} baseline entries missing a rationale"
                 if problems else "") + ")")
    return 1 if (fresh or problems) else 0


if __name__ == "__main__":
    sys.exit(main())
