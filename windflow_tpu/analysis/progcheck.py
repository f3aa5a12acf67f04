"""Device-program analyzer — Pillar 4 of the static-analysis layer (WF3xx).

Every other static gate reasons about Python *source* (WF1xx config/spec
validation, WF2xx invariant lint, WF26x concurrency).  This one walks the
closed jaxprs of the programs that actually run on the chip — obtained via
``jax.make_jaxpr`` over the same step body ``CompiledChain.warm`` traces
(zero FLOPs, zero device: inputs are ``jax.ShapeDtypeStruct``), recursing
through ``scan``/``cond``/``while``/``pjit`` sub-jaxprs — and checks the
invariants the whole system rests on (byte-identical replay, ordered host
effects, "OFF path is byte-for-byte") where they actually live: in the
traced equations.

====== ========= =====================================================
code   severity  invariant
====== ========= =====================================================
WF300  error     order-dependent float accumulation in a deterministic-
                 replay program: a ``scatter-add`` (``.at[].add`` /
                 ``segment_sum``) whose indices are not statically
                 unique accumulates float values in index-collision
                 order — XLA may reorder colliding adds per backend/
                 geometry, so supervised replay is only
                 bitwise-reproducible by luck.  Fix: integer
                 accumulation, ``unique_indices=True`` where provable,
                 or a sort-then-segment formulation
WF301  error     unordered host effect in a compiled body: an
                 ``io_callback`` without a literal ``ordered=True`` (or
                 a ``debug_callback``/``debug_print`` without
                 ``ordered=True``) reachable from a step program — the
                 effects of consecutive pushes interleave freely; the
                 jaxpr-level complement of the AST-only WF262, catching
                 aliased imports and wrapped call sites
WF302  warning   host-sync in the per-push hot path: a callback
                 primitive forces the device to round-trip to the host
                 (blocking D2H) on EVERY push, outside the
                 maintain/settle surfaces designed for it — rank the
                 site against wf_health's per-stage ``dispatch_ratio``
                 as a whole-graph fusion candidate (ROADMAP item 2)
WF303  warning   retrace-signature hazard from actual avals: a
                 weak-typed program input/const (a Python scalar the
                 caller may later pass strongly typed) or a weak-typed
                 promotion inside the program (Python-scalar closure
                 constant) — the same chain silently retraces when the
                 weak leaf strengthens; subsumes the WF102 heuristic
                 with evidence from the traced program itself
WF304  error     donated-buffer aliasing: a donated input is read by a
                 later equation (or returned) after the equation XLA
                 will alias it into, or is aliased into two outputs —
                 the classic donate_argnums use-after-free
WF305  warning   shard-variant float reduction: a float-dtype
                 ``reduce_sum``/``reduce_prod``/``cumsum``/
                 ``dot_general`` in a program analyzed under
                 shards>1 — float addition is non-associative,
                 so the reduction's grouping (and therefore the bytes)
                 can change with the composition geometry; the precise
                 static evidence needed to retire WF115 pairings one by
                 one (integer reductions are exact and never flagged)
====== ========= =====================================================

``program_fingerprint`` is the other half: a canonical structural hash of a
closed jaxpr — primitives, params, avals, topology under first-use variable
numbering, sub-jaxprs included, const values digested, callables reduced to
qualnames — a pure function of the program (no ids, no addresses), stable
across processes.  The prose claim "toggle OFF is byte-for-byte" becomes a
pinned program-identity test (``tests/test_program_fingerprint.py``).

Baseline: ``analysis/progcheck_baseline.json`` (override:
``WF_PROGCHECK_BASELINE``) suppresses audited findings, but EVERY entry must
carry a non-empty ``rationale`` — an entry without one fails the gate (the
WF26x discipline: suppression is an argued decision, not a shrug).
``scripts/wf_progcheck.py --update-baseline`` rewrites entries while
preserving rationales already written.

This module needs JAX (program analysis genuinely does); the CLI exits 2
cleanly on a box without it.  Registration of the WF3xx codes for
``wf_lint --explain``/``--select`` lives in ``lint.RULES`` (parsed without
importing this module).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from .lint import Finding

# --------------------------------------------------------------- programs


@dataclasses.dataclass
class Program:
    """One traced device program plus the execution context it was traced
    for — the unit every WF3xx rule runs over."""

    target: str              # audit-target label, e.g. "nexmark:q3"
    closed: Any              # jax ClosedJaxpr
    capacity: int
    shards: int = 1          # shard count the program will run under
    replay: bool = False     # deterministic-replay (supervised) context

    @property
    def path(self) -> str:
        """Baseline identity path (the lint Finding ``path`` slot)."""
        return f"{self.target}/step"


def abstract_batch(capacity: int, payload_spec) -> Any:
    """A ``Batch`` of ``ShapeDtypeStruct`` leaves — the abstract twin of
    ``Batch.empty`` (zero allocation, zero device)."""
    from ..batch import Batch, CTRL_DTYPE
    import jax.numpy as jnp

    def mk(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", jnp.float32)
        return jax.ShapeDtypeStruct((capacity,) + shape, dtype)

    ctrl = jax.ShapeDtypeStruct((capacity,), CTRL_DTYPE)
    return Batch(key=ctrl, id=ctrl, ts=ctrl,
                 payload=jax.tree.map(mk, payload_spec),
                 valid=jax.ShapeDtypeStruct((capacity,), jnp.bool_))


def _abstract_states(chain) -> tuple:
    """The chain's operator states as ShapeDtypeStructs (never reads the
    device buffers)."""
    return tuple(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
        st) for st in chain.states)


def trace_step(chain, capacity: int):
    """Closed jaxpr of the full-chain per-push step — the same body
    ``CompiledChain._step_fn(0)`` jits, traced abstractly."""
    states = _abstract_states(chain)
    b = abstract_batch(capacity, chain.specs[0])

    def step(states, batch):
        states = list(states)
        for j in range(len(chain.ops)):
            states[j], batch = chain.ops[j].apply(states[j], batch)
        return tuple(states), batch

    return jax.make_jaxpr(step)(states, b)


def chain_programs(chain, capacity: int = None,
                   shards: int = 1, replay: bool = False,
                   target: str = "chain") -> List[Program]:
    """The programs a driver will actually dispatch for ``chain`` under the
    given config: the per-push step — the ``warm`` surface."""
    if capacity is None:
        from ..basic import DEFAULT_BATCH_SIZE
        from ..runtime.pipeline import resolve_batch_hint
        capacity = resolve_batch_hint(chain.ops) or DEFAULT_BATCH_SIZE
    return [Program(target=target, closed=trace_step(chain, capacity),
                    capacity=capacity, shards=shards, replay=replay)]


# ----------------------------------------------------------- jaxpr walking


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """Every (param_name[index], jaxpr-like) nested under one equation —
    covers scan/pjit (``jaxpr``), cond (``branches``), while
    (``cond_jaxpr``/``body_jaxpr``), custom derivatives, remat: anything
    whose param value walks like a jaxpr."""
    out = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            j = getattr(v, "jaxpr", None)         # ClosedJaxpr
            if j is not None and hasattr(j, "eqns"):
                out.append((f"{name}[{i}]" if len(vals) > 1 else name, v))
            elif hasattr(v, "eqns"):              # bare Jaxpr
                out.append((f"{name}[{i}]" if len(vals) > 1 else name, v))
    return out


def iter_eqns(closed) -> Iterator[Tuple[Any, str]]:
    """Depth-first ``(eqn, path)`` over a closed jaxpr and every sub-jaxpr;
    ``path`` names the nesting (``scan.jaxpr/cond.branches[1]``) so a
    finding can point INTO the program."""
    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            yield eqn, prefix
            for pname, sub in _sub_jaxprs(eqn):
                inner = getattr(sub, "jaxpr", sub)
                yield from walk(inner,
                                f"{prefix}/{eqn.primitive.name}.{pname}"
                                if prefix else f"{eqn.primitive.name}.{pname}")
    yield from walk(getattr(closed, "jaxpr", closed), "")


def _is_inexact(aval) -> bool:
    import jax.numpy as jnp
    dt = getattr(aval, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.inexact)


def _aval_str(v) -> str:
    aval = getattr(v, "aval", None)
    if aval is None:
        return "?"
    w = "~w" if getattr(aval, "weak_type", False) else ""
    return f"{getattr(aval, 'dtype', '?')}{list(getattr(aval, 'shape', []))}{w}"


# ----------------------------------------------------------------- rules


#: callback primitives that force a host round trip inside a compiled body
#: (``debug_print``: what ``jax.debug.print`` binds since jax 0.9; before, it
#: was a ``debug_callback``)
_CALLBACK_PRIMS = ("io_callback", "debug_callback", "debug_print",
                   "pure_callback")

#: float reductions whose result depends on accumulation grouping
#: (max/min/and/or are associative-exact and never flagged)
_GROUPING_REDUCTIONS = ("reduce_sum", "reduce_prod", "cumsum", "cumprod",
                        "dot_general", "reduce_window_sum", "add_any")


def _finding(prog: Program, code: str, severity: str, line: int,
             message: str, text: str) -> Finding:
    return Finding(code=code, severity=severity, path=prog.path,
                   line=line, message=message, text=text)


def analyze_program(prog: Program) -> List[Finding]:
    """All WF30x findings for one traced program."""
    out: List[Finding] = []
    flagged_301: set = set()
    n = 0
    for eqn, where in iter_eqns(prog.closed):
        n += 1
        name = eqn.primitive.name
        at = f"@{where}" if where else "@top"

        # WF300 — order-dependent float accumulation under replay
        if name == "scatter-add" and prog.replay \
                and not eqn.params.get("unique_indices", False) \
                and any(_is_inexact(o.aval) for o in eqn.outvars):
            out.append(_finding(
                prog, "WF300", "error", n,
                f"scatter-add on {_aval_str(eqn.outvars[0])} with "
                f"possibly-duplicate indices ({at}) in a deterministic-"
                f"replay program: colliding float adds accumulate in an "
                f"order XLA may change per backend/geometry — replay is "
                f"bitwise-reproducible only by luck. Use integer "
                f"accumulation, unique_indices=True where provable, or "
                f"sort-then-segment",
                text=f"scatter-add {_aval_str(eqn.outvars[0])} {at}"))

        # WF301 — unordered host effects in compiled bodies
        if name == "io_callback" and eqn.params.get("ordered") is not True:
            flagged_301.add(id(eqn))
            out.append(_finding(
                prog, "WF301", "error", n,
                f"io_callback without ordered=True ({at}) in a compiled "
                f"step body: the host effects of consecutive pushes "
                f"interleave freely, breaking byte-identical replay — "
                f"pass ordered=True (the "
                f"jaxpr-level complement of WF262, which only sees "
                f"direct AST call sites)",
                text=f"io_callback unordered {at}"))
        elif (name == "debug_callback"
              and "OrderedDebug" not in str(eqn.params.get("effect", ""))) \
                or (name == "debug_print"
                    and eqn.params.get("ordered") is not True):
            flagged_301.add(id(eqn))
            out.append(_finding(
                prog, "WF301", "error", n,
                f"{name} without ordered=True ({at}) in a "
                f"compiled step body: effect order is unspecified "
                f"across pushes — pass "
                f"jax.debug.print(..., ordered=True) or drop it from the "
                f"compiled path",
                text=f"{name} unordered {at}"))

        # WF302 — host sync in the per-push hot path (skip eqns already
        # carrying the stronger WF301 verdict)
        if name in _CALLBACK_PRIMS and id(eqn) not in flagged_301:
            cb = eqn.params.get("callback")
            cb_name = getattr(cb, "callback_func", cb)
            cb_name = getattr(cb_name, "__qualname__",
                              getattr(cb_name, "__name__", "<callback>"))
            out.append(_finding(
                prog, "WF302", "warning", n,
                f"{name} -> {cb_name} ({at}): a blocking D2H round trip "
                f"on EVERY push, outside the maintain/settle surfaces — "
                f"the device idles at this equation until the host "
                f"answers. Rank against wf_health's per-stage "
                f"dispatch_ratio as a whole-graph fusion candidate "
                f"(ROADMAP item 2), or move the exchange to the "
                f"maintain path",
                text=f"{name} {cb_name} {at}"))

        # WF303 (in-program half) — Python-scalar promotion inside the body
        if name == "convert_element_type" \
                and eqn.params.get("weak_type", False):
            out.append(_finding(
                prog, "WF303", "warning", n,
                f"weak-typed promotion to "
                f"{eqn.params.get('new_dtype')} ({at}): a Python-scalar "
                f"closure constant entered the traced program — if the "
                f"Python value varies per call the program retraces per "
                f"value; pin it with jnp.asarray(x, dtype)",
                text=f"weak convert_element_type "
                     f"{eqn.params.get('new_dtype')} {at}"))

        # WF305 — grouping-variant float reductions under composition
        if prog.shards > 1 \
                and name in _GROUPING_REDUCTIONS \
                and any(_is_inexact(o.aval) for o in eqn.outvars):
            out.append(_finding(
                prog, "WF305", "warning", n,
                f"{name} on {_aval_str(eqn.outvars[0])} ({at}) in a "
                f"program composed under shards={prog.shards}: float "
                f"accumulation is non-associative, so a grouping change "
                f"with the "
                f"composition geometry can change the bytes — the exact "
                f"evidence WF115 retirement needs (prove the grouping "
                f"fixed, cast to integer, or keep the pairing rejected)",
                text=f"{name} {_aval_str(eqn.outvars[0])} {at}"))

        # WF304 — donated buffer read after its aliasing equation
        donated = eqn.params.get("donated_invars")
        if donated and any(donated):
            out += _check_donation(prog, eqn, n, at)

    out += _check_weak_signature(prog)
    return out


def _check_donation(prog: Program, eqn, n: int, at: str) -> List[Finding]:
    """WF304 for one pjit equation with donated inputs: (a) a donated
    outer var consumed again by a LATER equation or returned (XLA aliases
    the buffer into this call's outputs — the later read is
    use-after-free); (b) inside the sub-jaxpr, a donated input aliased
    into two outputs (one buffer cannot back both)."""
    out: List[Finding] = []
    donated = eqn.params["donated_invars"]
    jaxpr = getattr(prog.closed, "jaxpr", prog.closed)
    dvars = [v for v, d in zip(eqn.invars, donated)
             if d and hasattr(v, "aval") and not hasattr(v, "val")]

    def uses(e, v):
        return any(u is v for u in e.invars)

    # (a) read-after-donation in the enclosing frame
    seen = False
    for other in jaxpr.eqns:
        if other is eqn:
            seen = True
            continue
        if not seen:
            continue
        for v in dvars:
            if uses(other, v):
                out.append(_finding(
                    prog, "WF304", "error", n,
                    f"donated input {_aval_str(v)} is read by a later "
                    f"`{other.primitive.name}` after "
                    f"`{eqn.params.get('name', eqn.primitive.name)}` "
                    f"({at}) donates it: XLA aliases the buffer into the "
                    f"donated call's outputs, so the later read sees "
                    f"freed/overwritten memory — copy before donating or "
                    f"drop the donation",
                    text=f"donated {_aval_str(v)} read after "
                         f"{eqn.primitive.name} {at}"))
    for v in dvars:
        if any(o is v for o in jaxpr.outvars):
            out.append(_finding(
                prog, "WF304", "error", n,
                f"donated input {_aval_str(v)} is also returned by the "
                f"enclosing program ({at}): the caller receives an alias "
                f"of a buffer XLA already reused — copy before donating",
                text=f"donated {_aval_str(v)} returned {at}"))
    # (b) aliased into two outputs inside the called jaxpr
    sub = eqn.params.get("jaxpr")
    inner = getattr(sub, "jaxpr", sub)
    if inner is not None and hasattr(inner, "outvars"):
        for v, d in zip(inner.invars, donated):
            if not d:
                continue
            hits = sum(1 for o in inner.outvars if o is v)
            if hits > 1:
                out.append(_finding(
                    prog, "WF304", "error", n,
                    f"donated input {_aval_str(v)} is aliased into "
                    f"{hits} outputs of "
                    f"`{eqn.params.get('name', eqn.primitive.name)}` "
                    f"({at}): one donated buffer cannot back two "
                    f"outputs — at most one output can alias it",
                    text=f"donated {_aval_str(v)} x{hits} outputs {at}"))
    return out


def _check_weak_signature(prog: Program) -> List[Finding]:
    """WF303 (signature half): weak-typed top-level inputs/consts — the
    caller-side scalar that silently retraces when strongly typed."""
    out: List[Finding] = []
    jaxpr = getattr(prog.closed, "jaxpr", prog.closed)
    for group, vs in (("input", jaxpr.invars), ("const", jaxpr.constvars)):
        weak = [i for i, v in enumerate(vs)
                if getattr(getattr(v, "aval", None), "weak_type", False)]
        if weak:
            out.append(_finding(
                prog, "WF303", "warning", 0,
                f"{len(weak)} weak-typed program {group}(s) at "
                f"position(s) {weak}: the signature was traced from a "
                f"Python scalar — the same chain retraces (new "
                f"executable, new cache entry) the first time a caller "
                f"passes the leaf strongly typed; pin with "
                f"jnp.asarray(x, dtype) at the boundary",
                text=f"weak {group}s {weak}"))
    return out


def analyze_programs(programs: Sequence[Program]) -> List[Finding]:
    out: List[Finding] = []
    for p in programs:
        out += analyze_program(p)
    return sorted(out, key=lambda x: (x.path, x.line, x.code, x.text))


# ------------------------------------------------------- the fingerprint


_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


def _digest_value(v) -> str:
    """Stable digest of a constant array/scalar (values matter: two
    programs differing only in a baked-in table differ)."""
    import numpy as np
    try:
        a = np.asarray(v)
        if a.dtype == object:              # not a value array: repr-scrub
            return _ADDR_RE.sub("", repr(v))
        return (f"{a.dtype}{list(a.shape)}:"
                f"{hashlib.sha256(a.tobytes()).hexdigest()[:16]}")
    except Exception:  # noqa: BLE001 — non-array consts degrade to repr
        return _ADDR_RE.sub("", repr(v))


def _canon_param(v) -> str:
    """Canonical, address-free rendering of one eqn param (sub-jaxprs are
    rendered by the caller; callables reduce to their qualname)."""
    if hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None), "eqns"):
        return "<jaxpr>"                     # rendered via recursion
    if isinstance(v, (list, tuple)):
        return "(" + ",".join(_canon_param(x) for x in v) + ")"
    import jax.core
    if isinstance(v, jax.core.AbstractValue):
        # an aval param (io_callback result_avals etc.): structural only
        w = "~w" if getattr(v, "weak_type", False) else ""
        return (f"aval:{getattr(v, 'dtype', '?')}"
                f"{list(getattr(v, 'shape', []))}{w}")
    if callable(v) or type(v).__name__ == "_FlatCallback":
        fn = getattr(v, "callback_func", v)
        return f"fn:{getattr(fn, '__qualname__', getattr(fn, '__name__', type(fn).__name__))}"
    if hasattr(v, "dtype") and hasattr(v, "shape"):
        return _digest_value(v)
    return _ADDR_RE.sub("", repr(v))


def _canon_jaxpr(jaxpr, consts, h) -> None:
    """Feed a canonical rendering of ``jaxpr`` into hash ``h``: variables
    numbered in first-use order (never by id), params normalized, consts
    digested by value, sub-jaxprs recursed in param order."""
    ids: Dict[int, int] = {}

    def vid(v) -> str:
        if hasattr(v, "val"):                # Literal: value, not identity
            return f"lit({_digest_value(v.val)}:{_aval_str(v)})"
        k = id(v)
        if k not in ids:
            ids[k] = len(ids)
        return f"v{ids[k]}:{_aval_str(v)}"

    h.update(b"in[")
    for v in jaxpr.invars:
        h.update(vid(v).encode())
        h.update(b",")
    h.update(b"]const[")
    for v, c in zip(jaxpr.constvars, consts or [None] * len(jaxpr.constvars)):
        h.update(vid(v).encode())
        if c is not None:
            h.update(b"=")
            h.update(_digest_value(c).encode())
        h.update(b",")
    h.update(b"]")
    for eqn in jaxpr.eqns:
        h.update(eqn.primitive.name.encode())
        h.update(b"(")
        for v in eqn.invars:
            h.update(vid(v).encode())
            h.update(b",")
        h.update(b")->(")
        for v in eqn.outvars:
            h.update(vid(v).encode())
            h.update(b",")
        h.update(b"){")
        for pname in sorted(eqn.params):
            h.update(pname.encode())
            h.update(b"=")
            h.update(_canon_param(eqn.params[pname]).encode())
            h.update(b";")
            pv = eqn.params[pname]
            pvs = pv if isinstance(pv, (list, tuple)) else (pv,)
            for sub in pvs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    h.update(b"<<")
                    _canon_jaxpr(inner, getattr(sub, "consts", None), h)
                    h.update(b">>")
        h.update(b"}")
    h.update(b"out[")
    for v in jaxpr.outvars:
        h.update(vid(v).encode())
        h.update(b",")
    h.update(b"]")


def program_fingerprint(closed) -> str:
    """Canonical structural sha256 of a (closed) jaxpr — a pure function
    of the program: same equations, params, avals, topology and constant
    values => same hex digest, in any process (no ids, no addresses)."""
    h = hashlib.sha256()
    jaxpr = getattr(closed, "jaxpr", closed)
    _canon_jaxpr(jaxpr, getattr(closed, "consts", None), h)
    return h.hexdigest()


def step_fingerprint(chain, capacity: int = None) -> str:
    """Fingerprint of the chain's per-push step program — THE toggle-OFF
    identity gate primitive (tests/test_program_fingerprint.py)."""
    if capacity is None:
        from ..basic import DEFAULT_BATCH_SIZE
        from ..runtime.pipeline import resolve_batch_hint
        capacity = resolve_batch_hint(chain.ops) or DEFAULT_BATCH_SIZE
    return program_fingerprint(trace_step(chain, capacity))


# --------------------------------------------------------------- baseline


def baseline_path(root: str = None) -> str:
    """``WF_PROGCHECK_BASELINE`` (run time, CLI/validate invocation)
    overrides the checked-in ``analysis/progcheck_baseline.json``;
    ``root=None`` resolves next to this module (validate() runs from any
    cwd), a root resolves repo-relative (the CLI convention)."""
    override = os.environ.get("WF_PROGCHECK_BASELINE", "")
    if override:
        return override if os.path.isabs(override) \
            else os.path.join(root or ".", override)
    if root is None:
        return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "progcheck_baseline.json")
    return os.path.join(root, "windflow_tpu", "analysis",
                        "progcheck_baseline.json")


def load_baseline(path: str) -> Tuple[Dict[tuple, int], List[str]]:
    """(suppression counts, problems).  Problems are entries without a
    non-empty ``rationale`` — the gate REFUSES to ride them (the WF26x
    discipline: a suppression is an argued decision)."""
    if not os.path.exists(path):
        return {}, []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    counts: Dict[tuple, int] = {}
    problems: List[str] = []
    for e in data.get("findings", ()):
        k = (e["code"], e["path"], e.get("text", ""))
        if not str(e.get("rationale", "")).strip():
            problems.append(f"{e['code']} {e['path']} {e.get('text', '')!r}")
            continue
        counts[k] = counts.get(k, 0) + 1
    return counts, problems


def save_baseline(path: str, findings: Sequence[Finding]) -> None:
    """Write the baseline from ``findings``, PRESERVING rationales already
    written for entries that still match (an --update-baseline must never
    erase the written record of why a finding is accepted)."""
    old: Dict[tuple, List[str]] = {}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for e in json.load(f).get("findings", ()):
                k = (e["code"], e["path"], e.get("text", ""))
                r = str(e.get("rationale", "")).strip()
                if r:
                    old.setdefault(k, []).append(r)
    entries = []
    for x in findings:
        k = x.key()
        kept = old.get(k)
        entries.append({
            "code": x.code, "path": x.path, "text": x.text,
            "message": x.message,
            "rationale": kept.pop(0) if kept else "",
        })
    payload = {
        "comment": "audited wf_progcheck findings suppressed from the gate; "
                   "EVERY entry must carry a written rationale (empty "
                   "rationale = gate failure). Regenerate with "
                   "scripts/wf_progcheck.py --update-baseline (existing "
                   "rationales are preserved for entries that still match).",
        "findings": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def apply_baseline(findings: Sequence[Finding],
                   counts: Dict[tuple, int]) -> List[Finding]:
    """Findings not suppressed (count-aware, the lint.py semantics)."""
    remaining = dict(counts)
    fresh = []
    for x in findings:
        k = x.key()
        if remaining.get(k, 0) > 0:
            remaining[k] -= 1
        else:
            fresh.append(x)
    return fresh


# ----------------------------------------------------------- audit surface


def _mk_chain(src, ops, capacity: int):
    from ..runtime.pipeline import CompiledChain
    return CompiledChain(list(ops), src.payload_spec(),
                         batch_capacity=capacity)


def _nexmark_programs() -> List[Program]:
    """The Nexmark query set: every query's step program and the q3 tiered
    variant (the host-callback production path), all under replay semantics
    (every query runs under the supervised drivers in tier-1)."""
    from ..nexmark import queries as q
    out: List[Program] = []
    for name in q.QUERIES:
        src, ops = q.make_query(name, total=512)
        chain = _mk_chain(src, ops, 64)
        out += chain_programs(chain, capacity=64, replay=True,
                              target=f"nexmark:{name}")
    src, ops = q.q3_enrich_join(512, tiered=True)
    out += chain_programs(_mk_chain(src, ops, 64), capacity=64,
                          replay=True, target="nexmark:q3_tiered")
    return out


def _ysb_programs() -> List[Program]:
    from ..benchmarks import ysb
    out: List[Program] = []
    for label, mk in (("ysb", ysb.make_ops), ("ysb_wmr", ysb.make_ops_wmr)):
        src = ysb.make_source(total=2048)
        chain = _mk_chain(src, mk(), 1024)
        out += chain_programs(chain, capacity=1024, replay=True,
                              target=f"bench:{label}")
    return out


def _mp_matrix_programs() -> List[Program]:
    """The mp_test matrix topologies (tests/test_mp_matrix.py CASES): every
    window-pattern family at its tier-1 geometry, step programs under
    replay (the chaos suites replay all of them)."""
    import jax.numpy as jnp
    import windflow_tpu as wf
    from ..basic import win_type_t
    from ..operators.window import WindowSpec
    from ..operators.win_seq import Win_Seq
    from ..operators.win_patterns import (Win_Farm, Key_Farm, Key_FFAT,
                                          Pane_Farm, Win_MapReduce)
    K = 3
    cases = {
        "win_seq_cb": lambda: Win_Seq(lambda wid, it: it.sum("v"),
                                      WindowSpec(8, 4, win_type_t.CB),
                                      num_keys=K),
        "win_seq_tb": lambda: Win_Seq(lambda wid, it: it.sum("v"),
                                      WindowSpec(12, 6, win_type_t.TB),
                                      num_keys=K),
        "win_farm_cb": lambda: Win_Farm(lambda wid, it: it.sum("v"),
                                        WindowSpec(10, 5, win_type_t.CB),
                                        parallelism=4, num_keys=K),
        "key_farm_cb": lambda: Key_Farm(lambda wid, it: it.max("v"),
                                        WindowSpec(6, 3, win_type_t.CB),
                                        parallelism=3, num_keys=K),
        "key_ffat_cb": lambda: Key_FFAT(lambda t: t.v, jnp.add,
                                        spec=WindowSpec(8, 2, win_type_t.CB),
                                        num_keys=K),
        "pane_farm_cb": lambda: Pane_Farm(lambda pid, it: it.sum("v"),
                                          lambda wid, it: it.sum(),
                                          WindowSpec(9, 3, win_type_t.CB),
                                          num_keys=K),
        "wmr_cb": lambda: Win_MapReduce(lambda wid, it: it.sum("v"),
                                        lambda wid, it: it.sum(),
                                        WindowSpec(8, 8, win_type_t.CB),
                                        map_parallelism=2, num_keys=K),
    }
    out: List[Program] = []
    for label, mk in sorted(cases.items()):
        src = wf.Source(lambda i: {"v": ((i * 13) % 23)
                                   .astype(jnp.float32)},
                        total=240, num_keys=K)
        ops = mk()
        if not isinstance(ops, (list, tuple)):
            ops = [ops]
        chain = _mk_chain(src, list(ops), 48)
        out += chain_programs(chain, capacity=48, replay=True,
                              target=f"mp:{label}")
    return out


def _example_programs() -> List[Program]:
    """The example topologies (examples/01..06), rebuilt as op chains: the
    examples themselves are self-running scripts, so the audit mirrors
    their graphs from the same builders they use."""
    import jax.numpy as jnp
    import windflow_tpu as wf
    out: List[Program] = []
    # 01_wordcount: FlatMap -> Map -> KeyBy -> Accumulator
    VOCAB = 50

    def make_words(i):
        return {"w": jnp.stack([(i * 7) % VOCAB, (i * 13) % VOCAB,
                                (i * 29) % VOCAB])}

    def split_words(t, shipper):
        for j in range(3):
            shipper.push({"word": t.w[j]})

    src = wf.Source(make_words, total=512)
    ops = [wf.FlatMap(split_words, max_fanout=3),
           wf.Map(lambda t: {"one": jnp.ones((), jnp.int32),
                             "word": t.word}),
           wf.KeyBy(lambda t: t.word, num_keys=VOCAB),
           wf.Accumulator(lambda t: t.data["one"], init_value=0,
                          num_keys=VOCAB)]
    out += chain_programs(_mk_chain(src, ops, 64), capacity=64,
                          replay=True, target="example:wordcount")
    # 02 rides the YSB chains and 06 the nexmark q1 chain already audited;
    # 03/05 use the Key_FFAT/Win_Seq topologies the mp-matrix target owns.
    # 04 is the multichip launcher: audit ITS geometry — the same Key_FFAT
    # chain under shards=2 (the WF305 shard axis)
    from ..operators.window import WindowSpec
    from ..basic import win_type_t
    src = wf.Source(lambda i: {"v": ((i * 7) % 31).astype(jnp.float32)},
                    total=4096, num_keys=8)
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(8, 4, win_type_t.CB), num_keys=8)
    out += chain_programs(_mk_chain(src, [op], 256), capacity=256,
                          shards=2, replay=True, target="example:multichip")
    # 06 is the serving wrapper around a Pipeline chain, audited here via
    # its default echo graph
    src = wf.Source(lambda i: {"v": (i % 97).astype(jnp.int32)}, total=512,
                    num_keys=8)
    out += chain_programs(
        _mk_chain(src, [wf.Map(lambda t: {"v": t.v * 2})], 64),
        capacity=64, replay=True, target="example:serving_echo")
    return out


#: the audited whole-repo target set — ``scripts/wf_progcheck.py`` runs all
#: of these by default; tests exercise them one family at a time
AUDIT_TARGETS: Dict[str, Callable[[], List[Program]]] = {
    "nexmark": _nexmark_programs,
    "ysb": _ysb_programs,
    "mp-matrix": _mp_matrix_programs,
    "examples": _example_programs,
}


def run_progcheck(targets: Optional[Sequence[str]] = None) -> List[Finding]:
    """Trace + analyze every audit target (or the named subset)."""
    programs: List[Program] = []
    for name in (targets or sorted(AUDIT_TARGETS)):
        if name not in AUDIT_TARGETS:
            raise ValueError(f"unknown progcheck target {name!r}; "
                             f"registered: {sorted(AUDIT_TARGETS)}")
        programs += AUDIT_TARGETS[name]()
    return analyze_programs(programs)


def progcheck_repo(root: str = ".", targets: Optional[Sequence[str]] = None,
                   ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """(fresh, suppressed, baseline_problems) — THE gate semantics shared
    by the CLI and tests; ``baseline_problems`` (entries without a
    rationale) must fail the gate."""
    findings = run_progcheck(targets)
    counts, problems = load_baseline(baseline_path(root))
    fresh = apply_baseline(findings, counts)
    fresh_ids = {id(x) for x in fresh}
    return fresh, [x for x in findings if id(x) not in fresh_ids], problems
