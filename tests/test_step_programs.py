"""The fence around the benchmark cells' programs: one table of what each
program reads, equation for equation, keyed by what it guards.

A program is fenced as ``(count, sha256)`` of its equations, nested ones
included, in order: primitive, operand and result types, and what parameters
print the same in every process; scope names and source lines are left out,
so a scope added or renamed moves nothing, and an operation added, removed or
reordered moves the pair. The kinds:

- ``step``: a cell's whole step program at rehearsal sizes
  (:func:`step_operations`);
- ``taken_branch``: the branch of the window's fold that an in-order
  stream takes (the fast one: the outer ``cond``'s second branch under
  ``insert/fold``);
- ``fold``: ``ops/histogram.py::keyed_pane_fold`` at C = 8,192, K = 8,
  P = 256 with one int32 leaf (:func:`fold_program`): the whole program and
  its ``fast``, ``partial`` and ``scatter`` (the whole batch's) branches;
- ``element_form``: a cell's step with ``Win_SeqFFAT._emit``'s element
  takes forced (its ring rows cost more than they do at rehearsal sizes).

Taking a new pair is the one way a fenced program changes: a change that
moves it on purpose writes the new pair here, with a comment that names the
commit the old pair was read at, the old pair, and the reason."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from test_ysb_wmr_config import chain_step, equations, load_config
from windflow_tpu.ops.histogram import keyed_pane_fold


def operations(eqns):
    """(count, sha256) of these (equation, path) pairs: a line each of the
    primitive, operand and result types and the parameters that print the
    same in every process."""
    lines = []
    for eqn, _ in eqns:
        params = sorted(
            (k, re.sub(r"0x[0-9a-f]+", "0x", str(v)))
            for k, v in eqn.params.items()
            if not hasattr(getattr(v, "jaxpr", v), "eqns") and not callable(v)
            and not isinstance(v, (list, tuple)))
        lines.append(" ".join([
            eqn.primitive.name,
            ",".join(str(getattr(v, "aval", v)) for v in eqn.invars), "->",
            ",".join(str(v.aval) for v in eqn.outvars), str(params)]))
    return len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()


def step_jaxpr(name, batch_capacity=8192):
    """(ops, the jaxpr inside a cell's ``jit(step)`` at rehearsal sizes)."""
    jax.clear_caches()          # a cached inner jit keeps its first call site
    mod, cfg = load_config(name)
    ops, step, args = chain_step(cfg, mod, batch_capacity)
    (call,) = jax.make_jaxpr(step)(*args).jaxpr.eqns
    return ops, call.params["jaxpr"].jaxpr


def step_operations(name, batch_capacity=8192):
    """A cell's step program, every equation of it; the call of
    ``jit(step)`` itself is left out (its signature is the states' leaves,
    not an operation). -> (count, sha256)."""
    return operations(equations(step_jaxpr(name, batch_capacity)[1]))


def fold_program(C=8192):
    """(jitted fold, its shapes, the whole jaxpr, and its branches by name):
    the outer ``cond``'s branches are the fallbacks (0) and the fast one (1);
    inside the former a second ``cond`` holds the whole-batch scatters (0)
    and the partial branch (1)."""
    args = ((jax.ShapeDtypeStruct((C,), jnp.int32),) * 2
            + (jax.ShapeDtypeStruct((C,), bool),
               jax.ShapeDtypeStruct((C,), jnp.int32)))
    fold = jax.jit(lambda k, p, v, x: keyed_pane_fold(k, p, v, x, 8, 256))
    jaxpr = jax.make_jaxpr(fold)(*args).jaxpr.eqns[0].params["jaxpr"].jaxpr
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    slow, fast = (b.jaxpr for b in cond.params["branches"])
    (inner,) = [e for e in slow.eqns if e.primitive.name == "cond"]
    scatter, partial = (b.jaxpr for b in inner.params["branches"])
    return fold, args, jaxpr, {"whole": jaxpr, "fast": fast,
                               "scatter": scatter, "partial": partial}


#: (kind, what it guards) -> (count, sha256), and where each was read:
#:
#: - ``step`` of ``kcb``, ``ysb_wmr`` and ``kpf``: every list of rows in the
#:   two window engines finds its keys through
#:   ``ops/segment.py::enumerate_runs`` (at 992955a they read (453,
#:   "cf2391ad4be611da..."), (749, "30da5f53c44d9b80...") and (1279,
#:   "cb2f241e337299e9...")); ``kcb``'s moved again at 3e6b73a for its size
#:   alone: ``Win_SeqFFAT._emit`` takes whole ring rows at this rehearsal
#:   size (``P`` 32) and not at the cell's (``P`` 4,096); with the element
#:   takes forced it is the pair it read at cd258cb (``element_form``).
#: - ``step`` of ``kff``: the fold's fallback grew its partial branch (at
#:   87463aa it read (371, "e00cd3c7b7dbb22f..."); at 8ecdb84, before the
#:   values rode the counts' contraction, (331, "b0625d6fe9e9746a...")).
#:   ``taken_branch`` of ``kff``: read at 87463aa, and the partial branch
#:   left it as it was.
#: - ``step`` of ``kff_late``: read at 448ed3d; its configuration's counters
#:   are time-based per-key only, so the global-time path with a delay is
#:   ``kff``'s fold.
#: - ``step`` of ``kff_lag``: read at 1775387, where the per-key insert
#:   rides ``keyed_pane_fold`` on panes relative to each key's horizon.
#: - ``fold``: ``fast`` and ``scatter`` read at b38da88; ``whole`` and
#:   ``partial`` where the partial branch came in between them (at b38da88
#:   the whole program read (158, "75e125695bd7babc...")).
#: - ``step`` of ``ysb``: the count lift became ``keyed_pane_fold`` with no
#:   value leaf (the fold's partial branch and its three counters joined
#:   the step); at 1775387 it read (288, "3bf2d779d9de5f066e7fcd70e83a3c3c"
#:   "106173e6b3e12e5fda01330b69b7dca9"), a pair unmoved since 64560ca.
#: - ``step`` of ``kff_drawdown``: first read with the configuration, where
#:   ``Key_FFAT`` calls a combine that is not ``jnp.add``, ``maximum`` or
#:   ``minimum`` on whole structs (the ordered fold: sort, segmented scan,
#:   placement; ``ops/segment.py::ordered_reduce`` over a window's panes),
#:   followed by ``Map(dd)``.
PROGRAMS = {
    ("step", "ysb"): (448, "175f7f27f74840bd20e7656fa047a8a1"
                           "d295e466fd92cc56ee157c5d5d2d4174"),
    ("step", "kcb"): (427, "921764b74a9f69ea546e177a8cfce258"
                           "35ffc8adf3bf744cc39dd59d4d7e39d5"),
    ("step", "ysb_wmr"): (729, "9bea6c039a3f24015c46cd932f5776c9"
                               "b5f4b4f073d0e836b3076763fc2d4405"),
    ("step", "kpf"): (1239, "67b249f99659b352f5e55d08e28c5af5"
                            "72fce9816ad34fd54ed546a86fe7e316"),
    ("step", "kff"): (555, "fb889972b69d53450dfcc71606256c7a"
                           "9af864a3a2dbb6152c0a5a303439118a"),
    ("step", "kff_late"): (575, "e8c7f4f69d5e006ee4f9a767899172ae"
                                "010ea3241c962114130c8b3e62ffbe0a"),
    ("step", "kff_lag"): (728, "57672543f15dfcaac70f1ca36df8a37a"
                               "fe8b7845bdfa36cf3d664dfffbd4f15b"),
    ("step", "kff_drawdown"): (1614, "b00f6272c95a858099bd987cc9263954"
                                     "1bd3a5ad312fa108ce0b11309618266b"),
    ("taken_branch", "kff"): (84, "de6e080554ea1c022688a85acd3da342"
                                  "d523cd1fd7783aa49f2ff186625263b9"),
    ("fold", "whole"): (338, "f22ac6f677a322ff204422b3146a78f8"
                             "c506b47304c67c34fc705646ac2923bd"),
    ("fold", "partial"): (152, "748153c92391649ed1586c9a61787dde"
                               "443fd524d753c11b37a606a7597ee76a"),
    ("fold", "fast"): (84, "46f2b8ef40059876777ea2e167af7ded"
                           "148785a6bc219b755bc2c3bd05784858"),
    ("fold", "scatter"): (53, "8bae431394fb553754e85c515b668dfd"
                              "ddecfabe4dda694b6fbb23f000a1cbac"),
    ("element_form", "kcb"): (445, "03555a722655e4d39e8e56654b7eb342"
                                   "37af879e5058edbe40bcbbc4407047bd"),
}


def fenced(kind):
    return sorted(name for k, name in PROGRAMS if k == kind)


@pytest.mark.parametrize("name", fenced("step"))
def test_step_program(name):
    assert step_operations(name) == PROGRAMS["step", name]


@pytest.mark.parametrize("name", fenced("taken_branch"))
def test_taken_branch(name):
    ops, jaxpr = step_jaxpr(name)
    conds = [(eqn, path) for eqn, path in equations(jaxpr)
             if eqn.primitive.name == "cond"]
    (outer, path), (inner, _) = conds
    assert path == f"{ops[-1].scope_name()}/insert/fold"
    # the partial branch's cond lies inside the outer one's first branch
    assert inner in outer.params["branches"][0].jaxpr.eqns
    fast = outer.params["branches"][1].jaxpr
    assert operations(equations(fast)) == PROGRAMS["taken_branch", name]


@pytest.mark.parametrize("name", fenced("fold"))
def test_fold_program(name):
    branch = fold_program()[3][name]
    assert operations(equations(branch)) == PROGRAMS["fold", name]


@pytest.mark.parametrize("name", fenced("element_form"))
def test_element_form(name, monkeypatch):
    import windflow_tpu.operators.win_seqffat as engine
    monkeypatch.setattr(engine, "ROW_LANE_NS", float("inf"))
    assert step_operations(name) == PROGRAMS["element_form", name]
