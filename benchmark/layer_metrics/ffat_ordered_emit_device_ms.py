"""``ffat_ordered_emit_device_ms``: device time per batch of the traced slice
under ``emit/reduce/ordered`` of ``Key_FFAT`` (a fired window's panes reduced
by ``ops/segment.py::ordered_reduce``: neighbours paired, the older on the
left, the combine on whole structs), self time by the ``XLA Ops`` line. None
where the program opens no such scope."""

import importlib.util
import os

BELOW_OPERATOR = ("emit", "reduce", "ordered")


def _fold_reader():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ffat_ordered_fold_device_ms.py")
    spec = importlib.util.spec_from_file_location("ordered_fold_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    return _fold_reader().read(run, BELOW_OPERATOR)
