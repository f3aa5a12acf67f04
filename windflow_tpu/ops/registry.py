"""Per-backend kernel registry — ONE selection point for every hot-op impl.

Ad-hoc trace-time env toggles (``WF_LOOKUP_IMPL`` among them) once sat
next to each kernel. This module promotes them into a
real portability layer (the selection architecture of arXiv:2601.17526):
every kernel family with more than one implementation — the XLA reference
formulation, a fused Pallas kernel, its interpret-mode fallback — registers
here, and the op entry points resolve their implementation through
:func:`resolve_impl` instead of reading ``os.environ`` themselves.

Selection is keyed on (kernel, shape/dtype spec key, device kind) and
resolves in precedence order:

1. an explicit ``impl=`` argument at the call site (always wins);
2. ``WF_KERNEL_IMPL`` — per-kernel (``"segment_fold=pallas,lookup=xla"``) or
   global (``"pallas"``) override;
3. the deprecated per-kernel alias ``WF_LOOKUP_IMPL`` — still honored,
   read HERE and nowhere else;
4. a persisted autotuned winner from the PR 3 :class:`~windflow_tpu.control.
   autotune.TuningCache` (``attach_tuning_cache``), so chains warm-start
   with the best known impl for this (kernel, spec, device);
5. the kernel's registered default (the XLA reference).

TRACE-TIME HAZARD (the documented footgun of ``ops/lookup.py``, now
checkable): resolution happens at TRACE time, so a
jitted executable compiled before an env/cache change keeps the old impl
for the life of the process (XLA caches the traced program, not the env).
Every resolution is therefore RECORDED under its (kernel, spec key, device)
key; :func:`stale_selections` recomputes the current selection for each
record and reports disagreements, and ``analysis/validate.py`` surfaces
them as WF109 diagnostics.

Kernel and impl names are gated by the linter (WF250) against the central
``observability/names.py::KERNELS``/``KERNEL_IMPLS`` registries — a typo'd
name would silently fork the env-override/tuning-cache/WF109 namespaces.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional, Tuple

def _deprecated_alias_choice(kernel: str) -> Optional[str]:
    """The deprecated pre-registry toggle (docs/ENV_FLAGS.md marks it a
    deprecated alias), read HERE and nowhere else — at TRACE time, like
    everything in this module. One literal read per flag: the WF201 env
    inventory scanner ties each flag to its ``os.environ`` line. ``''``/
    ``'0'`` = no override (the repo-wide off convention, matching
    WF_KERNEL_IMPL); anything else must be a registered impl name."""
    if kernel != "lookup":
        return None
    value = os.environ.get("WF_LOOKUP_IMPL", "")
    return None if value in ("", "0") else value


class KernelRefused(RuntimeError):
    """Selecting an impl the TPU compiler is known to refuse. Raised at
    selection — the registry never substitutes another impl."""


class KernelImpl:
    """One registered implementation of a kernel family."""

    __slots__ = ("kernel", "name", "fn", "reference", "backends",
                 "tpu_refusal")

    def __init__(self, kernel: str, name: str, fn: Optional[Callable],
                 reference: bool, backends: Tuple[str, ...],
                 tpu_refusal: Optional[str] = None):
        self.kernel = kernel
        self.name = name
        self.fn = fn
        self.reference = reference
        self.backends = backends
        #: Mosaic's own message when it refuses to compile this impl on TPU
        #: (recorded from a chip run); None = compiles
        self.tpu_refusal = tpu_refusal

    def __repr__(self) -> str:
        return (f"KernelImpl({self.kernel}:{self.name}"
                f"{' [ref]' if self.reference else ''})")


def device_kind() -> str:
    """``platform:device_kind`` of the default backend — delegates to
    ``control/autotune.py::device_kind`` so kernel entries and capacity
    plans key the ONE shared TuningCache file with the same device string
    (a format change there cannot fork the two namespaces)."""
    from ..control.autotune import device_kind as _dk
    return _dk()


def pallas_interpret() -> bool:
    """THE interpret rule every Pallas kernel module shares: interpret on
    the ``cpu`` backend (tests, rehearsals), compile with Mosaic on ``tpu``,
    and refuse any other platform — a kernel that silently ran interpreted
    on an accelerator would pass every test and measure nothing."""
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run interpreted on 'cpu' and compiled on 'tpu'; "
        f"the default backend is {backend!r} — select the xla impl "
        f"(WF_KERNEL_IMPL=xla) on this platform")


def pallas_backend() -> str:
    """Which Pallas execution mode a ``pallas`` impl would use right now:
    ``"pallas-tpu"`` or ``"pallas-interpret"`` (see :func:`pallas_interpret`)."""
    return "pallas-interpret" if pallas_interpret() else "pallas-tpu"


def _parse_kernel_impl_env(value: str) -> Dict[str, str]:
    """``WF_KERNEL_IMPL`` grammar: ``"pallas"`` (global default under key
    ``"*"``) or ``"segment_fold=pallas,lookup=xla"`` (per-kernel); entries
    without ``=`` set the global default. ``''``/``'0'`` = no override (the
    WF_ORDERING_SKIP_SORTED off convention)."""
    out: Dict[str, str] = {}
    if value in ("", "0"):
        return out
    for part in value.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, _, v = part.partition("=")
            out[k.strip()] = v.strip()
        else:
            out["*"] = part
    return out


class KernelRegistry:
    """The per-backend kernel registry. One process-wide instance
    (:data:`REGISTRY`) backs the module-level convenience functions — the
    class exists so tests can build isolated registries."""

    def __init__(self):
        self._impls: Dict[str, Dict[str, KernelImpl]] = {}
        self._default: Dict[str, str] = {}
        self._cache = None                      # control.autotune.TuningCache
        # (kernel, spec_key, device) -> EVERY impl resolved at trace time
        # (a set, not last-wins: each resolution may live on in a cached
        # executable, so a later re-resolution must not silence the WF109
        # staleness check for the earlier one)
        self._records: Dict[Tuple[str, str, str], set] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ registration

    def register_kernel(self, kernel: str, impl: str,
                        fn: Optional[Callable] = None, *,
                        reference: bool = False,
                        backends: Tuple[str, ...] = ("xla",),
                        default: bool = False,
                        tpu_refusal: Optional[str] = None) -> None:
        """Register ``impl`` for ``kernel``. ``reference`` marks the
        byte-identical oracle every other impl is parity-tested against;
        ``default`` (implied by the first registration) is the selection
        when nothing overrides. ``tpu_refusal`` records Mosaic's message
        for an impl it will not compile: selecting that impl on a TPU
        backend raises :class:`KernelRefused` (it still runs interpreted on
        CPU). Re-registration replaces (module reload)."""
        with self._lock:
            fam = self._impls.setdefault(kernel, {})
            fam[impl] = KernelImpl(kernel, impl, fn, reference, backends,
                                   tpu_refusal)
            if default or kernel not in self._default:
                self._default[kernel] = impl

    def kernels(self) -> Tuple[str, ...]:
        return tuple(sorted(self._impls))

    def impls(self, kernel: str) -> Tuple[str, ...]:
        return tuple(sorted(self._impls.get(kernel, ())))

    def reference_impl(self, kernel: str) -> Optional[str]:
        for impl in self._impls.get(kernel, {}).values():
            if impl.reference:
                return impl.name
        return None

    # --------------------------------------------------------------- tuning

    def attach_tuning_cache(self, cache) -> None:
        """Warm-start selection from (and persist winners to) a PR 3
        ``TuningCache``. ``None`` detaches."""
        with self._lock:
            self._cache = cache

    def persist_winner(self, kernel: str, spec_key: str, impl: str,
                       tps: Optional[float] = None) -> None:
        """Record an autotuned winning impl in the attached TuningCache so
        later processes warm-start on it (schema: ``{"impl": ..., "tps":
        ..., "kernel": ...}`` under the kernel tuning key)."""
        self._require_impl(kernel, impl)
        if self._cache is None:
            return
        from ..control.autotune import kernel_tuning_key
        entry = {"impl": impl, "kernel": kernel, "spec": spec_key}
        if tps is not None:
            entry["tps"] = float(tps)
        self._cache.put(kernel_tuning_key(kernel, spec_key, device_kind()),
                        entry)

    def _cached_winner(self, kernel: str, spec_key: str) -> Optional[str]:
        if self._cache is None:
            return None
        from ..control.autotune import kernel_tuning_key
        hit = self._cache.get(
            kernel_tuning_key(kernel, spec_key, device_kind()))
        if hit and isinstance(hit.get("impl"), str):
            return hit["impl"]
        return None

    # ------------------------------------------------------------- selection

    def _require_impl(self, kernel: str, impl: str) -> str:
        fam = self._impls.get(kernel)
        if not fam:
            raise ValueError(
                f"unknown kernel {kernel!r}; registered kernels: "
                f"{', '.join(self.kernels()) or '(none)'}")
        if impl not in fam:
            raise ValueError(
                f"kernel {kernel!r} has no impl {impl!r}; registered impls: "
                f"{', '.join(self.impls(kernel))}")
        return impl

    def _select(self, kernel: str, spec_key: str,
                explicit: Optional[str]) -> str:
        if explicit:
            return self._require_impl(kernel, explicit)
        env = _parse_kernel_impl_env(os.environ.get("WF_KERNEL_IMPL", ""))
        choice = env.get(kernel) or env.get("*")
        if not choice:
            choice = _deprecated_alias_choice(kernel)
        if not choice:
            choice = self._cached_winner(kernel, spec_key)
        if not choice:
            choice = self._default.get(kernel)
        return self._require_impl(kernel, choice)

    def resolve_impl(self, kernel: str, *, spec_key: str = "",
                     impl: Optional[str] = None, record: bool = True) -> str:
        """Resolve the implementation for ``kernel`` (precedence: explicit
        ``impl=`` > ``WF_KERNEL_IMPL`` > deprecated alias > tuning-cache
        winner > registered default) and — because resolution happens at
        TRACE time and the compiled executable keeps it — record the choice
        under (kernel, spec_key, device) for the WF109 staleness check.
        Explicit ``impl=`` choices are NOT recorded: they are pinned in
        code, so an env change can neither invalidate them nor make the
        staleness comparison meaningful."""
        choice = self._select(kernel, spec_key, impl)
        refusal = self._impls[kernel][choice].tpu_refusal
        if refusal is not None and not pallas_interpret():
            raise KernelRefused(
                f"kernel {kernel!r} impl {choice!r} does not compile on TPU "
                f"(Mosaic: {refusal}); select another impl")
        if record and impl is None:
            self.record_impl(kernel, spec_key, choice)
        return choice

    def record_impl(self, kernel: str, spec_key: str, impl: str) -> None:
        """Record that a program was traced with ``impl`` under (kernel,
        spec_key, device): :meth:`resolve_impl` records its choice here, and
        a call site that routes a shape the choice cannot take to another
        form records the form it took."""
        dk = device_kind()
        with self._lock:
            self._records.setdefault((kernel, spec_key, dk), set()).add(impl)
        # runtime-health ledger (observability/device_health.py): a
        # resolution observed while a ledger is active journals a
        # kernel_resolve event — the compile ledger's record of WHICH
        # impl each executable was traced with (the WF109 evidence,
        # live). Lazy import + None check: trace-time-rare path, and
        # this module must stay importable before observability.
        try:
            from ..observability import device_health as _dh
        except ImportError:            # minimal/fixture trees
            _dh = None
        if _dh is not None:
            _dh.note_kernel_resolve(kernel, spec_key, impl, device=dk)

    # ------------------------------------------------------- WF109 records

    def trace_records(self) -> Dict[Tuple[str, str, str], frozenset]:
        """Snapshot of every (kernel, spec_key, device) -> set of impls
        resolved this process (≈ the impls baked into cached jitted
        executables — ALL of them, not just the latest)."""
        with self._lock:
            return {k: frozenset(v) for k, v in self._records.items()}

    def stale_selections(self) -> List[dict]:
        """Recorded trace-time impls the CURRENT selection (env/cache as of
        now; explicit args excluded — those are pinned in code) no longer
        agrees with. One entry per disagreeing impl — an executable compiled
        under it may still be cached — each feeding one WF109 diagnostic."""
        out = []
        for (kernel, spec_key, device), recorded in \
                sorted(self.trace_records().items()):
            try:
                current = self._select(kernel, spec_key, None)
            except ValueError:
                continue                      # kernel/impl unregistered now
            for impl in sorted(recorded - {current}):
                out.append({"kernel": kernel, "spec_key": spec_key,
                            "device": device, "recorded": impl,
                            "current": current})
        return out

    def reset_records(self) -> None:
        """Forget trace records (tests; a fresh process does this by
        construction)."""
        with self._lock:
            self._records.clear()


#: the process-wide registry instance the op modules register into
REGISTRY = KernelRegistry()


def register_kernel(kernel: str, impl: str, fn: Optional[Callable] = None, *,
                    reference: bool = False,
                    backends: Tuple[str, ...] = ("xla",),
                    default: bool = False,
                    tpu_refusal: Optional[str] = None) -> None:
    REGISTRY.register_kernel(kernel, impl, fn, reference=reference,
                             backends=backends, default=default,
                             tpu_refusal=tpu_refusal)


def resolve_impl(kernel: str, *, spec_key: str = "",
                 impl: Optional[str] = None, record: bool = True) -> str:
    return REGISTRY.resolve_impl(kernel, spec_key=spec_key, impl=impl,
                                 record=record)


def attach_tuning_cache(cache) -> None:
    REGISTRY.attach_tuning_cache(cache)


def persist_winner(kernel: str, spec_key: str, impl: str,
                   tps: Optional[float] = None) -> None:
    REGISTRY.persist_winner(kernel, spec_key, impl, tps)


def stale_selections() -> List[dict]:
    return REGISTRY.stale_selections()
