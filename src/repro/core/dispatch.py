"""Central kernel-dispatch registry for the unified GraphBLAS API.

Every compute path in the system — jnp word schemes (``repro.core.ops``),
their multi-device shard_map twins (``repro.core.ops_sharded``), Pallas
kernels (``repro.kernels.*.ops``), and the float-CSR baseline
(``repro.core.csr_backend``) — registers its implementations here at
import time, keyed by the full Table II/III coordinate:

    (op, rhs, out, backend, bucketed, masked, sharded)

  op        "mxv" | "mxm" | "mxm_sum" (the fused Σ mask ⊙ (A·B) reduction)
            | "mxv_pull" | "mxm_pull" (the direction-optimized pull
            traversal rows — masked-only, selected by
            ``Descriptor(direction="pull")``; DESIGN.md §12)
  rhs       operand kind of the right-hand side: "dense" | "bitvec" |
            "frontier" | "graph" | "tri" (the memoized lower-triangle pair)
            | "bitmat" (packed binarized activation matrix — the BitGNN
            bin·bin→full aggregation rows; DESIGN.md §15)
  out       "bin" (packed words) | "full" (dense values) — derived from
            the semiring: boolean ⊕.⊗ produces packed bits
  backend   "b2sr" | "b2sr_pallas" | "csr"
  bucketed  whether the SELL-style row-bucketed path is active
  masked    whether a §V output mask is applied
  sharded   whether the matrix is row-partitioned across a device mesh
            (``GraphMatrix.shard``): the row runs under ``jax.shard_map``
            over the stacked per-shard slabs (DESIGN.md §11)

``GraphMatrix`` resolves one entry per call instead of walking per-method
if/elif ladders; adding a backend or a Table row is a registration, not an
edit in seven methods (DESIGN.md §10).

Implementations have the uniform signature ``fn(g, rhs, call)`` where
``g`` is the GraphMatrix, ``rhs`` the raw right-hand operand (packed words
/ dense array / GraphMatrix / lower-triangle pair), and ``call`` an
:class:`OpCall` with the semiring and the normalized descriptor fields.
They return the *raw* result (words, grids, dense arrays); the generic
layer wraps it back into typed operands / GraphMatrix.

Backend modules are imported lazily on the first lookup for that backend,
so importing ``repro.core.graphblas`` does not pull in the Pallas stack.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import sys
import time
import warnings
from typing import (Any, Callable, Dict, Iterable, List, Optional, Tuple,
                    Union)

import jax.numpy as jnp

from repro.core.semiring import Semiring

Key = Tuple[str, str, str, str, bool, bool, bool]

#: op -> human-readable paper row, for docs and error messages
#: (DESIGN.md §10 carries the full Table II/III -> key mapping).
OPS = ("mxv", "mxm", "mxm_sum", "mxv_pull", "mxm_pull")

#: Ops whose rows exist only with a mask: pull *is* "scan my in-edges for
#: an unvisited-row parent" — without the visited mask it degenerates to
#: push, and mxm_sum is the fused masked reduction by definition. The
#: registry-completeness test exempts these from the full flag square.
MASKED_ONLY_OPS = ("mxm_sum", "mxv_pull", "mxm_pull")
RHS_KINDS = ("dense", "bitvec", "frontier", "graph", "tri", "bitmat")
OUT_KINDS = ("bin", "full")

_REGISTRY: Dict[Key, Callable] = {}

# Modules that register implementations for each backend, imported on the
# first resolve() against that backend (registration-at-import-time without
# eagerly importing the Pallas stack).
_BACKEND_MODULES: Dict[str, Tuple[str, ...]] = {
    "b2sr": ("repro.core.ops", "repro.core.ops_sharded"),
    "b2sr_pallas": (
        "repro.kernels.bmv.ops",
        "repro.kernels.spmm.ops",
        "repro.kernels.spgemm.ops",
        "repro.kernels.bmm.ops",
        "repro.core.ops_sharded",
    ),
    "csr": ("repro.core.csr_backend",),
}
_LOADED: set = set()

#: Dispatch counters: tests assert every public op resolves through here.
stats = {"resolves": 0}
last_key: Optional[Key] = None


class InjectedFault(RuntimeError):
    """A deterministic fault raised through the resolve hook.

    Stands in for a real kernel/backend failure (OOM, miscompiled Pallas
    kernel, device loss) so the serving layer's fallback and
    circuit-breaker behavior is testable without real GPU faults. Raised
    by the engine's :class:`~repro.engine.faults.FaultInjector` when it is
    installed via :func:`set_resolve_hook`.
    """


_RESOLVE_HOOK: Optional[Callable[[Key], None]] = None


def set_resolve_hook(hook: Optional[Callable[[Key], None]]
                     ) -> Optional[Callable[[Key], None]]:
    """Install (or clear, with ``None``) the resolve-time hook.

    The hook is called with the fully-specified key on every successful
    :func:`resolve` — i.e. at trace time for every kernel a plan bakes in
    — and may raise (typically :class:`InjectedFault`) to make that
    resolution fail exactly where a broken kernel would. Returns the
    previously installed hook so callers can restore it.
    """
    global _RESOLVE_HOOK
    prev = _RESOLVE_HOOK
    _RESOLVE_HOOK = hook
    return prev


def _run_resolve_hook(key: Key, t0: float) -> None:
    if _RESOLVE_HOOK is None:
        return
    try:
        _RESOLVE_HOOK(key)
    except BaseException as e:
        # the observe hook still sees the aborted resolution: injected
        # faults must land in the telemetry exactly like real ones
        _observe(key, t0, e)
        raise


#: key lists of the open :func:`recording` blocks
_RECORDINGS: List[List[Key]] = []


@contextlib.contextmanager
def recording():
    """Collect, in order, the key of every successful :func:`resolve`
    made inside the block (a plan's trace: the kernels it bakes in)."""
    keys: List[Key] = []
    _RECORDINGS.append(keys)
    try:
        yield keys
    finally:
        _RECORDINGS.remove(keys)


def replay(keys: Iterable[Key]) -> None:
    """Put recorded keys through the resolve hook again.

    A compiled plan resolves its kernels once, when it is traced; a warm
    plan calls this per launch, so an installed hook (fault injection)
    sees it as it would see a loop traced anew.
    """
    if _RESOLVE_HOOK is None:
        return
    for key in keys:
        _run_resolve_hook(key, time.perf_counter())


_OBSERVE_HOOK: Optional[Callable[[Key, float, Optional[BaseException]],
                                 None]] = None


def set_observe_hook(hook: Optional[Callable[[Key, float,
                                              Optional[BaseException]],
                                             None]]
                     ) -> Optional[Callable]:
    """Install (or clear) the read-only observe hook.

    The observability sibling of :func:`set_resolve_hook`: called as
    ``hook(key, duration_s, err)`` on **every** :func:`resolve` — whether
    it succeeded (``err is None``), the resolve hook aborted it (``err``
    is the raised exception, typically :class:`InjectedFault`), or the row
    was missing (``err`` is the :class:`NotImplementedError`).
    ``duration_s`` is the resolve wall time, lazy backend import included.

    Unlike the resolve hook it must never raise a control-flow exception:
    any exception it raises is swallowed — observation cannot change what
    executes. ``repro.obs`` installs a registry-counting default on
    import; returns the previously installed hook.
    """
    global _OBSERVE_HOOK
    prev = _OBSERVE_HOOK
    _OBSERVE_HOOK = hook
    return prev


def _observe(key: Key, t0: float, err: Optional[BaseException]) -> None:
    if _OBSERVE_HOOK is None:
        return
    try:
        _OBSERVE_HOOK(key, time.perf_counter() - t0, err)
    except Exception:                        # noqa: BLE001 — read-only hook
        pass


@dataclasses.dataclass
class OpCall:
    """The normalized per-call context handed to registered impls.

    ``mask`` is already in the row's raw form (packed words for packed
    outputs, a GraphMatrix for SpGEMM, a dense array for dense outputs) —
    the generic layer normalizes typed wrappers before dispatch.
    """

    semiring: Semiring
    mask: Any = None
    complement: bool = False
    row_chunk: Optional[int] = None
    a_value: float = 1.0
    out_dtype: Any = None


def _iter_flags(v: Union[bool, Iterable[bool]]) -> Tuple[bool, ...]:
    return (v,) if isinstance(v, bool) else tuple(v)


BOTH = (False, True)


def register(op: str, rhs: str, out: str, backend: str,
             bucketed: Union[bool, Iterable[bool]] = BOTH,
             masked: Union[bool, Iterable[bool]] = BOTH,
             sharded: Union[bool, Iterable[bool]] = False):
    """Decorator: register ``fn`` for every (bucketed, masked, sharded) combo.

    The flag params accept a bool or an iterable of bools; backends whose
    kernels take the mask as an argument register one function for both
    masked flags, backends with separate ``*_masked`` schemes register each
    flag separately. ``sharded`` defaults to False — single-device rows
    never see the flag; the shard_map twins in ``repro.core.ops_sharded``
    register with ``sharded=True``.
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
    if rhs not in RHS_KINDS:
        raise ValueError(f"unknown rhs kind {rhs!r}")
    if out not in OUT_KINDS:
        raise ValueError(f"unknown out kind {out!r}")

    def deco(fn: Callable) -> Callable:
        for b in _iter_flags(bucketed):
            for m in _iter_flags(masked):
                for s in _iter_flags(sharded):
                    key: Key = (op, rhs, out, backend, b, m, s)
                    if key in _REGISTRY:
                        raise ValueError(f"duplicate registration for {key}")
                    _REGISTRY[key] = fn
        return fn

    return deco


def _ensure_backend(backend: str) -> None:
    if backend in _LOADED:
        return
    for mod in _BACKEND_MODULES.get(backend, ()):
        importlib.import_module(mod)
    _LOADED.add(backend)


def resolve(op: str, rhs: str, out: str, backend: str, bucketed: bool,
            masked: bool, sharded: bool = False) -> Callable:
    """Look up the implementation for one fully-specified Table row."""
    global last_key
    t0 = time.perf_counter()
    key: Key = (op, rhs, out, backend, bucketed, masked, sharded)
    _ensure_backend(backend)
    fn = _REGISTRY.get(key)
    if fn is None:
        hint = (" (sharded rows exist only for the b2sr backends — "
                "call GraphMatrix.unshard() for this op)" if sharded else "")
        err = NotImplementedError(
            f"no kernel registered for op={op} rhs={rhs} out={out} "
            f"backend={backend} bucketed={bucketed} masked={masked} "
            f"sharded={sharded}{hint}; "
            f"registered rows: {sorted(k for k in _REGISTRY if k[0] == op)}")
        _observe(key, t0, err)
        raise err
    _run_resolve_hook(key, t0)
    _observe(key, t0, None)
    stats["resolves"] += 1
    last_key = key
    for keys in _RECORDINGS:
        keys.append(key)
    return fn


def registered_keys(load_all: bool = False) -> Tuple[Key, ...]:
    """All registered keys (optionally forcing every backend module in)."""
    if load_all:
        for backend in _BACKEND_MODULES:
            _ensure_backend(backend)
    return tuple(sorted(_REGISTRY))


def out_kind_for(semiring: Semiring, rhs: str) -> str:
    """Derive the Table-row output column from (semiring, operand kind).

    Boolean ⊕.⊗ over packed operands stays packed (bin·bin→bin); any other
    semiring — or a dense operand — produces full-precision output.
    """
    if semiring.name == "boolean" and rhs in ("bitvec", "frontier", "graph"):
        return "bin"
    return "full"


#: Semirings each (op, rhs) pair can honor. The "full" rows over packed
#: operands hard-code the plus-count / plus-times reduction, so any other
#: semiring must be rejected up front — never silently reinterpreted as
#: counts (dense-rhs mxv is the general-semiring row and accepts all).
SEMIRING_ROWS = {
    ("mxv", "bitvec"): ("boolean", "arithmetic"),
    ("mxm", "dense"): ("arithmetic",),
    ("mxm", "frontier"): ("boolean",),
    # bin·bin→full (BitGNN aggregation over binarized activations): the
    # popcount accumulation *is* the plus-and reduction — arithmetic only
    ("mxm", "bitmat"): ("arithmetic",),
    ("mxm", "graph"): ("boolean", "arithmetic"),
    # the pull rows are the boolean traversal only: early exit is "first
    # set bit wins", which no counting/min-plus reduction can honor
    ("mxv_pull", "bitvec"): ("boolean",),
    ("mxm_pull", "frontier"): ("boolean",),
}


def check_semiring(op: str, rhs: str, semiring: Semiring) -> None:
    """Reject semirings the resolved Table row cannot honor."""
    allowed = SEMIRING_ROWS.get((op, rhs))
    if allowed is not None and semiring.name not in allowed:
        raise NotImplementedError(
            f"{op} over a {rhs} operand supports only the {allowed} "
            f"semiring(s), got {semiring.name!r}")


def reject_sharded_row_chunk(op: str, row_chunk) -> None:
    """Raise on ``row_chunk`` + sharded *before* any operand staging.

    The sharded rows cannot honor chunked row evaluation — the row
    partition already bounds per-device memory — and their own backstop
    checks only fire inside the adapter, after the generic layer has
    staged operands for tracing. ``GraphMatrix.mxv``/``mxm``/``tri_count``
    call this first so the error is immediate and names the op.
    """
    if row_chunk is not None:
        raise ValueError(
            f"{op}: row_chunk is not supported on the sharded path — the "
            "row partition already bounds per-device memory (unshard() "
            "first if chunked evaluation is required)")


def apply_output_mask(y, mask, complement: bool, identity):
    """§V mask-at-store for dense outputs: masked-out entries → identity.

    The one shared post-mask used by every adapter whose scheme has no
    fused masked variant (jnp-bucketed, Pallas, CSR counts), so the mask
    semantics live in exactly one place.
    """
    keep = (mask == 0) if complement else (mask != 0)
    return jnp.where(keep, y, identity)


# ---------------------------------------------------------------------------
# Deprecation machinery for the legacy per-row method names
# ---------------------------------------------------------------------------

class GraphBLASDeprecationWarning(DeprecationWarning):
    """Raised (as a warning) by the legacy ``GraphMatrix`` method shims."""


def warn_deprecated(old: str, new: str) -> None:
    """Warn that a legacy method shim was called; *raise* for internal code.

    External callers get a :class:`GraphBLASDeprecationWarning` and the old
    behavior. Call sites inside ``repro.*`` raise instead — ``algorithms/``
    and ``engine/`` can never quietly regress onto the shims (the CI
    contract; see ISSUE 4 / DESIGN.md §10).
    """
    caller = sys._getframe(2).f_globals.get("__name__", "")
    msg = (f"GraphMatrix.{old} is deprecated; use {new} "
           f"(see DESIGN.md §10)")
    if caller.split(".", 1)[0] == "repro":
        raise RuntimeError(
            f"{msg} — repro-internal call sites must use the unified API "
            f"(called from {caller})")
    warnings.warn(msg, GraphBLASDeprecationWarning, stacklevel=3)
