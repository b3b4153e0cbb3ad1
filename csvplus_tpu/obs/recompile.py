"""Compile/recompile accounting for the module-level jitted kernels.

The r06 diagnosis and the serving tier both converged on the same
invariant: a warm pass over an already-seen shape must lower NOTHING.
``PlanCache`` asserts it for plan shapes via its ``lowered`` counter;
this module extends it to every module-level kernel — the exact
functions whose eager predecessors caused the r05 warm-join regression.

Kernels are made at definition site by the one decorator that jits,
names and registers them::

    @register_kernel("join.pack_qk", static_argnames=("shifts",))
    def _pack_qk_kernel(...): ...

The name reaches the device twice.  The program is called
``jit_csvplus.join.pack_qk``, which is what a TPU profile prints on its
``XLA Modules`` line (an ``XLA Ops`` event carries no scope, PERF.md
§3), so device time is attributed by kernel.  And the body is traced
under ``jax.named_scope("csvplus.join.pack_qk")``, so every operation's
``op_name`` in the lowered text starts with it.  Both are static
strings fixed at import: nothing runs on the call path, and a second
call with seen shapes lowers nothing.  (The program's name is part of
the persistent compile cache's key; the scope, like all metadata, is
not.)

:func:`compile_counts` reads each registered function's jit-cache
entry count (``PjitFunction._cache_size`` — the number of distinct
lowerings jax holds for it).  A grown count between two snapshots IS a
(re)compile; :class:`RecompileWatch` packages the
snapshot/delta/assert-zero workflow the benches and tests use::

    with RecompileWatch() as w:
        ...warm passes...
    w.assert_zero()        # raises listing every kernel that lowered

``_cache_size`` is jax-private but present on the installed jax; a jax
that drops it fails here loudly rather than letting ``assert_zero``
pass on nothing.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict

_REGISTRY_LOCK = threading.Lock()
_KERNELS: Dict[str, Any] = {}


def register_kernel(name: str, **jit_kwargs) -> Callable:
    """Decorator: ``jax.jit`` the function (*jit_kwargs* are jit's, e.g.
    ``static_argnames``) as the program ``jit_csvplus.<name>`` with its
    body under ``jax.named_scope("csvplus.<name>")``, and register the
    jitted callable under *name* for compile-count accounting.  The
    wrapper runs at trace time only — zero call-path overhead."""
    import jax  # here, not at import: ``import csvplus_tpu`` stays jax-free

    from .span import journal_compiles

    journal_compiles()  # jax's compile events join the process journal
    scope = f"csvplus.{name}"

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        scoped.__name__ = scoped.__qualname__ = scope  # jit names the program after it
        with _REGISTRY_LOCK:
            jitted = _KERNELS[name] = jax.jit(scoped, **jit_kwargs)
        return jitted

    return deco


def registered_kernels() -> Dict[str, Any]:
    """Name -> jitted callable snapshot of the registry."""
    with _REGISTRY_LOCK:
        return dict(_KERNELS)


def compile_counts() -> Dict[str, int]:
    """Per-kernel count of distinct lowerings jax currently caches."""
    return {name: int(fn._cache_size()) for name, fn in registered_kernels().items()}


class RecompileWatch:
    """Asserts the zero-warm-recompiles invariant over a region.

    Snapshot on ``__enter__``; :meth:`delta` reports every kernel whose
    lowering count grew (plus the plan cache's ``lowered`` counter when
    one was passed); :meth:`assert_zero` raises ``AssertionError``
    naming the offenders.  Kernels registered *inside* the region count
    from zero — a brand-new kernel compiling in a warm region is a
    recompile by definition.
    """

    def __init__(self, plancache=None):
        self._plancache = plancache
        self._before: Dict[str, int] = {}
        self._plan_before = 0

    def __enter__(self) -> "RecompileWatch":
        self._before = compile_counts()
        if self._plancache is not None:
            self._plan_before = self._plancache.stats()["lowered"]
        return self

    def __exit__(self, *exc) -> None:
        pass

    def delta(self) -> Dict[str, int]:
        """Kernels (and ``plancache``) whose lowering count grew since
        ``__enter__``; empty dict == the invariant held."""
        out: Dict[str, int] = {}
        after = compile_counts()
        for name, n in after.items():
            base = self._before.get(name, 0)
            if n > base:
                out[name] = n - base
        if self._plancache is not None:
            grew = self._plancache.stats()["lowered"] - self._plan_before
            if grew > 0:
                out["plancache"] = grew
        return out

    def assert_zero(self, context: str = "warm pass") -> None:
        d = self.delta()
        if d:
            detail = ", ".join(f"{k}:+{v}" for k, v in sorted(d.items()))
            raise AssertionError(
                f"recompiles during {context}: {detail} — the zero-warm-"
                "recompiles invariant is broken (r06 regression shape)"
            )
