"""Pallas TPU kernel: fused multi-column predicate mask.

Lowers a conjunction/disjunction of column equality tests — the device
form of ``Like``/``All``/``Any`` (csvplus.go:1243-1293) — into ONE pass
over VMEM-tiled code arrays: each grid step streams an (8, 128) int32
tile per referenced column from HBM into VMEM and emits the combined
boolean tile, so k-column predicates read each row exactly once instead
of materializing k intermediate masks.

XLA usually fuses the jnp formulation well on its own; this kernel exists
to (a) pin the fusion (no dependence on XLA heuristics for wide
predicates), and (b) serve as the Pallas integration point of the ops
layer — kernels take a jnp fallback, run in interpret mode on CPU CI,
and compiled on TPU.

Limitations: up to ``MAX_COLS`` equality terms per fused kernel (wider
predicates fall back to jnp); target codes are compile-time constants
(one cached executable per distinct predicate); rows padded to the
(8, 128) int32 tile.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

MAX_COLS = 8
_TILE = 8 * 128


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.jit, static_argnames=("mode", "targets", "interpret")
)
def _fused_mask_call(  # analysis: allow[JIT001] — arity fixed per pipeline shape
    mode: str,
    targets: "Tuple[Tuple[int, ...], ...]",
    interpret: bool,
    *codes: jax.Array,
) -> jax.Array:
    from jax.experimental import pallas as pl

    n_cols = len(targets)
    padded = codes[0].shape[0]
    rows = padded // 128

    def kernel(*refs):
        in_refs, out_ref = refs[:-1], refs[-1]
        acc = None
        for j, col_targets in enumerate(targets):
            tile = in_refs[j][:]  # each column streams exactly once
            eq = None
            for t in col_targets:  # IN-list membership per column
                e = tile == jnp.int32(t)
                eq = e if eq is None else (eq | e)
            acc = eq if acc is None else (acc & eq if mode == "all" else acc | eq)
        out_ref[:] = acc

    block = pl.BlockSpec((8, 128), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.bool_),
        grid=(rows // 8,),
        in_specs=[block] * n_cols,
        out_specs=block,
        interpret=interpret,
    )(*(c.reshape(rows, 128) for c in codes))
    return out.reshape(padded)


def fused_equality_mask(
    code_arrays: Sequence[jax.Array],
    target_codes: "Sequence[int] | Sequence[Sequence[int]]",
    nrows: int,
    mode: str = "all",
) -> "jax.Array | None":
    """Fused mask over up to MAX_COLS distinct columns.

    Each entry of *target_codes* is one target (or, in "any" mode, a
    LIST of targets — IN-list membership) for the matching code array;
    every column streams through VMEM exactly once regardless of how
    many values it is compared against.  Returns a bool[nrows] device
    array, or None when the predicate shape doesn't fit this kernel
    (caller uses the jnp path).  A kernel that fails to compile or run
    raises: it is an error, not a reason to take the other path.
    """
    k = len(code_arrays)
    if k == 0 or k > MAX_COLS or nrows == 0:
        return None
    norm = tuple(
        tuple(int(x) for x in t) if isinstance(t, (list, tuple)) else (int(t),)
        for t in target_codes
    )
    pad = (-nrows) % _TILE
    cols = []
    for c in code_arrays:
        c = c.astype(jnp.int32)
        if pad:
            # pad value -2 never equals a real code (-1 = absent, >=0 real)
            c = jnp.concatenate([c, jnp.full(pad, -2, dtype=jnp.int32)])
        cols.append(c)
    return _fused_mask_call(mode, norm, _use_interpret(), *cols)[:nrows]
