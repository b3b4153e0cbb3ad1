"""The flagship workload: 3-way lookup join as one fused device step.

Reference call stack being replaced (SURVEY.md §3.3): per orders row, two
host binary searches with per-comparison map lookups + two map merges
(csvplus.go:552-583).  Here the whole thing is ONE jit-compiled step over
dictionary codes:

* both build sides (customers, products) are unique indexes, so each
  stream row matches at most one build row — the output is statically
  shaped ``(n_orders,)`` and the entire step (two vectorized binary
  searches + validity mask) fuses on device;
* the probe keys are the orders' key columns pre-translated into each
  index's dictionary space (host translation table + device gather at
  build time);
* sharded mode lays the orders out row-sharded over a 1-D mesh and
  replicates the (small) key arrays — XLA runs the step data-parallel
  with no collectives in the hot loop; the partitioned all-to-all path
  (:mod:`..parallel.pjoin`) covers build sides too large to replicate.

``threeway_step`` is the jittable "forward step" exposed through
``__graft_entry__.entry()``; the join itself runs through the plan path
(``orders.join(cust, ...).join(prod, ...)``, :mod:`..ops.join`).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


@jax.jit
def threeway_step(
    cust_keys: jax.Array,  # sorted unique customer key codes
    prod_keys: jax.Array,  # sorted unique product key codes
    qk_cust: jax.Array,  # orders' cust key, translated codes (-1 = miss)
    qk_prod: jax.Array,  # orders' prod key, translated codes
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused probe step: (cust row id, prod row id, valid mask)."""
    lo_c = jnp.searchsorted(cust_keys, qk_cust, side="left")
    lo_c = jnp.minimum(lo_c, cust_keys.shape[0] - 1)
    hit_c = (jnp.take(cust_keys, lo_c, axis=0) == qk_cust) & (qk_cust >= 0)

    lo_p = jnp.searchsorted(prod_keys, qk_prod, side="left")
    lo_p = jnp.minimum(lo_p, prod_keys.shape[0] - 1)
    hit_p = (jnp.take(prod_keys, lo_p, axis=0) == qk_prod) & (qk_prod >= 0)

    valid = hit_c & hit_p
    return lo_c.astype(jnp.int32), lo_p.astype(jnp.int32), valid


def example_step_args(n_orders: int = 4096, n_cust: int = 512, n_prod: int = 64):
    """Deterministic small example inputs for compile checks."""
    cust_keys = jnp.arange(n_cust, dtype=jnp.int32)
    prod_keys = jnp.arange(n_prod, dtype=jnp.int32)
    qk_c = jnp.arange(n_orders, dtype=jnp.int32) % (n_cust + 7) - 3
    qk_p = jnp.arange(n_orders, dtype=jnp.int32) % (n_prod + 3) - 1
    return cust_keys, prod_keys, qk_c, qk_p
