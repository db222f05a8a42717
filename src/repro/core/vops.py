"""Vector ops the fused step needs, in forms the Pallas TPU kernel lowers.

The fused episode step runs inside a Pallas TPU kernel, whose lowering
has no gather or scatter on values and no 1-D iota, and cannot reduce a
1-D vector to a scalar when that vector came out of a table column.

Dynamic indexing: these helpers read
and write one element, row or column at a traced index with a
``where(iota == idx, ...)`` select, and reduce a selected row with a max
whose fill is the identity (-inf, or the integer minimum).  Every other
entry is replaced before the reduction, so a NaN elsewhere cannot leak
into the result — which multiplying by a one-hot mask would do
(``0 * NaN == NaN``).  The result is bitwise the indexed value,
including -0.0 and NaN, so the XLA reference scan that shares this code
stays bitwise-equal to the unfused step.

Reductions: :func:`vsum` sums a 1-D vector as a ``(n, 1)`` column, which
the kernel lowers whichever layout the vector has; on XLA it is the same
sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def iota(n: int) -> jnp.ndarray:
    """``arange(n)`` as int32, built from a 2-D iota (TPU needs >= 2-D)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).squeeze(-1)


def _fill(dtype):
    # A numpy scalar inlines as a literal; a captured jnp constant would be
    # rejected inside a Pallas kernel body.
    if jnp.issubdtype(dtype, jnp.floating):
        return np.array(-np.inf, dtype)
    return np.array(np.iinfo(dtype).min, dtype)


def take(vec: jnp.ndarray, i) -> jnp.ndarray:
    """``vec[i]`` for a traced scalar ``i`` (bool vectors: any-of-select)."""
    hot = iota(vec.shape[0]) == i
    if vec.dtype == jnp.bool_:
        return jnp.any(vec & hot)
    return jnp.max(jnp.where(hot, vec, _fill(vec.dtype)))


def take_row(mat: jnp.ndarray, i) -> jnp.ndarray:
    """``mat[i]`` for a traced row index ``i``."""
    hot = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 0) == i
    return jnp.max(jnp.where(hot, mat, _fill(mat.dtype)), axis=0)


def put_row(mat: jnp.ndarray, i, row) -> jnp.ndarray:
    """``mat.at[i].set(row)`` for a traced row index ``i``."""
    hot = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 0) == i
    return jnp.where(hot, jnp.broadcast_to(row, mat.shape), mat)


def take_col(mat: jnp.ndarray, j) -> jnp.ndarray:
    """``mat[:, j]`` for a traced column index ``j``."""
    hot = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 1) == j
    return jnp.max(jnp.where(hot, mat, _fill(mat.dtype)), axis=1)


def put_col(mat: jnp.ndarray, j, col) -> jnp.ndarray:
    """``mat.at[:, j].set(col)`` for a traced column index ``j``."""
    hot = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 1) == j
    return jnp.where(hot, jnp.broadcast_to(col[:, None], mat.shape), mat)


def vsum(vec: jnp.ndarray) -> jnp.ndarray:
    """``jnp.sum(vec)`` of a 1-D vector, reduced as an ``(n, 1)`` column."""
    return jnp.sum(vec[:, None])
