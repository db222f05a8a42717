"""Cohmeleon state space (paper Table 3).

A state is a 5-tuple of discretized attributes, each taking one of three
values, so |S| = 3^5 = 243.  The attributes capture a compact snapshot of
the SoC at invocation time:

  0. fully_coh_acc      — number of active fully-coherent accelerators
                          {0, 1, 2+}
  1. non_coh_per_tile   — avg number of non-coherent accelerators per memory
                          partition needed by this invocation {0, 1, 2+}
  2. to_llc_per_tile    — avg number of accelerators per LLC partition needed
                          by this invocation {0, 1, 2+}
  3. tile_footprint     — avg utilization of each needed cache-hierarchy
                          partition {<=L2, <=LLC slice, >LLC slice}
  4. acc_footprint      — memory footprint of this invocation
                          {<=L2, <=LLC slice, >LLC slice}

Everything here is pure-jnp and jit/vmap friendly: states are encoded as a
single int32 index into the Q-table.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.modes import CoherenceMode

N_ATTRS = 5
N_LEVELS = 3
N_STATES = N_LEVELS**N_ATTRS  # 243

ATTR_NAMES = (
    "fully_coh_acc",
    "non_coh_per_tile",
    "to_llc_per_tile",
    "tile_footprint",
    "acc_footprint",
)


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """Capacities needed to discretize footprints (bytes)."""

    l2_bytes: int
    llc_slice_bytes: int
    n_mem_tiles: int


def _bucket_count(x):
    """{0, 1, 2+} bucket for a count (works on scalars or arrays)."""
    return jnp.clip(jnp.asarray(x, jnp.int32), 0, 2)


def _bucket_footprint(bytes_, geom: CacheGeometry):
    """{<=L2, <=LLC slice, >LLC slice} bucket for a byte footprint."""
    b = jnp.asarray(bytes_, jnp.float64 if jnp.asarray(bytes_).dtype == jnp.float64 else jnp.float32)
    return jnp.where(
        b <= geom.l2_bytes,
        0,
        jnp.where(b <= geom.llc_slice_bytes, 1, 2),
    ).astype(jnp.int32)


def encode_attrs(attrs) -> jnp.ndarray:
    """Pack a length-5 attribute vector (each in [0,3)) into a state index."""
    attrs = jnp.asarray(attrs, jnp.int32)
    # Unrolled weighted sum: scalar literals only, so the encoding traces
    # without array constants (Pallas kernel bodies reject captured
    # device-array constants).
    out = attrs[..., 0]
    for i in range(1, N_ATTRS):
        out = out + attrs[..., i] * (N_LEVELS**i)
    return out


def decode_state(idx: int) -> tuple[int, ...]:
    """Inverse of :func:`encode_attrs` (host-side helper)."""
    out = []
    for _ in range(N_ATTRS):
        out.append(int(idx % N_LEVELS))
        idx //= N_LEVELS
    return tuple(out)


def observe(
    *,
    active_modes: jnp.ndarray,      # (max_accs,) int32 CoherenceMode, -1 = inactive
    active_footprints: jnp.ndarray,  # (max_accs,) float32 bytes, 0 = inactive
    needed_tiles: jnp.ndarray,       # (max_accs, n_tiles) bool — tiles each acc touches
    target_tiles: jnp.ndarray,       # (n_tiles,) bool — tiles this invocation needs
    target_footprint,                # scalar bytes of this invocation
    geom: CacheGeometry,
    active_fp_per_tile: jnp.ndarray | None = None,  # (max_accs,) bytes/tile
) -> jnp.ndarray:
    """Sense the SoC and return the encoded state index (paper §4.1 Sense).

    All inputs are fixed-size arrays so this function can live inside
    ``lax.scan``/``vmap`` in the vectorized environment.

    ``active_fp_per_tile`` optionally supplies each active slot's
    ``footprint / |needed tiles|`` precomputed (zero for inactive slots).
    A slot's value changes exactly when that slot issues a new invocation,
    so the vectorized environment caches it in its scan carry next to the
    (dram, llc) demand cache and skips the per-step row division here.
    Because the tile masks are exact {0, 1} factors, supplying the cached
    quantity is bitwise-identical to the recompute path.
    """
    active = active_modes >= 0

    fully_coh = jnp.sum(
        jnp.where(active & (active_modes == int(CoherenceMode.FULLY_COH)), 1, 0)
    )

    n_target_tiles = jnp.maximum(jnp.sum(target_tiles.astype(jnp.int32)), 1)

    # Per needed tile: how many active non-coherent accelerators touch it.
    non_coh_mask = active & (active_modes == int(CoherenceMode.NON_COH_DMA))
    per_tile_non_coh = jnp.sum(
        needed_tiles.astype(jnp.int32) * non_coh_mask.astype(jnp.int32)[:, None],
        axis=0,
    )
    avg_non_coh = (
        jnp.sum(jnp.where(target_tiles, per_tile_non_coh, 0)) / n_target_tiles
    )

    # Per needed tile: how many active accelerators route through its LLC
    # slice (all modes except non-coherent DMA).
    llc_mask = active & (active_modes != int(CoherenceMode.NON_COH_DMA))
    per_tile_llc = jnp.sum(
        needed_tiles.astype(jnp.int32) * llc_mask.astype(jnp.int32)[:, None],
        axis=0,
    )
    avg_llc = jnp.sum(jnp.where(target_tiles, per_tile_llc, 0)) / n_target_tiles

    # Average utilization (bytes of active data) of each needed partition.
    if active_fp_per_tile is None:
        active_fp_per_tile = (
            jnp.where(active, active_footprints, 0.0)
            / jnp.maximum(jnp.sum(needed_tiles, axis=-1), 1))
    per_tile_bytes = jnp.sum(
        needed_tiles.astype(jnp.float32) * active_fp_per_tile[:, None],
        axis=0,
    )
    avg_tile_bytes = (
        jnp.sum(jnp.where(target_tiles, per_tile_bytes, 0.0)) / n_target_tiles
    )

    attrs = jnp.stack(
        [
            _bucket_count(fully_coh),
            _bucket_count(jnp.round(avg_non_coh).astype(jnp.int32)),
            _bucket_count(jnp.round(avg_llc).astype(jnp.int32)),
            _bucket_footprint(avg_tile_bytes, geom),
            _bucket_footprint(target_footprint, geom),
        ]
    )
    return encode_attrs(attrs)


def observe_host(
    *,
    active_modes: Sequence[int],
    active_footprints: Sequence[float],
    needed_tiles: Sequence[Sequence[bool]],
    target_tiles: Sequence[bool],
    target_footprint: float,
    geom: CacheGeometry,
) -> int:
    """Host-side (numpy) convenience wrapper used by the discrete-event sim."""
    n_tiles = len(target_tiles)
    if len(active_modes) == 0:
        modes = np.full((1,), -1, np.int32)
        fps = np.zeros((1,), np.float32)
        tiles = np.zeros((1, n_tiles), bool)
    else:
        modes = np.asarray(active_modes, np.int32)
        fps = np.asarray(active_footprints, np.float32)
        tiles = np.asarray(needed_tiles, bool).reshape(len(active_modes), n_tiles)
    return int(
        observe(
            active_modes=jnp.asarray(modes),
            active_footprints=jnp.asarray(fps),
            needed_tiles=jnp.asarray(tiles),
            target_tiles=jnp.asarray(np.asarray(target_tiles, bool)),
            target_footprint=float(target_footprint),
            geom=geom,
        )
    )
