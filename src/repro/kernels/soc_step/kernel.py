"""Pallas kernel: a whole fused-step SoC episode as ONE kernel launch.

The grid is ``(S,)`` — one sequential grid step per invocation — and the
episode state (Q-table, reward extrema, packed thread-slot table) lives
in VMEM scratch, which persists across the sequential grid axis.  Each
grid step loads its scratch, runs
:func:`repro.kernels.soc_step.ref.fused_step` on the values (kernel and
reference share one step implementation, so they cannot drift), stores
the updated state back, and emits one packed trace row; the final
Q-table is written on the last grid step.

Compared to the ``lax.scan`` lowering, every per-step quantity the step
needs arrives as one row of a packed float input (VMEM) and one row of a
packed int input (SMEM, where the scalar unit reads indices and flags);
:func:`repro.kernels.soc_step.ref.pack_inputs` owns the layout.  So
observe's per-tile masked reductions and the Q-row select/blend/write-back
run over VMEM-resident state with no HBM round trip per step.  The TPU
lowering has no value gather or scatter, so the shared step indexes by
iota-compare selects (:mod:`repro.core.vops`).

``interpret=True`` executes the body with the Pallas interpreter — the
CPU test path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rewards
from repro.kernels.soc_step.ref import (SERVE_YCOLS, YCOLS, ServeCarry,
                                        ServeParams, derive_geom,
                                        fused_step, init_slot_table,
                                        serve_step, tbl_width,
                                        unpack_inputs)
from repro.soc.memsys import SoCStatic

N_STATIC = len(SoCStatic._fields)
# consts vector layout: the SoCStatic scalars, then learned, then (x, y, z).
N_CONSTS = N_STATIC + 4
# serving consts: the episode consts plus the ServeParams scalars.
N_SERVE_CONSTS = N_CONSTS + len(ServeParams._fields)

# Per-step rows (the packed inputs and the trace) travel in ROWS-row
# blocks: a TPU block's second-to-last dimension must be a multiple of 8
# or the whole array.  Grid step i works on row i % ROWS of block
# i // ROWS; consecutive steps share a block, so it is fetched and written
# back once per ROWS steps.  The last block may be partial.
ROWS = 8


def _rows(width: int, memory_space=None) -> pl.BlockSpec:
    return pl.BlockSpec((ROWS, width), lambda i: (i // ROWS, 0),
                        memory_space=memory_space)


def _whole(shape) -> pl.BlockSpec:
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _consts(consts):
    """Unpack the (1, n) SMEM consts row into (SoCStatic, learned, reward
    weights, the trailing scalars)."""
    c = [consts[0, j] for j in range(consts.shape[1])]
    weights = rewards.RewardWeights(*c[N_STATIC + 1:N_CONSTS])
    return (SoCStatic(*c[:N_STATIC]), c[N_STATIC] != 0.0, weights,
            c[N_CONSTS:])


def _row_inputs(xf, xi, r, **kw):
    """Row ``r`` of the current float (VMEM) and int (SMEM) row blocks."""
    return unpack_inputs(xf[pl.ds(r, 1), :][0],
                         [xi[r, k] for k in range(xi.shape[1])], **kw)


def _episode_kernel(*refs, n_steps: int, n_tiles: int, n_threads: int,
                    n_actions: int, ddr_attribution: bool, gated: bool,
                    faulted: bool, mlp_dims, mlp_feats: str):
    # ``mlp_dims`` (static) selects the ref layout: the MLP variant adds
    # a packed-weights input, output and VMEM scratch (the weights
    # persist across the sequential grid exactly like the Q-table).
    if mlp_dims is None:
        (xf, xi, consts, qt0, ex0, y_out, qt_out, qt, ex, tbl) = refs
        wp0 = wp_out = wp = None
    else:
        (xf, xi, consts, qt0, ex0, wp0,
         y_out, qt_out, wp_out, qt, ex, tbl, wp) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        qt[...] = qt0[...]
        ex[...] = ex0[...]
        tbl[...] = init_slot_table(n_threads, n_tiles)
        if wp is not None:
            wp[...] = wp0[...]

    s, learned, weights, extra = _consts(consts)
    geom, warm_cap = derive_geom(s)
    r = i % ROWS
    x = _row_inputs(xf, xi, r, n_tiles=n_tiles, n_threads=n_threads,
                    n_actions=n_actions, faulted=faulted)

    if mlp_dims is None:
        qtable_new, rs_new, tbl_new, y = fused_step(
            s, geom, warm_cap, learned, weights, qt[...],
            rewards.RewardState(extrema=ex[...]), tbl[...], x,
            ddr_attribution=ddr_attribution, gated=gated)
        wp_new = None
    else:
        qfun = extra[0] != 0.0
        mlp_lr = extra[1]
        qtable_new, rs_new, tbl_new, wp_new, y = fused_step(
            s, geom, warm_cap, learned, weights, qt[...],
            rewards.RewardState(extrema=ex[...]), tbl[...], x,
            ddr_attribution=ddr_attribution, gated=gated, wpack=wp[...],
            qfun=qfun, mlp_lr=mlp_lr, mlp_dims=mlp_dims,
            mlp_feats=mlp_feats)
        wp[...] = wp_new

    qt[...] = qtable_new
    ex[...] = rs_new.extrema
    tbl[...] = tbl_new
    y_out[pl.ds(r, 1), :] = y[None, :]

    @pl.when(i == n_steps - 1)
    def _finish():
        qt_out[...] = qtable_new
        if wp_out is not None:
            wp_out[...] = wp_new


@functools.partial(
    jax.jit,
    static_argnames=("n_threads", "n_tiles", "n_actions",
                     "ddr_attribution", "gated", "faulted", "interpret",
                     "mlp_dims", "mlp_feats"))
def soc_step_episode(xf, xi, consts, qtable0, extrema0, wpack0=None, *,
                     n_threads: int, n_tiles: int, n_actions: int,
                     ddr_attribution: bool = False, gated: bool = False,
                     faulted: bool = False, interpret: bool = False,
                     mlp_dims=None, mlp_feats: str = "sense"):
    """Run the packed episode through the Pallas kernel.

    ``xf (S, NF)`` f32 / ``xi (S, 5)`` i32 are the packed per-step input
    rows from :func:`~repro.kernels.soc_step.ref.pack_inputs`; ``consts
    (N_CONSTS,)`` f32 is the SoCStatic scalars + learned + reward
    weights.  ``faulted`` says whether ``xf`` carries the four trailing
    fault columns (the row width flows through ``xf.shape`` either way).
    Returns ``(qtable_final, y (S, 6))`` with ``y`` columns
    :data:`~repro.kernels.soc_step.ref.YCOLS`.

    The function-approximation variant (``wpack0`` + static ``mlp_dims``
    tuple / ``mlp_feats`` embedding name, :mod:`repro.soc.nn`) appends
    ``[qfun, mlp_lr]`` to ``consts`` (width ``N_CONSTS + 2``), keeps the
    packed weights VMEM-resident across the grid like the Q-table, and
    returns ``(qtable_final, wpack_final, y)``.
    """
    n_steps, n_f = xf.shape
    n_i = xi.shape[1]
    n_states, _ = qtable0.shape
    n_accs = extrema0.shape[1]

    in_specs = [
        _rows(n_f), _rows(n_i, pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        _whole((n_states, n_actions)), _whole((4, n_accs)),
    ]
    operands = [xf, xi, consts[None, :], qtable0, extrema0]
    out_specs = [_rows(len(YCOLS)), _whole((n_states, n_actions))]
    out_shape = [
        jax.ShapeDtypeStruct((n_steps, len(YCOLS)), jnp.float32),
        jax.ShapeDtypeStruct((n_states, n_actions), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((n_states, n_actions), jnp.float32),       # Q-table
        pltpu.VMEM((4, n_accs), jnp.float32),                 # extrema
        pltpu.VMEM((n_threads, tbl_width(n_tiles)), jnp.float32),
    ]
    if mlp_dims is not None:
        wshape = wpack0.shape
        in_specs.append(_whole(wshape))
        operands.append(wpack0.astype(jnp.float32))
        out_specs.append(_whole(wshape))
        out_shape.append(jax.ShapeDtypeStruct(wshape, jnp.float32))
        scratch_shapes.append(pltpu.VMEM(wshape, jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_episode_kernel, n_steps=n_steps,
                          n_tiles=n_tiles, n_threads=n_threads,
                          n_actions=n_actions,
                          ddr_attribution=ddr_attribution, gated=gated,
                          faulted=faulted, mlp_dims=mlp_dims,
                          mlp_feats=mlp_feats),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*operands)
    if mlp_dims is None:
        y, qtable = outs
        return qtable, y
    y, qtable, wpack = outs
    return qtable, wpack, y


def _serve_kernel(xf, xi, xv, consts, qt0, ex0, tbl0, busy0, fin0, head0,
                  misc0, st0,
                  y_out, qt_out, ex_out, tbl_out, busy_out, fin_out,
                  head_out, misc_out, st_out,
                  qt, ex, tbl, busy, fin, head, misc, sti,
                  *, n_steps: int, n_tiles: int, n_accs: int,
                  n_actions: int, ddr_attribution: bool, faulted: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        qt[...] = qt0[...]
        ex[...] = ex0[...]
        tbl[...] = tbl0[...]
        busy[...] = busy0[...]
        fin[...] = fin0[...]
        head[...] = head0[...]
        misc[...] = misc0[...]
        sti[...] = st0[...]

    s, learned, weights, extra = _consts(consts)
    sp = ServeParams(*extra)
    geom, warm_cap = derive_geom(s)

    # Serving slots are accelerators, so the packed row's placeholder
    # others column has width n_accs (serve_step overwrites it anyway).
    r = i % ROWS
    x = _row_inputs(xf, xi, r, n_tiles=n_tiles, n_threads=n_accs,
                    n_actions=n_actions, faulted=faulted)
    v = [xv[r, k] for k in range(xv.shape[1])]

    carry = ServeCarry(
        qtable=qt[...], extrema=ex[...], tbl=tbl[...], busy=busy[...][0],
        fin=fin[...], head=head[...][0], pressure=misc[...][0, 0],
        tripped=misc[...][0, 1], step=sti[...][0, 0])
    carry, y = serve_step(s, geom, warm_cap, learned, weights, sp, carry,
                          x, v[0], v[1], v[2],
                          ddr_attribution=ddr_attribution)

    qt[...] = carry.qtable
    ex[...] = carry.extrema
    tbl[...] = carry.tbl
    busy[...] = carry.busy[None, :]
    fin[...] = carry.fin
    head[...] = carry.head[None, :]
    misc[...] = jnp.stack([carry.pressure, carry.tripped]).reshape(1, 2)
    sti[...] = carry.step.reshape(1, 1)
    y_out[pl.ds(r, 1), :] = y[None, :]

    @pl.when(i == n_steps - 1)
    def _finish():
        qt_out[...] = carry.qtable
        ex_out[...] = carry.extrema
        tbl_out[...] = carry.tbl
        busy_out[...] = carry.busy[None, :]
        fin_out[...] = carry.fin
        head_out[...] = carry.head[None, :]
        misc_out[...] = jnp.stack([carry.pressure,
                                   carry.tripped]).reshape(1, 2)
        st_out[...] = carry.step.reshape(1, 1)


@functools.partial(
    jax.jit,
    static_argnames=("n_tiles", "n_actions", "ddr_attribution", "faulted",
                     "interpret"))
def soc_step_serve(xf, xi, xv, consts, carry0: ServeCarry, *,
                   n_tiles: int, n_actions: int,
                   ddr_attribution: bool = False, faulted: bool = False,
                   interpret: bool = False):
    """Run a packed arrival-stream chunk through the Pallas serve kernel.

    Same launch shape as :func:`soc_step_episode` — grid ``(S,)``, one
    sequential step per offered request, all serving state VMEM-resident —
    but the whole :class:`~repro.kernels.soc_step.ref.ServeCarry` rides
    as kernel inputs/outputs so chunks (and checkpoint restores) chain
    bitwise.  ``xv (S, 3)`` f32 carries ``[t_arr, deadline, priority]``;
    ``consts (N_SERVE_CONSTS,)`` appends the ServeParams scalars to the
    episode consts.  Returns ``(carry_final, y (S, len(SERVE_YCOLS)))``.
    """
    n_steps, n_f = xf.shape
    n_i = xi.shape[1]
    n_states, _ = qt_shape = carry0.qtable.shape
    n_accs = carry0.busy.shape[0]
    queue_cap = carry0.fin.shape[-1]

    carry_specs = [
        _whole(qt_shape), _whole((4, n_accs)),
        _whole((n_accs, tbl_width(n_tiles))), _whole((1, n_accs)),
        _whole((n_accs, queue_cap)), _whole((1, n_accs)), _whole((1, 2)),
        _whole((1, 1)),
    ]
    carry_shapes = [
        jax.ShapeDtypeStruct(qt_shape, jnp.float32),
        jax.ShapeDtypeStruct((4, n_accs), jnp.float32),
        jax.ShapeDtypeStruct((n_accs, tbl_width(n_tiles)), jnp.float32),
        jax.ShapeDtypeStruct((1, n_accs), jnp.float32),
        jax.ShapeDtypeStruct((n_accs, queue_cap), jnp.float32),
        jax.ShapeDtypeStruct((1, n_accs), jnp.int32),
        jax.ShapeDtypeStruct((1, 2), jnp.float32),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    ]
    outs = pl.pallas_call(
        functools.partial(_serve_kernel, n_steps=n_steps, n_tiles=n_tiles,
                          n_accs=n_accs, n_actions=n_actions,
                          ddr_attribution=ddr_attribution,
                          faulted=faulted),
        grid=(n_steps,),
        in_specs=[_rows(n_f), _rows(n_i, pltpu.SMEM),
                  _rows(3, pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)] + carry_specs,
        out_specs=[_rows(len(SERVE_YCOLS))] + carry_specs,
        out_shape=[jax.ShapeDtypeStruct((n_steps, len(SERVE_YCOLS)),
                                        jnp.float32)] + carry_shapes,
        scratch_shapes=[
            pltpu.VMEM(qt_shape, jnp.float32),
            pltpu.VMEM((4, n_accs), jnp.float32),
            pltpu.VMEM((n_accs, tbl_width(n_tiles)), jnp.float32),
            pltpu.VMEM((1, n_accs), jnp.float32),
            pltpu.VMEM((n_accs, queue_cap), jnp.float32),
            pltpu.VMEM((1, n_accs), jnp.int32),
            pltpu.VMEM((1, 2), jnp.float32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xf, xi, xv, consts[None, :], carry0.qtable, carry0.extrema,
      carry0.tbl,
      carry0.busy.reshape(1, n_accs), carry0.fin,
      carry0.head.reshape(1, n_accs),
      jnp.stack([carry0.pressure, carry0.tripped]).reshape(1, 2),
      carry0.step.reshape(1, 1))
    y, qt, ex, tbl, busy, fin, head, misc, st = outs
    carry = ServeCarry(
        qtable=qt, extrema=ex, tbl=tbl, busy=busy.reshape(n_accs),
        fin=fin, head=head.reshape(n_accs), pressure=misc[0, 0],
        tripped=misc[0, 1], step=st[0, 0])
    return carry, y
