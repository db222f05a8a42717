"""Public entry point for the fused SoC episode step.

:func:`fused_episode` is what :mod:`repro.soc.vecenv` calls when built
with ``fused_step=True``: it takes the precomputed :class:`~repro.kernels.
soc_step.ref.StepInputs` trace of an episode plus the initial Q-table /
reward extrema and returns the trained table and the per-step trace.

Dispatch follows the suite's ``interpret=None -> cpu`` auto-detection
convention (see ``flash_attention.ops``), with one extra knob because
this kernel's sequential grid only pays off where VMEM scratch is real:

  * ``kernel=None`` (default) lowers through the Pallas kernel on
    accelerator backends and through the pure-XLA
    :func:`~repro.kernels.soc_step.ref.episode_ref` scan on CPU — the
    same fused formulation, compiled the way each backend runs it best
    (the interpreted Pallas body is a correctness tool, not a fast path);
  * ``kernel=True`` forces the Pallas kernel; ``interpret=None`` then
    auto-enables the interpreter on CPU, which is how the kernel-vs-ref
    tests execute the kernel body without a TPU.

The batched entry points choose the lowering from the size of the call
(:func:`episode_kernel`): the kernel's grid walks a vmapped batch's
episodes one after another, the scan steps them side by side, so a call
of :data:`SCAN_MIN_EPISODES` or more episodes takes the scan on every
platform.

Both lowerings share :func:`~repro.kernels.soc_step.ref.fused_step` and
the :func:`~repro.kernels.soc_step.ref.pack_inputs` row layout, so they
agree to float tolerance by construction (bitwise on CPU).  Both entry
points run under the ``cohm_step`` name scope: the step keeps one name
in the compiled program whichever lowering carries it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.soc.memsys import SoCStatic
from repro.kernels.soc_step import kernel as _kernel
from repro.kernels.soc_step.ref import (StepInputs, episode_ref,
                                        pack_inputs, unpack_ys)


# Episodes per call from which the XLA scan beats the Pallas kernel on a
# TPU v5e (crossover table in PERF.md section 6).
SCAN_MIN_EPISODES = 8


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def episode_kernel(n_episodes: int) -> bool:
    """Whether a call running ``n_episodes`` episodes at once lowers them
    through the Pallas kernel: below :data:`SCAN_MIN_EPISODES` on an
    accelerator, never on the CPU."""
    return n_episodes < SCAN_MIN_EPISODES and not _on_cpu()


def fused_episode(s: SoCStatic, learned, weights, qtable0, extrema0,
                  xs: StepInputs, *, ddr_attribution: bool = False,
                  gated: bool = False, kernel: bool | None = None,
                  interpret: bool | None = None, qfun=None, mlp=None):
    """Run one fused episode; returns ``(qtable_final, ys)``.

    ``xs`` leaves carry a leading (S,) axis (see :class:`StepInputs`);
    ``ys`` is the per-step ``(mode, state_idx, action, exec_cycles,
    offchip, reward)`` tuple with integer columns as int32.

    With a function-approximation agent (``mlp`` — a
    :class:`repro.soc.nn.MLPQState` — plus the spec's traced ``qfun``
    flag) the packed weights ride the episode next to the Q-table and
    the return becomes ``(qtable_final, wpack_final, ys)``.  Both
    lowerings support it: the XLA scan scans the weights in the carry;
    the Pallas kernel adds a VMEM-resident weights operand and appends
    ``[qfun, mlp_lr]`` to the consts row.
    """
    with jax.named_scope("cohm_step"):
        mlp_dims = None
        if mlp is not None:
            from repro.soc import nn as socnn
            mlp_dims = socnn.mlp_dims(mlp.cfg)
        if kernel is None:
            kernel = not _on_cpu()
        if not kernel:
            if mlp is None:
                qtable, ys = episode_ref(
                    s, learned, weights, qtable0, extrema0, xs,
                    ddr_attribution=ddr_attribution, gated=gated)
                return qtable, ys
            return episode_ref(
                s, learned, weights, qtable0, extrema0, xs,
                ddr_attribution=ddr_attribution, gated=gated,
                wpack0=mlp.wpack, qfun=qfun, mlp_lr=mlp.lr,
                mlp_dims=mlp_dims, mlp_feats=mlp.cfg.features)
        if interpret is None:
            interpret = _on_cpu()

        f32 = jnp.float32
        xf, xi = pack_inputs(xs)
        consts_parts = [
            jnp.stack([jnp.asarray(getattr(s, f), f32)
                       for f in SoCStatic._fields]),
            jnp.stack([jnp.asarray(learned, f32),
                       jnp.asarray(weights.x, f32),
                       jnp.asarray(weights.y, f32),
                       jnp.asarray(weights.z, f32)]),
        ]
        if mlp is not None:
            consts_parts.append(jnp.stack([jnp.asarray(qfun, f32),
                                           jnp.asarray(mlp.lr, f32)]))
        consts = jnp.concatenate(consts_parts)
        out = _kernel.soc_step_episode(
            xf, xi, consts, qtable0.astype(f32), extrema0.astype(f32),
            mlp.wpack if mlp is not None else None,
            n_threads=xs.others.shape[-1], n_tiles=xs.tiles.shape[-1],
            n_actions=xs.avail.shape[-1],
            ddr_attribution=ddr_attribution, gated=gated,
            faulted=xs.f_exec is not None,
            interpret=interpret, mlp_dims=mlp_dims,
            mlp_feats=mlp.cfg.features if mlp is not None else "sense")
        if mlp is None:
            qtable, y = out
            return qtable, unpack_ys(y)
        qtable, wpack, y = out
        return qtable, wpack, unpack_ys(y)


def fused_serve_episode(s: SoCStatic, learned, weights, serve_params,
                        carry0, xs: StepInputs, t_arr, deadline, priority,
                        *, ddr_attribution: bool = False,
                        kernel: bool | None = None,
                        interpret: bool | None = None,
                        qfun=None, mlp=None):
    """Run one arrival-stream chunk through the fused serving step.

    Dispatch mirrors :func:`fused_episode`: the Pallas serve kernel on
    accelerator backends, the ``serve_episode_ref`` scan on CPU, and
    ``kernel=True, interpret=None`` for the interpreted kernel-vs-ref
    test path.  ``xs`` is a (n_requests,)-leading :class:`StepInputs`
    whose ``thread``/``fresh``/``others``/``valid``/``eps``/``alpha``
    columns are placeholders (the serve step owns them — see
    :func:`~repro.kernels.soc_step.ref.serve_step`); ``carry0`` is a
    :class:`~repro.kernels.soc_step.ref.ServeCarry`.  Returns
    ``(carry_final, ys (n_requests, len(SERVE_YCOLS)))``.
    """
    with jax.named_scope("cohm_step"):
        from repro.kernels.soc_step.ref import serve_episode_ref

        if mlp is not None:
            # nn-policy serving always takes the XLA scan: the serve kernel
            # does not carry the weight pack (serving is admission-bound and
            # CPU CI must never compile the kernel), and the MLP weights ride
            # ``carry0.wpack`` so chunking/checkpointing work unchanged.
            from repro.soc import nn as socnn
            return serve_episode_ref(
                s, learned, weights, serve_params, carry0, xs, t_arr, deadline,
                priority, ddr_attribution=ddr_attribution, qfun=qfun,
                mlp_lr=mlp.lr, mlp_dims=socnn.mlp_dims(mlp.cfg),
                mlp_feats=mlp.cfg.features)
        if kernel is None:
            kernel = not _on_cpu()
        if not kernel:
            return serve_episode_ref(
                s, learned, weights, serve_params, carry0, xs, t_arr, deadline,
                priority, ddr_attribution=ddr_attribution)
        if interpret is None:
            interpret = _on_cpu()

        f32 = jnp.float32
        xf, xi = pack_inputs(xs)
        xv = jnp.stack([jnp.asarray(t_arr, f32), jnp.asarray(deadline, f32),
                        jnp.asarray(priority, f32)], axis=-1)
        consts = jnp.concatenate([
            jnp.stack([jnp.asarray(getattr(s, f), f32)
                       for f in SoCStatic._fields]),
            jnp.stack([jnp.asarray(learned, f32),
                       jnp.asarray(weights.x, f32),
                       jnp.asarray(weights.y, f32),
                       jnp.asarray(weights.z, f32)]),
            jnp.stack([jnp.asarray(getattr(serve_params, f), f32)
                       for f in type(serve_params)._fields]),
        ])
        return _kernel.soc_step_serve(
            xf, xi, xv, consts, carry0,
            n_tiles=xs.tiles.shape[-1], n_actions=xs.avail.shape[-1],
            ddr_attribution=ddr_attribution, faulted=xs.f_exec is not None,
            interpret=interpret)
