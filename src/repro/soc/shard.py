"""shard_map scale-out for the batched SoC trainer.

:class:`~repro.soc.vecenv.VecEnv` and
:class:`~repro.soc.stacked.StackedVecEnv` already batch (SoC lanes x
reward weights x seeds) with ``vmap`` inside one jitted call; this module
splits that batch across every available device with ``shard_map`` over
the 1-D lane mesh from :func:`repro.distributed.sharding.lane_mesh`.

The batch entries are fully independent (pure data parallelism, no
collectives), so each device runs the unmodified vmapped program on its
slice of the batch:

  * :func:`sharded_train_batched` shards ``VecEnv.train_batched`` over
    the agent axis B (reward-weight / seed pairs);
  * :func:`sharded_train_batched_stacked` shards
    ``StackedVecEnv.train_batched`` over the agent axis B of its (K, B)
    grid (the K SoC-lane parameters ride in the closure, so every device
    keeps all lanes and takes a slice of the agents);
  * :func:`sharded_episodes` shards ``StackedVecEnv.episodes`` over the
    policy axis N of its (K, N) spec grid.

Whenever the mesh has a single device the wrappers fall back to the
plain vmap call, which is bitwise-identical by construction.  On a mesh
of several devices the batch axis must divide the device count: the
wrappers raise rather than quietly run the whole batch on one device.
``force_shard_map=True`` runs
shard_map even on one device; that path recompiles the program under the
shard_map wrapper, so float leaves agree with vmap to roundoff (~1e-7,
XLA refuses in a different order) while integer state (visits, step
counters, modes) stays bitwise — the equivalence tests pin both.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import lane_mesh

__all__ = ["lane_mesh", "sharded_train_batched",
           "sharded_train_batched_stacked", "sharded_episodes",
           "sharded_serve"]


def _axis_spec(tree, axis: int | None):
    """P(None, ..., "lanes") at position ``axis`` for every leaf;
    ``axis=None`` replicates the whole tree (``P()``) — how scalar pytrees
    like a FaultSpec ride along without a batch axis."""
    spec = P() if axis is None else P(*([None] * axis + ["lanes"]))
    return jax.tree_util.tree_map(lambda _: spec, tree)


# jit cache for the shard_map wrappers: each public function builds a
# fresh ``run`` closure per call, which would defeat ``jax.jit``'s
# function-identity cache and recompile every invocation.  Entries key on
# the mesh devices, the axis layout and the *identities* of the closure
# constants (env, schedules, cfg, ...); holding strong references to those
# constants keeps their ids from being reused.
_JIT_CACHE: list = []


def _shard_call(fn, mesh: Mesh, args, in_axes, out_axis: int, consts=()):
    """shard_map ``fn`` with each arg split on its ``in_axes`` entry.

    ``out_specs`` comes from ``jax.eval_shape``, so any output pytree
    (QState, EpisodeResult, eval histories or none) shards on
    ``out_axis`` without the caller spelling out its structure.
    ``consts`` are the values ``fn`` closes over — two calls with
    identical consts reuse one jitted program (steady-state calls stop
    paying a retrace)."""
    mesh_key = tuple(d.id for d in mesh.devices.flat)
    for c, mk, ia, oa, jitted in _JIT_CACHE:
        if (mk == mesh_key and ia == in_axes and oa == out_axis
                and len(c) == len(consts)
                and all(a is b for a, b in zip(c, consts))):
            return jitted(*args)
    in_specs = tuple(_axis_spec(a, ax) for a, ax in zip(args, in_axes))
    out_specs = _axis_spec(jax.eval_shape(fn, *args), out_axis)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    jitted = jax.jit(sharded)
    _JIT_CACHE.append((tuple(consts), mesh_key, in_axes, out_axis, jitted))
    return jitted(*args)


def _use_mesh(mesh: Mesh | None, batch: int, force: bool):
    """Resolve the mesh; None means 'fall back to plain vmap'."""
    mesh = lane_mesh() if mesh is None else mesh
    n = int(mesh.devices.size)
    if n == 1:
        return mesh if force else None
    if batch % n != 0:
        raise ValueError(
            f"batch of {batch} does not divide over the {n}-device mesh; "
            f"pad it to a multiple of {n} or pass a smaller mesh")
    return mesh


def sharded_train_batched(env, train_apps, cfg, weights_batch, keys, *,
                          eval_app=None, faults=None,
                          mesh: Mesh | None = None,
                          force_shard_map: bool = False):
    """``VecEnv.train_batched`` with the B agents split across devices.

    Same signature and results as the method; ``mesh`` defaults to
    :func:`lane_mesh` over all devices.  Falls back to the plain vmap
    call when the mesh is a single device (unless ``force_shard_map``);
    on a larger mesh B must divide the device count.

    ``faults`` (a ``soc.faults.FaultSpec``) replicates to every device as
    a *traced* argument (``P()``), so sweeping fault intensities reuses
    one compiled program instead of retracing per spec value.
    """
    mesh = _use_mesh(mesh, int(keys.shape[0]), force_shard_map)
    if mesh is None:
        return env.train_batched(train_apps, cfg, weights_batch, keys,
                                 eval_app, faults)

    if faults is None:
        def run(w, k):
            return env.train_batched(train_apps, cfg, w, k, eval_app)

        return _shard_call(run, mesh, (weights_batch, keys), (0, 0), 0,
                           consts=(env, *train_apps, cfg, eval_app))

    def run(w, k, f):
        return env.train_batched(train_apps, cfg, w, k, eval_app, f)

    return _shard_call(run, mesh, (weights_batch, keys, faults),
                       (0, 0, None), 0,
                       consts=(env, *train_apps, cfg, eval_app, "faulted"))


def sharded_train_batched_stacked(env, stacked_iters, cfg, weights_batch,
                                  keys, *, eval_stacked=None, faults=None,
                                  mesh: Mesh | None = None,
                                  force_shard_map: bool = False):
    """``StackedVecEnv.train_batched`` with the B agents split across
    devices (keys are (K, B, 2); every device keeps all K lanes).
    ``faults`` replicates like in :func:`sharded_train_batched`.

    Under ``shard_map`` the method's own host preparation is traced into
    the program, so this entry point holds the per-call ``cohm.prep``
    (mesh and argument layout) and ``cohm.launch`` spans itself."""
    with jax.profiler.TraceAnnotation("cohm.prep"):
        mesh = _use_mesh(mesh, int(keys.shape[1]), force_shard_map)
        consts = (env, *stacked_iters, cfg, eval_stacked)
        if faults is None:
            args, in_axes = (weights_batch, keys), (0, 1)
        else:
            args, in_axes = (weights_batch, keys, faults), (0, 1, None)
            consts += ("faulted",)

        def run(w, k, *f):
            return env.train_batched(stacked_iters, cfg, w, k, eval_stacked,
                                     *f)

    if mesh is None:
        return env.train_batched(stacked_iters, cfg, weights_batch, keys,
                                 eval_stacked, faults)
    with jax.profiler.TraceAnnotation("cohm.launch"):
        return _shard_call(run, mesh, args, in_axes, 1, consts=consts)


def sharded_episodes(env, stacked, specs, cfg=None, keys=None, *,
                     mesh: Mesh | None = None,
                     force_shard_map: bool = False):
    """``StackedVecEnv.episodes`` with the N policies split across
    devices (specs are (K, N); every device keeps all K lanes)."""
    if keys is None:
        keys = env._default_keys(*specs.learned.shape)
    mesh = _use_mesh(mesh, int(specs.learned.shape[1]), force_shard_map)
    if mesh is None:
        return env.episodes(stacked, specs, cfg, keys)

    def run(sp, k):
        return env.episodes(stacked, sp, cfg, k)

    return _shard_call(run, mesh, (specs, keys), (1, 1), 1,
                       consts=(env, stacked, cfg))


def sharded_serve(env, stacked, specs, traffic, cfg=None, keys=None, *,
                  queue_cap: int = 8, n_requests: int = 1024,
                  mesh: Mesh | None = None,
                  force_shard_map: bool = False):
    """``StackedVecEnv.serve`` with the N policies split across devices
    (specs are (K, N); every device keeps all K lanes and the whole
    offered stream — the TrafficSpec replicates as a scalar pytree, the
    same ``P()`` protocol as a FaultSpec)."""
    if keys is None:
        keys = env._default_keys(*specs.learned.shape)

    def call(sp, k):
        return env.serve(stacked, sp, traffic, cfg, k,
                         queue_cap=queue_cap, n_requests=n_requests)

    mesh = _use_mesh(mesh, int(specs.learned.shape[1]), force_shard_map)
    if mesh is None:
        return call(specs, keys)

    return _shard_call(call, mesh, (specs, keys), (1, 1), 1,
                       consts=(env, stacked, cfg, traffic, queue_cap,
                               n_requests))
