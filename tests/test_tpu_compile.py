"""The soc_step Pallas kernels compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described v5e:2x2
topology, which raises whatever the chip's compiler would raise (block
shapes, unsupported primitives, VMEM use).  The shapes are SoC3's
widths (16 accelerators, 4 memory tiles on a 5x5 NoC) with 12 thread
slots, the widest of the Fig. 9 set, under ``jax.vmap`` over a batch of
8 agents — the way the batched entry points reach the kernels.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.  Where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.soc_step import kernel as K
from repro.kernels.soc_step.ref import ServeCarry, tbl_width
from repro.soc import nn as socnn

B, T, N_TILES, N_ACCS, N_ACT, N_FEAT, N_STATES = 8, 12, 4, 16, 4, 9, 243
S = 723            # not a multiple of the 8-row block: a partial last block
N_REQ, QUEUE_CAP = 1024, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _episode_args(dev, faulted, n_consts):
    n_f = 4 + N_TILES + T + N_FEAT + 3 * N_ACT + (4 if faulted else 0)
    sds = lambda shape, dt: jax.ShapeDtypeStruct((B,) + shape, dt,
                                                 sharding=dev)
    return (sds((S, n_f), jnp.float32), sds((S, 5), jnp.int32),
            sds((n_consts,), jnp.float32),
            sds((N_STATES, N_ACT), jnp.float32),
            sds((4, N_ACCS), jnp.float32))


@pytest.mark.parametrize("ddr,gated,faulted", [
    (False, False, False), (True, True, False), (False, True, False),
    (True, False, False), (True, True, True)])
def test_episode_kernel_table_compiles_for_v5e(one_chip, ddr, gated,
                                               faulted):
    def ep(xf, xi, c, qt, ex):
        return K.soc_step_episode(
            xf, xi, c, qt, ex, n_threads=T, n_tiles=N_TILES,
            n_actions=N_ACT, ddr_attribution=ddr, gated=gated,
            faulted=faulted)

    _compile(ep, *_episode_args(one_chip, faulted, K.N_CONSTS))


@pytest.mark.parametrize("ddr,gated", [
    (False, False), (True, True), (False, True), (True, False)])
def test_episode_kernel_mlp_compiles_for_v5e(one_chip, ddr, gated):
    dims = socnn.mlp_dims(socnn.MLPConfig())
    wshape = socnn.pack_shape(dims)

    def ep(xf, xi, c, qt, ex, wp):
        return K.soc_step_episode(
            xf, xi, c, qt, ex, wp, n_threads=T, n_tiles=N_TILES,
            n_actions=N_ACT, ddr_attribution=ddr, gated=gated,
            mlp_dims=dims, mlp_feats="sense")

    args = _episode_args(one_chip, False, K.N_CONSTS + 2)
    wp = jax.ShapeDtypeStruct((B,) + wshape, jnp.float32, sharding=one_chip)
    _compile(ep, *args, wp)


@pytest.mark.parametrize("faulted", [False, True])
def test_serve_kernel_compiles_for_v5e(one_chip, faulted):
    n_f = 4 + N_TILES + N_ACCS + N_FEAT + 3 * N_ACT + (4 if faulted else 0)
    sds = lambda shape, dt: jax.ShapeDtypeStruct((B,) + shape, dt,
                                                 sharding=one_chip)
    f32, i32 = jnp.float32, jnp.int32
    carry = ServeCarry(
        qtable=sds((N_STATES, N_ACT), f32), extrema=sds((4, N_ACCS), f32),
        tbl=sds((N_ACCS, tbl_width(N_TILES)), f32),
        busy=sds((N_ACCS,), f32), fin=sds((N_ACCS, QUEUE_CAP), f32),
        head=sds((N_ACCS,), i32), pressure=sds((), f32),
        tripped=sds((), f32), step=sds((), i32))

    def serve(xf, xi, xv, c, carry0):
        return K.soc_step_serve(xf, xi, xv, c, carry0, n_tiles=N_TILES,
                                n_actions=N_ACT, ddr_attribution=False,
                                faulted=faulted)

    _compile(serve, sds((N_REQ, n_f), f32), sds((N_REQ, 5), i32),
             sds((N_REQ, 3), f32), sds((K.N_SERVE_CONSTS,), f32), carry)
