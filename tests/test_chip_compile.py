"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

No chip is attached: the TPU compiler compiles for a described `v5e:2x2`
topology and refuses what the chip would refuse (unaligned slices, VMEM
over budget, programs past HBM) — which the `-interpret` kernels the rest
of the suite runs cannot show. Nothing executes, so these say nothing
about results or times.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and under xdist every worker imports
this file. Keep these tests in this one file so one worker loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from ggrs_tpu.models.ex_game import ExGame

T_BATCH = 60  # fused ticks per dispatch (chip_smoke phase A, bench)
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _struct(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _synctest_carry(game, check_distance, sharding):
    from ggrs_tpu.tpu import TpuSyncTestSession

    sess = TpuSyncTestSession(
        game, 2, check_distance, backend="xla", _defer_carry=True
    )

    def build():
        sess._build_initial_carry()
        return sess.carry

    return _struct(jax.eval_shape(build), sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _inputs(one_chip, t=T_BATCH):
    return jax.ShapeDtypeStruct((t, 2, 1), jnp.uint8, sharding=one_chip)


def test_whole_batch_synctest_kernel_compiles(one_chip):
    """The synctest_d8 cell's batch, and the coalesced program that runs K
    such batches per dispatch: a scan over them whose executable holds the
    kernel once."""
    from ggrs_tpu.tpu.pallas_core import PallasSyncTestCore
    from ggrs_tpu.tpu.sync_test import COALESCE_FRAMES, group_program

    game = ExGame(2, 4096)
    core = PallasSyncTestCore(game, 2, 8)
    carry = _synctest_carry(game, 8, one_chip)
    _compile_kernel(core.batch, carry, _inputs(one_chip))
    k = COALESCE_FRAMES // T_BATCH
    block = jax.ShapeDtypeStruct((k, T_BATCH, 2, 1), jnp.uint8,
                                 sharding=one_chip)
    group = group_program(jax.jit(core.batch))
    assert _compile_kernel(group, carry, block).as_text().count(KERNEL) == 1


def test_tiled_synctest_kernel_compiles(one_chip):
    from ggrs_tpu.tpu.pallas_tiled import PallasTiledSyncTestCore

    game = ExGame(2, 65536)
    core = PallasTiledSyncTestCore(game, 2, 8)
    _compile_kernel(core.batch, _synctest_carry(game, 8, one_chip),
                    _inputs(one_chip))


def test_sharded_tiled_synctest_compiles_loop_free(topo):
    """The four-chip SyncTest batch (the synctest_mesh4 benchmark cell's
    shape): the kernel and its psum, and a first-seen verdict with no
    device loop around them; and the coalesced program that runs K such
    batches per dispatch, which scans over them so that its executable
    holds the kernel once."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from ggrs_tpu.tpu.pallas_tiled import ShardedPallasTiledCore
    from ggrs_tpu.tpu.sync_test import COALESCE_FRAMES, group_program

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("beam", "entity"))
    game, d = ExGame(2, 13056), 16
    core = ShardedPallasTiledCore(game, 2, d, mesh)
    shapes = _synctest_carry(game, d, None)
    carry = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes,
        core._carry_specs(shapes),
    )
    inputs = jax.ShapeDtypeStruct(
        (T_BATCH, 2, 1), jnp.uint8,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )
    text = _compile_kernel(core.batch, carry, inputs).as_text()
    assert " while(" not in text
    k = COALESCE_FRAMES // T_BATCH
    block = jax.ShapeDtypeStruct(
        (k, T_BATCH, 2, 1), jnp.uint8,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )
    group = group_program(jax.jit(core.batch))
    assert _compile_kernel(group, carry, block).as_text().count(KERNEL) == 1


@pytest.mark.parametrize("rows", [1, 8])
def test_tick_kernel_compiles(one_chip, rows):
    from ggrs_tpu.tpu.pallas_resim import PallasTickCore
    from ggrs_tpu.tpu.resim import ResimCore

    core = ResimCore(ExGame(2, 4096), 8, 2, spec_backend="xla",
                     tick_backend="xla")
    packed = jax.ShapeDtypeStruct((rows, core._packed_len), jnp.int32,
                                  sharding=one_chip)
    _compile_kernel(PallasTickCore(core).tick_multi,
                    _struct(core.ring, one_chip),
                    _struct(core.state, one_chip), packed, {})


def test_beam_rollout_kernel_compiles(one_chip):
    from ggrs_tpu.tpu.pallas_beam import PallasBeamRollout

    game, beam, depth = ExGame(2, 65536), 12, 4
    rollout = PallasBeamRollout(game, 2, beam, max_rollout=depth)
    anchor = _struct(jax.eval_shape(game.init_state), one_chip)
    inputs = jax.ShapeDtypeStruct((beam, depth, 2, 1), jnp.uint8,
                                  sharding=one_chip)
    _compile_kernel(rollout.rollout, anchor, inputs)
