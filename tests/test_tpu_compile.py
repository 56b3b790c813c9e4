"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the fused copyscore kernel at the engine's tile and chunk widths,
and the shard_mapped tile scan on one- and four-chip meshes. What Mosaic or
XLA would refuse on the chip fails here, without one.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.distributed import _sharded_tile_fn, _sharded_tile_fn_2d
from repro.kernels.copyscore import copyscore_fused_pallas
from repro.kernels.vote import vote_fused_pallas

TILE = 256          # EngineOptions.tile
BLOCK = 128         # pair block the engine picks for a 256 tile
CHUNK = 512         # DEFAULT_CHUNK_ENTRIES: one kernel entry block per chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("K", [1, 2, 8])
def test_fused_kernel_compiles_for_v5e(topo, K):
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    fn = jax.jit(lambda v, vc, p, a, ac, d, m: copyscore_fused_pallas(
        v, p, a, v_cols=vc, acc_cols=ac, delta_blk=d, nout_blk=m, s=0.8,
        n_false=50.0, block_i=BLOCK, block_j=BLOCK, block_e=CHUNK))
    compiled = fn.lower(
        sds((TILE, K * CHUNK), jnp.int8), sds((TILE, K * CHUNK), jnp.int8),
        sds((K,), jnp.float32), sds((TILE,), jnp.float32),
        sds((TILE,), jnp.float32), sds((K,), jnp.float32),
        sds((K,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", [(1,), (4,), (2, 2)],
                         ids=["1chip", "4chips", "2x2"])
def test_tile_scan_compiles_with_pallas_kernel(topo, layout):
    devs = np.array(topo.devices[:int(np.prod(layout))]).reshape(layout)
    if len(layout) == 1:
        mesh = Mesh(devs, ("shards",))
        fn = _sharded_tile_fn(mesh, TILE, 0.8, 50.0, "pallas", BLOCK, BLOCK)
        v_spec, s_spec, t_spec = P(), P(), P("shards")
    else:
        mesh = Mesh(devs, ("data", "pod"))
        fn = _sharded_tile_fn_2d(mesh, TILE, 0.8, 50.0, "pallas", BLOCK,
                                 BLOCK)
        v_spec, s_spec, t_spec = P(None, "pod", None), P("pod"), P("data")
    S_pad, K, n_tiles = 13 * TILE, 4, 24   # Book-full rows, 4-chunk groups

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    compiled = fn.lower(
        sds((S_pad, K, CHUNK), jnp.int8, v_spec), sds((S_pad,), jnp.float32),
        sds((K,), jnp.float32, s_spec), sds((K,), jnp.float32, s_spec),
        sds((K,), jnp.float32, s_spec), sds((n_tiles, 2), jnp.int32, t_spec)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s_pad,block_s", [(3200, 640), (384, 384)],
                         ids=["book_full", "small"])
def test_vote_kernel_compiles_for_v5e(topo, s_pad, block_s):
    """The vote kernel at Book-full's padded sources (3,182 → 3,200, source
    blocks of 640) over 64 value-group blocks; the program picks
    ``block_s`` as ``truthfind._vote_block_s`` does."""
    from repro.core.truthfind import VOTE_BLOCK_E, _vote_block_s

    assert _vote_block_s(s_pad) == block_s
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    fn = jax.jit(lambda lh, v, sg: vote_fused_pallas(
        lh, v, sg, block_s=block_s, block_e=VOTE_BLOCK_E))
    compiled = fn.lower(sds((3, s_pad, s_pad), jnp.bfloat16),
                        sds((s_pad, 64 * VOTE_BLOCK_E), jnp.int8),
                        sds((s_pad, 1), jnp.float32)).compile()
    assert "vote_fused_pallas" in compiled.as_text()
