"""Compile rehearsals for the TPU v5e: the server's merge kernels at the
paper model's real width, compiled for a described ``v5e:2x2`` topology
(no chip attached — nothing runs, so these prove only that the chip's
compiler accepts the programs).

P = 1,630,090 is one f32 row of ``cnn-mnist``; K spans cohort buckets
from the paper's setting (16) to a 256-row window.  The 4-chip test
compiles the row-sharded store's kernel merge: per-shard
``fedagg_partial`` inside ``shard_map`` plus one psum.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library, and
every test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.aggregate import _merge_fn
from repro.kernels.fedagg import fedagg, fedagg_fold, fedagg_partial

P_CNN_MNIST = 1_630_090


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # fedlint: disable=FED007 -- the topology library's failure types vary by install; any failure to describe the chip means the rehearsal cannot run here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_call(name, k, sh):
    u = _spec((k, P_CNN_MNIST), sh)
    if name == "fedagg":
        return (lambda u, w, a: fedagg(u, w, alphas=a),
                (u, _spec((k,), sh), _spec((k,), sh)))
    if name == "fedagg_fold":
        return (lambda u, g, c: fedagg_fold(u, g, c),
                (u, _spec((P_CNN_MNIST,), sh), _spec((k + 1,), sh)))
    return (lambda u, c: fedagg_partial(u, c), (u, _spec((k,), sh)))


@pytest.mark.parametrize("k", [16, 64, 256])
@pytest.mark.parametrize("name", ["fedagg", "fedagg_fold",
                                  "fedagg_partial"])
def test_fedagg_kernels_compile_for_v5e(one_chip, name, k):
    fn, args = _kernel_call(name, k, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_store_kernel_merge_compiles_for_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices), ("clients",))
    rows = NamedSharding(mesh, P("clients"))
    k = 32
    compiled = _merge_fn(mesh, True, False).lower(
        _spec((k, P_CNN_MNIST), rows), _spec((k,), rows)).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt
