"""Compile guard for the Pallas kernels on a TPU v5e, with no chip attached.

Each case AOT-compiles one ``*_pallas`` entry point with ``interpret=False``
for a described ``v5e:2x2`` topology at the main path's real shapes: the
build batch (256 rows) and the query chunk (512 rows), the root's
order+1 = 21 slots padded to 128 centres, d = 8000 → 8064 (dense and ELL) and
d = 128 (Random Indexing projection), kq = beam (4) and k (10), and the ELL
``nnz_max`` the RCV1-like corpus generates. The chip's compiler refuses what
interpret mode accepts — misaligned blocks, too much VMEM, primitives Mosaic
cannot lower — so these catch it without chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time, and
every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.nn_assign import nn_assign_pallas
from repro.kernels.nn_topk import nn_topk_pallas

BUILD_BATCH, QUERY_CHUNK, ROOT_K = 256, 512, 128
DENSE_D, RP_D = 8064, 128
BEAM, K = 4, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [DENSE_D, RP_D])
def test_nn_assign_compiles_for_v5e(one_chip, d):
    f32 = jnp.float32
    compiled = _compile(
        lambda x, c, b: nn_assign_pallas(x, c, b), one_chip,
        ((BUILD_BATCH, d), f32), ((ROOT_K, d), f32), ((1, ROOT_K), f32),
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize("kq", [BEAM, K])
@pytest.mark.parametrize("d", [DENSE_D, RP_D])
def test_nn_topk_compiles_for_v5e(one_chip, d, kq):
    f32 = jnp.float32
    compiled = _compile(
        lambda x, c, b: nn_topk_pallas(x, c, b, kq=kq), one_chip,
        ((QUERY_CHUNK, d), f32), ((ROOT_K, d), f32), ((1, ROOT_K), f32),
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize("rows", [BUILD_BATCH, QUERY_CHUNK])
@pytest.mark.parametrize("nnz_max", [176, 184])
def test_ell_spmm_compiles_for_v5e(one_chip, rows, nnz_max):
    compiled = _compile(
        lambda v, c, ct: ell_spmm_pallas(v, c, ct), one_chip,
        ((nnz_max, rows), jnp.float32), ((nnz_max, rows), jnp.int32),
        ((ROOT_K, DENSE_D), jnp.float32),
    )
    _assert_kernel(compiled)
