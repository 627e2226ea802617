"""Compile guard for the Pallas kernels on a TPU v5e, with no chip attached.

Each case AOT-compiles one ``*_pallas`` entry point with ``interpret=False``
for a described ``v5e:2x2`` topology at the main path's real shapes: the
build batch (256 rows) and the query chunk (512 rows), the root's
order+1 = 21 slots padded to 128 centres, d = 8000 → 8064 (dense and ELL) and
d = 128 (Random Indexing projection), kq = beam (4) and k (10), and the ELL
``nnz_max`` the RCV1-like corpus generates. The chip's compiler refuses what
interpret mode accepts — misaligned blocks, too much VMEM, primitives Mosaic
cannot lower — so these catch it without chip time.

The last cases compile the five programs that take the whole tree
(search, routing, the insertion wave, both splits) at the ``inex-dense``
tree's shapes and count the copies of the stored tree in them: the chip's
layout choice for ``centers`` (DESIGN.md §3) shows in the compiled text.

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


# --- the programs that take the whole tree ---------------------------------
# The inex-dense tree (20,000 docs of d = 8000 at order 20): 7,232 nodes.
TREE_NODES, TREE_ORDER, TREE_DOCS = 7232, 20, 20_000


def _chip_smoke():
    """The repository's ``chip_smoke.py``, whose ``tree_copies`` the chip
    run applies to the same compiled text."""
    import importlib.util
    import sys

    if "chip_smoke" not in sys.modules:
        path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules["chip_smoke"])
    return sys.modules["chip_smoke"]


def _tree_program(one_chip, program, stored=None):
    """Compiled HLO text of ``program`` for the described chip, over the
    inex-dense tree's shapes (``stored``: the centres' slot width, default
    the tree's own), with the Pallas kernels the chip would run."""
    import dataclasses

    from repro.core import ktree as kt
    from repro.core import query as qm
    from repro.core.backend import DenseBackend

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tree = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: kt.ktree_init(TREE_NODES, TREE_ORDER, DENSE_D)),
    )
    if stored is not None:
        tree = dataclasses.replace(
            tree, centers=sds((TREE_NODES, stored, DENSE_D), jnp.float32))
    be = DenseBackend(sds((TREE_DOCS, DENSE_D), jnp.float32))
    rows, level = sds((BUILD_BATCH,), jnp.int32), sds((), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), 8))
    calls = {
        "_beam_search": lambda: qm._beam_search.lower(
            tree, be, sds((1,), jnp.int32), level, max_levels=2, beam=BEAM, k=K),
        "_insert_wave": lambda: kt._insert_wave.lower(
            tree, be, rows, rows, sds((BUILD_BATCH,), bool), level, max_levels=2),
        "_route_jit": lambda: kt._route_jit.lower(
            tree, be, rows, level, max_levels=2),
        "split_node": lambda: kt.split_node.lower(
            tree, level, sds(key.shape, key.dtype)),
        "split_nodes_batch": lambda: kt.split_nodes_batch.lower(
            tree, sds((8,), jnp.int32), sds((8,), bool),
            sds(keys.shape, keys.dtype)),
    }
    with jax.default_device(next(iter(one_chip.device_set))):
        return calls[program]().compile().as_text(), tree.centers.shape


@pytest.mark.parametrize("program", [
    "_beam_search", "_insert_wave", "_route_jit", "split_node",
    "split_nodes_batch",
])
def test_tree_programs_copy_no_tree(one_chip, program):
    """Stored 24 slots wide, the tree stays row-major on the chip: no
    program that takes it copies the whole of its centres."""
    text, shape = _tree_program(one_chip, program)
    assert shape == (TREE_NODES, 24, DENSE_D)
    assert _chip_smoke().tree_copies(text, shape) == 0


def test_tree_copy_seen_when_stored_unpadded(one_chip):
    """The control: centres stored m+1 = 21 slots wide are laid out with the
    node axis second-minor, and a search call copies the whole tree."""
    text, shape = _tree_program(one_chip, "_beam_search", stored=TREE_ORDER + 1)
    assert _chip_smoke().tree_copies(text, shape) >= 1
