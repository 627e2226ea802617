"""The route a configuration picks, and what the drivers read from it, for
the dense and ELL configurations that routed before Random Indexing came
in: the same search program, kernel width and host tree as before."""
import os

import numpy as np
import pytest

import run
from lib import cells, reference, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIGS = ["inex-dense", "rcv1-ell"]


def _config(name):
    return run.load_json(os.path.join(run.BENCH, "configs", name + ".json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_rows_route_as_they_are(name):
    cfg = _config(name)
    assert not cells.is_rp(cfg)
    assert cells.search_program(cfg) == "_beam_search"
    assert cells.route_dim(cfg, 8000) == cfg["corpus"]["culled_vocab"] == 8000


def test_rp_routes_in_its_projection():
    cfg = dict(_config("rcv1-ell"), representation="rp",
               rp={"dim": 128, "seed": 0, "kind": "gaussian"})
    assert cells.search_program(cfg) == "_chunk_candidates_jit"
    assert cells.route_dim(cfg, 8000) == 128


@pytest.mark.parametrize("name", CONFIGS)
def test_recorded_serve_trace_reads_as_before(name):
    """On a trace recorded on a TPU v5e (12 one-row searches), the route's
    program gives the summary and the calls per row that ``_beam_search``
    gave before the driver named it."""
    tr = trace.load(os.path.join(DATA, "serve_small.xplane.pb"))
    (w,) = [e for e in tr.host if e.name == trace.WINDOW_SPAN]
    window = (w.start_ns, w.start_ns + w.dur_ns)
    kernels = ("nn_topk", "nn_assign", "ell_spmm")
    program = cells.search_program(_config(name))
    got = trace.summarize(tr, kernels=kernels, programs=(program,), window=window)
    assert got == trace.summarize(tr, kernels=kernels, programs=("_beam_search",), window=window)
    layer = {"trace": got, "rows_traced": 12}
    named = run.read_metric("search.device_calls_per_row", dict(layer, search_program=program))
    assert named == run.read_metric("search.device_calls_per_row", layer) == 1.0


class _Tree:
    """A device tree's fields as the reference reads them."""

    def __init__(self, d_stored):
        rng = np.random.default_rng(3)
        self.centers = rng.random((5, 24, d_stored), dtype=np.float32)
        self.counts = np.ones((5, 21), np.float32)
        self.child = np.zeros((5, 21), np.int32)
        self.n_entries = np.ones(5, np.int32)
        self.is_leaf = np.ones(5, bool)
        self.root, self.n_nodes, self.depth, self.medoid = 0, 1, 1, False


@pytest.mark.parametrize("d_stored", [8000, 8064])
def test_host_tree_holds_the_route_width(d_stored):
    tree = _Tree(d_stored)
    host = reference.HostTree.from_device(tree, 8000)
    assert host.centers.shape == (5, 24, 8000)
    assert np.array_equal(host.centers, tree.centers[:, :, :8000])
