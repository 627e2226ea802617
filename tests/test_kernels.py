"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


SHAPES = [
    (1, 1, 1), (7, 5, 3), (128, 128, 128), (130, 257, 64),
    (64, 1000, 96), (200, 300, 1000), (33, 129, 2048),
]


@pytest.mark.parametrize("b,k,d", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nn_assign_sweep(b, k, d, dtype):
    rng = np.random.default_rng(b * 1000 + k + d)
    x = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32)).astype(dtype)
    c = jnp.asarray(rng.normal(0, 1, (k, d)).astype(np.float32)).astype(dtype)
    idx, dist = ops.nn_assign(x, c)
    ridx, rdist = ref.nn_assign_ref(x, c)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    # discrete boundary: accept either equal idx or equal distance within tol
    same = np.asarray(idx) == np.asarray(ridx)
    close = np.abs(np.asarray(dist) - np.asarray(rdist)) <= tol * (1 + np.abs(np.asarray(rdist)))
    assert (same | close).all()
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rdist), rtol=tol, atol=tol)


def test_nn_assign_valid_mask():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (50, 64)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (300, 64)).astype(np.float32))
    valid = jnp.asarray(rng.random(300) > 0.7)
    idx, dist = ops.nn_assign(x, c, valid=valid)
    ridx, rdist = ref.nn_assign_ref(x, c, valid=valid)
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    assert np.asarray(valid)[np.asarray(idx)].all()


def test_nn_assign_block_sizes():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (100, 70)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (190, 70)).astype(np.float32))
    ridx, _ = ref.nn_assign_ref(x, c)
    for bm, bk in [(32, 64), (128, 128), (256, 128)]:
        idx, _ = ops.nn_assign(x, c, bm=bm, bk=bk)
        assert (np.asarray(idx) == np.asarray(ridx)).all(), (bm, bk)


ELL_SHAPES = [(1, 8, 1, 16), (8, 16, 9, 40), (130, 32, 120, 256), (64, 64, 200, 1000)]


@pytest.mark.parametrize("b,nz,k,d", ELL_SHAPES)
def test_ell_spmm_sweep(b, nz, k, d):
    rng = np.random.default_rng(b + nz + k)
    vals = rng.normal(0, 1, (b, nz)).astype(np.float32)
    vals[:, nz // 2:] *= rng.random((b, nz - nz // 2)) > 0.4  # padding pattern
    cols = rng.integers(0, d, (b, nz)).astype(np.int32)
    c = rng.normal(0, 1, (k, d)).astype(np.float32)
    s = ops.ell_spmm(jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(c))
    rs = ref.ell_spmm_ref(jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(c))
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=3e-5, atol=3e-5)


def test_ell_spmm_duplicate_columns():
    # repeated column ids within a row must accumulate
    vals = jnp.asarray([[1.0, 2.0, 3.0, 0.0]])
    cols = jnp.asarray([[2, 2, 5, 0]], dtype=jnp.int32)
    c = jnp.asarray(np.eye(8, dtype=np.float32))
    s = ops.ell_spmm(vals, cols, c)
    assert float(s[0, 2]) == pytest.approx(3.0)
    assert float(s[0, 5]) == pytest.approx(3.0)


def test_medoid_assign_sparse_matches_dense():
    rng = np.random.default_rng(2)
    from repro.sparse import csr_from_dense, ell_from_csr
    x = (rng.normal(0, 1, (40, 64)) * (rng.random((40, 64)) < 0.3)).astype(np.float32)
    m = csr_from_dense(x)
    e = ell_from_csr(m)
    centers = jnp.asarray(rng.normal(0, 1, (17, 64)).astype(np.float32))
    row_sq = jnp.asarray((x * x).sum(1))
    idx, dist = ops.medoid_assign_sparse(e.values, e.cols, row_sq, centers)
    ridx, rdist = ref.nn_assign_ref(jnp.asarray(x), centers)
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rdist), rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.integers(1, 150), st.integers(1, 128), st.integers(0, 9999))
def test_nn_assign_property(b, k, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (k, d)).astype(np.float32))
    idx, dist = ops.nn_assign(x, c)
    ridx, rdist = ref.nn_assign_ref(x, c)
    assert (np.asarray(idx) == np.asarray(ridx)).all()


# ---------------------------------------------------------------------------
# nn_topk — masked top-k accumulator (query engine, DESIGN.md §7)
# ---------------------------------------------------------------------------

TOPK_SHAPES = [
    (1, 1, 1, 1), (7, 5, 3, 3), (128, 128, 128, 8), (130, 257, 64, 10),
    (33, 129, 200, 17), (64, 300, 96, 32),
]


@pytest.mark.parametrize("b,k,d,kq", TOPK_SHAPES)
def test_nn_topk_sweep(b, k, d, kq):
    """Kernel (interpret mode) vs oracle on non-multiple-of-tile shapes:
    distances must match at every rank, and each returned id must be
    consistent with its rank's distance (robust to argmin boundary ulps)."""
    rng = np.random.default_rng(b * 1000 + k + d + kq)
    x = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (k, d)).astype(np.float32))
    idx, dist = ops.nn_topk(x, c, kq)
    ridx, rdist = ref.nn_topk_ref(x, c, kq)
    np.testing.assert_allclose(
        np.asarray(dist), np.asarray(rdist), rtol=3e-5, atol=3e-5
    )
    # verify ids against the true distance matrix at the claimed ranks
    d_true = np.asarray(ref._full_sqdist(x, c))
    ii = np.asarray(idx)
    got = np.where(ii >= 0, d_true[np.arange(b)[:, None], np.maximum(ii, 0)], np.inf)
    np.testing.assert_allclose(
        got, np.asarray(rdist), rtol=3e-5, atol=3e-5
    )
    assert (np.sort(np.asarray(dist), axis=1) == np.asarray(dist)).all()


def test_nn_topk_k_exceeds_centres():
    """k > centre count (k > docs-in-leaf in the query engine): the tail pads
    with (−1, +inf) in both kernel and oracle."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (9, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (5, 16)).astype(np.float32))
    idx, dist = ops.nn_topk(x, c, 12)
    ridx, rdist = ref.nn_topk_ref(x, c, 12)
    assert (np.asarray(idx)[:, 5:] == -1).all()
    assert np.isinf(np.asarray(dist)[:, 5:]).all()
    assert (np.asarray(idx)[:, :5] == np.asarray(ridx)[:, :5]).all()
    np.testing.assert_allclose(
        np.asarray(dist)[:, :5], np.asarray(rdist)[:, :5], rtol=3e-5, atol=3e-5
    )


def test_nn_topk_all_masked():
    """Every centre masked out → all results are (−1, +inf)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (17, 8)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (140, 8)).astype(np.float32))
    valid = jnp.zeros((140,), bool)
    idx, dist = ops.nn_topk(x, c, 4, valid=valid)
    ridx, rdist = ref.nn_topk_ref(x, c, 4, valid=valid)
    assert (np.asarray(idx) == -1).all() and (np.asarray(ridx) == -1).all()
    assert np.isinf(np.asarray(dist)).all() and np.isinf(np.asarray(rdist)).all()


def test_nn_topk_partial_mask_agrees():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (40, 32)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (260, 32)).astype(np.float32))
    valid = jnp.asarray(rng.random(260) > 0.5)
    idx, dist = ops.nn_topk(x, c, 9, valid=valid)
    ridx, rdist = ref.nn_topk_ref(x, c, 9, valid=valid)
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rdist), rtol=3e-5, atol=3e-5)
    assert np.asarray(valid)[np.asarray(idx)].all()


def test_nn_topk_ties_lowest_index_first():
    """Exact-arithmetic ties (duplicate centres) resolve to ascending centre
    id — ``lax.top_k`` stability, which the online merge must reproduce even
    across tile boundaries."""
    x = jnp.zeros((3, 8), jnp.float32)
    c = jnp.zeros((260, 8), jnp.float32)  # 260 > bk: ties span two tiles
    idx, dist = ops.nn_topk(x, c, 6)
    ridx, rdist = ref.nn_topk_ref(x, c, 6)
    expect = np.broadcast_to(np.arange(6, dtype=np.int32), (3, 6))
    np.testing.assert_array_equal(np.asarray(idx), expect)
    np.testing.assert_array_equal(np.asarray(ridx), expect)
    assert (np.asarray(dist) == 0).all()
    # two-level ties: duplicates at integer distances across tiles
    base = np.zeros((300, 4), np.float32)
    base[150:, 0] = 1.0     # second tile rows at distance 1
    base[:150, 0] = 2.0     # first tile rows at distance 4
    base[7, 0] = 1.0        # one first-tile row joins the distance-1 group
    xq = jnp.zeros((2, 4), jnp.float32)
    cq = jnp.asarray(base)
    idx, dist = ops.nn_topk(xq, cq, 4)
    ridx, _ = ref.nn_topk_ref(xq, cq, 4)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    assert np.asarray(idx)[0, 0] == 7  # lowest id of the nearest tie group


def test_nn_topk_top1_matches_nn_assign():
    """The kernel family is internally consistent: top-1 of nn_topk equals
    nn_assign on the same inputs (both stable-tie argmin semantics)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (70, 48)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (210, 48)).astype(np.float32))
    i1, d1 = ops.nn_assign(x, c)
    it, dt = ops.nn_topk(x, c, 3)
    assert (np.asarray(i1) == np.asarray(it)[:, 0]).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(dt)[:, 0], rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 40), st.integers(1, 150), st.integers(1, 64),
       st.integers(1, 20), st.integers(0, 9999))
def test_nn_topk_property(b, k, d, kq, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (k, d)).astype(np.float32))
    idx, dist = ops.nn_topk(x, c, kq)
    ridx, rdist = ref.nn_topk_ref(x, c, kq)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rdist),
                               rtol=3e-5, atol=3e-5)
    # padding iff rank beyond the centre count
    assert ((np.asarray(idx) == -1) == ~np.isfinite(np.asarray(dist))).all()


def test_kernel_flag_in_kmeans():
    """assign(use_kernel=True) plugs into the clustering stack."""
    from repro.core.kmeans import assign as km_assign
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (60, 32)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (9, 32)).astype(np.float32))
    i1, d1 = km_assign(x, c, use_kernel=False)
    i2, d2 = km_assign(x, c, use_kernel=True)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("scope", ["device", "platform"])
def test_dispatch_follows_default_device_scope(monkeypatch, scope):
    """On a TPU host the kernels run unless a ``jax.default_device`` scope
    names the CPU: then the backends call the oracles, as the oracle search
    of ``chip_smoke.py`` relies on."""
    from repro.core.backend import _use_pallas
    from repro.kernels import on_tpu
    assert not on_tpu() and ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert on_tpu() and _use_pallas() and not ops._interpret()
    cpu = jax.devices("cpu")[0] if scope == "device" else "cpu"
    with jax.default_device(cpu):
        assert not on_tpu() and not _use_pallas() and ops._interpret()
    assert on_tpu()


def test_topk_merge_ref_exact_union():
    """The serving merge oracle: merging per-shard top-k lists over disjoint id
    subsets equals a global top-k over the union; (−1, +inf) padding is
    ignored; ties resolve shard-major."""
    rng = np.random.default_rng(11)
    b, s, kq, n = 7, 4, 5, 64
    dist_full = rng.random((b, n)).astype(np.float32)
    dist_full[0, 3] = dist_full[0, 19]  # cross-shard exact tie
    ids_full = np.arange(n)
    # per-shard lists: shard j owns the contiguous id range [j*16, (j+1)*16)
    shard_ids = np.full((b, s, kq), -1, np.int32)
    shard_d = np.full((b, s, kq), np.inf, np.float32)
    for j in range(s):
        seg = dist_full[:, j * 16:(j + 1) * 16]
        order = np.argsort(seg, axis=1, kind="stable")[:, :kq]
        shard_ids[:, j] = order + j * 16
        shard_d[:, j] = np.take_along_axis(seg, order, 1)
    got_ids, got_d = ref.topk_merge_ref(
        jnp.asarray(shard_ids), jnp.asarray(shard_d), kq)
    want = np.argsort(dist_full, axis=1, kind="stable")[:, :kq]
    np.testing.assert_array_equal(np.asarray(got_ids), ids_full[want])
    np.testing.assert_allclose(
        np.asarray(got_d), np.take_along_axis(dist_full, want, 1), rtol=1e-6)


def test_topk_merge_ref_padding_and_k_growth():
    """Shards with fewer than k finite candidates pad; a merge wider than the
    finite union pads with (−1, +inf)."""
    ids = jnp.asarray([[[0, 1, -1], [17, -1, -1]]], jnp.int32)
    d = jnp.asarray([[[0.5, 2.0, np.inf], [1.0, np.inf, np.inf]]], jnp.float32)
    got_ids, got_d = ref.topk_merge_ref(ids, d, 5)
    np.testing.assert_array_equal(np.asarray(got_ids[0]), [0, 17, 1, -1, -1])
    assert np.isinf(np.asarray(got_d[0][3:])).all()
