"""What a traffic file asks of a window: arrival processes, query draws,
tenants, and the engine calls that set-up warms."""
import numpy as np
import pytest

from lib import arrivals, load

ONE_ROW = {"rate_rows_per_s": 20.0, "row_budget": 256, "max_queue": 128}


def _rng(seed):
    return np.random.default_rng(seed)


def test_poisson_count_is_fixed_and_times_ascend():
    for seed in (1, 2**31 + 5):
        t = arrivals.poisson_arrivals(20.0, 51.0, _rng(seed))
        assert t.size == 1020 and np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < 51.0


def test_pattern_is_one_gap_sequence_rotated_by_the_seed():
    a = arrivals.pattern_arrivals(20.0, 51.0, _rng(1), pattern_seed=7)
    b = arrivals.pattern_arrivals(20.0, 51.0, _rng(2), pattern_seed=7)
    assert a.size == b.size == 1020 and a[0] == b[0] == 0.0 and max(a[-1], b[-1]) < 51.0
    ga, gb = np.diff(a), np.diff(b)
    assert not np.array_equal(ga, gb)
    # the same cyclic sequence of gaps: b's gaps appear in a's, in order
    ring = np.concatenate([ga, ga])
    hits = [i for i in range(ga.size) if np.allclose(ring[i:i + 50], gb[:50])]
    assert hits
    c = arrivals.pattern_arrivals(20.0, 51.0, _rng(1), pattern_seed=7)
    assert np.array_equal(a, c)


def test_bursts_repeat_each_instant():
    t = arrivals.due_times({"process": "poisson", "burst": 4}, 20.0, 10.0, _rng(3))
    assert t.size == 200 and np.all(t.reshape(-1, 4) == t[::4, None])
    with pytest.raises(ValueError):
        arrivals.due_times({"process": "no-such"}, 20.0, 10.0, _rng(3))


def test_zipf_queries_repeat_popular_rows():
    u = load.query_rows({}, 4096, (5000, 1), _rng(4))
    z = load.query_rows({"dist": "zipf", "s": 1.1}, 4096, (5000, 1), _rng(4))
    assert u.shape == z.shape == (5000, 1) and z.max() < 4096
    assert np.unique(z).size < 0.5 * np.unique(u).size


def test_tenants_get_their_share():
    ts = [{"k": 10, "beam": 4, "share": 2}, {"k": 5, "beam": 2, "share": 1}]
    got = load.assign_tenants(ts, 301, _rng(5))
    assert np.bincount(got).tolist() == [201, 100]
    p = load.plan(dict(ONE_ROW, rows=3, tenants=ts), 30.0, 10.0, 64,
                  arrivals_rng=_rng(1), pool_rng=_rng(2), tenant_rng=_rng(3))
    assert p.which.shape == (100, 3) and set(p.k.tolist()) == {10, 5}
    assert np.all(p.beam[p.k == 5] == 2)


def test_engine_calls_of_one_row_traffic():
    calls = load.engine_calls(ONE_ROW, 512)
    assert calls == [(10, 4, 1 << i, 1) for i in range(8)]
    assert load.rows_per_call(ONE_ROW, 512) == (1, 4)


def test_engine_calls_follow_rows_cache_and_tenants():
    three = load.engine_calls(dict(ONE_ROW, rows=3), 512)
    assert three == [(10, 4, 4 * (1 << i), 4) for i in range(8)]   # 85 requests a batch
    cached = load.engine_calls(dict(ONE_ROW, rows=3, answer_cache=100), 512)
    assert cached == [(10, 4, 1 << i, 1) for i in range(9)]          # 255 missed rows
    solo = load.engine_calls(dict(ONE_ROW, rows=600), 512)
    assert solo == [(10, 4, 600, None)] and load.rows_per_call(dict(ONE_ROW, rows=600), 512) == (512, 4)
    ts = [{"k": 10, "beam": 4, "share": 1}, {"k": 5, "beam": 2, "share": 1}]
    two = load.engine_calls(dict(ONE_ROW, tenants=ts), 512)
    assert len(two) == 16 and load.rows_per_call(dict(ONE_ROW, tenants=ts), 512) is None


@pytest.mark.parametrize("mix,config", [("whole_builds", "inex-dense"),
                                        ("whole_builds_shuffled", "rcv1-ell")])
def test_build_mixes_do_the_same_work_for_every_seed(mix, config):
    import os

    import run
    from lib import cells

    cfg = run.load_json(os.path.join(run.BENCH, "configs", config + ".json"))
    traffic = run.load_json(os.path.join(run.BENCH, "traffic", mix + ".json"))
    (a, ka), (b, kb) = (cells.build_inputs(cfg, traffic, s, 300) for s in (3, 2**31 + 9))
    assert ka == kb and a.n_cols == b.n_cols
    assert np.array_equal(a.dense(np.arange(300)), b.dense(np.arange(300)))
    # without work_seed the seed draws the order and the key, as before
    free = {k: v for k, v in traffic.items() if k != "work_seed"}
    (_, kc), (_, kd) = (cells.build_inputs(cfg, free, s, 300) for s in (3, 2**31 + 9))
    assert kc != kd
