"""What an ``open_loop`` traffic file asks of a window: the requests (due
time, query rows, k and beam), and the batch shapes the engine can form from
them, which set-up compiles.

Traffic keys, with the values a one-row mix uses:

- ``rate_rows_per_s``: query rows offered a second; requests come at this
  rate over ``rows``.
- ``rows`` (1): query rows in each request.
- ``tenants`` ([{"k": 10, "beam": 4, "share": 1.0}]): the (k, beam) settings
  sent, each on its share of the requests.
- ``queries`` ({"dist": "uniform"}): how each row is drawn from the held-out
  pool of ``query_pool`` documents; ``{"dist": "zipf", "s": 1.1}`` repeats
  popular ones.
- ``arrivals`` ({"process": "poisson"}): see ``lib/arrivals.py``.
- ``answer_cache`` (0): entries of the ``AnswerCache`` the engine stages
  requests through; 0 runs without one.
- ``row_budget``, ``max_queue``, ``max_wait_s``: the engine's settings.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from lib import arrivals


@dataclasses.dataclass
class Plan:
    """The requests of one window, in due order."""
    due: np.ndarray       # f64[n] seconds from the window's start
    which: np.ndarray     # i64[n, rows] query pool rows of each request
    tenant: np.ndarray    # i64[n] index into the traffic's tenants
    tenants: list         # [{"k", "beam", "share"}]

    @property
    def k(self) -> np.ndarray:
        return np.asarray([t["k"] for t in self.tenants])[self.tenant]

    @property
    def beam(self) -> np.ndarray:
        return np.asarray([t["beam"] for t in self.tenants])[self.tenant]


def tenants(traffic: dict) -> list:
    ts = traffic.get("tenants") or [{"k": 10, "beam": 4, "share": 1.0}]
    if any(t["k"] < 1 or t["beam"] < 1 or t["share"] <= 0 for t in ts):
        raise ValueError(f"tenants need k, beam >= 1 and a share > 0: {ts}")
    return ts


def query_rows(spec: dict, n_pool: int, shape, rng: np.random.Generator) -> np.ndarray:
    """Pool rows for ``shape`` query slots under the traffic's ``queries``."""
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return rng.integers(0, n_pool, shape)
    if dist == "zipf":
        p = 1.0 / np.arange(1, n_pool + 1, dtype=np.float64) ** float(spec["s"])
        ranks = rng.choice(n_pool, size=shape, p=p / p.sum())
        return rng.permutation(n_pool)[ranks]
    raise ValueError(f"no query distribution {dist!r}; have uniform, zipf")


def assign_tenants(ts: list, n: int, rng: np.random.Generator) -> np.ndarray:
    """Tenant of each of n requests: each tenant's share of n (largest
    remainders), in an order from ``rng``."""
    share = np.asarray([t["share"] for t in ts], np.float64)
    want = share / share.sum() * n
    count = np.floor(want).astype(np.int64)
    count[np.argsort(count - want, kind="stable")[: n - count.sum()]] += 1
    return rng.permutation(np.repeat(np.arange(len(ts)), count))


def plan(traffic: dict, rate_rows_per_s: float, seconds: float, n_pool: int, *,
         arrivals_rng, pool_rng, tenant_rng) -> Plan:
    """The requests of a window of ``seconds`` at ``rate_rows_per_s``."""
    rows = int(traffic.get("rows", 1))
    due = arrivals.due_times(traffic.get("arrivals", {"process": "poisson"}),
                             rate_rows_per_s / rows, seconds, arrivals_rng)
    ts = tenants(traffic)
    which = query_rows(traffic.get("queries", {}), n_pool, (due.size, rows), pool_rng)
    return Plan(due, which, assign_tenants(ts, due.size, tenant_rng), ts)


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def engine_calls(traffic: dict, chunk_cap: int) -> list:
    """Every (k, beam, total rows, chunk_rows) call that ``ServingEngine``
    can make to its search fn under this traffic, as its ``_run_fragment``
    forms them: requests of ``rows`` rows padded to a power-of-two bucket and
    a power-of-two count of them run at ``chunk_rows = bucket``; with an
    answer cache, a power-of-two count of missed rows runs at ``chunk_rows =
    1``; requests over the search fn's chunk run alone at its own chunk
    (``chunk_rows`` None)."""
    rows = int(traffic.get("rows", 1))
    per_batch = max(min(traffic["max_queue"], traffic["row_budget"] // rows), 1)
    out = []
    for t in tenants(traffic):
        k, beam = t["k"], t["beam"]
        if rows > chunk_cap:
            calls = [(rows, None)]
        elif traffic.get("answer_cache", 0):
            top = pow2(min(traffic["row_budget"], per_batch * rows))
            calls = [(1 << i, 1) for i in range(top.bit_length())]
        else:
            bucket = pow2(rows)
            calls = [((1 << i) * bucket, bucket) for i in range(pow2(per_batch).bit_length())]
        out.extend((k, beam, n, c) for n, c in calls)
    return list(dict.fromkeys(out))


def rows_per_call(traffic: dict, chunk_cap: int):
    """Query rows each jitted search step handles, and its beam, where every
    call of the mix has the same; None otherwise."""
    shapes = {(c if c is not None else min(n, chunk_cap), beam)
              for _, beam, n, c in engine_calls(traffic, chunk_cap)}
    return shapes.pop() if len(shapes) == 1 else None
