"""Open-loop arrival times, by the process a traffic file names.

The program's ``repro.launch.engine.open_loop_arrivals`` draws exponential
gaps from a seeded generator, so the number of requests that fall in a window
changes from seed to seed. Here the count is always ``round(rate ×
seconds)``, so every seed offers the same amount of work:

- ``poisson``: the order statistics of uniform draws from the seed, a
  Poisson process conditioned on its count. The gaps themselves change from
  seed to seed, and with them how often requests bunch up.
- ``poisson_pattern``: one Poisson draw of gaps from the traffic file's
  ``pattern_seed``, the same for every seed, rotated to start at a gap that
  the seed picks. Every seed then offers the same gaps in the same local
  order, so a tail that depends on how requests bunch up is comparable from
  run to run.

``burst`` (default 1) makes each arrival instant carry that many requests at
once, the instants following the process at ``rate / burst``.
"""
from __future__ import annotations

import numpy as np


def _count(rate: float, seconds: float) -> int:
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be > 0, got {rate}, {seconds}")
    return max(int(round(rate * seconds)), 1)


def poisson_arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Ascending due times in [0, seconds) of round(rate × seconds) requests."""
    return np.sort(rng.uniform(0.0, seconds, _count(rate, seconds)))


def pattern_arrivals(rate: float, seconds: float, rng: np.random.Generator, *,
                     pattern_seed: int) -> np.ndarray:
    """Ascending due times in [0, seconds) of round(rate × seconds) requests:
    the exponential gaps drawn from ``pattern_seed``, rotated by an offset
    from ``rng``, scaled so that all the gaps together span the window."""
    n = _count(rate, seconds)
    gaps = np.random.default_rng(pattern_seed).exponential(1.0 / rate, n)
    gaps = np.roll(gaps, -int(rng.integers(n)))
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * (seconds / gaps.sum())


PROCESSES = {
    "poisson": lambda rate, seconds, rng: poisson_arrivals(rate, seconds, rng),
    "poisson_pattern": pattern_arrivals,
}


def due_times(spec: dict, rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times of the requests of one window under the traffic file's
    ``arrivals`` entry (``process``, its parameters, and ``burst``), at
    ``rate`` requests a second."""
    params = {k: v for k, v in spec.items() if k not in ("process", "burst")}
    burst = int(spec.get("burst", 1))
    if burst < 1:
        raise ValueError(f"burst must be >= 1, got {burst}")
    try:
        process = PROCESSES[spec["process"]]
    except KeyError:
        raise ValueError(f"no arrival process {spec['process']!r}; have {sorted(PROCESSES)}") from None
    return np.repeat(process(rate / burst, seconds, rng, **params), burst)
