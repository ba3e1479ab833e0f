"""The one general generator of request arrivals, driven by a traffic file.

A traffic file (``traffic/<name>.json``) holds parameters only:

  ``arrivals``     "poisson" (the one process this generator makes);
  ``rate_per_s``   mean offered load, requests per second (open loop);
  ``deadline_ms``  the fixed latency SLO every request carries;
  ``eps_max``      the refinement fraction the mix asks for;
  ``max_error``    the accuracy SLO every request carries, or null.

Arrivals are Poisson-like with a fixed count: ``round(rate * seconds)``
requests whose inter-arrival gaps are the exponential distribution's
quantiles, in one fixed shuffled order.  Every run of a mix therefore
offers the same arrivals: in a queue the order of the gaps moves a tail as
much as the gaps do, so a path drawn from the run's seed would make seeds
spread far wider than runs of one seed.  The run's seed draws which pooled query each request
sends (and, in the harness, the data).
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a run seed (any size int)."""
    return np.random.default_rng([int(seed) % 2**64, int(stream)])


def unit_gaps(n: int) -> np.ndarray:
    """n exponential(1) quantiles, the fixed multiset of gaps."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def schedule(traffic: dict, seed: int, seconds: float, pool_size: int):
    """-> (due offsets in s, sorted; pooled query index per request)."""
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = unit_gaps(n)
    rng_for(0, 1).shuffle(gaps)
    # The n arrivals fill [0, seconds): scale the fixed gap multiset so the
    # last one lands half a mean gap before the window closes.
    span = seconds - 0.5 / rate
    t = np.cumsum(gaps) * (span / gaps.sum())
    pool_idx = rng_for(seed, 2).integers(0, pool_size, size=n)
    return t, pool_idx
