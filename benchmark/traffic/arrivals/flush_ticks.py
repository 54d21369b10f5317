"""``flush_ticks``: ``forwarders`` log forwarders, each flushing every
``flush_s`` seconds, one request per pod with new lines at each flush.

Forwarder ``f`` ticks at ``(f + u_f) / F × flush_s + k × flush_s``, with
its phase ``u_f`` drawn once from ``ORDER_SEED``. Every request of a tick
is due at the tick. The ticks carry the quantiles of a Poisson count
with mean ``rate_per_s × flush_s / F``, adjusted so that the window holds
exactly ``round(rate_per_s × seconds)`` requests, and the requests take
the quantiles of the size distribution. As in ``poisson``, the order of
both is drawn once from ``ORDER_SEED`` and the run's seed only rotates
it, so every seed gets the same amount of work.
"""

import math

import numpy as np

ORDER_SEED = 1
_PLAN = 3


def poisson_quantiles(mean, q):
    """The smallest ``k`` with P(X ≤ k) ≥ ``q``, for X ~ Poisson(``mean``)."""
    q = np.asarray(q, dtype=float)
    top = max(1, int(mean + 12 * math.sqrt(mean) + 12))
    k = np.arange(top + 1)
    log_pmf = k * math.log(mean) - mean - np.array([math.lgamma(i + 1) for i in k])
    cdf = np.cumsum(np.exp(log_pmf))
    return np.minimum(np.searchsorted(cdf, q, side="left"), top)


def plan(loop, sizes, seed, seconds):
    rate = float(loop["rate_per_s"])
    n_fwd = int(loop["forwarders"])
    flush = float(loop["flush_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([ORDER_SEED, _PLAN])
    phase = rng.random(n_fwd)
    offsets = np.sort((np.arange(n_fwd) + phase) / n_fwd * flush)
    ticks = np.concatenate([
        k * flush + offsets for k in range(int(math.ceil(seconds / flush)))
    ])
    ticks = ticks[ticks < seconds]
    if not ticks.size:
        ticks = offsets[:1] * 0.0
    m = ticks.size
    counts = poisson_quantiles(rate * flush / n_fwd, (np.arange(m) + 0.5) / m)
    # the quantiles, highest first, give up or take one request each
    # until the window holds ``n``
    extra = n - int(counts.sum())
    order = np.arange(m)[::-1]
    while extra:
        for t in order:
            if extra > 0:
                counts[t] += 1
                extra -= 1
            elif counts[t] > 0:
                counts[t] -= 1
                extra += 1
            if not extra:
                break
    rng.shuffle(counts)
    size = np.array(sizes((np.arange(n) + 0.5) / n))
    rng.shuffle(size)
    counts = np.roll(counts, -(seed % m))
    size = np.roll(size, -(seed % n))
    due = np.repeat(ticks, counts)
    return due.tolist(), [int(s) for s in size]
