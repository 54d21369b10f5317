"""``poisson``: arrivals at ``rate_per_s``, exactly ``rate × seconds``
requests due in the window.

Every seed gets the same inter-arrival gaps and request sizes, the
quantiles of their distributions, so the seed changes the order of the
work and not its amount. That order is drawn once, from ``ORDER_SEED``,
and the run's seed only rotates it: near capacity the tail follows the
order (a run of large requests in short gaps builds a queue), and a
rotation keeps every seed's queueing the same but for the one seam.
"""

import numpy as np

ORDER_SEED = 1
_PLAN = 3


def plan(loop, sizes, seed, seconds):
    rate = float(loop["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([ORDER_SEED, _PLAN])
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    size = np.array(sizes(q))
    rng.shuffle(size)
    turn = seed % n
    gaps, size = np.roll(gaps, -turn), np.roll(size, -turn)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due.tolist(), [int(s) for s in size]
