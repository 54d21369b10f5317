"""``zipf``: lines drawn from a fixed list, the ``r``-th with weight 1/r
(``bench_common.zipf_template``'s law)."""

import numpy as np


def lines(choices, rng, positions, ctx):
    p = 1.0 / np.arange(1, len(choices) + 1)
    return [choices[c]
            for c in rng.choice(len(choices), len(positions), p=p / p.sum()).tolist()]
