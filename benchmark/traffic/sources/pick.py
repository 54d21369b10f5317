"""``pick``: lines drawn uniformly from a fixed list."""


def lines(choices, rng, positions, ctx):
    return [choices[c] for c in rng.integers(0, len(choices), len(positions)).tolist()]
