"""``library_hits``: lines that name a pattern of the configuration's
generated library, each in its pattern's own shape."""

from benchmark.libraries import synth_hit_line


def lines(_params, rng, positions, ctx):
    lib = ctx["config"]["library"]
    pats = rng.integers(0, int(lib["patterns"]), len(positions)).tolist()
    nums = rng.integers(1000, 100000, len(positions)).tolist()
    return [synth_hit_line(lib, p, num) for p, num in zip(pats, nums)]
