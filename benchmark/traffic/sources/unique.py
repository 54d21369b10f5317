"""``unique``: lines from a format, unique to their request and position:
``{tag}`` is the request's tag, ``{i}`` the line's position, ``{m}`` and
``{s}`` a minute and second drawn from the seed."""


def lines(fmt, rng, positions, ctx):
    mm = rng.integers(0, 60, len(positions)).tolist()
    ss = rng.integers(0, 60, len(positions)).tolist()
    return [fmt.format(i=i, tag=ctx["tag"], m=a, s=b)
            for i, a, b in zip(positions, mm, ss)]
