"""The one traffic generator: a mix file of numbers in, requests out.

A mix (``traffic/<mix>.json``) gives the loop, the request sizes
(``fixed`` lines or ``log_uniform`` between two bounds), the line mix,
an optional ``block`` of fixed lines placed in a range of each request,
and the warm-up set. The loop is ``closed`` (``clients`` each posting
one request after the other, from a pool of ``pool_per_s`` requests per
second of window made at set-up) or ``open`` (each request due at the
time an arrival process gives it).

What a mix can say grows by files found by name, as the mixes are:

- an arrival process is ``traffic/arrivals/<kind>.py``, named by the
  open loop's ``arrivals``, with ``plan(loop, sizes, seed, seconds) ->
  (due offsets, sizes)``, where ``sizes(q)`` maps quantiles in (0, 1)
  to request sizes;
- a line source is ``traffic/sources/<name>.py``, named by the one key
  of a mix entry besides its ``weight``, with ``lines(params, rng,
  positions, ctx) -> list[str]``, which draws from ``rng`` the lines at
  ``positions`` of one request.

Every request is a pure function of ``(seed, stream, index)``: the load
generator makes them, and the reference makes them again by request id.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

import numpy as np

WINDOW, WARMUP = 0, 1
_PLAN = 3
TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")
_PLUGINS: dict = {}


def plugin(kind: str, name: str):
    """The module ``traffic/<kind>/<name>.py``."""
    path = os.path.join(TRAFFIC_DIR, kind, name + ".py")
    module = _PLUGINS.get(path)
    if module is None:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_traffic_{kind}_{name}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _PLUGINS[path] = module
    return module


def request_id(stream: int, k: int) -> str:
    return ("w" if stream == WARMUP else "r") + str(k)


def parse_request_id(rid: str) -> tuple[int, int]:
    return (WARMUP if rid[0] == "w" else WINDOW), int(rid[1:])


def _source_name(entry: dict) -> str:
    names = [k for k in entry if k != "weight"]
    if len(names) != 1:
        raise ValueError(f"a mix entry names one line source: {entry}")
    return names[0]


class Traffic:
    def __init__(self, spec: dict, config: dict, seed: int):
        self.spec = spec
        self.config = config
        self.seed = int(seed) % (1 << 64)
        mix = spec["mix"]
        w = np.array([float(m["weight"]) for m in mix])
        self._cum = np.cumsum(w) / w.sum()
        self._sources = [
            (plugin("sources", _source_name(m)), m[_source_name(m)]) for m in mix
        ]

    # ------------------------------------------------------------ schedule

    @property
    def open_loop(self) -> bool:
        return self.spec["loop"]["kind"] == "open"

    def quantile_sizes(self, q) -> list[int]:
        lines = self.spec["lines"]
        if "fixed" in lines:
            return [int(lines["fixed"])] * len(q)
        lo, hi = lines["log_uniform"]
        return [
            int(round(math.exp(math.log(lo) + x * (math.log(hi) - math.log(lo)))))
            for x in q
        ]

    def open_plan(self, seconds: float) -> tuple[list[float], list[int]]:
        """Due offsets (s from the window's start) and sizes of an open
        loop's window requests, from its arrival process."""
        loop = self.spec["loop"]
        return plugin("arrivals", loop["arrivals"]).plan(
            loop, self.quantile_sizes, self.seed, seconds
        )

    def pool_size(self, seconds: float) -> int:
        """The window requests a closed loop may send."""
        loop = self.spec["loop"]
        return int(math.ceil(float(loop["pool_per_s"]) * seconds)) + int(loop["clients"])

    def size(self, k: int) -> int:
        """Lines of closed-loop window request ``k``."""
        lines = self.spec["lines"]
        if "fixed" in lines:
            return int(lines["fixed"])
        rng = np.random.default_rng([self.seed, _PLAN, k])
        return self.quantile_sizes(rng.random(1))[0]

    def warmup_sizes(self) -> list[int]:
        warm = self.spec["warmup"]
        if "requests" in warm:
            return [self.size(k) for k in range(int(warm["requests"]))]
        lo, hi = self.spec["lines"]["log_uniform"]
        per = int(warm["grid_per_octave"])
        sizes = []
        j = 0
        while True:
            n = int(round(lo * 2.0 ** (j / per)))
            if n >= hi:
                break
            if not sizes or n != sizes[-1]:
                sizes.append(n)
            j += 1
        return sizes + [int(hi)]

    # ------------------------------------------------------------- content

    def logs(self, stream: int, k: int, n: int) -> str:
        """The ``n``-line log of request ``(stream, k)``."""
        rng = np.random.default_rng([self.seed, stream, k])
        ctx = {"tag": ("w" if stream == WARMUP else "") + format(k, "x"),
               "config": self.config}
        src = np.minimum(
            np.searchsorted(self._cum, rng.random(n), side="right"),
            len(self._cum) - 1,
        )
        out: list[str] = [""] * n
        for j, (source, params) in enumerate(self._sources):
            idx = np.flatnonzero(src == j).tolist()
            if idx:
                for i, line in zip(idx, source.lines(params, rng, idx, ctx)):
                    out[i] = line
        block = self.spec.get("block")
        if block:
            lines = block["lines"][:n]
            lo, hi = block["at"]
            at = min(int(rng.uniform(lo, hi) * n), n - len(lines))
            out[at:at + len(lines)] = lines
        return "\n".join(out)

    def body(self, stream: int, k: int, n: int) -> dict:
        return {
            "pod": {"metadata": {"name": "bench-" + request_id(stream, k)}},
            "logs": self.logs(stream, k, n),
        }


# ------------------------------------------------ bodies made in a pool

_W: dict = {}


def _init(spec: dict, config: dict, seed: int) -> None:
    _W["traffic"] = Traffic(spec, config, seed)


def _body(job: tuple[int, int, int]) -> bytes:
    return json.dumps(_W["traffic"].body(*job)).encode()


def make_bodies(spec: dict, config: dict, seed: int, plan: list, workers: int) -> list[bytes]:
    """The JSON bodies of ``plan``'s ``(stream, k, lines)`` requests,
    made by ``workers`` processes that never import JAX."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn"),
        initializer=_init, initargs=(spec, config, seed),
    ) as pool:
        return list(pool.map(_body, plan))
