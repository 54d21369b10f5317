"""One matcher tier plan on every backend.

``MatcherBanks`` routes each column to a tier from the bank alone: the
CPU test suite must build the same tiers, and lower the same cube
program, as the chip serves. Each library is planned twice, with
``jax.default_backend`` reporting "cpu" and then "tpu"; the column →
tier assignment and the lowered ``cube`` text must be identical.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import pytest

from log_parser_tpu.ops.match import MatcherBanks
from log_parser_tpu.patterns.bank import PatternBank

T, B = 64, 32


def _builtin():
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    return load_builtin_pattern_sets()


def _synthetic():
    import bench_bank

    return bench_bank.synth_library(200)


def _long_literal():
    from tests.test_engine_parity import random_long_library

    return random_long_library(random.Random(31000), 5)


def _host_only():
    from helpers import make_pattern, make_pattern_set

    return [make_pattern_set([
        make_pattern("look", regex="timeout(?= after)", confidence=0.5),
        make_pattern("nolook", regex="refused(?!, retrying)", confidence=0.5),
        make_pattern("backref", regex="(\\w+) \\1 again", confidence=0.5),
    ])]


LIBRARIES = {
    "builtin": _builtin,
    "synthetic200": _synthetic,
    "long_literal": _long_literal,
    "host_only": _host_only,
}


def _tiers(mb: MatcherBanks) -> dict:
    return {
        "shiftor": list(mb.shiftor_cols),
        "bitglush": list(mb.bitglush_cols),
        "approx": list(mb.approx_cols),
        "prefilter": list(mb.prefilter_cols),
        "multi": [list(g.cols) for g in mb.multi_groups],
        "dfa": list(mb.dfa_cols),
        "host": list(mb.host_cols),
    }


@pytest.fixture(scope="module", params=sorted(LIBRARIES))
def plans(request):
    """{backend: (tier assignment, lowered cube text)} for one library."""
    bank = PatternBank(LIBRARIES[request.param]())
    out = {}
    for backend in ("cpu", "tpu"):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda b=backend: b)
            mb = MatcherBanks(bank)
            text = (
                jax.jit(mb.cube)
                .lower(
                    jax.ShapeDtypeStruct((T, B), jnp.uint8),
                    jax.ShapeDtypeStruct((B,), jnp.int32),
                )
                .as_text()
            )
        out[backend] = (_tiers(mb), text)
    return request.param, bank, out


def test_same_tier_assignment_on_every_backend(plans):
    name, bank, out = plans
    assert out["cpu"][0] == out["tpu"][0]
    tiers = out["tpu"][0]
    # each library reaches the tiers it was chosen for
    if name == "host_only":
        primaries = {int(c) for c in bank.primary_columns}
        assert primaries and primaries <= set(tiers["host"])
    elif name == "long_literal":
        assert tiers["approx"]
    else:
        assert tiers["shiftor"] or tiers["bitglush"] or tiers["prefilter"]


def test_same_cube_program_on_every_backend(plans):
    _, _, out = plans
    assert out["cpu"][1] == out["tpu"][1]
