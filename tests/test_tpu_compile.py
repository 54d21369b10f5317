"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler installed here compiles for a chip that is
described, not attached (on-chip-measurement guide §2), so a Mosaic
tiling refusal or a VMEM overrun fails here at no chip time. Interpret
mode (tests/test_bitglush.py, tests/test_matchdfa_pallas.py) cannot see
either. Shapes are the builtin bank's at B=8192 rows (and 16, the
short-request rung), T=128 bytes.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the driver's xdist workers
all import every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

B, T = 8192, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this build
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def bank():
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    return PatternBank(load_builtin_pattern_sets())


def _lower_through_mosaic(monkeypatch):
    """The Pallas entry points pick interpret mode from
    jax.default_backend(); steer it to "tpu" for the duration of the
    test, so they lower through Mosaic instead of the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("rows", [16, B])
def test_bitglush_kernel_compiles(one_chip, bank, monkeypatch, rows):
    from log_parser_tpu.ops.bitglush_pallas import bitglush_hits_pallas
    from log_parser_tpu.ops.match import MatcherBanks

    _lower_through_mosaic(monkeypatch)
    mb = MatcherBanks(bank)
    # the builtin bank's bit tier under its word budget
    assert 64 < mb.bitglush.n_words <= MatcherBanks.BITGLUSH_MAX_WORDS
    lines = jax.ShapeDtypeStruct((T, rows), jnp.uint8, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda a, b: bitglush_hits_pallas(mb.bitglush, a, b))
        .lower(lines, lens)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def _union_matchers(bank, monkeypatch):
    """Matchers with the bit tier off: the only layout in which the
    builtin bank packs union groups; its admitted plan re-splits two
    groups to fit the VMEM budget (chip_smoke.py's union-DFA engine)."""
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.ops.matchdfa_pallas import DFA_VMEM_BUDGET

    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    _lower_through_mosaic(monkeypatch)
    mb = MatcherBanks(bank, bitglush_max_words=0)
    assert mb.multidfa_pallas_reason == "split"
    assert mb.dfa_kernel_geometry["vmemPerStep"] <= DFA_VMEM_BUDGET
    return mb


@pytest.mark.parametrize("rows", [16, B])
def test_union_dfa_kernel_compiles(one_chip, bank, monkeypatch, rows):
    from log_parser_tpu.ops.matchdfa_pallas import multidfa_reported_pallas

    plan = _union_matchers(bank, monkeypatch)._dfa_pallas_plan
    lines = jax.ShapeDtypeStruct((T, rows), jnp.uint8, sharding=one_chip)
    compiled = (
        jax.jit(lambda a: multidfa_reported_pallas(plan, a))
        .lower(lines)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["xla", "bitglush", "union_dfa"])
def test_fused_step_compiles(one_chip, bank, monkeypatch, kernel):
    """The served path's whole device step, as the engine builds it:
    every tier on the XLA scan (default), or the bit tier as the Pallas
    kernel (LOG_PARSER_TPU_PALLAS=1), or the union tier as the Pallas
    kernel."""
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.ops.fused import FusedMatchScore
    from log_parser_tpu.ops.match import MatcherBanks

    if kernel == "union_dfa":
        mb = _union_matchers(bank, monkeypatch)
    else:
        monkeypatch.setenv(
            "LOG_PARSER_TPU_PALLAS", "1" if kernel == "bitglush" else "0"
        )
        if kernel == "bitglush":
            _lower_through_mosaic(monkeypatch)
        mb = MatcherBanks(bank)
    fused = FusedMatchScore(bank, ScoringConfig(), mb)
    lines = jax.ShapeDtypeStruct((B, T), jnp.uint8, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda a, b, c: fused._step(4096, a, b, c, None))
        .lower(lines, lens, n)
        .compile()
    )
    assert ("tpu_custom_call" in compiled.as_text()) == (kernel != "xla")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30  # fits one v5e's HBM
