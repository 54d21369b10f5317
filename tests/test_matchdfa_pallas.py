"""Pallas union-DFA kernel (ops/matchdfa_pallas.py) vs the XLA scan tier.

Bit-identical semantics are the kernel's contract: every test pins the
kernel's reported flags (interpreter mode — the same kernel semantics
Mosaic lowers on TPU) against the scan tier's pair_stepper carry and an
independent numpy byte-walk of the packed table, over the union fixture
set plus adversarial shapes: pair-stride odd-length tails, padding-class
rows, the dense re-scan ``lax.cond`` recovery path, zero-match batches,
the oversized-table / no-tile admission fallbacks, and a kernel fault
that raises instead of hiding — batched (the
micro-batcher's vmapped program) and unbatched.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from log_parser_tpu.ops import matchdfa_pallas as mdp
from log_parser_tpu.ops.encode import encode_lines
from log_parser_tpu.ops.match import (
    MatcherBanks,
    MultiDfaBank,
    pack_byte_pairs,
)
from log_parser_tpu.patterns.bank import PatternBank
from log_parser_tpu.patterns.regex.multidfa import pack_union_groups
from log_parser_tpu.runtime import faults
from log_parser_tpu.runtime.faults import FaultRegistry
from tests.helpers import make_pattern, make_pattern_set
from tests.test_multidfa import LINES, REGEXES


def _group_banks(max_states: int = 400, max_group: int = 6):
    """Union groups over the shared multidfa fixture regexes, forced into
    SEVERAL groups (small budget) so the kernel's grid dimension is
    exercised; built through the Python union construction."""
    entries = [(j, rx, ci) for j, (rx, ci) in enumerate(REGEXES)]
    groups, rejected = pack_union_groups(
        entries, max_states=max_states, max_group=max_group
    )
    assert groups, "fixture regexes must pack at least one union group"
    return [MultiDfaBank(md, list(range(len(keys)))) for keys, md in groups]


def _encode_tb(lines: list[str]):
    enc = encode_lines(lines)
    return jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)


def _numpy_reported(groups, arr_tb: np.ndarray) -> np.ndarray:
    """Independent reference: single-byte walk of each group's packed
    table in numpy — no jax, no pairing."""
    T, B = arr_tb.shape
    outs = []
    for g in groups:
        tbl = np.asarray(g._packed_byte_np, dtype=np.int64)
        s = np.full(B, g.start, np.int64)
        rep = np.full(B, g.start_reports, bool)
        for t in range(T):
            v = tbl[s * 256 + arr_tb[t].astype(np.int64)]
            s = v & g._STATE_MASK
            rep |= v >= g._REPORT_BIT
        outs.append(rep)
    return np.stack(outs, axis=1).astype(np.int32)


def _scan_reported(groups, lines_tb: jax.Array) -> np.ndarray:
    """The XLA scan tier's carry, finished: the exact computation cube()
    fuses when the kernel is off (lengths are unused by the gate-free
    pair_stepper)."""
    B = int(lines_tb.shape[1])
    lengths = jnp.zeros((B,), jnp.int32)
    pairs, ts = pack_byte_pairs(lines_tb)
    outs = []
    for g in groups:
        init, step, finish = g.pair_stepper(B, lengths)

        def f(c, xs):
            pair_t, t = xs
            return step(c, pair_t[0], pair_t[1], t), None

        final, _ = jax.lax.scan(f, init, (pairs, ts))
        outs.append(np.asarray(finish(final)[1]))
    return np.stack(outs, axis=1).astype(np.int32)


@pytest.fixture
def multi_engaged(monkeypatch):
    """Force the multi tier on hosts without the native library: the
    MatcherBanks gate sees a library while the union builder takes the
    Python construction."""
    import log_parser_tpu.native as native
    import log_parser_tpu.native.dfabuild as dfabuild

    monkeypatch.setattr(native, "get_lib", lambda: object())
    monkeypatch.setattr(dfabuild, "get_lib", lambda: None)


# ------------------------------------------------------------ kernel parity


def test_kernel_parity_both_strides():
    groups = _group_banks()
    lines_tb, _ = _encode_tb(LINES)
    ref = _scan_reported(groups, lines_tb)
    ref_np = _numpy_reported(groups, np.asarray(lines_tb))
    np.testing.assert_array_equal(ref, ref_np)
    plan, reason = mdp.build_dfa_plan(groups)
    assert reason in mdp.ADMITTED and plan is not None
    for stride in (2, 1):
        out = np.asarray(
            mdp.multidfa_reported_pallas(
                plan, lines_tb, stride=stride, interpret=True
            )
        )
        np.testing.assert_array_equal(out, ref, err_msg=f"stride {stride}")


def test_kernel_pair_stride_odd_length_tail():
    groups = _group_banks()
    lines_tb, _ = _encode_tb(LINES)
    odd_tb = lines_tb[: int(lines_tb.shape[0]) - 1]  # odd T
    assert int(odd_tb.shape[0]) % 2 == 1
    ref = _numpy_reported(groups, np.asarray(odd_tb))
    plan, _ = mdp.build_dfa_plan(groups)
    for stride in (2, 1):
        out = np.asarray(
            mdp.multidfa_reported_pallas(
                plan, odd_tb, stride=stride, interpret=True
            )
        )
        np.testing.assert_array_equal(out, ref, err_msg=f"stride {stride}")


def test_kernel_padding_class_rows():
    """Rows far shorter than T (and empty rows) ride the byte-0
    self-loop identity class; high random bytes exercise every byte
    column of the planes."""
    rng = np.random.default_rng(11)

    def _blob(n: int) -> str:
        raw = rng.integers(1, 256, size=n).astype(np.uint8)
        raw[(raw == 10) | (raw == 13)] = 32  # newlines would split rows
        return bytes(raw).decode("latin-1")

    lines = ["", "a", "panic: ", "x" * 3] + [
        _blob(int(n)) for n in rng.integers(0, 60, size=12)
    ]
    groups = _group_banks()
    lines_tb, _ = _encode_tb(lines)
    ref = _scan_reported(groups, lines_tb)
    np.testing.assert_array_equal(
        ref, _numpy_reported(groups, np.asarray(lines_tb))
    )
    plan, _ = mdp.build_dfa_plan(groups)
    out = np.asarray(mdp.multidfa_reported_pallas(plan, lines_tb, interpret=True))
    np.testing.assert_array_equal(out, ref)


def test_kernel_zero_match_batch():
    entries = [(0, "OutOfMemoryError", False), (1, "panic: ", False)]
    groups, _rej = pack_union_groups(entries, max_states=400)
    banks = [MultiDfaBank(md, list(range(len(keys)))) for keys, md in groups]
    lines_tb, _ = _encode_tb(["nothing here", "all quiet", ""])
    ref = _scan_reported(banks, lines_tb)
    assert not ref.any()
    plan, _ = mdp.build_dfa_plan(banks)
    out = np.asarray(mdp.multidfa_reported_pallas(plan, lines_tb, interpret=True))
    np.testing.assert_array_equal(out, ref)


def test_kernel_under_vmap_batched():
    """The micro-batcher vmaps the fused step over stacked requests; the
    kernel must batch identically."""
    groups = _group_banks()
    lines_tb, _ = _encode_tb(LINES)
    rev_tb = lines_tb[:, ::-1]
    ref0 = _scan_reported(groups, lines_tb)
    ref1 = _scan_reported(groups, rev_tb)
    plan, _ = mdp.build_dfa_plan(groups)
    f = jax.jit(
        jax.vmap(lambda x: mdp.multidfa_reported_pallas(plan, x, interpret=True))
    )
    out = np.asarray(f(jnp.stack([lines_tb, rev_tb])))
    np.testing.assert_array_equal(out[0], ref0)
    np.testing.assert_array_equal(out[1], ref1)


# ------------------------------------------------------------- admission


def _group_banks_with_entries(max_states: int = 400, max_group: int = 6):
    """Like ``_group_banks`` but keeps the GLOBAL entry keys on the banks
    and returns the per-group entries the split planner needs."""
    entries = [(j, rx, ci) for j, (rx, ci) in enumerate(REGEXES)]
    groups, _rej = pack_union_groups(
        entries, max_states=max_states, max_group=max_group
    )
    emap = {e[0]: e for e in entries}
    banks = [MultiDfaBank(md, keys) for keys, md in groups]
    return banks, [[emap[k] for k in keys] for keys, _ in groups]


def test_oversized_table_refused_without_entries():
    groups = _group_banks()
    plan, reason = mdp.build_dfa_plan(groups, budget=64 * 1024)
    assert plan is None and reason == "table_too_large"


def test_oversized_table_refused_when_singletons_inadmissible():
    """Entries enable re-splitting, but no split can beat the per-group
    VMEM floor (~736 KB at the nominal tile) under a 64 KB budget — the
    planner must refuse rather than loop."""
    banks, gents = _group_banks_with_entries()
    plan, reason = mdp.build_dfa_plan(banks, budget=64 * 1024, entries=gents)
    assert plan is None and reason == "table_too_large"


def test_admission_split_repartitions():
    """A budget above the per-group floor but below the packed fixture
    cost forces the admissible re-partition path: more groups, the same
    columns in the same order, and bit parity on the split plan. The
    fixture regexes ride ONE union group here (large ``max_group``) so
    its padded planes overflow 900 KB while the split halves fit."""
    banks, gents = _group_banks_with_entries(max_states=4096, max_group=64)
    assert len(banks) == 1
    plan, reason = mdp.build_dfa_plan(banks, budget=900 * 1024, entries=gents)
    assert plan is not None and reason == "split"
    assert plan.geometry["split"]
    assert len(plan.groups) > len(banks)
    assert [k for b in plan.groups for k in b.cols] == [
        k for b in banks for k in b.cols
    ]
    lines_tb, _ = _encode_tb(LINES)
    ref = _scan_reported(plan.groups, lines_tb)
    np.testing.assert_array_equal(
        ref, _numpy_reported(plan.groups, np.asarray(lines_tb))
    )
    out = np.asarray(mdp.multidfa_reported_pallas(plan, lines_tb, interpret=True))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.slow
def test_builtin_bank_admits_under_production_budget():
    """The PR's acceptance criterion, pinned: the builtin bank's union
    groups (python pack, disk-cached by the tool) admit under the
    production 12 MB VMEM budget. Mirrors hygiene check 15 in-process."""
    import importlib.util
    import pathlib
    import sys as _sys

    tool = (
        pathlib.Path(__file__).resolve().parents[1]
        / "tools"
        / "check_dfa_admission.py"
    )
    spec = importlib.util.spec_from_file_location("check_dfa_admission", tool)
    mod = importlib.util.module_from_spec(spec)
    _sys.modules["check_dfa_admission"] = mod
    spec.loader.exec_module(mod)
    report = mod.run_admission()
    assert report["admitted"], report
    assert report["geometry"]["vmemPerStep"] <= mdp.DFA_VMEM_BUDGET


def test_no_tile_for_unaligned_batch():
    groups = _group_banks()
    plan, _ = mdp.build_dfa_plan(groups)
    assert mdp.dfa_tile(plan, 12) is None  # no multiple-of-8 divisor
    assert mdp.dfa_tile(plan, 256) is not None


def test_vmem_estimate_monotone():
    assert mdp._vmem_estimate(256, 16, 128, 64) < mdp._vmem_estimate(
        512, 16, 128, 64
    )
    assert mdp._vmem_estimate(256, 8, 128, 64) < mdp._vmem_estimate(
        256, 16, 128, 64
    )
    assert mdp._vmem_estimate(256, 16, 64, 64) < mdp._vmem_estimate(
        256, 16, 128, 64
    )


# ------------------------------------------------- MatcherBanks integration

_KW = dict(
    shiftor_min_columns=10**9,
    prefilter_min_columns=10**9,
    multi_min_columns=2,
    bitglush_max_words=0,
)


def _fixture_bank() -> PatternBank:
    patterns = [
        make_pattern(f"p{j}", regex=rx, confidence=0.5, severity="LOW")
        for j, (rx, ci) in enumerate(REGEXES)
        if not ci and rx != "x?"  # bank-level: keep deterministic columns
    ]
    return PatternBank([make_pattern_set(patterns)])


def test_cube_parity_kernel_tier(multi_engaged, monkeypatch):
    bank = _fixture_bank()
    monkeypatch.delenv("LOG_PARSER_TPU_PALLAS_DFA", raising=False)
    off = MatcherBanks(bank, **_KW)
    assert off.multi_groups and not off.multidfa_use_pallas
    assert off.multidfa_pallas_reason == "off"
    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    on = MatcherBanks(bank, **_KW)
    assert on.multidfa_use_pallas
    assert on.multidfa_pallas_reason in mdp.ADMITTED
    assert on.dfa_kernel_geometry is not None
    assert on.dfa_kernel_geometry["states"] <= on.dfa_kernel_geometry["statesUnmin"]
    enc = encode_lines(LINES, 4096, 128, 8)
    lt, ln = jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)
    got = np.asarray(on.cube(lt, ln))
    want = np.asarray(off.cube(lt, ln))
    np.testing.assert_array_equal(got, want)
    assert want[: len(LINES)].any()
    assert on.dfa_kernel_active(int(ln.shape[0]))


def test_cube_parity_dense_rescan_cond_path(multi_engaged, monkeypatch):
    """More flagged rows than the sparse recovery capacity K forces the
    in-program ``lax.cond`` dense re-scan — with the kernel feeding the
    flags."""
    bank = _fixture_bank()
    lines = ["ERROR and FATAL", "panic: oops"] * 1024  # every row flagged
    enc = encode_lines(lines)
    lt, ln = jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)
    B = int(ln.shape[0])
    assert B >= 2048  # K = max(1024, B // 64) < n_flagged
    monkeypatch.delenv("LOG_PARSER_TPU_PALLAS_DFA", raising=False)
    off = MatcherBanks(bank, **_KW)
    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    on = MatcherBanks(bank, **_KW)
    np.testing.assert_array_equal(
        np.asarray(on.cube(lt, ln)), np.asarray(off.cube(lt, ln))
    )


def test_cube_oversized_table_falls_back(multi_engaged, monkeypatch):
    bank = _fixture_bank()
    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    monkeypatch.setattr(mdp, "DFA_VMEM_BUDGET", 64 * 1024)
    on = MatcherBanks(bank, **_KW)
    assert not on.multidfa_use_pallas
    assert on.multidfa_pallas_reason == "table_too_large"
    monkeypatch.delenv("LOG_PARSER_TPU_PALLAS_DFA")
    off = MatcherBanks(bank, **_KW)
    enc = encode_lines(LINES, 4096, 128, 8)
    lt, ln = jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)
    np.testing.assert_array_equal(
        np.asarray(on.cube(lt, ln)), np.asarray(off.cube(lt, ln))
    )


def test_cube_kernel_fault_raises(multi_engaged, monkeypatch):
    """A kernel failure is never swallowed in cube(): an injected kernel
    fault raises the device-classified error (the chaos_sweep --group
    kernel scenario, at unit scope), and the next trace runs the kernel
    with scan-tier parity."""
    from log_parser_tpu.runtime.engine import is_device_error

    bank = _fixture_bank()
    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    on = MatcherBanks(bank, **_KW)
    monkeypatch.delenv("LOG_PARSER_TPU_PALLAS_DFA")
    off = MatcherBanks(bank, **_KW)
    enc = encode_lines(LINES, 4096, 128, 8)
    lt, ln = jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)
    faults.install(FaultRegistry.parse("kernel_raise:1.0@times=1", seed=1))
    try:
        with pytest.raises(faults.InjectedDeviceFault) as exc_info:
            on.cube(lt, ln)
        assert is_device_error(exc_info.value)
        got = np.asarray(on.cube(lt, ln))  # the fault fired its one time
    finally:
        faults.install(None)
    assert on.multidfa_pallas_reason in mdp.ADMITTED
    np.testing.assert_array_equal(got, np.asarray(off.cube(lt, ln)))


def test_engine_counts_kernel_fault_as_fallback(monkeypatch):
    """Through the engine, the raised kernel fault is served by the
    golden fallback and COUNTED (fallbackCount), and the next request
    rides the kernel."""
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine
    from tests.test_engine_parity import assert_results_match

    sets = [make_pattern_set([
        make_pattern(f"p{j}", regex=rx, confidence=0.5, severity="LOW")
        for j, (rx, ci) in enumerate(REGEXES)
        if not ci and rx != "x?"
    ])]
    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS_DFA", "1")
    engine = AnalysisEngine(sets, ScoringConfig())
    # the bit tier off, so these columns pack union groups for the
    # kernel (as chip_smoke.py's union-DFA engine does)
    engine._matchers = MatcherBanks(engine.bank, bitglush_max_words=0)
    engine.fallback_to_golden = True
    golden = GoldenAnalyzer(sets, ScoringConfig())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs="\n".join(LINES))
    faults.install(FaultRegistry.parse("kernel_raise:1.0@times=1", seed=1))
    try:
        assert_results_match(engine.analyze(data), golden.analyze(data))
        assert engine.fallback_count == 1
        assert_results_match(engine.analyze(data), golden.analyze(data))
    finally:
        faults.install(None)
    assert engine.fallback_count == 1
    k = engine.kernel_stats.stats()
    assert k["reason"] in mdp.ADMITTED and k["kernelBatches"] >= 1, k


def test_engine_kernel_stats_counters():
    from log_parser_tpu.runtime.engine import KernelTierStats

    ks = KernelTierStats()
    assert ks.stats() == {
        "enabled": False,
        "reason": "off",
        "kernelBatches": 0,
        "kernelRows": 0,
        "xlaBatches": 0,
        "geometry": None,
    }
    geom = {"nGroups": 2, "sPad": 128}
    ks.note(128, active=True, enabled=True, reason="byte_classed",
            geometry=geom)
    ks.note(64, active=False, enabled=True, reason="no_tile", geometry=geom)
    ks.note(32, active=False, enabled=False, reason="off")  # not counted
    s = ks.stats()
    assert s["kernelBatches"] == 1 and s["kernelRows"] == 128
    assert s["xlaBatches"] == 1
    assert s["enabled"] is False and s["reason"] == "off"
    assert s["geometry"] is None  # last note carried no plan geometry


def test_reason_codes_documented():
    """Every runtime reason the tier can report is a REASONS key (the
    hygiene gate pins REASONS keys to docs/OPS.md rows)."""
    assert set(mdp.REASONS) >= {
        "byte_classed",
        "split",
        "off",
        "no_union_groups",
        "table_too_large",
        "no_tile",
    }
    assert "fault" not in mdp.REASONS  # kernel errors raise, never hide
    assert "ok" not in mdp.REASONS  # replaced by the admission provenance
    assert mdp.ADMITTED == {"byte_classed", "split"}
