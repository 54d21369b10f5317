"""Row-padding rung properties (``ops/encode._pad_rows``).

The rung ladder bounds both padding waste and the compile-shape set:
plain pow2 ≤ 8k rows, quarter rungs to 64k (≤25% waste), eighth rungs
above (≤12.5% waste). The large-batch branch is otherwise exercised only
by 1M-line bench runs on hardware, so its arithmetic is pinned here.
"""

from __future__ import annotations

import pytest

from log_parser_tpu.ops.encode import (
    _EIGHTH_RUNG_FLOOR,
    _QUARTER_RUNG_FLOOR,
    _pad_rows,
)


@pytest.mark.parametrize(
    "n,expected",
    [
        (1, 1),
        (100, 128),
        (8192, 8192),  # pow2 branch, exact at the floor
        (8193, 10240),  # first quarter rung: 8192 + 2048
        (65536, 65536),  # quarter branch, exact at the octave edge
        (65537, 73728),  # first eighth rung: 65536 + 8192
        (200000, 212992),  # 131072 + 5 * 16384 (was 229376 on quarters)
        (1000000, 1048576),  # lands on the pow2 edge either way
    ],
)
def test_pad_rows_values(n, expected):
    assert _pad_rows(n, 1) == expected


def test_pad_rows_properties():
    prev = 0
    for n in range(1, 300000, 997):
        rows = _pad_rows(n, 1)
        assert rows >= n
        assert rows >= prev  # monotonic in n
        prev = rows
        if n > _EIGHTH_RUNG_FLOOR:
            assert (rows - n) / n <= 0.125
            # eighth rungs above 64k are multiples of 8192: keeps every
            # batch-axis alignment downstream (128 lanes, 8 sublanes,
            # bitglush_pallas tile divisibility) trivially satisfied
            assert rows % 8192 == 0
        elif n > _QUARTER_RUNG_FLOOR:
            assert (rows - n) / n <= 0.25
            assert rows % 1024 == 0


def test_pad_rows_min_rows_divisibility():
    for min_rows in (1, 3, 7, 8, 48):
        for n in (1, 5000, 70000, 200001):
            rows = _pad_rows(n, min_rows)
            assert rows % min_rows == 0
            assert rows >= n


def test_nul_line_routes_to_host():
    from log_parser_tpu.ops.encode import encode_lines

    enc = encode_lines(["plain ok", "has\x00nul", "also fine"])
    assert not enc.needs_host[0]
    assert enc.needs_host[1]  # content NUL -> host re-match
    assert not enc.needs_host[2]


def test_nul_line_routes_to_host_native():
    from log_parser_tpu.native import available
    from log_parser_tpu.native.ingest import Corpus

    if not available():
        pytest.skip("native library unavailable")
    enc = Corpus("plain ok\nhas\x00nul\nalso fine").encoded
    assert not enc.needs_host[0]
    assert enc.needs_host[1]
    assert not enc.needs_host[2]


def test_bit_tiers_pad0_transparent_for_builtin_bank():
    """Byte 0 is stripped from every device byteset (NUL lines are
    needs_host), so both bit tiers must take the gate-free stepper —
    a regression here silently re-adds two [B, W] selects per byte."""
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    mb = MatcherBanks(PatternBank(load_builtin_pattern_sets()))
    assert mb.shiftor is not None and mb.shiftor.pad0_transparent
    assert mb.bitglush is not None and mb.bitglush.pad0_transparent
