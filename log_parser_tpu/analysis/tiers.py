"""Device-compilability classifier: predict a regex's matcher tier.

Predicts which tier a column lands in *without building an engine*, by
running the SAME compile entry points :class:`PatternBank._intern_column`
runs (patterns/bank.py) and catching the same typed exceptions:

==========  =========================================================
tier        meaning
==========  =========================================================
shiftor     fixed byte-class sequences — bit-parallel Shift-Or capable
dfa         compiles to a packed DFA (dense / union multi-DFA tiers)
host        automaton path declined — host ``re`` fallback column
skipped     even the host translation fails — pattern is dropped
==========  =========================================================

``reason_code`` cites :mod:`log_parser_tpu.patterns.regex.reasons` via
the exception's own ``code`` attribute, so the prediction and an actual
build failure can never disagree on the reason — they are the same
object. ``bit_capable`` is the orthogonal capability bit for the
gather-free bit-parallel engine (ops/match.py admits bit programs per
word budget; capability here is the budget-independent part: the
program compiles and fits the column position cap).

The classifier is deliberately *capability*-level: MatcherBanks picks
the executed tier per bank size (e.g. Shift-Or only beyond
``shiftor_min_columns``), but artifacts are what the build produces and
what the parity test (tests/test_patlint.py) pins column-for-column.
"""

from __future__ import annotations

import dataclasses
import re

from log_parser_tpu.golden.javacompat import compile_java_regex
from log_parser_tpu.patterns.regex import reasons
from log_parser_tpu.patterns.regex.bitprog import (
    BitUnsupportedError,
    compile_bitprog,
)
from log_parser_tpu.patterns.regex.cache import compile_regex_to_dfa_cached
from log_parser_tpu.patterns.regex.dfa import CompiledDfa, DfaLimitError
from log_parser_tpu.patterns.regex.literals import (
    exact_sequences,
    extract_literals,
)
from log_parser_tpu.patterns.regex.parser import (
    RegexUnsupportedError,
    parse_java_regex,
)

# mirror of ops/match.py MatcherBanks.BITGLUSH_MAX_COLUMN_POSITIONS — the
# platform-independent per-column cap (asserted equal in test_patlint.py)
BIT_MAX_COLUMN_POSITIONS = 512

SHIFTOR, DFA, HOST, SKIPPED = "shiftor", "dfa", "host", "skipped"


@dataclasses.dataclass
class TierPrediction:
    regex: str
    case_insensitive: bool
    tier: str  # shiftor | dfa | host | skipped
    reason_code: str  # reasons.* — SUPPORTED unless host/skipped
    detail: str = ""
    bit_capable: bool = False
    bit_reason_code: str = ""  # reasons.* when not bit_capable
    literal_count: int = 0  # extractable required literals (0 = none)
    max_literal_len: int = 0  # longest required literal in bytes
    dfa: CompiledDfa | None = None  # kept for subsumption reuse

    def to_json(self) -> dict:
        out = {
            "regex": self.regex,
            "tier": self.tier,
            "reason": self.reason_code,
            "bitCapable": self.bit_capable,
            "literals": self.literal_count,
        }
        if self.detail:
            out["detail"] = self.detail
        if self.bit_reason_code:
            out["bitReason"] = self.bit_reason_code
        return out


def classify_regex(regex: str, case_insensitive: bool = False) -> TierPrediction:
    """Predict the matcher tier of one column regex.

    Runs host compile → parse → exact_sequences → extract_literals →
    DFA → bit program, in the bank's order, reusing the bank's own disk
    cache for the DFA so a lint pass warms the subsequent build.
    """
    try:
        compile_java_regex(regex, case_insensitive)
    except (re.error, ValueError) as exc:
        return TierPrediction(
            regex=regex,
            case_insensitive=case_insensitive,
            tier=SKIPPED,
            reason_code=reasons.RX_SYNTAX,
            detail=str(exc),
        )

    try:
        node = parse_java_regex(regex, case_insensitive)
    except RegexUnsupportedError as exc:
        literal_count, max_len = _lenient_literals(regex, case_insensitive)
        return TierPrediction(
            regex=regex,
            case_insensitive=case_insensitive,
            tier=HOST,
            reason_code=exc.code,
            detail=str(exc),
            literal_count=literal_count,
            max_literal_len=max_len,
        )

    exact = exact_sequences(node)
    literals = extract_literals(node)
    literal_count = len(literals) if literals else 0
    max_len = max((len(l.text) for l in literals), default=0) if literals else 0

    try:
        dfa = compile_regex_to_dfa_cached(regex, case_insensitive, node=node)
    except (RegexUnsupportedError, DfaLimitError) as exc:
        if exact is None:
            return TierPrediction(
                regex=regex,
                case_insensitive=case_insensitive,
                tier=HOST,
                reason_code=exc.code,
                detail=str(exc),
                literal_count=literal_count,
                max_literal_len=max_len,
            )
        # exact_seqs survive a DFA decline: the column still rides
        # Shift-Or (bank.py keeps exact_seqs; MatcherBanks never needs
        # the DFA for a shiftor column)
        dfa = None

    bit_capable, bit_reason = _bit_capability(node)
    return TierPrediction(
        regex=regex,
        case_insensitive=case_insensitive,
        tier=SHIFTOR if exact is not None else DFA,
        reason_code=reasons.SUPPORTED,
        bit_capable=bit_capable,
        bit_reason_code=bit_reason,
        literal_count=literal_count,
        max_literal_len=max_len,
        dfa=dfa,
    )


def _bit_capability(node) -> tuple[bool, str]:
    try:
        prog = compile_bitprog(node)
    except BitUnsupportedError as exc:
        return False, exc.code
    if prog.n_positions > BIT_MAX_COLUMN_POSITIONS:
        return False, reasons.BIT_TOO_WIDE
    return True, ""


def _lenient_literals(regex: str, case_insensitive: bool) -> tuple[int, int]:
    """Literal prefilter stats for a host-only column, via the same
    lenient language-widening parse the bank attempts (bank.py)."""
    try:
        literals = extract_literals(
            parse_java_regex(regex, case_insensitive, lenient=True)
        )
    except (RegexUnsupportedError, ValueError):
        return 0, 0
    if not literals:
        return 0, 0
    return len(literals), max(len(l.text) for l in literals)
