"""Differential fuzz for the numpy-vectorized Corpus fallback (ISSUE 10).

The vectorized ingest (native/ingest._split_offsets +
_vectorized_encode) must be bit-identical to BOTH references:

- the scalar fallback it replaced — ``encode_lines(java_split_lines(s))``
  is the parity authority for split semantics, width/rows geometry,
  lengths, and needs_host flags;
- the native scanner, when the shared object loads on this host.

Hostile shapes pinned here: CR/LF/CRLF mixes (a lone ``\\r`` is CONTENT
under Java split semantics, ``\\r\\n`` is one separator), lone
surrogates (cannot strict-encode → the per-line scalar escape hatch),
empty blob, trailing-newline runs (Java drops ALL trailing empty
parts), lines past ``max_line_bytes``, multi-byte UTF-8 straddling the
width cap, and NUL content. Plus: the line-cache keying lane
(``dedup_slots``) against the per-line dict loop it replaced, and
StreamNormalizer chunk-split invariance feeding the vectorized path.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

import log_parser_tpu.native.ingest as ingest_mod
from log_parser_tpu.golden.javacompat import java_split_lines
from log_parser_tpu.native import available
from log_parser_tpu.native.ingest import Corpus, StreamNormalizer
from log_parser_tpu.ops.encode import encode_lines
from log_parser_tpu.runtime.linecache import dedup_slots, line_keys

HOSTILE = [
    "",
    "\n",
    "\r",
    "\r\n",
    "\n\n",
    "a",
    "a\n",
    "a\r\nb",
    "a\rb",          # lone \r is content, NOT a separator
    "a\r\r\nb",      # first \r content, second consumed by the CRLF sep
    "a\r\rb",
    "\na",
    "\ra",
    "x\n\n\n",       # ALL trailing empty parts dropped
    "x\r\n\r\n",
    "\n\r\n\r",      # trailing part "\r" is non-empty — kept
    "\r\r\r",
    "€é漢\n字",
    "a\x00b\nc",     # NUL content → needs_host
    "\ud800oops\nok",  # lone surrogate → scalar escape hatch
    "ok\n\ud800",
    "a" * 9000 + "\nshort",  # > max_line_bytes
    ("€" * 40 + "\n") * 5,   # multi-byte UTF-8 at the width cap
    "tail no nl",
    "mél\r\nx",
    "  \n\t\n",
]

KWARG_VARIANTS = [
    {},
    {"max_line_bytes": 16},
    {"pad_to_multiple": 8, "min_rows": 5},
]


@pytest.fixture
def no_native(monkeypatch):
    """Force the vectorized fallback regardless of host toolchain."""
    monkeypatch.setattr(ingest_mod, "get_lib", lambda: None)


def _fuzz_cases(n=250, seed=7):
    rng = random.Random(seed)
    alphabet = "ab\r\n \t€é\x00"
    return [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 120)))
        for _ in range(n)
    ]


def _assert_corpus_matches_scalar(logs: str, **kw) -> None:
    parts = java_split_lines(logs)
    corpus = Corpus(logs, **kw)
    assert list(corpus) == parts
    try:
        ref = encode_lines(parts, **kw)
    except UnicodeEncodeError:
        # scalar encode raises only where Corpus also took its scalar
        # path; nothing further to compare at the array level
        return
    enc = corpus.encoded
    assert np.array_equal(ref.u8, enc.u8)
    assert np.array_equal(ref.lengths, enc.lengths)
    assert np.array_equal(ref.needs_host, enc.needs_host)
    assert ref.n_lines == enc.n_lines
    for i, part in enumerate(parts):
        assert corpus.line(i) == part
        assert corpus.line_key_bytes(i) == part.encode(
            "utf-8", errors="replace"
        )


class TestVectorizedVsScalar:
    @pytest.mark.parametrize("logs", HOSTILE)
    def test_hostile_cases(self, no_native, logs):
        for kw in KWARG_VARIANTS:
            _assert_corpus_matches_scalar(logs, **kw)

    def test_fuzz(self, no_native):
        for logs in _fuzz_cases():
            _assert_corpus_matches_scalar(logs)

    def test_fuzz_narrow_width(self, no_native):
        for logs in _fuzz_cases(n=80, seed=11):
            _assert_corpus_matches_scalar(logs, max_line_bytes=16)
            _assert_corpus_matches_scalar(
                logs, pad_to_multiple=8, min_rows=5
            )

    def test_surrogate_falls_back_to_scalar_strings(self, no_native):
        corpus = Corpus("ok\n\ud800bad")
        assert corpus._lines is not None  # the escape hatch, not arrays
        assert corpus.key_view() is None
        assert corpus.line(1) == "\ud800bad"  # original str, unreplaced
        assert corpus.line_key_bytes(1) == "\ud800bad".encode(
            "utf-8", errors="replace"
        )

    def test_clean_input_is_blob_backed(self, no_native):
        corpus = Corpus("a\nbb\nccc")
        blob, starts, ends = corpus.key_view()
        n = corpus.n_lines
        got = [
            blob[a:b]
            for a, b in zip(starts[:n].tolist(), ends[:n].tolist())
        ]
        assert got == [b"a", b"bb", b"ccc"]


@pytest.mark.skipif(not available(), reason="native library not loadable")
class TestVectorizedVsNative:
    @pytest.mark.parametrize("logs", HOSTILE)
    def test_hostile_cases(self, logs, monkeypatch):
        native_corpus = Corpus(logs)
        monkeypatch.setattr(ingest_mod, "get_lib", lambda: None)
        vec_corpus = Corpus(logs)
        assert list(native_corpus) == list(vec_corpus)
        a, b = native_corpus.encoded, vec_corpus.encoded
        assert np.array_equal(a.u8, b.u8)
        assert np.array_equal(a.lengths, b.lengths)
        assert np.array_equal(a.needs_host, b.needs_host)
        assert a.n_lines == b.n_lines
        for i in range(a.n_lines):
            assert native_corpus.line_key_bytes(i) == vec_corpus.line_key_bytes(i)


def _key_bytes(keys, s: int) -> bytes:
    """The content bytes key ``s`` holds (its words up to its length)."""
    return keys.words[keys.rows[s]].tobytes()[: int(keys.lengths[s])]


class TestDedupSlots:
    """The content-keyed dedup lane vs the per-line dict loop it replaced."""

    def _reference(self, corpus):
        slot_of: dict[bytes, int] = {}
        reps: list[int] = []
        line_slot = []
        for i in range(corpus.n_lines):
            lb = corpus.line_key_bytes(i)
            s = slot_of.get(lb)
            if s is None:
                s = len(reps)
                slot_of[lb] = s
                reps.append(i)
            line_slot.append(s)
        keys = list(slot_of)
        counts = np.bincount(
            np.asarray(line_slot, dtype=np.int64), minlength=len(reps)
        )
        return line_slot, reps, keys, counts

    def test_fuzz_matches_dict_loop(self, no_native):
        rng = random.Random(3)
        pool = (
            ["err %d" % i for i in range(8)]
            + ["x" * 9000 + str(i) for i in range(3)]  # truncated, ambiguous
            + ["", "a\x00b", "€é", "a" * 63, "a" * 64, "a" * 65]
        )
        for _ in range(150):
            lines = [rng.choice(pool) for _ in range(rng.randrange(0, 60))]
            corpus = Corpus("\n".join(lines))
            got = dedup_slots(corpus)
            assert got is not None
            line_slot, reps, keys, counts = got
            ref_slot, ref_reps, ref_keys, ref_counts = self._reference(corpus)
            assert line_slot.tolist() == ref_slot
            assert reps.tolist() == ref_reps
            # a storable key is the line's bytes, with the probe the
            # bytes alone give; the rest (needs_host) are never stored
            needs_host = corpus.encoded.needs_host[reps]
            assert keys.storable.tolist() == (~needs_host).tolist()
            for s in np.flatnonzero(keys.storable).tolist():
                assert _key_bytes(keys, s) == ref_keys[s]
                assert keys.probes[s] == line_keys([ref_keys[s]]).probes[0]
            assert counts.tolist() == ref_counts.tolist()

    def test_long_lines_grouped_exactly(self, no_native):
        # same truncated prefix + same length, different tails: the u8
        # matrix cannot tell them apart — the blob regroup must
        a = "x" * 5000 + "A"
        b = "x" * 5000 + "B"
        corpus = Corpus("\n".join([a, b, a, b, a]))
        line_slot, reps, keys, counts = dedup_slots(corpus)
        assert line_slot.tolist() == [0, 1, 0, 1, 0]
        assert counts.tolist() == [3, 2]
        # both keys hold the same truncated words and are never stored
        assert _key_bytes(keys, 0) == _key_bytes(keys, 1)
        assert not keys.storable.any()

    def test_surrogate_corpus_returns_none(self, no_native):
        # lone-surrogate corpora key from their encode like any other:
        # the surrogate line is needs_host, the clean line storable
        corpus = Corpus("\ud800x\nok\n\ud800x")
        line_slot, reps, keys, counts = dedup_slots(corpus)
        assert line_slot.tolist() == [0, 1, 0]
        assert keys.storable.tolist() == [False, True]
        assert _key_bytes(keys, 1) == b"ok"

    def test_empty_string_is_one_empty_line(self, no_native):
        # Java split: "" -> [""] — one (empty) line, one slot
        line_slot, reps, keys, counts = dedup_slots(Corpus(""))
        assert line_slot.tolist() == [0]
        assert _key_bytes(keys, 0) == b"" and keys.storable.tolist() == [True]
        assert keys.probes[0] == line_keys([b""]).probes[0]

    def test_zero_line_corpus(self, no_native):
        # "\n" -> ["", ""] -> all trailing empties dropped -> no lines
        line_slot, reps, keys, counts = dedup_slots(Corpus("\n"))
        assert line_slot.size == 0 and keys.rows.size == 0


class TestStreamNormalizerChunkInvariance:
    """Arbitrary chunkings of one byte stream must produce the same
    normalized text — and therefore the same vectorized Corpus — as the
    joined blob."""

    def test_multibyte_splits(self, no_native):
        text = "héllo €uro\n漢字 line\nplain\r\ntail€"
        blob = text.encode("utf-8")
        joined_corpus = Corpus(text)
        rng = random.Random(5)
        for _ in range(50):
            cuts = sorted(
                rng.randrange(0, len(blob) + 1)
                for _ in range(rng.randrange(0, 6))
            )
            norm = StreamNormalizer()
            pieces = []
            lo = 0
            for cut in cuts + [len(blob)]:
                pieces.append(norm.feed(blob[lo:cut]))
                lo = cut
            pieces.append(norm.flush())
            reassembled = "".join(pieces)
            assert reassembled == text
            corpus = Corpus(reassembled)
            assert np.array_equal(
                corpus.encoded.u8, joined_corpus.encoded.u8
            )
            assert list(corpus) == list(joined_corpus)

    def test_truncated_trailing_sequence(self, no_native):
        blob = "ok line\n€".encode("utf-8")[:-1]  # truncated 3-byte seq
        norm = StreamNormalizer()
        out = norm.feed(blob) + norm.flush()
        assert out == blob.decode("utf-8", errors="replace")
        corpus = Corpus(out)
        assert corpus.n_lines == 2
        assert bool(corpus.encoded.needs_host[1])  # U+FFFD is non-ASCII
