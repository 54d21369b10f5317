"""Streaming follow-mode: incremental tail ingestion with carried scan
state and monotone early-emit.

The one-shot engine sees a complete post-mortem blob; pod logs arrive as
*tails*, and the operator wants time-to-first-detection, not
time-to-post-mortem. This module is the session layer that turns the
batch pipeline into a streaming one without forking its semantics:

- **Reassembly.** Raw byte chunks pass through
  :class:`~log_parser_tpu.native.ingest.StreamNormalizer` (incremental
  UTF-8 ``errors="replace"`` — split-invariant, so a multi-byte sequence
  cut by a chunk boundary decodes exactly as the joined blob would) and
  an incremental ``\\r?\\n`` splitter that holds a trailing ``\\r`` until
  the next byte disambiguates separator from content. Every line is
  device-scored exactly once, when it completes.

- **Carried scan state.** The line that straddles a chunk boundary is
  not rescanned: :meth:`FusedMatchScore.host_carry` (ops/fused.py →
  ops/match.py) exposes the match cube's per-line automata — Shift-Or
  bit registers, dense-DFA states, union-DFA states — as a resumable
  carry that feeds forward across chunks and snapshots the exact cube
  row at any prefix. Whole lines completed inside one chunk batch
  through the normal residual cube dispatch; repeat lines are served by
  the line cache and never touch either path.

- **Monotone early-emit.** After each chunk the session re-finalizes the
  window (context/proximity/chronological factors legitimately move as
  the window grows; the frequency read is a rolled-back peek under
  ``state_lock`` — nothing is recorded until close). Events at or above
  the emit threshold produce ``emit`` frames; any change to an already
  emitted event — firming up, shifting down, or vanishing — produces an
  explicit ``revised`` frame. An emitted score is never silently
  retracted.

- **Replay theorem.** ``close()`` rebuilds the full-blob
  :class:`Corpus`, splices the engine's own override cube over the
  per-line bits accumulated above, and runs the exact ``_finish``
  sequence (read-before-record frequency, ``finalize_batch``, assembly)
  under ``_request_scope`` + ``state_lock``. Feeding a blob in N chunks
  of any split therefore yields final scores bit-identical to one-shot
  ``analyze()`` on the concatenation — pinned by tests/test_stream.py.

- **Reliability.** Sessions are first-class citizens of the existing
  layer: :class:`StreamManager` admits each open session through the
  shared admission gate (open sessions count against the in-flight
  budget) and reaps idle ones after ``--stream-ttl-s``; the
  ``quarantine`` fault site fires per chunk with the chunk's content as
  the key, so a poison frame strikes its own fingerprint and kills the
  SESSION, not the server; a non-poison device fault flips the session
  to a golden continuation (host path) that still closes with committed
  frequency state; an ``apply_library`` hot-swap is detected by reload
  epoch and the session re-bases — re-scores its window under the new
  bank inside the next chunk's ``_request_scope`` — emitting ``revised``
  frames for anything the new library no longer supports.
"""

from __future__ import annotations

import math
import threading
import time
import uuid

import numpy as np

from log_parser_tpu import _clock as pclock
from log_parser_tpu.golden.engine import (
    build_metadata,
    build_summary,
    extract_context,
)
from log_parser_tpu.models.analysis import AnalysisResult, MatchedEvent
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.native.ingest import Corpus, StreamNormalizer
from log_parser_tpu.ops.encode import DEFAULT_MAX_LINE_BYTES, _pad_rows
from log_parser_tpu.runtime import faults
from log_parser_tpu.runtime.finalize import finalize_batch
from log_parser_tpu.runtime.linecache import line_keys, records_from_bits
from log_parser_tpu.runtime.quarantine import fingerprint as quarantine_fingerprint

DEFAULT_EMIT_THRESHOLD = 0.0
DEFAULT_STREAM_TTL_S = 300.0

# The streaming frame vocabulary (docs/OPS.md "Streaming" runbook rows —
# pinned by tools/hygiene.py check 12). Every NDJSON / gRPC frame a
# session produces carries exactly one of these in its "type" field.
FRAME_TYPES = {
    "emit": "event crossed the emit threshold for the first time",
    "revised": "an emitted event's score changed or was retracted",
    "final": "close(): the full one-shot-identical AnalysisResult",
    "error": "structured failure; the session is dead after this frame",
}


class StreamError(Exception):
    """Structured session failure: carried verbatim into an ``error``
    frame. ``reason`` is a stable machine code (``closed``, ``poison``,
    ``fault``, ``ttl``, ``admission``, ``internal``)."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


def _is_pure_line(line: str) -> bytes | None:
    """The ingest-normalized bytes of ``line`` when its device bits are a
    pure function of content — ASCII, no content NUL, within the device
    line budget, no lone surrogates — else None. Mirrors the stable half
    of ``encode_lines``'s ``needs_host`` verdict (the width-dependent
    ``len > device_width`` term is handled by the override splice, which
    covers every ``needs_host`` line of the frame's corpus)."""
    try:
        b = line.encode("utf-8")
    except UnicodeEncodeError:
        return None
    if not b.isascii() or b"\x00" in b or len(b) > DEFAULT_MAX_LINE_BYTES:
        return None
    return b


def _scores_equal(a: float, b: float) -> bool:
    if math.isnan(a) and math.isnan(b):
        return True
    return a == b


class StreamSession:
    """One follow-mode session: feed byte chunks, receive frames, close
    for the one-shot-identical final result. Thread-safe per session;
    sessions only hold engine-wide resources (``_request_scope``,
    ``state_lock``) inside a single ``feed``/``close`` call, never while
    idle between chunks — so a hot reload quiesces normally and the
    session re-bases on its next chunk."""

    def __init__(
        self,
        engine,
        session_id: str,
        emit_threshold: float = DEFAULT_EMIT_THRESHOLD,
        manager: "StreamManager | None" = None,
    ):
        self.engine = engine
        self.session_id = session_id
        self.emit_threshold = float(emit_threshold)
        self.manager = manager
        self._lock = threading.RLock()
        self._start = pclock.mono()
        self.last_active = manager.clock() if manager else pclock.mono()

        self._normalizer = StreamNormalizer()
        self._text = ""  # full decoded window (the would-be blob)
        self._lines: list[str] = []  # completed (newline-terminated) lines
        self._bits: list[np.ndarray | None] = []  # pre-override rows
        self._pending = ""  # text since the last line terminator
        self._tail_fed = 0  # chars of _pending already fed to the carry
        self._tail_pure = True
        self._carry = engine.fused.host_carry()
        if self._carry is not None:
            self._carry.reset()
        self._epoch = engine.reload_epoch

        self.mode = "device"  # "device" | "golden"
        self.closed = False
        self.kill_reason: str | None = None
        # optional operator-facing detail for the dead-session error
        # frame — a "migrated" kill names the new owner here so the
        # client knows where to reconnect
        self.kill_message: str | None = None
        self._seq = 0
        self._chunks = 0  # fed chunks, for the session span
        # (line_idx, pattern_id) -> last reported score, for events that
        # crossed the emit threshold: the monotone-refinement ledger
        self._ledger: dict[tuple[int, str], float] = {}

    # ---------------------------------------------------------------- frames

    def _frame(self, ftype: str, **fields) -> dict:
        self._seq += 1
        frame = {"type": ftype, "session": self.session_id, "seq": self._seq}
        frame.update(fields)
        if self.manager is not None:
            self.manager._note_frame(ftype)
        return frame

    def _error_frame(self, err: StreamError) -> dict:
        return self._frame("error", reason=err.reason, message=str(err))

    def _diff_frames(self, current: dict[tuple[int, str], float]) -> list[dict]:
        """Ledger reconciliation: emit/revised frames for this window
        evaluation. ``current`` maps (0-based line, pattern id) to score."""
        frames: list[dict] = []
        for key, score in current.items():
            line_idx, pid = key
            prev = self._ledger.get(key)
            if prev is None:
                if score >= self.emit_threshold:
                    frames.append(
                        self._frame(
                            "emit", line=line_idx + 1, patternId=pid,
                            score=score,
                        )
                    )
                    self._ledger[key] = score
            elif not _scores_equal(prev, score):
                frames.append(
                    self._frame(
                        "revised", line=line_idx + 1, patternId=pid,
                        score=score, previousScore=prev,
                        retracted=bool(score < self.emit_threshold),
                    )
                )
                self._ledger[key] = score
        for key in [k for k in self._ledger if k not in current]:
            prev = self._ledger.pop(key)
            frames.append(
                self._frame(
                    "revised", line=key[0] + 1, patternId=key[1],
                    score=None, previousScore=prev, retracted=True,
                )
            )
        return frames

    # ------------------------------------------------------------- lifecycle

    def kill(self, reason: str, message: str | None = None) -> None:
        """Terminate the session (poison chunk, injected fault, TTL reap,
        transport drop, migration/drain). Idempotent; releases the
        admission slot. ``message`` rides the dead-session ``error``
        frame — a migration kill carries the new owner's URL."""
        with self._lock:
            if self.closed:
                return
            self.closed = True
            self.kill_reason = reason
            self.kill_message = message
        self._commit_session_span(reason)
        if self.manager is not None:
            self.manager._discard(self, reason)

    def _touch(self) -> None:
        self.last_active = (
            self.manager.clock() if self.manager else pclock.mono()
        )

    # ----------------------------------------------------------- span hooks

    def _note_chunk_span(self, t0: float, n_bytes: int, n_frames: int,
                         error: str | None = None) -> None:
        """Stage one per-chunk child span under the session's trace
        (trace id == session id, so mesh/demux work keyed by the session
        attributes here too)."""
        attrs = {"bytes": n_bytes, "frames": n_frames, "mode": self.mode}
        if error:
            attrs["error"] = error
        self.engine.obs.spans.annotate(
            self.session_id, "chunk", time.perf_counter() - t0, attrs=attrs
        )

    def _commit_session_span(self, outcome: str) -> None:
        """Commit the session's long-lived span; the chunk/rebase
        children staged under the session id attach here. force=True:
        sessions are rare relative to requests and the only place
        per-chunk causality lives — sampling must never drop them."""
        eng = self.engine
        eng.obs.spans.end_trace(
            self.session_id,
            duration_s=pclock.mono() - self._start,
            tenant=eng.obs_tenant,
            name="session",
            attrs={
                "outcome": outcome,
                "chunks": self._chunks,
                "frames": self._seq,
                "lines": len(self._lines),
                "mode": self.mode,
            },
            force=True,
        )

    # --------------------------------------------------------------- feeding

    def feed(self, chunk: bytes) -> list[dict]:
        """Ingest one byte chunk; returns the frames it produced. A dead
        session answers every feed with a single ``error`` frame."""
        with self._lock:
            if self.closed:
                return [
                    self._frame(
                        "error", reason=self.kill_reason or "closed",
                        message=self.kill_message or "session is closed",
                    )
                ]
            self._touch()
            t0 = time.perf_counter()
            try:
                with self.engine._request_scope():
                    frames = self._feed_in_scope(bytes(chunk))
                self._chunks += 1
                self._note_chunk_span(t0, len(chunk), len(frames))
                return frames
            except StreamError as err:
                frame = self._error_frame(err)
                # stage the chunk span BEFORE kill commits the session
                # trace, so the fatal chunk attaches to the tree
                self._note_chunk_span(t0, len(chunk), 1, error=err.reason)
                self.kill(err.reason)
                return [frame]
            except Exception as exc:  # wedged sessions are forbidden
                frame = self._frame(
                    "error", reason="internal", message=repr(exc)
                )
                self._note_chunk_span(t0, len(chunk), 1, error="internal")
                self.kill("internal")
                return [frame]

    def _feed_in_scope(self, chunk: bytes) -> list[dict]:
        eng = self.engine
        if eng.reload_epoch != self._epoch:
            self._rebase()
        text = self._normalizer.feed(chunk)
        try:
            faults.fire("stream", key=text)
        except Exception as exc:
            raise StreamError("fault", f"stream fault: {exc!r}") from exc
        if self.manager is not None:
            self.manager._note_chunk(len(chunk))
        self._text += text
        if self.mode == "golden":
            return self._provisional_golden()
        batch_idx = self._ingest_text(text)
        try:
            self._chunk_device_step(text, batch_idx)
        except Exception as exc:
            self._handle_device_exc(exc, text)
            return self._provisional_golden()
        return self._provisional_device()

    def _ingest_text(self, text: str) -> list[int]:
        """Incremental split: complete lines, keep the partial tail (and
        its carry) warm. Returns indices of completed lines that still
        need the chunk's residual cube dispatch."""
        eng = self.engine
        buf = self._pending + text
        pieces = buf.split("\n")
        batch_idx: list[int] = []
        for piece in pieces[:-1]:
            line = piece[:-1] if piece.endswith("\r") else piece
            idx = len(self._lines)
            self._lines.append(line)
            pure = _is_pure_line(line)
            if pure is None:
                self._bits.append(None)
                self._tail_pure = False  # consistency; reset below
            else:
                row = self._cache_lookup(pure)
                if row is not None:
                    self._bits.append(row)
                elif self._carry is not None and self._tail_pure:
                    # the straddler (or an in-chunk line): finish it on
                    # the carried automata state instead of rescanning
                    rest = line[self._tail_fed:]
                    if rest:
                        self._carry.feed(
                            rest.encode("utf-8", errors="replace")
                        )
                    self._bits.append(self._carry.snapshot_bits())
                    self._cache_populate(pure, self._bits[-1])
                else:
                    self._bits.append(None)  # filled by the chunk batch
                    batch_idx.append(idx)
            if self._carry is not None:
                self._carry.reset()
            self._tail_fed = 0
            self._tail_pure = True
        self._pending = pieces[-1]
        # advance the tail carry, holding back a trailing "\r" (separator
        # vs content is decided by the NEXT character) and stopping for
        # good once the tail is no longer device-pure
        if self._tail_pure and _is_pure_line(self._pending) is None:
            self._tail_pure = False
        if self._carry is not None and self._tail_pure:
            target = len(self._pending)
            if self._pending.endswith("\r"):
                target -= 1
            if target > self._tail_fed:
                self._carry.feed(
                    self._pending[self._tail_fed:target].encode(
                        "utf-8", errors="replace"
                    )
                )
                self._tail_fed = target
        return batch_idx

    def _cache_lookup(self, line_bytes: bytes) -> np.ndarray | None:
        cache = self.engine.line_cache
        if cache is None:
            return None
        found = cache.lookup(line_keys([line_bytes]))
        if found.row[0] < 0:
            return None
        return cache.unpack(found.packed)[0]

    def _cache_populate(self, line_bytes: bytes, row: np.ndarray) -> None:
        cache = self.engine.line_cache
        if cache is not None:
            cache.populate(
                line_keys([line_bytes]), np.asarray(row, dtype=bool)[None, :]
            )

    def _chunk_device_step(self, chunk_text: str, batch_idx: list[int]) -> None:
        """The chunk's device dispatch, under the watchdog with the same
        chaos points as the one-shot path — keyed by THIS chunk's content,
        so a ``match=`` poison spec fires on (and quarantines) exactly the
        chunk that carries it."""
        eng = self.engine

        def _device_step():
            faults.fire("quarantine", key=chunk_text)  # conlint: contained-by-caller (watchdog.run)
            faults.fire("device")  # conlint: contained-by-caller (watchdog.run)
            if not batch_idx:
                return None
            lines_b = [
                self._lines[i].encode("utf-8", errors="replace")
                for i in batch_idx
            ]
            u = len(lines_b)
            width = max(32, -(-max(len(b) for b in lines_b) // 32) * 32)
            pad = _pad_rows(u, eng._corpus_min_rows())
            u8 = np.zeros((pad, width), dtype=np.uint8)
            lengths = np.zeros(pad, dtype=np.int32)
            for j, b in enumerate(lines_b):
                u8[j, : len(b)] = np.frombuffer(b, dtype=np.uint8)
                lengths[j] = len(b)
            return eng._run_cube(u8, lengths, u)

        fresh = eng.watchdog.run(_device_step)
        if batch_idx:
            fresh = np.asarray(fresh)[: len(batch_idx)].astype(bool)
            for j, i in enumerate(batch_idx):
                self._bits[i] = fresh[j]
                self._cache_populate(
                    self._lines[i].encode("utf-8", errors="replace"), fresh[j]
                )

    def _handle_device_exc(self, exc: Exception, chunk_text: str) -> None:
        """Poison kills the session (strikes its chunk fingerprint); any
        other device-classified failure flips this session to a golden
        continuation. Non-device failures propagate as session errors."""
        from log_parser_tpu.runtime.engine import is_device_error

        eng = self.engine
        if not is_device_error(exc):
            raise StreamError("fault", f"chunk ingest failed: {exc!r}") from exc
        if eng._strike_worthy(exc):
            fp = quarantine_fingerprint(chunk_text)
            eng.quarantine.strike(fp)
            if self.manager is not None:
                self.manager._note_poison()
            raise StreamError(
                "poison",
                f"poison chunk (fingerprint {fp[:12]}…): {exc!r}",
            ) from exc
        if not eng.fallback_to_golden:
            raise StreamError("fault", f"device failed: {exc!r}") from exc
        self.mode = "golden"
        if self.manager is not None:
            self.manager._note_golden()

    # ------------------------------------------------------- window evals

    def _assemble_bits(self, corpus: Corpus, tail_bits) -> np.ndarray:
        n = corpus.n_lines
        bits = np.zeros((n, self.engine.bank.n_columns), dtype=bool)
        for i in range(min(n, len(self._lines))):
            row = self._bits[i]
            if row is not None:
                bits[i] = row
        if tail_bits is not None and n == len(self._lines) + 1:
            bits[n - 1] = tail_bits
        return bits

    def _records_for(self, corpus: Corpus, bits: np.ndarray):
        eng = self.engine
        overrides = eng._overrides(corpus)
        if overrides is not None:
            om, ov = overrides
            n = corpus.n_lines
            bits = np.where(om[:n], ov[:n], bits)
        recs = records_from_bits(bits, corpus.n_lines, eng.bank, eng.tables)
        return eng._verify_approx(corpus, recs)

    def _provisional_device(self) -> list[dict]:
        """Re-finalize the current window read-only: stored per-line bits
        + the tail carry's snapshot + the engine's own override cube,
        finalized against a frequency PEEK (read under ``state_lock``,
        never recorded) — the factors legitimately move as the window
        grows, and the ledger diff turns movement into frames."""
        eng = self.engine
        corpus = Corpus(self._text, min_rows=eng._corpus_min_rows())
        tail_bits = None
        if (
            self._carry is not None
            and self._tail_pure
            and corpus.n_lines == len(self._lines) + 1
        ):
            tail_bits = self._carry.snapshot_bits()
        bits = self._assemble_bits(corpus, tail_bits)
        recs = self._records_for(corpus, bits)
        freq_base, freq_exists = self._freq_peek()
        fin = finalize_batch(
            eng.bank, eng.tables, eng.config, recs, corpus.n_lines,
            freq_base, freq_exists,
        )
        current = {
            (int(fin.line[i]), eng.bank.patterns[int(fin.pattern[i])].id):
                float(fin.scores[i])
            for i in range(len(fin.scores))
        }
        return self._diff_frames(current)

    def _freq_peek(self) -> tuple[np.ndarray, np.ndarray]:
        eng = self.engine
        freq_base = np.zeros(max(1, eng.bank.n_freq_slots), dtype=np.float64)
        freq_exists = np.zeros(max(1, eng.bank.n_freq_slots), dtype=bool)
        with eng.state_lock:
            for slot, pid in enumerate(eng.bank.freq_ids):
                freq_base[slot] = eng.frequency.get_windowed_count(pid)
                freq_exists[slot] = eng.frequency.has_entry(pid)
        return freq_base, freq_exists

    def _provisional_golden(self) -> list[dict]:
        """Golden-continuation window eval: run the host analyzer over the
        window with the shared frequency tracker rolled back — the peek
        must not record (close commits exactly once)."""
        eng = self.engine
        with eng.state_lock:
            saved = eng.frequency._save_state()
            try:
                res = eng.golden_fallback.analyze(
                    PodFailureData(logs=self._text)
                )
            finally:
                eng.frequency._load_state(saved)
        current = {
            (ev.line_number - 1, ev.matched_pattern.id): float(ev.score)
            for ev in res.events
        }
        return self._diff_frames(current)

    # --------------------------------------------------------------- rebase

    def _rebase(self) -> None:
        """A hot reload swapped the library while this session was open:
        drop every stored bit row (the column space changed), rebuild the
        carry against the new fused program, and re-score the window under
        the new bank. Caller is inside ``_request_scope`` — the swap
        itself already completed, this is the re-base half of the
        drain-or-rebase contract."""
        eng = self.engine
        t0 = time.perf_counter()
        self._epoch = eng.reload_epoch
        self._carry = eng.fused.host_carry()
        if self._carry is not None:
            self._carry.reset()
        self._tail_fed = 0
        self._bits = [None] * len(self._lines)
        if self.mode != "golden":
            batch_idx = []
            for i, line in enumerate(self._lines):
                pure = _is_pure_line(line)
                if pure is None:
                    continue
                row = self._cache_lookup(pure)
                if row is not None:
                    self._bits[i] = row
                else:
                    batch_idx.append(i)
            self._chunk_device_step("", batch_idx)
            # re-feed the partial tail so its carry resumes under the new
            # automata
            if self._carry is not None and self._tail_pure:
                target = len(self._pending)
                if self._pending.endswith("\r"):
                    target -= 1
                if target > 0:
                    self._carry.feed(
                        self._pending[:target].encode(
                            "utf-8", errors="replace"
                        )
                    )
                self._tail_fed = max(target, 0)
        eng.obs.spans.annotate(
            self.session_id, "rebase", time.perf_counter() - t0,
            attrs={"epoch": self._epoch, "lines": len(self._lines),
                   "mode": self.mode},
        )
        if self.manager is not None:
            self.manager._note_rebase()

    # ------------------------------------------------------------ migration

    def export_carry(self) -> dict:
        """Portable session state for a tenant migration bundle
        (runtime/migrate.py): the decoded window text, the monotone-emit
        ledger, and the frame sequence. Device artifacts (bit rows, the
        automata carry) deliberately do NOT travel — the importer
        re-scores the window under its own bank, which the migration
        protocol has already verified is content-identical, so the
        replayed scores match bit-for-bit. Caller holds the quiesce
        gate, so the cut is consistent; bytes still undecoded in the
        normalizer are flushed into the window (and ingested, so the
        source session stays coherent if the migration aborts) — a
        multi-byte sequence torn exactly at the cut decodes as
        replacement characters, the same verdict a torn end-of-stream
        gets."""
        with self._lock:
            tail = self._normalizer.flush()
            if tail:
                self._text += tail
                self._ingest_text(tail)
            return {
                "sessionId": self.session_id,
                "mode": self.mode,
                "emitThreshold": self.emit_threshold,
                "text": self._text,
                "seq": self._seq,
                "chunks": self._chunks,
                "ledger": [
                    [line_idx, pid, score]
                    for (line_idx, pid), score in self._ledger.items()
                ],
            }

    def restore_carry(self, carry: dict) -> None:
        """Rebuild this freshly-opened session from an exported carry:
        re-ingest the window text (scoring uncached lines once under the
        importer's bank) and restore the ledger + sequence so the
        client's monotone-emit contract continues unbroken across the
        move."""
        with self._lock:
            self.mode = str(carry.get("mode", "device"))
            self._seq = int(carry.get("seq", 0))
            self._chunks = int(carry.get("chunks", 0))
            self.emit_threshold = float(
                carry.get("emitThreshold", self.emit_threshold)
            )
            self._ledger = {
                (int(line_idx), str(pid)): float(score)
                for line_idx, pid, score in carry.get("ledger", ())
            }
            text = str(carry.get("text", ""))
            if not text:
                return
            with self.engine._request_scope():
                self._text = text
                if self.mode != "golden":
                    batch_idx = self._ingest_text(text)
                    self._chunk_device_step(text, batch_idx)

    def rebase_onto(self, engine) -> None:
        """Live-session half of a local tenant handoff: re-point this
        session at the destination engine and re-base its window there
        (the same machinery as a hot-reload rebase), so the next feed
        continues seamlessly under the new owner."""
        with self._lock:
            self.engine = engine
            with engine._request_scope():
                self._epoch = None  # force: the epoch spaces differ
                self._rebase()

    # ---------------------------------------------------------------- close

    def close(self) -> list[dict]:
        """End of stream: resolve the reassembly tail, score it, and run
        the one-shot finish sequence over the accumulated window. The
        final frame's result is bit-identical to ``analyze()`` on the
        concatenated blob (the replay theorem); frequency state commits
        exactly once, here."""
        with self._lock:
            if self.closed:
                return [
                    self._frame(
                        "error", reason=self.kill_reason or "closed",
                        message=self.kill_message or "session is closed",
                    )
                ]
            self._touch()
            try:
                with self.engine._request_scope():
                    frames = self._close_in_scope()
                self.closed = True
                self.kill_reason = None
                self._commit_session_span("closed")
                if self.manager is not None:
                    self.manager._discard(self, "closed")
                return frames
            except StreamError as err:
                frame = self._error_frame(err)
                self.kill(err.reason)
                return [frame]
            except Exception as exc:
                frame = self._frame(
                    "error", reason="internal", message=repr(exc)
                )
                self.kill("internal")
                return [frame]

    def _close_in_scope(self) -> list[dict]:
        eng = self.engine
        if eng.reload_epoch != self._epoch:
            self._rebase()
        tail = self._normalizer.flush()
        if tail:
            self._text += tail
            if self.mode != "golden":
                self._ingest_text(tail)
        if self.mode == "golden":
            with eng.state_lock:
                result = eng._golden_serve(PodFailureData(logs=self._text))
            return self._final_frames(result)

        corpus = Corpus(self._text, min_rows=eng._corpus_min_rows())
        n = corpus.n_lines
        try:
            tail_bits = self._close_tail_bits(corpus)
            bits = self._assemble_bits(corpus, tail_bits)
            recs = self._records_for(corpus, bits)
        except Exception as exc:
            self._handle_device_exc(exc, self._pending)
            with eng.state_lock:
                result = eng._golden_serve(PodFailureData(logs=self._text))
            return self._final_frames(result)

        with eng.state_lock:
            saved = eng.frequency._save_state()
            try:
                faults.fire("stream_close")
                freq_base = np.zeros(
                    max(1, eng.bank.n_freq_slots), dtype=np.float64
                )
                freq_exists = np.zeros(
                    max(1, eng.bank.n_freq_slots), dtype=bool
                )
                for slot, pid in enumerate(eng.bank.freq_ids):
                    freq_base[slot] = eng.frequency.get_windowed_count(pid)
                    freq_exists[slot] = eng.frequency.has_entry(pid)
                fin = finalize_batch(
                    eng.bank, eng.tables, eng.config, recs, n,
                    freq_base, freq_exists,
                )
                for slot, count in enumerate(
                    fin.slot_batch_counts[: eng.bank.n_freq_slots]
                ):
                    eng.frequency.record_pattern_matches(
                        eng.bank.freq_ids[slot], int(count)
                    )
                events: list[MatchedEvent] = []
                for i in range(len(fin.scores)):
                    line_idx = int(fin.line[i])
                    pattern = eng.bank.patterns[int(fin.pattern[i])]
                    events.append(
                        MatchedEvent(
                            line_number=line_idx + 1,
                            matched_pattern=pattern,
                            context=extract_context(corpus, line_idx, pattern),
                            score=float(fin.scores[i]),
                        )
                    )
                result = AnalysisResult(
                    events=events,
                    analysis_id=str(uuid.uuid4()),
                    metadata=build_metadata(
                        self._start, n, eng.bank.pattern_sets
                    ),
                    summary=build_summary(events),
                )
            except Exception as exc:
                eng.frequency._load_state(saved)
                raise StreamError(
                    "fault", f"close finalize failed: {exc!r}"
                ) from exc
        return self._final_frames(result)

    def _close_tail_bits(self, corpus: Corpus) -> np.ndarray | None:
        """Device bits for the unterminated tail line, if the final corpus
        keeps one: finish it on the carry when it tracked the whole tail,
        else score it as a one-line residual."""
        eng = self.engine
        n = corpus.n_lines
        if n != len(self._lines) + 1:
            return None
        tail = corpus.line(n - 1)
        pure = _is_pure_line(tail)
        if pure is None:
            return None  # fully overridden by the splice
        row = self._cache_lookup(pure)
        if row is not None:
            return row
        if self._carry is not None and self._tail_pure:
            rest = tail[self._tail_fed:]
            if rest:
                self._carry.feed(rest.encode("utf-8", errors="replace"))
            self._tail_fed = len(tail)
            row = self._carry.snapshot_bits()
            self._cache_populate(pure, row)
            return row
        batch_idx = [len(self._lines)]
        self._lines.append(tail)
        self._bits.append(None)
        self._chunk_device_step(tail, batch_idx)
        row = self._bits.pop()
        self._lines.pop()
        return row

    def _final_frames(self, result: AnalysisResult) -> list[dict]:
        current = {
            (ev.line_number - 1, ev.matched_pattern.id): float(ev.score)
            for ev in result.events
        }
        frames = self._diff_frames(current)
        frames.append(self._frame("final", result=result.to_dict(drop_none=True)))
        return frames


class StreamManager:
    """Session registry + reliability wiring: admission-gated opens, TTL
    reaping, and the ``/trace/last`` ``stream`` counter block."""

    def __init__(
        self,
        engine,
        emit_threshold: float = DEFAULT_EMIT_THRESHOLD,
        ttl_s: float = DEFAULT_STREAM_TTL_S,
        clock=pclock.mono,
        start_reaper: bool = True,
    ):
        self.engine = engine
        self.emit_threshold = float(emit_threshold)
        self.ttl_s = float(ttl_s)
        self.clock = clock
        self._lock = threading.Lock()
        self._sessions: dict[str, StreamSession] = {}
        self._next_id = 0
        # counters (GET /trace/last "stream"; guarded by _lock)
        self.sessions_opened = 0
        self.sessions_closed = 0
        self.sessions_killed = 0
        self.sessions_reaped = 0
        self.sessions_rebased = 0
        self.sessions_migrated = 0  # moved OUT by a tenant migration
        self.sessions_adopted = 0  # moved/restored IN by a migration
        self.chunks_ingested = 0
        self.bytes_ingested = 0
        self.frames_emitted = 0
        self.frames_revised = 0
        self.golden_continuations = 0
        self.poison_kills = 0
        self._reaper: threading.Thread | None = None
        self._stop = threading.Event()
        if start_reaper and self.ttl_s > 0:
            self._reaper = threading.Thread(
                target=self._reap_loop, name="stream-reaper", daemon=True
            )
            self._reaper.start()

    # ----------------------------------------------------------- lifecycle

    def open(self, deadline_ms: float | None = None) -> StreamSession:
        """Open one session through the shared admission gate — an open
        session holds an in-flight slot until it closes, is killed, or is
        reaped, so streaming load and one-shot load share one budget.
        Raises :class:`AdmissionRejected` when the gate refuses."""
        from log_parser_tpu.serve.admission import shared_gate

        gate = shared_gate(self.engine)
        gate.acquire(deadline_ms=deadline_ms, batchable=False)
        with self._lock:
            self._next_id += 1
            sid = f"s{self._next_id:06d}"
            sess = StreamSession(
                self.engine, sid, self.emit_threshold, manager=self
            )
            self._sessions[sid] = sess
            self.sessions_opened += 1
        return sess

    def get(self, session_id: str) -> StreamSession | None:
        with self._lock:
            return self._sessions.get(session_id)

    # ------------------------------------------------------------ migration

    def adopt(self, sess: StreamSession) -> StreamSession:
        """Move a LIVE session from another manager onto this engine (the
        local-handoff half of a tenant migration): acquire this engine's
        admission slot, release the source's, re-register the session
        (keeping its id unless taken) and re-base its window here. The
        session object survives — the client's next feed lands on the
        new owner without ever seeing an error frame."""
        from log_parser_tpu.serve.admission import shared_gate

        shared_gate(self.engine).acquire(batchable=False)
        src = sess.manager
        if src is not None and src is not self:
            moved_out = False
            with src._lock:
                if src._sessions.pop(sess.session_id, None) is not None:
                    moved_out = True
                    src.sessions_migrated += 1
            if moved_out:
                shared_gate(src.engine).release()
        with self._lock:
            sid = sess.session_id
            if sid in self._sessions:
                self._next_id += 1
                sid = f"s{self._next_id:06d}"
                sess.session_id = sid
            self._sessions[sid] = sess
            self.sessions_adopted += 1
        sess.manager = self
        sess.rebase_onto(self.engine)
        return sess

    def adopt_carry(self, carry: dict) -> StreamSession:
        """Restore an exported session carry (cross-process migration):
        open a fresh admission-gated session here and replay the carried
        window into it. The restored session keeps the source's frame
        sequence, so the client's monotone contract holds if it
        reconnects by session id."""
        sess = self.open()
        try:
            sess.restore_carry(carry)
        except Exception:
            sess.kill("internal")
            raise
        with self._lock:
            self.sessions_adopted += 1
        return sess

    def _discard(self, sess: StreamSession, reason: str) -> None:
        from log_parser_tpu.serve.admission import shared_gate

        released = False
        with self._lock:
            if self._sessions.pop(sess.session_id, None) is not None:
                released = True
                if reason == "closed":
                    self.sessions_closed += 1
                elif reason == "ttl":
                    self.sessions_reaped += 1
                else:
                    self.sessions_killed += 1
        if released:
            shared_gate(self.engine).release()

    # --------------------------------------------------------------- reaper

    def reap_now(self) -> int:
        """Kill every session idle past the TTL; returns how many died.
        The background reaper calls this on a cadence; tests with an
        injected clock call it directly."""
        if self.ttl_s <= 0:
            return 0
        now = self.clock()
        with self._lock:
            # Clock stepped backwards (injected/wall clocks only — the
            # default is monotonic): rebase instead of letting the negative
            # idle age shield the session from the TTL forever.
            for s in self._sessions.values():
                if s.last_active > now:
                    s.last_active = now
            stale = [
                s for s in self._sessions.values()
                if now - s.last_active > self.ttl_s
            ]
        for sess in stale:
            sess.kill("ttl")
        return len(stale)

    def _reap_loop(self) -> None:
        interval = max(0.05, min(self.ttl_s / 4.0, 1.0))
        while not pclock.wait(self._stop, interval):
            self.reap_now()

    def shutdown(self) -> None:
        self._stop.set()
        with self._lock:
            live = list(self._sessions.values())
        for sess in live:
            sess.kill("shutdown")

    # ------------------------------------------------------------- counters

    def _note_chunk(self, n_bytes: int) -> None:
        with self._lock:
            self.chunks_ingested += 1
            self.bytes_ingested += n_bytes

    def _note_frame(self, ftype: str) -> None:
        with self._lock:
            if ftype == "emit":
                self.frames_emitted += 1
            elif ftype == "revised":
                self.frames_revised += 1

    def _note_golden(self) -> None:
        with self._lock:
            self.golden_continuations += 1

    def _note_poison(self) -> None:
        with self._lock:
            self.poison_kills += 1

    def _note_rebase(self) -> None:
        with self._lock:
            self.sessions_rebased += 1

    def stats(self) -> dict:
        with self._lock:
            return {
                "openSessions": len(self._sessions),
                "sessionsOpened": self.sessions_opened,
                "sessionsClosed": self.sessions_closed,
                "sessionsKilled": self.sessions_killed,
                "sessionsReaped": self.sessions_reaped,
                "sessionsRebased": self.sessions_rebased,
                "sessionsMigrated": self.sessions_migrated,
                "sessionsAdopted": self.sessions_adopted,
                "chunksIngested": self.chunks_ingested,
                "bytesIngested": self.bytes_ingested,
                "framesEmitted": self.frames_emitted,
                "framesRevised": self.frames_revised,
                "goldenContinuations": self.golden_continuations,
                "poisonKills": self.poison_kills,
            }


_shared_lock = threading.Lock()

# /metrics view over StreamManager.stats() — registered against the
# engine's obs bundle in shared_manager() (log_parser_tpu/obs)
METRIC_SAMPLES = (
    ("openSessions", "logparser_stream_sessions", {}),
    ("chunksIngested", "logparser_stream_chunks_total", {}),
    ("framesEmitted", "logparser_stream_frames_total", {}),
)


def shared_manager(engine) -> StreamManager:
    """ONE manager per engine, shared across transports — the streaming
    analogue of ``serve.admission.shared_gate``. HTTP ``/parse/stream``
    and gRPC ``StreamParse`` sessions land in the same registry, so they
    draw on one admission budget, one TTL reaper, and one ``stream``
    counter block on ``/trace/last``. Thresholds come from the same env
    vars the serve flags mirror."""
    import os

    with _shared_lock:
        mgr = getattr(engine, "stream_manager", None)
        if mgr is None:
            mgr = StreamManager(
                engine,
                emit_threshold=float(
                    os.environ.get(
                        "LOG_PARSER_TPU_STREAM_EMIT_THRESHOLD",
                        str(DEFAULT_EMIT_THRESHOLD),
                    )
                ),
                ttl_s=float(
                    os.environ.get(
                        "LOG_PARSER_TPU_STREAM_TTL_S", str(DEFAULT_STREAM_TTL_S)
                    )
                ),
            )
            engine.stream_manager = mgr
            obs = getattr(engine, "obs", None)
            if obs is not None:
                obs.add_stats_collector(
                    f"stream-{id(mgr)}", mgr.stats, METRIC_SAMPLES,
                    labels={"tenant": getattr(engine, "obs_tenant", "default")},
                )
        return mgr
