"""Routing tier in front of the match cube: the exact-match line cache.

Real pod logs are overwhelmingly repeats of a small template set
(CelerLog routes by shape so only novel lines pay full parsing; Logram's
n-gram dictionaries are an O(1) membership test — PAPERS.md). The match
cube is gather-bound at ~9 ns/element and pays per (row × automaton ×
byte) (PERF.md §1), so the cheapest row is the one that never reaches
the device. This module memoizes the per-line *device-side* result — the
post-valid match-bit row of the cube, NOT final scores — keyed by the
hash of the ingest-normalized line bytes (the same normalization the
quarantine fingerprint uses, native/ingest.py ``normalize_blob``).

What is cacheable, exactly: in ``FusedMatchScore._step`` everything
downstream of the cube is a pure function of the post-override bit
matrix plus the request's line count. The PRE-override bit row is a pure
per-line function of (line bytes, bank identity): the automata consume
exactly ``length`` bytes, zero padding is automaton-neutral, and lines
flagged ``needs_host`` — whose truncated encode IS width-dependent — are
excluded from population (their rows are fully host-overridden anyway).
So the cache stores pre-override rows and the engine re-applies the
request's override cube (host-only columns, breaker-overridden patterns,
needs_host lines) on top at assembly time. That makes breaker handling
exact *by construction*: a tripped pattern's columns are served from the
host regex for cached and fresh rows alike — the per-pattern slice of
every cached entry is invalidated the instant the breaker opens, without
dropping the other patterns' bits.

Cross-line factors (proximity distances, sequence chains, context
windows) are NOT per-line — they are recomputed per request by
:func:`records_from_hits`, a sparse numpy replay of the device
extraction (same discovery order, same integer semantics). The bits
never form a dense per-line matrix: each unique line's set columns are
read from its packed cache row or its readback row
(:func:`slot_hits`), fanned out to the request's lines as sorted
``(line, col)`` coordinates with the override splice applied
(:func:`request_hits`), and the distances, sequence chains and
windows are ``searchsorted`` queries over each column's hit lines at
the record lines only. So the host's cost follows the request's hits,
cached requests produce bit-identical ``MatchRecords``, and the
frequency-coupled factors replay on the host under ``state_lock``
exactly as before.

Novel lines flow to the device as a *compacted* residual batch —
deduplicated by key within a request and within a batcher flush before
padding, one device row per unique line — then populate the cache on the
way back (``dedupFanout`` counts the rows that never had to exist).

Invalidation: wholesale on ``reload_epoch`` bump (``apply_library``
flushes under the quiesced swap, so no stale populate can race it) and
functionally per-pattern on a shadow-verifier breaker trip via the
override replay described above. Bounded: LRU by resident bytes
(``--line-cache-mb``). Quarantine-compatible: a request served entirely
from cache never reaches the device step, so it can never strike.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict, deque
from typing import NamedTuple

import numpy as np

from log_parser_tpu.golden.engine import SEQUENCE_NEAR_WINDOW
from log_parser_tpu.ops.fused import FusedStaticTables, MatchRecords, NO_HIT
from log_parser_tpu.patterns.bank import (
    CTX_ERROR,
    CTX_EXCEPTION,
    CTX_STACK,
    CTX_WARN,
    PatternBank,
)

DEFAULT_LINE_CACHE_MB = 64.0

# per-entry bookkeeping estimate beyond key + packed row: OrderedDict
# node, bytes objects' headers. Deliberately generous — the budget is an
# operator-facing ceiling, and under-counting would let the cache outgrow
# its flag.
_ENTRY_OVERHEAD = 96


def line_key(line_bytes: bytes) -> bytes:
    """Cache key for one ingest-normalized line. blake2b-128 over the
    exact content bytes: collisions are cryptographically negligible and
    cache poisoning is impossible — there is no way to make line A serve
    line B's bits without a preimage."""
    return hashlib.blake2b(line_bytes, digest_size=16).digest()


_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def probe64(v64: np.ndarray, lengths: np.ndarray, width: int) -> np.ndarray:
    """Vectorized 64-bit probe over :func:`dedup_slots`' int64 key-matrix
    rows: an FNV-1a fold of each row's content-carrying words plus its
    length, splitmix64-finalized. Width-independent for lines that fit
    the device width (the padding past a line's last partial word is
    zeros at every width, and padded-only words are skipped), so the
    same line yields the same probe across requests with different
    batch widths — the property the cross-request :class:`KeyInterner`
    needs. Lines longer than ``width`` hash their truncated prefix — an
    ambiguous key, which is why :meth:`KeyInterner.digests` never interns
    them (the stored word row would be truncated too, so the memcmp
    verify could not tell two same-length lines apart)."""
    n = v64.shape[0]
    wc_total = width // 8
    u = v64[:, :wc_total].view(np.uint64)
    # words that carry content; the fold skips the all-padding tail so
    # probes do not depend on this batch's padded width
    nw = np.minimum(-(-lengths // 8), wc_total)
    h = np.full(n, _FNV_OFFSET, dtype=np.uint64)
    max_w = int(nw.max()) if n else 0
    for j in range(max_w):
        h = np.where(nw > j, (h ^ u[:, j]) * _FNV_PRIME, h)
    h = (h ^ lengths.astype(np.uint64)) * _FNV_PRIME
    h ^= h >> np.uint64(30)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(27)
    h *= np.uint64(0x94D049BB133111EB)
    h ^= h >> np.uint64(31)
    return h


DEFAULT_INTERNER_MB = 32.0

# interned-content ceiling: 64 words = 512 bytes covers essentially every
# real log line (device_width already sits at the 99.5% length quantile);
# longer lines simply keep paying blake2b — exactness never depends on
# the ceiling
_INTERN_WORDS = 64
# fixed per-entry cost: words row + probe + length + recency stamp +
# digest bytes object + ndarray slot overheads
_INTERN_ENTRY_BYTES = _INTERN_WORDS * 8 + 8 + 8 + 8 + 16 + _ENTRY_OVERHEAD


class KeyInterner:
    """Two-level cache keying (PERF.md §15): the per-unique-line
    blake2b-128 fan-in is the keying lane's floor once ingest is
    vectorized, and repeat traffic pays it again for lines whose digest
    an earlier request already computed. The interner short-circuits
    that: a vectorized :func:`probe64` per unique line, a single
    ``searchsorted`` against the flat probe table, and a numpy
    word-matrix equality check (the vectorized memcmp) — warm requests
    recover their digests with ZERO per-line Python and zero
    cryptographic hashing. Only first-touch lines (and the
    cryptographically-negligible probe collisions) pay blake2b.

    Poisoning stays impossible: a digest is only ever returned for
    content whose padded word row AND true length compared equal to the
    content blake2b was run on — the same (prefix, length) ⇒ equality
    argument :func:`dedup_slots` rests on. Digests are pure functions of
    line content, so entries survive pattern reloads and breaker trips;
    the only bound is the byte budget, enforced by evicting the
    least-recently-used half when full.
    """

    def __init__(self, budget_bytes: int = int(DEFAULT_INTERNER_MB * 2**20)):
        self.lock = threading.Lock()
        self.budget_bytes = max(0, int(budget_bytes))
        self.max_entries = max(64, self.budget_bytes // _INTERN_ENTRY_BYTES)
        self._n = 0
        self._probes = np.zeros(0, dtype=np.uint64)
        self._words = np.zeros((0, _INTERN_WORDS), dtype=np.uint64)
        self._lengths = np.zeros(0, dtype=np.int64)
        self._stamp = np.zeros(0, dtype=np.int64)  # recency, for eviction
        self._digests = np.zeros(0, dtype=object)
        self._gen = 0
        self._sorted: tuple[np.ndarray, np.ndarray] | None = None
        self.probe_hits = 0
        self.inserts = 0
        self.collisions = 0
        self.evictions = 0

    def _sorted_view(self) -> tuple[np.ndarray, np.ndarray]:
        if self._sorted is None:
            order = np.argsort(self._probes[: self._n], kind="stable")
            self._sorted = (self._probes[order], order)
        return self._sorted

    def _grow(self, need: int) -> None:
        cap = len(self._probes)
        if need <= cap:
            return
        new = max(need, 256, cap * 2)
        for name in ("_probes", "_lengths", "_stamp", "_digests"):
            old = getattr(self, name)
            buf = np.zeros(new, dtype=old.dtype)
            buf[: self._n] = old[: self._n]
            setattr(self, name, buf)
        w = np.zeros((new, _INTERN_WORDS), dtype=np.uint64)
        w[: self._n] = self._words[: self._n]
        self._words = w

    def evict_half(self) -> int:
        """Memory-pressure lever (runtime/pressure.py): drop the
        least-recently-used half of the *current* entries, regardless of
        table fullness. Returns how many entries were dropped. Safe at
        any time — a dropped digest is recomputed on next touch."""
        with self.lock:
            keep_n = self._n // 2
            if self._n <= 1 or keep_n < 1:
                return 0
            dropped = self._n - keep_n
            keep = np.argpartition(self._stamp[: self._n], dropped)[dropped:]
            self.evictions += dropped
            for name in ("_probes", "_lengths", "_stamp", "_digests"):
                arr = getattr(self, name)
                arr[:keep_n] = arr[keep]
                setattr(self, name, arr)
            self._words[:keep_n] = self._words[keep]
            self._n = keep_n
            self._sorted = None
            return dropped

    def _evict_half(self) -> None:
        """Table full: keep the most-recently-used half. Coarser than a
        per-entry LRU but keeps eviction a single vectorized compaction
        instead of a per-insert OrderedDict walk."""
        keep_n = self.max_entries // 2
        if self._n <= keep_n:
            return
        keep = np.argpartition(self._stamp[: self._n], self._n - keep_n)[
            self._n - keep_n:
        ]
        self.evictions += self._n - keep_n
        for name in ("_probes", "_lengths", "_stamp", "_digests"):
            arr = getattr(self, name)
            arr[:keep_n] = arr[keep]
            setattr(self, name, arr)
        self._words[:keep_n] = self._words[keep]
        self._n = keep_n
        self._sorted = None

    def digests(
        self,
        v64_rows: np.ndarray,
        lengths: np.ndarray,
        width: int,
        blob,
        starts,
        ends,
    ) -> list[bytes]:
        """Digest per unique line, hashing only first-touch content.
        ``v64_rows``/``lengths`` are :func:`dedup_slots`' int64 key-matrix
        rows and true byte lengths for the unique lines;
        ``starts``/``ends`` are plain lists indexing ``blob`` (the same
        slices :func:`line_key` would hash)."""
        n = v64_rows.shape[0]
        if n == 0:
            return []
        probes = probe64(v64_rows, lengths, width)
        wc = width // 8
        u = v64_rows[:, : min(wc, _INTERN_WORDS)].view(np.uint64)
        if wc >= _INTERN_WORDS:
            batch_words = np.ascontiguousarray(u)
            internable = lengths <= _INTERN_WORDS * 8
        else:
            batch_words = np.zeros((n, _INTERN_WORDS), dtype=np.uint64)
            batch_words[:, :wc] = u
            # rows longer than the device width are TRUNCATED in v64: two
            # distinct lines sharing a width prefix (and length) would
            # compare equal word-for-word and share one digest. They stay
            # on blake2b — the same guard the wide branch applies at the
            # interning ceiling.
            internable = lengths <= width
        # comparing only the words any batch line can occupy is exact: an
        # entry with content past that point has a larger length, and the
        # length check fails first
        wmax = max(1, min(_INTERN_WORDS, -(-int(lengths.max()) // 8)))
        out = np.empty(n, dtype=object)
        found = np.zeros(n, dtype=bool)
        with self.lock:
            self._gen += 1
            present = np.zeros(n, dtype=bool)
            if self._n:
                sp, sid = self._sorted_view()
                pos = np.minimum(
                    np.searchsorted(sp, probes), self._n - 1
                )
                present = sp[pos] == probes
                cand = np.flatnonzero(present & internable)
                if cand.size:
                    eid = sid[pos[cand]]
                    ok = (self._lengths[eid] == lengths[cand]) & (
                        self._words[eid, :wmax] == batch_words[cand, :wmax]
                    ).all(axis=1)
                    hit_rows = cand[ok]
                    hit_eids = eid[ok]
                    self._stamp[hit_eids] = self._gen
                    self.probe_hits += len(hit_rows)
                    out[hit_rows] = self._digests[hit_eids]
                    found[hit_rows] = True
                    # probe matched but content differs: a 64-bit
                    # collision — those lines stay on blake2b forever
                    self.collisions += int(ok.size - ok.sum())
            miss_rows = np.flatnonzero(~found).tolist()
            ins_rows: list[int] = []
            batch_probes: set[int] = set()
            for i in miss_rows:
                out[i] = line_key(blob[starts[i] : ends[i]])
                p = int(probes[i])
                if internable[i] and not present[i] and p not in batch_probes:
                    batch_probes.add(p)
                    ins_rows.append(i)
            if self._n + len(ins_rows) > self.max_entries:
                self._evict_half()
                ins_rows = ins_rows[: max(0, self.max_entries - self._n)]
            if ins_rows:
                self._grow(self._n + len(ins_rows))
                ir = np.asarray(ins_rows, dtype=np.int64)
                sl = slice(self._n, self._n + len(ins_rows))
                self._probes[sl] = probes[ir]
                self._words[sl] = batch_words[ir]
                self._lengths[sl] = lengths[ir]
                self._stamp[sl] = self._gen
                self._digests[sl] = out[ir]
                self._n += len(ins_rows)
                self.inserts += len(ins_rows)
                self._sorted = None
        return out.tolist()

    def stats(self) -> dict:
        with self.lock:
            return {
                "budgetMb": round(self.budget_bytes / 2**20, 3),
                "entries": self._n,
                "residentBytes": self._n * _INTERN_ENTRY_BYTES,
                "probeHits": self.probe_hits,
                "inserts": self.inserts,
                "collisions": self.collisions,
                "evictions": self.evictions,
            }


def dedup_slots(
    corpus, interner: "KeyInterner | None" = None
) -> tuple[np.ndarray, np.ndarray, list[bytes], np.ndarray] | None:
    """Vectorized request-level dedup: unique lines and the line→slot
    fan-in in array speed instead of a per-line dict loop.

    Returns ``(line_slot, rep_lines, keys, counts)`` where slots are
    numbered by first appearance (bit-compatible with the scalar dict
    loop it replaces), ``rep_lines[s]`` is the first line index of slot
    ``s``, ``keys[s]`` its :func:`line_key` digest and ``counts[s]`` its
    multiplicity. Returns ``None`` when the corpus has no contiguous
    byte view (the lone-surrogate scalar path) — callers keep the dict
    loop there.

    Exactness: the comparison key is the encoded ``[width]`` u8 row
    concatenated with the true byte length. For lines that fit the
    device width the row IS the content (zero-padding is disambiguated
    by the length word: equal lengths + equal prefix ⇒ equal bytes).
    Lines longer than the width are ambiguous under truncation, so they
    are re-grouped exactly on their blob slices — they can never collide
    with a short line (lengths differ) and are rare by construction
    (device_width covers the 99.5% quantile, ops/encode.py).
    """
    kv = corpus.key_view()
    if kv is None:
        return None
    blob, starts, ends = kv
    enc = corpus.encoded
    n = int(enc.n_lines)
    if n == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z, [], z
    # the offset arrays may carry dropped trailing-empty parts past n
    starts = starts[:n]
    ends = ends[:n]
    width = enc.u8.shape[1]
    lengths = (ends - starts).astype(np.int64)
    # key row = u8 content ‖ true length, padded to an int64 boundary so
    # the grouping sort runs over a handful of int64 columns (a memcmp
    # sort over void rows is ~1.5× slower at this shape)
    kw = -(-(width + 8) // 8) * 8
    km = np.zeros((n, kw), dtype=np.uint8)
    km[:, :width] = enc.u8[:n]
    km[:, width : width + 8] = lengths.astype("<i8").reshape(n, 1).view(np.uint8)
    v64 = km.view("<i8")
    order = np.lexsort(v64.T[::-1])
    srt = v64[order]
    newrun = np.empty(n, dtype=bool)
    newrun[0] = True
    np.any(srt[1:] != srt[:-1], axis=1, out=newrun[1:])
    gid_sorted = np.cumsum(newrun) - 1
    group = np.empty(n, dtype=np.int64)
    group[order] = gid_sorted
    # lexsort is stable, so the first member of each run is the group's
    # first appearance in line order
    first_idx = order[np.flatnonzero(newrun)]
    long_lines = np.flatnonzero(lengths > width)
    if long_lines.size:
        next_gid = int(first_idx.size)
        exact: dict[bytes, int] = {}
        s_l = starts.tolist()
        e_l = ends.tolist()
        for i in long_lines.tolist():
            content = blob[s_l[i] : e_l[i]]
            gid = exact.get(content)
            if gid is None:
                gid = next_gid
                next_gid += 1
                exact[content] = gid
            group[i] = gid
        # regrouping may have emptied gids and appended new ones: rebuild
        # first-occurrence indices the general way
        uniq_g, first = np.unique(group, return_index=True)
        ord2 = np.argsort(first, kind="stable")
        remap = np.empty(uniq_g.size, dtype=np.int64)
        remap[ord2] = np.arange(uniq_g.size)
        line_slot = remap[np.searchsorted(uniq_g, group)]
        rep_lines = first[ord2]
    else:
        # renumber groups by first appearance so slot order matches the
        # scalar dict loop byte-for-byte
        ord2 = np.argsort(first_idx, kind="stable")
        remap = np.empty(first_idx.size, dtype=np.int64)
        remap[ord2] = np.arange(first_idx.size)
        line_slot = remap[group]
        rep_lines = first_idx[ord2]
    s_l = starts[rep_lines].tolist()
    e_l = ends[rep_lines].tolist()
    if interner is not None and width % 8 == 0:
        # two-level keying: vectorized probes + word-matrix-verified
        # digest reuse; blake2b only for lines never seen before
        keys = interner.digests(
            v64[rep_lines], lengths[rep_lines], width, blob, s_l, e_l
        )
    else:
        keys = [line_key(blob[a:b]) for a, b in zip(s_l, e_l)]
    counts = np.bincount(line_slot, minlength=rep_lines.size)
    return line_slot, rep_lines, keys, counts


class LineCache:
    """Bounded LRU of per-line pre-override match-bit rows.

    Thread-safe: one lock acquisition per ``lookup_packed`` /
    ``populate`` call (the batcher and concurrent pipelined requests
    share one instance). Rows are stored bit-packed (``np.packbits``) —
    a 600-column bank costs 75 bytes per resident line."""

    def __init__(self, n_columns: int, budget_bytes: int):
        self.lock = threading.Lock()
        self.budget_bytes = max(0, int(budget_bytes))
        self._entries: OrderedDict[bytes, bytes] = OrderedDict()
        self._set_columns(n_columns)
        self.resident_bytes = 0
        # counters (GET /trace/last "lineCache"; guarded by lock)
        self.hits = 0
        self.misses = 0
        self.residual_rows = 0
        self.dedup_fanout = 0
        self.evictions = 0
        self.epoch_flushes = 0

    def _set_columns(self, n_columns: int) -> None:
        self.n_columns = int(n_columns)
        self._row_bytes = (self.n_columns + 7) // 8
        self._entry_cost = 16 + self._row_bytes + _ENTRY_OVERHEAD

    # ------------------------------------------------------------- data path

    def lookup_packed(
        self, keys: list[bytes], counts: list[int] | None = None
    ) -> list[bytes | None]:
        """Per-key packed bit rows (or None for misses), LRU touch +
        hit/miss accounting in one lock acquisition. ``counts`` weights
        each key by its line multiplicity — the hot paths dedup a request
        to unique keys before looking up, but the counters keep describing
        LINES (hit rate stays meaningful to an operator) while the
        residual keeps describing device rows."""
        packed: list[bytes | None] = []
        with self.lock:
            hits = misses = 0
            for j, k in enumerate(keys):
                row = self._entries.get(k)
                w = counts[j] if counts is not None else 1
                if row is None:
                    misses += w
                else:
                    self._entries.move_to_end(k)
                    hits += w
                packed.append(row)
            self.hits += hits
            self.misses += misses
        return packed

    def unpack(self, packed: list[bytes]) -> np.ndarray:
        """Batch-unpack packed rows to bool [len(packed), n_columns] in
        one ``np.unpackbits`` call — the per-row variant is ~20x slower
        on a repeat-heavy request (PERF.md §11)."""
        if not packed:
            return np.zeros((0, self.n_columns), dtype=bool)
        buf = np.frombuffer(b"".join(packed), dtype=np.uint8)
        return np.unpackbits(
            buf.reshape(len(packed), self._row_bytes),
            axis=1,
            count=self.n_columns,
        ).astype(bool)

    def row_hits(self, packed: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
        """``(row, col)`` of the set bits of packed rows, sorted by row
        then column — the extract path's view: only the nonzero bytes
        are unpacked, never the whole rows."""
        if not packed:
            z = np.zeros(0, dtype=np.int64)
            return z, z
        buf = np.frombuffer(b"".join(packed), dtype=np.uint8)
        flat = _nonzero_bytes(buf)
        k, bit = np.nonzero(np.unpackbits(buf[flat][:, None], axis=1))
        row, byte = np.divmod(flat[k], self._row_bytes)
        return row, byte * 8 + bit

    def lookup(self, keys: list[bytes]) -> list[np.ndarray | None]:
        """Per-key bit rows (bool [n_columns]) or None for misses —
        convenience wrapper over :meth:`lookup_packed` for tests and
        small callers; the engine/batcher hot paths stay packed."""
        packed = self.lookup_packed(keys)
        hit = [p for p in packed if p is not None]
        rows = self.unpack(hit)
        out: list[np.ndarray | None] = []
        j = 0
        for p in packed:
            if p is None:
                out.append(None)
            else:
                out.append(rows[j])
                j += 1
        return out

    def populate_rows(self, keys: list[bytes], rows: np.ndarray) -> None:
        """Insert freshly computed rows (bool [len(keys), n_columns]),
        packed in one ``np.packbits`` call, evicting LRU entries past the
        byte budget."""
        if not keys:
            return
        packed = np.packbits(np.asarray(rows, dtype=bool), axis=1)
        ready = [(k, packed[j].tobytes()) for j, k in enumerate(keys)]
        self._insert(ready)

    def populate(self, items: list[tuple[bytes, np.ndarray]]) -> None:
        """Insert freshly computed (key, bool-row) pairs — convenience
        wrapper over :meth:`populate_rows`."""
        if items:
            self.populate_rows(
                [k for k, _ in items], np.stack([r for _, r in items])
            )

    def set_budget(self, budget_bytes: int) -> None:
        """Re-arbitrate the byte budget live (fleet/budget.py pushes
        shares through ``POST /admin/budget``): shrink evicts LRU
        entries down to the new budget immediately."""
        with self.lock:
            self.budget_bytes = max(0, int(budget_bytes))
            while self.resident_bytes > self.budget_bytes and self._entries:
                self._entries.popitem(last=False)
                self.resident_bytes -= self._entry_cost
                self.evictions += 1

    def _insert(self, ready: list[tuple[bytes, bytes]]) -> None:
        with self.lock:
            for k, p in ready:
                if k in self._entries:
                    self._entries.move_to_end(k)
                    continue
                self._entries[k] = p
                self.resident_bytes += self._entry_cost
            while self.resident_bytes > self.budget_bytes and self._entries:
                self._entries.popitem(last=False)
                self.resident_bytes -= self._entry_cost
                self.evictions += 1

    def note_residual(self, rows: int, fanout: int) -> None:
        """Account one residual dispatch: ``rows`` unique device rows
        actually sent, ``fanout`` duplicate lines they fanned back out to."""
        with self.lock:
            self.residual_rows += rows
            self.dedup_fanout += fanout

    def flush(self, n_columns: int | None = None) -> None:
        """Wholesale invalidation — the reload-epoch path. Called inside
        ``apply_library``'s quiesced critical section, after every
        in-flight populate has drained, so a stale hit across a pattern
        swap is structurally impossible. ``n_columns`` re-binds the row
        width when the new library changes the bank's column count."""
        with self.lock:
            self._entries.clear()
            self.resident_bytes = 0
            self.epoch_flushes += 1
            if n_columns is not None and n_columns != self.n_columns:
                self._set_columns(n_columns)

    # ------------------------------------------------------- observability

    def stats(self) -> dict:
        with self.lock:
            return {
                "budgetMb": round(self.budget_bytes / (1024 * 1024), 3),
                "entries": len(self._entries),
                "residentBytes": self.resident_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "residualRows": self.residual_rows,
                "dedupFanout": self.dedup_fanout,
                "evictions": self.evictions,
                "epochFlushes": self.epoch_flushes,
            }


# /metrics views over LineCache.stats() / KeyInterner.stats() — read by
# the obs engine collector at scrape time (log_parser_tpu/obs), so the
# exposition and /trace/last can never disagree on these counters
CACHE_METRIC_SAMPLES = (
    ("hits", "logparser_line_cache_hits_total", {}),
    ("misses", "logparser_line_cache_misses_total", {}),
    ("evictions", "logparser_line_cache_evictions_total", {}),
    ("residentBytes", "logparser_line_cache_resident_bytes", {}),
)
INTERNER_METRIC_SAMPLES = (
    ("probeHits", "logparser_interner_probe_hits_total", {}),
    ("inserts", "logparser_interner_inserts_total", {}),
)


# ------------------------------------------------------------ miss-stream tap

DEFAULT_TAP_CAPACITY = 4096


class MissTap:
    """Sampled, bounded, drop-counted feed of line-cache misses to the
    template miner (:mod:`log_parser_tpu.mining`).

    The hot path calls :meth:`offer` once per unique miss line — one lock
    acquisition appending the ingest-normalized line bytes to a bounded
    deque. Nothing ever blocks and nothing is retried: when the queue is
    full the line is counted in ``dropped`` and forgotten. The miner is
    an optimization; the parse path is the product, so saturation must
    cost one counter bump, never latency.

    Sampling is a deterministic stride over the offer sequence number
    (``sample=0.25`` keeps every 4th offer), so a chaos drill or test
    replays bit-identically without an RNG on the hot path; skipped
    offers are counted in ``sampledOut``.

    The consumer (:meth:`drain`) waits on an event with a timeout: the
    miner thread wakes promptly under traffic and idles cheaply without
    polling the lock.
    """

    def __init__(
        self, capacity: int = DEFAULT_TAP_CAPACITY, sample: float = 1.0
    ):
        self.lock = threading.Lock()
        self.capacity = max(1, int(capacity))
        self.sample = min(max(float(sample), 0.0), 1.0)
        self._q: deque[tuple[bytes, int]] = deque()
        self._seq = 0  # offers seen, pre-sampling (stride numerator)
        self._kept = 0  # offers past the sampler so far
        self.tapped = 0
        self.dropped = 0
        self.sampled_out = 0
        self._event = threading.Event()
        self._closed = False

    def offer(self, line_bytes: bytes, count: int = 1) -> bool:
        """Non-blocking hot-path enqueue of one miss line (``count`` = its
        multiplicity in the request). Returns True iff enqueued."""
        with self.lock:
            if self._closed:
                return False
            self._seq += 1
            want = int(self._seq * self.sample)
            if want <= self._kept:
                self.sampled_out += 1
                return False
            self._kept = want
            if len(self._q) >= self.capacity:
                self.dropped += 1
                return False
            self._q.append((bytes(line_bytes), int(count)))
            self.tapped += 1
        self._event.set()
        return True

    def drain(
        self, max_items: int = 512, timeout: float | None = 0.25
    ) -> list[tuple[bytes, int]]:
        """Consumer side: up to ``max_items`` queued (line_bytes, count)
        pairs, waiting up to ``timeout`` seconds for the first one."""
        if timeout and not self._event.is_set():
            self._event.wait(timeout)
        out: list[tuple[bytes, int]] = []
        with self.lock:
            while self._q and len(out) < max_items:
                out.append(self._q.popleft())
            if not self._q:
                self._event.clear()
        return out

    def close(self) -> None:
        with self.lock:
            self._closed = True
            self._q.clear()
        self._event.set()

    def stats(self) -> dict:
        with self.lock:
            return {
                "capacity": self.capacity,
                "sample": self.sample,
                "queued": len(self._q),
                "tapped": self.tapped,
                "dropped": self.dropped,
                "sampledOut": self.sampled_out,
            }


# --------------------------------------------------------- host extraction
#
# The match bits travel through extraction as sparse (line, column) hit
# coordinates: a request's cost follows its hits, not lines × columns.


def _nonzero_bytes(buf: np.ndarray) -> np.ndarray:
    """Flat indices, ascending, of the nonzero bytes of a contiguous
    uint8 buffer: one pass reads it as uint64 words, and only the nonzero
    words are resolved to their bytes."""
    nw = buf.size // 8
    words = np.flatnonzero(buf[: nw * 8].view(np.uint64))
    idx = (words[:, None] * 8 + np.arange(8)).ravel()
    idx = idx[buf[idx] != 0]
    tail = np.flatnonzero(buf[nw * 8 :])
    return np.concatenate([idx, tail + nw * 8]) if tail.size else idx


def bool_hits(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(row, col)`` of the set entries of a bool ``[r, C]`` matrix,
    sorted by row then column."""
    if rows.strides[0] < rows.strides[1]:
        # column-major memory (the v5e's cube readback comes so): scan the
        # transpose in its own order and sort the few hits, since a
        # transposing copy of the whole matrix costs far more than the scan
        col, row = bool_hits(rows.T)
        order = np.lexsort((col, row))
        return row[order], col[order]
    r, c = rows.shape
    if r == 0 or c == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    if rows.strides[1] != 1 or rows.strides[0] < c:
        rows = np.ascontiguousarray(rows)
    # the rows are contiguous but may sit apart (rows sliced off a
    # column-major matrix, transposed): scan the one span that holds
    # them, gaps included, and drop the gaps' hits
    s = rows.strides[0]
    span = np.lib.stride_tricks.as_strided(
        rows.view(np.uint8), shape=((r - 1) * s + c,), strides=(1,)
    )
    row, col = np.divmod(_nonzero_bytes(span), s)
    keep = col < c
    return row[keep], col[keep]


class SlotHits(NamedTuple):
    """The hit columns of a request's (or flush's) unique lines, by slot:
    slot ``s`` holds ``cols[start[s] : start[s] + count[s]]``, ascending."""

    start: np.ndarray  # int64 [U]
    count: np.ndarray  # int64 [U]
    cols: np.ndarray  # int64 [H]


def slot_hits(
    cache: LineCache,
    packed: list[bytes | None],
    miss_slots: list[int],
    fresh: np.ndarray | None,
) -> SlotHits:
    """Hit columns per unique slot from the cached rows (``packed[s]``
    where not None) and the readback rows (``fresh[j]`` for slot
    ``miss_slots[j]``), never unpacked to a dense matrix."""
    U = len(packed)
    start = np.zeros(U, dtype=np.int64)
    count = np.zeros(U, dtype=np.int64)
    hit_slots = [s for s, p in enumerate(packed) if p is not None]
    groups = []
    if hit_slots:
        groups.append(
            (hit_slots, cache.row_hits([packed[s] for s in hit_slots]))
        )
    if fresh is not None and miss_slots:
        groups.append((miss_slots, bool_hits(fresh)))
    parts: list[np.ndarray] = []
    base = 0
    for slots, (row, col) in groups:
        c = np.bincount(row, minlength=len(slots))
        idx = np.asarray(slots, dtype=np.int64)
        count[idx] = c
        start[idx] = base + np.cumsum(c) - c
        parts.append(col)
        base += col.size
    cols = np.concatenate(parts) if parts else np.zeros(0, dtype=np.int64)
    return SlotHits(start, count, cols)


def request_hits(
    hits: SlotHits,
    line_slot: np.ndarray,
    n_lines: int,
    om: np.ndarray | None = None,
    ov: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """One request's ``(line, col)`` hits: each line takes its slot's
    columns, then the request's override cube (``om``/``ov``, host-only
    columns, needs_host lines and OPEN breakers) replaces the masked
    coordinates."""
    cnt = hits.count[line_slot]
    first = np.cumsum(cnt) - cnt
    total = int(cnt.sum())
    line = np.repeat(np.arange(n_lines, dtype=np.int64), cnt)
    col = hits.cols[
        np.repeat(hits.start[line_slot] - first, cnt)
        + np.arange(total, dtype=np.int64)
    ]
    if om is None:
        return line, col
    keep = ~om[line, col]
    o_line, o_col = bool_hits(ov[:n_lines])
    on = om[o_line, o_col]
    return (
        np.concatenate([line[keep], o_line[on]]),
        np.concatenate([col[keep], o_col[on]]),
    )


class _ColumnLines:
    """The sorted hit lines of a few columns, one segment per column,
    kept as keys ``u * (n_lines + 1) + line`` in one sorted array so a
    single ``searchsorted`` answers a query for any column ``u``."""

    def __init__(self, line, col, cols, n_lines: int, n_columns: int):
        lut = np.full(max(1, n_columns), -1, dtype=np.int64)
        lut[np.asarray(cols, dtype=np.int64)] = np.arange(len(cols))
        u = lut[col]
        sel = u >= 0
        self.stride = int(n_lines) + 1
        # a sentinel past every segment keeps the array non-empty
        self.keys = np.append(
            np.sort(u[sel] * self.stride + line[sel]), len(cols) * self.stride
        )
        bounds = np.searchsorted(
            self.keys, np.arange(len(cols) + 1) * self.stride
        )
        self.lo, self.hi = bounds[:-1], bounds[1:]

    def before(self, u, at):
        """Last hit line of column ``u`` strictly before ``at``, -1 if none."""
        base = u * self.stride
        p = np.searchsorted(self.keys, base + at, "left") - 1
        return np.where(p >= self.lo[u], self.keys[np.maximum(p, 0)] - base, -1)

    def after(self, u, at):
        """First hit line of column ``u`` strictly after ``at``, -1 if none."""
        base = u * self.stride
        q = np.searchsorted(self.keys, base + at, "right")
        return np.where(q < self.hi[u], self.keys[q] - base, -1)

    def count(self, u, lo, hi):
        """Hits of column ``u`` on lines ``[lo, hi)``."""
        base = u * self.stride
        return np.searchsorted(self.keys, base + hi) - np.searchsorted(
            self.keys, base + lo
        )


def records_from_hits(
    line: np.ndarray,
    col: np.ndarray,
    n_lines: int,
    bank: PatternBank,
    tables: FusedStaticTables,
) -> MatchRecords:
    """The device extraction, replayed on the host from a request's
    post-override hits (``line``, ``col``, in any order).
    Mirrors ``FusedMatchScore._extract`` — same discovery order (line
    then pattern), same per-pattern slot layout (``pat_sec``/``pat_seq``/
    ``pat_ctx_shape``), same integer semantics — so the records are
    bit-identical to what the device would have produced for the full
    batch. Arrays are exact-size (K = M): finalize_batch and
    _verify_approx slice ``[:n_matches]``, so no padding rows are
    needed."""
    B = int(n_lines)
    C = bank.n_columns
    s_w = max(1, tables.s_max)
    q_w = max(1, tables.q_max)

    # ---- primaries: columns are interned, so one column may be the
    # primary of several patterns — expand each hit through a column →
    # patterns CSR, then order by (line, pattern) --------------------------
    pcols = bank.primary_columns.astype(np.int64)
    per_col = np.bincount(pcols, minlength=C)
    k = per_col[col]
    m = int(k.sum())
    if m == 0:
        return MatchRecords(
            n_matches=0,
            line=np.zeros(0, dtype=np.int32),
            pattern=np.zeros(0, dtype=np.int32),
            sec_dist=np.full((0, s_w), NO_HIT, dtype=np.int32),
            seq_ok=np.zeros((0, q_w), dtype=bool),
            ctx_counts=np.zeros((0, 5), dtype=np.int32),
        )
    col_pats = np.argsort(pcols, kind="stable")
    col_start = np.cumsum(per_col) - per_col
    src = np.repeat(col_start[col] - (np.cumsum(k) - k), k) + np.arange(m)
    rl = np.repeat(line, k)
    rp = col_pats[src]
    order = np.lexsort((rp, rl))
    rl = rl[order]
    rec_pat = rp[order].astype(np.int32)
    rec_line = rl.astype(np.int32)

    # ---- proximity distances: once per distinct secondary column, at the
    # record lines only; strict prev/next hit (own row excluded) ----------
    rec_dist = np.full((m, s_w), NO_HIT, dtype=np.int32)
    if len(tables.sec_cols):
        ucols, inv = np.unique(tables.sec_cols, return_inverse=True)
        sec = _ColumnLines(line, col, ucols, B, C)
        sec_idx = tables.pat_sec[rec_pat]  # [m, s_w]
        r, j = np.nonzero(sec_idx >= 0)
        u = inv[sec_idx[r, j]]
        at = rl[r]
        prev = sec.before(u, at)
        nxt = sec.after(u, at)
        d_prev = np.where(prev >= 0, at - prev, int(NO_HIT))
        d_next = np.where(nxt >= 0, nxt - at, int(NO_HIT))
        rec_dist[r, j] = np.minimum(d_prev, d_next)

    # ---- sequence flags: last event within ±SEQUENCE_NEAR_WINDOW, earlier
    # events chained strictly backwards from the primary line -------------
    rec_seq = np.zeros((m, q_w), dtype=bool)
    if bank.sequences:
        ev = _ColumnLines(line, col, tables.seq_event_cols, B, C)
        w = SEQUENCE_NEAR_WINDOW
        flags = np.zeros((m, len(bank.sequences)), dtype=bool)
        for qi, seq in enumerate(bank.sequences):
            if not seq.event_columns:
                continue
            last_e = tables.seq_col_pos[seq.event_columns[-1]]
            lo = np.clip(rl - w, 0, B)
            hi = np.clip(np.minimum(rl + w + 1, B), 0, B)
            ok = ev.count(last_e, lo, hi) > 0
            cur = rl
            for c in reversed(seq.event_columns[:-1]):
                g = ev.before(tables.seq_col_pos[c], cur)
                ok &= g >= 0
                cur = np.clip(g, 0, B - 1)
            flags[:, qi] = ok
        q_idx = tables.pat_seq[rec_pat]  # [m, q_w]
        rec_seq = np.where(
            q_idx >= 0,
            flags[np.arange(m)[:, None], np.maximum(q_idx, 0)],
            False,
        )

    # ---- context window counts -------------------------------------------
    flags4 = np.zeros((B, 4), dtype=np.int64)  # err, warn, stack, exc
    for f, c in enumerate((CTX_ERROR, CTX_WARN, CTX_STACK, CTX_EXCEPTION)):
        flags4[line[col == c], f] = 1
    flags4[:, 1] &= 1 - flags4[:, 0]  # warn is shadowed by error
    ps = np.concatenate(
        [np.zeros((1, 4), dtype=np.int64), np.cumsum(flags4, axis=0)]
    )
    shape_ids = tables.pat_ctx_shape[rec_pat]  # [m]
    rec_ctx = np.zeros((m, 5), dtype=np.int32)
    for s, (has_rules, before, after) in enumerate(tables.ctx_shapes):
        sel = shape_ids == s
        if not sel.any():
            continue
        li = rl[sel]
        if not has_rules:
            # context = the matched line only (AnalysisService.java:135-139)
            counts = flags4[li]
            total = np.ones(len(li), dtype=np.int64)
        else:
            lo = np.clip(li - before, 0, B)
            hi = np.clip(np.minimum(li + 1 + after, B), 0, B)
            counts = ps[hi] - ps[lo]
            total = hi - lo
        rec_ctx[sel] = np.concatenate(
            [counts, total[:, None]], axis=1
        ).astype(np.int32)

    return MatchRecords(
        n_matches=m,
        line=rec_line,
        pattern=rec_pat,
        sec_dist=rec_dist,
        seq_ok=rec_seq,
        ctx_counts=rec_ctx,
    )


def records_from_bits(
    bits: np.ndarray,
    n_lines: int,
    bank: PatternBank,
    tables: FusedStaticTables,
) -> MatchRecords:
    """:func:`records_from_hits` over a dense post-override bit matrix
    ``bits`` [n_lines, n_columns] (the follow-mode stream keeps one)."""
    line, col = bool_hits(bits[:n_lines])
    return records_from_hits(line, col, n_lines, bank, tables)
