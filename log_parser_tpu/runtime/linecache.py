"""Routing tier in front of the match cube: the exact-match line cache.

Real pod logs are overwhelmingly repeats of a small template set
(CelerLog routes by shape so only novel lines pay full parsing; Logram's
n-gram dictionaries are an O(1) membership test — PAPERS.md). The match
cube is gather-bound at ~9 ns/element and pays per (row × automaton ×
byte) (PERF.md §1), so the cheapest row is the one that never reaches
the device. This module memoizes the per-line *device-side* result — the
post-valid match-bit row of the cube, NOT final scores — keyed by the
ingest-normalized line bytes themselves (the same normalization the
quarantine fingerprint uses, native/ingest.py ``normalize_blob``): the
encoded row's content words and length, compared word for word, so a
row is only ever served to the bytes it was computed for.

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
deduplicated by content within a request and within a batcher flush
before padding, one device row per unique line — then populate the cache
on the way back (``dedupFanout`` counts the rows that never had to
exist).

Invalidation: wholesale on ``reload_epoch`` bump (``apply_library``
flushes under the quiesced swap, so no stale populate can race it) and
functionally per-pattern on a shadow-verifier breaker trip via the
override replay described above. Bounded by the arrays' bytes
(``--line-cache-mb``): the oldest entries are evicted first.
Quarantine-compatible: a request served entirely from cache never
reaches the device step, so it can never strike.
"""

from __future__ import annotations

import threading
from collections import deque
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

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)

# bytes of word rows a chunked pass holds at once (~16 MB), so probing
# or verifying a wide batch never doubles its size in temporaries
_COMPARE_BYTES = 1 << 24

# the stamp of a free slot: never the oldest, never evicted again
_FREE = np.iinfo(np.int64).max

# an index bucket: empty ends a probe walk; deleted (an evicted entry's)
# is walked past, and taken again by the next key stored there
_EMPTY, _DELETED = -1, -2

# buckets a round of probe walks reads, over all the walks still open
_WALK_READS = 1 << 10

# an index's buckets, and so its slots, are numbered in int32
_MAX_BUCKETS = 1 << 31


def _fmix64(x: np.ndarray) -> np.ndarray:
    """murmur3's 64-bit finalizer, in place: a bijection with fmix(0) = 0."""
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xFF51AFD7ED558CCD)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xC4CEB9FE1A85EC53)
    x ^= x >> np.uint64(33)
    return x


def probe64(words: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """64-bit probe of each row of ``words`` (uint64 content words, zero
    past the row's ``lengths`` bytes): the sum of each word mixed with a
    multiplier of its position, then the length, finalized. A zero word
    adds nothing, so a line has one probe at every batch width. The
    probe only indexes: equality is decided by the words and lengths."""
    n, w = words.shape
    mult = (np.arange(w, dtype=np.uint64) * np.uint64(2) + np.uint64(1)) * _GOLDEN
    h = np.empty(n, dtype=np.uint64)
    step = max(1, _COMPARE_BYTES // max(8, 8 * w))
    for lo in range(0, n, step):
        h[lo : lo + step] = _fmix64(words[lo : lo + step] * mult).sum(axis=1)
    h ^= _fmix64(lengths.astype(np.uint64) + _GOLDEN)
    return _fmix64(h)


def word_class(lengths: np.ndarray) -> np.ndarray:
    """The words a line's key holds: its content words rounded up to a
    power of two, as the device width's rungs are."""
    nw = np.maximum(1, -(-np.asarray(lengths, dtype=np.int64) // 8))
    return np.left_shift(1, np.ceil(np.log2(nw)).astype(np.int64))


def _as_words(u8: np.ndarray) -> np.ndarray:
    """uint64 view of a ``[n, width]`` uint8 batch, zero-padded to whole
    words where the width is not."""
    if u8.shape[1] % 8 or not u8.flags.c_contiguous:
        buf = np.zeros((u8.shape[0], -(-u8.shape[1] // 8) * 8), dtype=np.uint8)
        buf[:, : u8.shape[1]] = u8
        u8 = buf
    return u8.view(np.uint64)


class LineKeys(NamedTuple):
    """Line-cache keys: key ``i`` is the content words ``words[rows[i]]``
    (zero past the line) and the byte length ``lengths[i]``, indexed by
    ``probes[i]``. Only ``storable`` keys are looked up or stored: the
    others are ``needs_host`` lines, whose device bits are not a function
    of these bytes alone (their columns are overridden on the host)."""

    words: np.ndarray  # uint64 [R, W]
    rows: np.ndarray  # int64 [U]
    lengths: np.ndarray  # int64 [U]
    probes: np.ndarray  # uint64 [U]
    storable: np.ndarray  # bool [U]

    def take(self, idx) -> "LineKeys":
        return LineKeys(self.words, self.rows[idx], self.lengths[idx],
                        self.probes[idx], self.storable[idx])

    def words_of(self, idx: np.ndarray, k: int) -> np.ndarray:
        """The first ``k`` content words of keys ``idx``."""
        w = self.words[self.rows[idx], :k]
        return np.pad(w, ((0, 0), (0, k - w.shape[1]))) if w.shape[1] < k else w


def line_keys(lines: list[bytes]) -> LineKeys:
    """Storable keys of lines given as their ingest-normalized bytes (the
    follow-mode stream keys its device-pure lines so)."""
    n = len(lines)
    lengths = np.fromiter(map(len, lines), dtype=np.int64, count=n)
    words = np.zeros((n, int(word_class(lengths).max(initial=1))), np.uint64)
    for j, b in enumerate(lines):
        words[j].view(np.uint8)[: len(b)] = np.frombuffer(b, dtype=np.uint8)
    return LineKeys(words, np.arange(n), lengths, probe64(words, lengths),
                    np.ones(n, dtype=bool))


def _by_first_appearance(group: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(slot, first)`` for arbitrary group ids: slots numbered by the
    first row of each group, as the scalar dict loop numbers them."""
    uniq, first = np.unique(group, return_index=True)
    order = np.argsort(first, kind="stable")
    remap = np.empty(uniq.size, dtype=np.int64)
    remap[order] = np.arange(uniq.size)
    return remap[np.searchsorted(uniq, group)], first[order]


def group_rows(
    words: np.ndarray, lengths: np.ndarray, probes: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Group rows of equal content (``words`` and ``lengths``): the slot
    of each row, numbered by first appearance, and each slot's first row.
    One argsort of the probes; every row of a run of equal probes is
    compared word for word with the run's first, and the rows of a run
    that holds different lines (a probe collision) are regrouped on
    their whole content."""
    n = probes.size
    order = np.argsort(probes)
    newrun = np.empty(n, dtype=bool)
    newrun[:1] = True
    sp = probes[order]
    np.not_equal(sp[1:], sp[:-1], out=newrun[1:])
    run = np.cumsum(newrun) - 1
    starts = np.flatnonzero(newrun)
    heads = np.minimum.reduceat(order, starts) if n else starts
    member = np.flatnonzero(~newrun)
    a, b = order[member], heads[run[member]]
    bad = lengths[a] != lengths[b]
    step = max(1, _COMPARE_BYTES // max(1, words.itemsize * words.shape[1]))
    for lo in range(0, member.size, step):
        hi = lo + step
        bad[lo:hi] |= (words[a[lo:hi]] != words[b[lo:hi]]).any(axis=1)
    group = np.empty(n, dtype=np.int64)
    group[order] = run
    if bad.any():
        rows = order[np.isin(run, run[member[bad]])]
        content = np.concatenate(
            [words[rows], lengths[rows, None].astype(np.uint64)], axis=1
        )
        _, inv = np.unique(content, axis=0, return_inverse=True)
        group[rows] = heads.size + inv.ravel()
        return _by_first_appearance(group)
    # number the runs by their first rows, in line order
    is_head = np.zeros(n, dtype=bool)
    is_head[heads] = True
    rank = np.cumsum(is_head) - 1
    return rank[heads][group], np.flatnonzero(is_head)


def regroup_exact(
    slot: np.ndarray, rows: np.ndarray, content_of
) -> tuple[np.ndarray, np.ndarray]:
    """Regroup ``rows`` on their whole bytes (``content_of(row)``): lines
    past the batch width are truncated in their word rows, so two can
    share words and length (rare: ``device_width`` covers 99.5%)."""
    slot = slot.copy()
    base = int(slot.max()) + 1
    exact: dict[bytes, int] = {}
    for i in rows.tolist():
        slot[i] = exact.setdefault(content_of(i), base + len(exact))
    return _by_first_appearance(slot)


def dedup_slots(corpus) -> tuple[np.ndarray, np.ndarray, LineKeys, np.ndarray]:
    """Request-level dedup at array speed: ``(line_slot, rep_lines, keys,
    counts)``, slots numbered by first appearance (as the scalar dict
    loop numbers them), ``rep_lines[s]`` the first line of slot ``s``,
    ``keys`` each slot's :class:`LineKeys` entry, ``counts[s]`` its lines.

    Exactness: a line's encoded ``u8`` row and length are its bytes when
    it fits the batch width. A line past the width is ``needs_host`` at
    the width's length, so those lines are regrouped on their bytes
    (:func:`regroup_exact`), and never stored. Lone-surrogate corpora
    key the same way: their encode replaces as ``line_key_bytes`` does."""
    enc = corpus.encoded
    n = int(enc.n_lines)
    width = enc.u8.shape[1]
    words = _as_words(enc.u8[:n])
    lengths = enc.lengths[:n].astype(np.int64)
    probes = probe64(words, lengths)
    slot, first = group_rows(words, lengths, probes)
    long_lines = np.flatnonzero(enc.needs_host[:n] & (lengths >= width))
    if long_lines.size:
        slot, first = regroup_exact(slot, long_lines, corpus.line_key_bytes)
    keys = LineKeys(words, first, lengths[first], probes[first],
                    ~enc.needs_host[first])
    return slot, first, keys, np.bincount(slot, minlength=first.size)


def _classes(keys: LineKeys):
    """``(k, idx)``: the storable keys of each word class."""
    idx = np.flatnonzero(keys.storable)
    cls = word_class(keys.lengths[idx])
    for k in np.flatnonzero(np.bincount(cls)).tolist():
        yield k, idx[cls == k]


def _buckets(cap: int) -> int:
    """Index buckets for ``cap`` slots: the least power of two that is at
    least twice as many (none for none)."""
    return 1 << (2 * cap - 1).bit_length() if cap else 0


class _Table:
    """The cached lines of one word class, ``k`` content words each, in
    arrays of capacity ``cap``: slots ``[0, n)`` are in use but those in
    ``free`` (evicted, stamp ``_FREE``), which the next appends take
    first. ``index`` finds a live entry by its probe, one entry a probe:
    an open-addressed hash of at least two buckets a slot, each holding
    a slot, ``_EMPTY`` or ``_DELETED`` (an evicted entry's). A probe's
    home is its top bits, and its entry the first bucket on (linear
    probing) whose slot has that probe; a walk ends at an empty bucket.
    Each slot keeps its probe and its bucket, so a call's upkeep follows
    the keys it stores and the entries it evicts, never those that stay."""

    def __init__(self, k: int, row_bytes: int):
        self.k = k
        self.n = self.epoch = 0  # epoch: compactions, which move entries
        self.layout = 0  # index rebuilds, which move every bucket
        self.deleted = 0  # _DELETED buckets
        self.free = np.zeros(0, dtype=np.int64)
        self.words = np.zeros((0, k), dtype=np.uint64)
        self.lengths = np.zeros(0, dtype=np.int32)
        self.packed = np.zeros((0, row_bytes), dtype=np.uint8)
        self.stamp = np.zeros(0, dtype=np.int64)  # the call that last touched it
        self.probe = np.zeros(0, dtype=np.uint64)
        self.bucket = np.zeros(0, dtype=np.int32)
        self.index = np.zeros(0, dtype=np.int32)
        # a slot's bytes in every array of capacity; the index adds its own
        self.entry_bytes = k * 8 + 4 + row_bytes + 8 + 8 + 4

    @property
    def cap(self) -> int:
        return len(self.lengths)

    @property
    def live(self) -> int:
        return self.n - self.free.size

    @property
    def nbytes(self) -> int:
        return self.cap * self.entry_bytes + self.index.nbytes

    def bytes_at(self, cap: int, tight: bool = False) -> int:
        """The table's bytes at ``cap`` slots: grown to them, or (``tight``)
        with the least index that holds them."""
        size = _buckets(cap)
        if not tight:
            size = max(size, self.index.size)
        return cap * self.entry_bytes + size * self.index.itemsize

    def _home(self, probes: np.ndarray) -> np.ndarray:
        shift = np.uint64(65 - self.index.size.bit_length())
        return (probes >> shift).astype(np.int64)

    def _walk(self, start: np.ndarray, stop) -> np.ndarray:
        """For each walk ``i`` from bucket ``start[i]``, the first bucket
        on whose slot ``stop(i, slots)`` accepts, unwrapped (past the
        last bucket the walk goes on at the first). Each round reads a
        window of buckets for every walk still open: one while a call's
        many keys are open, more as they end, so a long run of full
        buckets costs a few rounds and never a loop over keys. A quarter
        of the buckets at least are empty, so a walk past every bucket
        means a corrupt index: it raises rather than spin under the lock."""
        mask = self.index.size - 1
        end = start.copy()  # where each open walk is, then where it ended
        open_ = np.arange(start.size)
        walked = 0
        while open_.size:
            if walked > mask:
                raise RuntimeError("line cache index: a walk found no stop")
            w = min(64, max(1, _WALK_READS // open_.size))
            walked += w
            if w == 1:  # a row-wise argmax of one column costs more than the reads
                at = end[open_]
                done = stop(open_, self.index[at & mask][:, None])[:, 0]
            else:
                ok = stop(open_, self.index[
                    (end[open_, None] + np.arange(w)) & mask])
                j = ok.argmax(axis=1)
                done = ok[np.arange(open_.size), j]
                end[open_[done]] += j[done]
            open_ = open_[~done]
            end[open_] += w
        return end

    def find(self, keys: LineKeys, idx: np.ndarray, store: bool = False):
        """``(entry, collided, at, reads)`` for keys ``idx``: the entry
        holding each key's line (-1 where none does), the keys whose
        probe leads to a different line, and the buckets the walks read
        past their homes. With ``store``, ``at`` is where each absent
        key's walk passed its first free bucket, for :meth:`append`."""
        probes = keys.probes[idx]
        eid = np.full(idx.size, -1, dtype=np.int64)
        collided = np.zeros(idx.size, dtype=bool)
        if not self.live:
            return eid, collided, None, 0
        home = self._home(probes)

        def stop(i, s, free):
            match = (s >= 0) & (self.probe[np.maximum(s, 0)] == probes[i, None])
            return match | (s < 0 if free else s == _EMPTY)

        end = self._walk(home, lambda i, s: stop(i, s, store))
        at = end
        if store:
            # a walk that met a deleted bucket goes on to the line or an
            # empty bucket; the deleted one is where the line would go
            on = np.flatnonzero(self.index[end & (self.index.size - 1)]
                                == _DELETED)
            if on.size:
                end = end.copy()
                end[on] = self._walk(
                    end[on] + 1, lambda i, s: stop(on[i], s, False))
        s = self.index[end & (self.index.size - 1)]
        cand = np.flatnonzero(s >= 0)
        e, i = s[cand].astype(np.int64), idx[cand]
        ok = (self.lengths[e] == keys.lengths[i]) & (
            self.words[e] == keys.words_of(i, self.k)
        ).all(axis=1)
        eid[cand[ok]] = e[ok]
        collided[cand[~ok]] = True
        return eid, collided, at, int((end - home).sum())

    def _place(self, at: np.ndarray, slots: np.ndarray, free=False) -> int:
        """Put ``slots`` (in probe order, their probes not in the index)
        in the first free bucket from ``at`` on (``free``: the buckets
        ``at`` are free), one slot a bucket a round, the first in probe
        order: the others walk on from the bucket past it. Returns the
        buckets read past ``at``."""
        mask = self.index.size - 1
        end = at if free else self._walk(at, lambda i, s: s < 0)
        placed = np.empty(slots.size, dtype=np.int64)
        open_ = np.arange(slots.size)
        while True:
            pos = end & mask
            if (pos[1:] >= pos[:-1]).all():
                # walks from buckets in order end in order (the first free
                # bucket on is monotone), so a bucket's walks are neighbours
                first = np.flatnonzero(np.r_[True, pos[1:] != pos[:-1]])
            else:  # a walk went on past the last bucket
                first = np.unique(pos, return_index=True)[1]
            won, pos = slots[open_[first]], pos[first]
            self.deleted -= int((self.index[pos] == _DELETED).sum())
            self.index[pos] = won
            self.bucket[won] = pos
            placed[open_[first]] = end[first]
            if first.size == open_.size:
                return int((placed - at).sum())
            lost = np.ones(open_.size, dtype=bool)
            lost[first] = False
            open_ = open_[lost]
            end = self._walk(end[lost] + 1, lambda i, s: s < 0)

    def _rebuild(self, size: int) -> None:
        """A fresh index of ``size`` buckets over the live entries, with
        no deleted buckets. In order of their homes, linear probing puts
        each entry at its home or just past the one before, so one
        running maximum places them all; the few past the last bucket go
        on at the first. The old index lists the entries nearly in that
        order already, so a merge sort finds it in about one pass."""
        self.layout += 1
        self.deleted = 0
        slots = self.index[self.index >= 0].astype(np.int64)
        self.index = np.full(size, _EMPTY, dtype=np.int32)
        if not slots.size:
            return
        home = self._home(self.probe[slots])
        order = np.argsort(home, kind="stable")
        slots, home = slots[order], home[order]
        r = np.arange(slots.size)
        pos = np.maximum.accumulate(home - r) + r
        fits = pos < size
        self.index[pos[fits]] = slots[fits]
        self.bucket[slots[fits]] = pos[fits]
        if not fits.all():
            self._place(np.full(int((~fits).sum()), size), slots[~fits])

    def resize(self, cap: int, tight: bool = False) -> None:
        """Grow or shrink the arrays to ``cap`` slots in place: a realloc,
        which does not copy the entries kept. ``ndarray.resize`` refuses
        an array that anything else references, so each is resized
        through its attribute. The index is rebuilt where ``cap``
        outgrows it, or shrinks to an eighth of it (between two rebuilds
        the capacity has doubled or halved), or (``tight``) to the least
        that holds it."""
        self.words.resize((cap, self.k))
        self.lengths.resize(cap)
        self.packed.resize((cap, self.packed.shape[1]))
        self.stamp.resize(cap)
        self.probe.resize(cap)
        self.bucket.resize(cap)
        size = self.index.size
        if 2 * cap > size or 8 * cap <= size or tight and _buckets(cap) < size:
            self._rebuild(_buckets(cap))

    def drop(self, slots: np.ndarray) -> None:
        """Evict the entries in ``slots``: their buckets deleted, their
        slots free for reuse."""
        self.index[self.bucket[slots]] = _DELETED
        self.deleted += slots.size
        self.stamp[slots] = _FREE
        self.free = np.concatenate([self.free, slots])

    def copy(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Copy the entries in slots ``src`` to slots ``dst``."""
        for name in ("words", "lengths", "packed", "stamp", "probe", "bucket"):
            arr = getattr(self, name)
            arr[dst] = arr[src]

    def compact(self, tight: bool = False) -> None:
        """Close up the free slots, down to a capacity of ``live``: the
        live entries past it move into the free slots below it, and
        their buckets point at them there (``tight``: see :meth:`resize`)."""
        self.epoch += 1
        live = self.live
        holes = self.free[self.free < live]
        movers = live + np.flatnonzero(self.stamp[live : self.n] != _FREE)
        self.index[self.bucket[movers]] = holes
        self.copy(movers, holes)
        self.n, self.free = live, self.free[:0]
        self.resize(live, tight)

    def append(self, keys: LineKeys, idx: np.ndarray, packed, gen: int,
               at, layout: int) -> int:
        """Store keys ``idx`` (in probe order, none of them present),
        free slots first, each in the index from ``at`` on: the bucket
        its :meth:`find` walk found free under index ``layout``, or its
        home where the index has been rebuilt since. The deleted buckets
        are cleared first where they would fill more than half the index,
        or, in a table so full that they must, more than half its live
        entries: a rebuild then follows at least that many evictions.
        Returns the buckets read past ``at``."""
        fill = self.live + idx.size
        if self.deleted > max(self.index.size // 2 - fill, fill // 2):
            self._rebuild(self.index.size)
        probes = keys.probes[idx]
        free = at is not None and self.layout == layout
        if not free:
            at = self._home(probes)
        f = min(idx.size, self.free.size)
        slots = np.concatenate(
            [self.free[:f], np.arange(self.n, self.n + idx.size - f)]
        )
        self.free = self.free[f:]
        self.n += idx.size - f
        self.words[slots] = keys.words_of(idx, self.k)
        self.lengths[slots] = keys.lengths[idx]
        self.packed[slots] = packed
        self.stamp[slots] = gen
        self.probe[slots] = probes
        return self._place(at, slots, free)


class CachedRows(NamedTuple):
    """What one lookup found: ``row[s]`` is key ``s``'s row in
    ``packed`` (-1 for a miss), the hits' bit-packed rows copied under
    the lock in key order; ``touched`` the hit entries, ``(table, epoch,
    slots)``, for the request's populate to touch again."""

    row: np.ndarray  # int64 [U]
    packed: np.ndarray  # uint8 [H, ceil(n_columns / 8)]
    touched: tuple = ()


class LineCache:
    """Content-addressed table of per-line pre-override match-bit rows.

    One :class:`_Table` a word class holds each cached line's content
    words, length, packed bit row (``np.packbits``: a 108-column bank
    costs 14 bytes a line), recency stamp and probe, in a hashed index of
    the probes. Lookup and populate are array operations under one lock
    acquisition a call; a hit needs equal words and length, so a row is
    only ever served to the bytes it was computed for. ``budget_bytes``
    bounds the arrays' bytes at their capacity, the index's included:
    past it the oldest entries are evicted, never those the current call
    touched."""

    def __init__(self, n_columns: int, budget_bytes: int):
        self.lock = threading.Lock()
        self.budget_bytes = max(0, int(budget_bytes))
        self._set_columns(n_columns)
        self._gen = 0
        # counters (GET /trace/last "lineCache"; guarded by lock)
        self.hits = 0
        self.misses = 0
        self.residual_rows = 0
        self.dedup_fanout = 0
        self.evictions = 0
        self.epoch_flushes = 0
        self.probe_collisions = 0
        self.index_probes = 0

    def _set_columns(self, n_columns: int) -> None:
        self.n_columns = int(n_columns)
        self._row_bytes = (self.n_columns + 7) // 8
        self._tables: dict[int, _Table] = {}

    def _resident(self) -> int:
        return sum(t.nbytes for t in self._tables.values())

    # ------------------------------------------------------------- data path

    def lookup(self, keys: LineKeys, counts=None) -> CachedRows:
        """Find each key's row, touching the entries hit. ``counts``
        weights each key by its line multiplicity, so the hit and miss
        counters describe lines while the residual describes device rows;
        keys that are not storable count as misses."""
        U = keys.probes.size
        w = np.ones(U, dtype=np.int64) if counts is None else np.asarray(counts)
        found, rows, touched = [], [], []
        with self.lock:
            self._gen += 1
            for k, idx in _classes(keys):
                table = self._tables.get(k)
                if table is None:
                    continue
                eid, collided, _, reads = table.find(keys, idx)
                self.probe_collisions += int(collided.sum())
                self.index_probes += reads
                e = eid[eid >= 0]
                table.stamp[e] = self._gen
                touched.append((table, table.epoch, e))
                found.append(idx[eid >= 0])
                rows.append(table.packed[e])
            hit = np.concatenate(found) if found else np.zeros(0, dtype=np.int64)
            h = int(w[hit].sum())
            self.hits += h
            self.misses += int(w.sum()) - h
        row = np.full(U, -1, dtype=np.int64)
        if not hit.size:
            return CachedRows(row, np.zeros((0, self._row_bytes), dtype=np.uint8))
        order = np.argsort(hit)
        row[hit[order]] = np.arange(hit.size)
        return CachedRows(row, np.concatenate(rows)[order], tuple(touched))

    def unpack(self, packed: np.ndarray) -> np.ndarray:
        """Packed rows to bool ``[H, n_columns]`` in one ``np.unpackbits``."""
        return np.unpackbits(packed, axis=1, count=self.n_columns).astype(bool)

    def populate(self, keys: LineKeys, rows: np.ndarray, found=None) -> None:
        """Store the storable keys' freshly computed rows (bool
        ``[U, n_columns]``, one a key), bit-packed. A line already there
        is touched; a key whose probe leads to a different line is
        counted and not stored. The entries the request's lookup hit
        (``found``) are touched again first: they are in use until the
        request is done, so its rows never evict them."""
        # packed from the set bits: the readback comes column-major, and
        # a row-wise pack of it would stride across the whole matrix
        r, c = bool_hits(np.asarray(rows, dtype=bool))
        packed = np.zeros((len(rows), self._row_bytes), dtype=np.uint8)
        np.bitwise_or.at(packed, (r, c >> 3), (128 >> (c & 7)).astype(np.uint8))
        sel = np.flatnonzero(keys.storable)
        if sel.size < keys.storable.size:
            keys, packed = keys.take(sel), packed[sel]
        # outside the lock: each class's keys in probe order (the index's
        # walks then read it in order), where a later key of this call
        # under the same probe (the same line, or a collision) sits right
        # after the first
        classes = []
        for k, idx in _classes(keys):
            idx = idx[np.argsort(keys.probes[idx])]
            p = keys.probes[idx]
            later = np.zeros(idx.size, dtype=bool)
            np.equal(p[1:], p[:-1], out=later[1:])
            head = idx[np.maximum.accumulate(
                np.where(later, 0, np.arange(idx.size)))]
            collided = later.copy()
            collided[later] = ~(
                (keys.lengths[idx[later]] == keys.lengths[head[later]])
                & (keys.words_of(idx[later], k)
                   == keys.words_of(head[later], k)).all(axis=1)
            )
            classes.append((k, idx, later, collided))
        with self.lock:
            self._gen += 1
            for table, epoch, slots in found.touched if found else ():
                if table.epoch == epoch:  # no compaction has moved them
                    slots = slots[table.stamp[slots] != _FREE]
                    table.stamp[slots] = self._gen
            for k, idx, later, collided in classes:
                table = self._tables.get(k) or self._tables.setdefault(
                    k, _Table(k, self._row_bytes)
                )
                layout = table.layout
                eid, coll, at, reads = table.find(keys, idx, store=True)
                table.stamp[eid[eid >= 0]] = self._gen
                self.probe_collisions += int((collided | coll).sum())
                self.index_probes += reads
                new = (eid < 0) & ~coll & ~later
                self._insert(table, keys, idx[new], packed[idx[new]],
                             None if at is None else at[new], layout)
            for k in [k for k, t in self._tables.items() if not t.live]:
                del self._tables[k]

    def _insert(self, table: _Table, keys: LineKeys, idx, packed, at,
                layout: int) -> None:
        """Store in ``table`` (``at``, ``layout``: where :meth:`_Table.find`
        left each key): in its free slots, then in capacity grown within
        the budget. Where the budget has no room, the oldest entries of
        every class make way, at least a sixteenth of the budget at a
        time: an eviction costs a pass over every entry's stamp, so calls
        far smaller than the budget share one. What still does not fit
        is not stored."""
        short = idx.size - table.free.size - (table.cap - table.n)
        grow = self._room(table, short)
        if grow < short:
            over = (self._resident() - table.nbytes
                    + table.bytes_at(table.cap + short) - self.budget_bytes)
            self._evict(max(over, self.budget_bytes // 16), table)
            short = idx.size - table.free.size - (table.cap - table.n)
            grow = self._room(table, short)
        if grow:
            table.resize(table.cap + grow)
        if short > grow:
            idx, packed = idx[: grow - short], packed[: grow - short]
            at = None if at is None else at[: grow - short]
        if idx.size:
            self.index_probes += table.append(
                keys, idx, packed, self._gen, at, layout)

    def _room(self, table: _Table, want: int) -> int:
        """The most slots, up to ``want``, that ``table`` can grow by
        within the budget: at each index size from its own up to the one
        ``want`` needs, the slots that size holds and the budget pays
        for besides it. Buckets and slots are int32, so an index stops
        at 2^31 buckets."""
        if want <= 0:
            return 0
        spare = self.budget_bytes - self._resident() + table.nbytes
        cap, eb = table.cap, table.entry_bytes
        size = max(table.index.size, _buckets(cap + 1))
        best = 0
        while True:
            fits = (spare - size * table.index.itemsize) // eb
            best = max(best, min(cap + want, size // 2, fits) - cap)
            if 2 * (cap + want) <= size or size >= _MAX_BUCKETS:
                return best
            size *= 2

    def _evict(self, need: int, keep: _Table | None = None) -> None:
        """Evict the oldest entries of every class until they free
        ``need`` bytes, never one this call touched (stamp >= the current
        call). ``keep`` keeps its freed slots for its next append; any
        other table that loses entries is compacted, giving its bytes
        back to the budget. The cost follows the entries: one partition
        of their stamps picks the oldest that could cover ``need``, and
        only those are sorted."""
        tables = [t for t in self._tables.values() if t.n]
        if need <= 0 or not tables:
            return
        # free slots (stamp _FREE) and this call's entries sort last
        stamps = np.concatenate([t.stamp[: t.n] for t in tables])
        ends = np.cumsum([t.n for t in tables])
        eb = np.array([t.entry_bytes for t in tables])
        k = min(stamps.size, -(-need // int(eb.min())))
        sel = (np.argpartition(stamps, k - 1)[:k] if k < stamps.size
               else np.arange(stamps.size))
        sel = sel[stamps[sel] < self._gen]
        sel = sel[np.argsort(stamps[sel], kind="stable")]
        which = np.searchsorted(ends, sel, side="right")
        n = int(np.searchsorted(np.cumsum(eb[which]), need)) + 1
        self._drop(tables, ends, sel[:n], which[:n], keep)

    def _drop(self, tables, ends, sel, which, keep=None) -> None:
        """Evict entries ``sel``, numbered over the ``tables``' slots in
        turn (table ``which``, ending at ``ends``): every table that loses
        any but ``keep`` is compacted."""
        for j, t in enumerate(tables):
            slots = sel[which == j] - (ends[j] - t.n)
            if slots.size:
                self.evictions += slots.size
                t.drop(slots)
                if t is not keep:
                    t.compact()

    def set_budget(self, budget_bytes: int) -> None:
        """Re-arbitrate the byte budget live (fleet/budget.py pushes
        shares through ``POST /admin/budget``): a shrink evicts the oldest
        entries down to the new budget at once. An index shrinks by
        halves, so the bytes an eviction frees are not its entries'
        alone: a bisection finds the fewest oldest entries whose eviction,
        each table's index shrunk to the least that holds what stays,
        fits the budget."""
        with self.lock:
            self.budget_bytes = budget = max(0, int(budget_bytes))
            if self._resident() <= budget:
                return
            tables = list(self._tables.values())
            for t in tables:
                t.compact(tight=True)
            stamps = np.concatenate([t.stamp[: t.n] for t in tables])
            ends = np.cumsum([t.n for t in tables])
            age = np.argsort(stamps, kind="stable")
            which = np.searchsorted(ends, age, side="right")
            live = np.array([t.n for t in tables])

            def fits(m: int) -> bool:
                left = live - np.bincount(which[:m], minlength=len(tables))
                return sum(t.bytes_at(c, tight=True)
                           for t, c in zip(tables, left.tolist())) <= budget

            lo, hi = 0, age.size  # with every entry evicted, nothing is left
            while lo < hi:
                mid = (lo + hi) // 2
                lo, hi = (lo, mid) if fits(mid) else (mid + 1, hi)
            self._drop(tables, ends, age[:lo], which[:lo])
            for t in tables:
                t.compact(tight=True)

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
            self._set_columns(
                self.n_columns if n_columns is None else n_columns
            )
            self.epoch_flushes += 1

    # ------------------------------------------------------- observability

    def stats(self) -> dict:
        with self.lock:
            return {
                "budgetMb": round(self.budget_bytes / (1024 * 1024), 3),
                "entries": sum(t.live for t in self._tables.values()),
                "residentBytes": self._resident(),
                "hits": self.hits,
                "misses": self.misses,
                "residualRows": self.residual_rows,
                "dedupFanout": self.dedup_fanout,
                "evictions": self.evictions,
                "epochFlushes": self.epoch_flushes,
                "probeCollisions": self.probe_collisions,
                "indexProbes": self.index_probes,
            }


# /metrics views over LineCache.stats() — read by the obs engine
# collector at scrape time (log_parser_tpu/obs), so the exposition and
# /trace/last can never disagree on these counters
CACHE_METRIC_SAMPLES = (
    ("hits", "logparser_line_cache_hits_total", {}),
    ("misses", "logparser_line_cache_misses_total", {}),
    ("evictions", "logparser_line_cache_evictions_total", {}),
    ("residentBytes", "logparser_line_cache_resident_bytes", {}),
    ("probeCollisions", "logparser_line_cache_probe_collisions_total", {}),
    ("indexProbes", "logparser_line_cache_index_probes_total", {}),
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


def packed_hits(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(row, col)`` of the set bits of bit-packed rows ``[H, bytes]``,
    sorted by row then column: only the nonzero bytes are unpacked."""
    if not packed.size:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    buf = np.ascontiguousarray(packed).ravel()
    flat = _nonzero_bytes(buf)
    k, bit = np.nonzero(np.unpackbits(buf[flat][:, None], axis=1))
    row, byte = np.divmod(flat[k], packed.shape[1])
    return row, byte * 8 + bit


def slot_hits(
    found: CachedRows,
    miss_slots: np.ndarray,
    fresh: np.ndarray | None,
) -> SlotHits:
    """Hit columns per unique slot from the cached rows (``found``) and
    the readback rows (``fresh[j]`` for slot ``miss_slots[j]``), never
    unpacked to a dense matrix."""
    U = found.row.size
    start = np.zeros(U, dtype=np.int64)
    count = np.zeros(U, dtype=np.int64)
    groups = []
    if found.packed.shape[0]:
        groups.append((np.flatnonzero(found.row >= 0), packed_hits(found.packed)))
    if fresh is not None and len(miss_slots):
        groups.append((np.asarray(miss_slots, dtype=np.int64), bool_hits(fresh)))
    parts: list[np.ndarray] = []
    base = 0
    for idx, (row, col) in groups:
        c = np.bincount(row, minlength=idx.size)
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
