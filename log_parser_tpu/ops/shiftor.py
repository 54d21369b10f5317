"""Bit-parallel Shift-Or matcher: the exact fast path for literal-shaped
regexes.

Most real failure patterns are literal-bearing ("OutOfMemoryError",
"Connection refused", "status=[45]\\d\\d"): their regexes reduce to a few
fixed-length byte-class sequences (literals.exact_sequences), and substring
search for those needs no DFA at all. Shift-Or packs every sequence of all
such matcher columns into 32-bit bit-planes and advances them with three
vector ops per byte — shift, and, or — plus one 256-row table row-select.
Per step the whole bank costs O(B × W) lane-local integer ops (W = packed
words), independent of how many patterns share the bank: the per-regex
axis of the DFA bank disappears, which is what makes the 10k-pattern
configuration tractable on one chip.

Bit layout per 32-bit word (first-fit packing): a sequence of length m at
offset o uses bits [o, o+m). After each byte: ``D = ((D << 1) & start_clear)
| mask[byte]`` where ``start_clear`` zeroes each sequence's start bit
(fresh shift-in, isolating neighbors) and mask bit (o+j) = 1 iff the byte
CANNOT be position j of the sequence (Shift-Or convention: 0 = still
alive). A sequence has matched at this position iff bit (o+m-1) is 0; hits
accumulate over positions ``t < length``.

Sequences longer than 32 positions ride *cross-word chains*: they take a
word-aligned run of whole words, and the shift propagates bit 31 of each
chain word into bit 0 of the next (``cont_mask`` marks receiving words —
only there is the shift's incoming 0 replaced by the carry, everywhere
else bit 0 is either a start bit, restarted by ``start_clear``, or inert).
The tail word's remaining bits stay open to first-fit short sequences.
Chains let DFA-less literal columns with >32-position sequences (this
tier is their only device path) stay exact instead of falling to host.

The row-select ``mask[byte]`` is a small-table ``jnp.take`` ([256, W]
rows, contiguous): one-level takes from a 256-row table indexed by the
raw byte, minimal row width. The stepper consumes one byte at a time and
accumulates hits in an ungated carry: on v5e the take cost scales with
the gathered row width and table locality, not take count, so the bank
packs each sequence bare (no extra state bits). Two families of
table-side "improvements" were built, measured SLOWER on TPU v5e, and
deleted:

- *m-term-precomposed tables* (``D2 = (D<<2) & SC2 | M2[b1,b2]`` with
  ``M2`` materialized per byte pair): the composed tables need 1.5-2x
  the row words or 65536 rows (0.130-0.242s vs 0.089s for the builtin
  bank, 229k lines);
- *byte-class indirection* (``[C², 2W]`` tables behind a ``[256]``
  class map, C=62): any dependent two-level gather inside the scan adds
  ~3ms/step at this batch — 0.24-0.29s even with 40KB tables.

A one-hot MXU matmul variant was likewise prototyped and DELETED: with
the SHIFTOR_MAX_WORDS gate this tier only ever runs at <=128 words where
the take is already cheap, the matmul would materialize a [B, 256] f32
one-hot (~235 MB per scan step at the 229k-row config-2 batch — pure HBM
traffic), and the very wide banks an MXU formulation could serve no
longer reach Shift-Or at all (they route to the any-hit prefilter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ByteSeq = tuple  # tuple[frozenset[int], ...]


class FirstFitPacker:
    """Incremental first-fit packing state — the same rule as
    :func:`first_fit_plan` (which is implemented on top of it), exposed
    statefully so tier admission gates can price one candidate in
    O(candidate + words) instead of repacking every already-admitted
    allocation per candidate (r5 code review: the full repack made
    MatcherBanks construction quadratic on wide libraries)."""

    __slots__ = ("word_fill",)

    def __init__(self) -> None:
        self.word_fill: list[int] = []

    def add(self, alloc: int) -> int:
        """Pack one allocation; returns its start bit.  Allocations ≤ 32
        bits first-fit within any word, never straddling one (the
        chainless-shift invariant both banks rely on); larger
        allocations take word-aligned runs of whole words whose tail
        remainder stays open to first-fit."""
        word_fill = self.word_fill
        if alloc > 32:
            w0 = len(word_fill)
            nw = (alloc + 31) // 32
            word_fill.extend([32] * (nw - 1))
            word_fill.append(alloc - 32 * (nw - 1))
            return w0 * 32
        w = next(
            (i for i, used in enumerate(word_fill) if used + alloc <= 32),
            None,
        )
        if w is None:
            w = len(word_fill)
            word_fill.append(0)
        start = w * 32 + word_fill[w]
        word_fill[w] += alloc
        return start

    @property
    def n_words(self) -> int:
        return len(self.word_fill)

    def clone(self) -> "FirstFitPacker":
        p = FirstFitPacker()
        p.word_fill = list(self.word_fill)
        return p


def first_fit_plan(allocs, budget: int | None = None):
    """First-fit word-packing plan shared by the two bit tiers — THE
    single source of the packing rule (tier gates that estimate word
    cost must agree with the bank constructors; both route through
    :class:`FirstFitPacker`).  Returns (per-allocation start bits,
    n_words); with a ``budget``, bails early once the word count
    exceeds it."""
    packer = FirstFitPacker()
    starts: list[int] = []
    for alloc in allocs:
        starts.append(packer.add(alloc))
        if budget is not None and packer.n_words > budget:
            return starts, packer.n_words
    return starts, max(1, packer.n_words)


class ShiftOrBank:
    """Packed Shift-Or program for a set of (column, sequences) entries."""

    @staticmethod
    def count_packed_words(seq_lengths, budget: int | None = None) -> int:
        """Words the bank packs (:func:`first_fit_plan`; a sequence's
        allocation is exactly its length)."""
        return first_fit_plan(seq_lengths, budget)[1]

    def __init__(self, column_seqs: list[tuple[int, tuple[ByteSeq, ...]]]):
        self.columns = [c for c, _ in column_seqs]
        flat = [(col, seq) for col, seqs in column_seqs for seq in seqs]
        starts, self.n_words = first_fit_plan([len(seq) for _, seq in flat])
        self.n_seqs = len(flat)

        # mask[c, w]: bit (o+j) = 1 iff byte c not allowed at position j;
        # unused bits are always-1 (inert)
        mask = np.full((256, self.n_words), 0xFFFFFFFF, dtype=np.uint32)
        start_clear = np.full(self.n_words, 0xFFFFFFFF, dtype=np.uint32)
        cont_mask = np.zeros(self.n_words, dtype=np.uint32)
        end_mask = np.zeros(self.n_words, dtype=np.uint32)
        for (col, seq), g in zip(flat, starts):
            start_clear[g // 32] &= ~np.uint32(1 << (g % 32))
            for j, byteset in enumerate(seq):
                p = g + j
                bit = np.uint32(1 << (p % 32))
                for c in byteset:
                    # byte 0 is padding-only (NUL-bearing lines are
                    # needs_host — encode): keep mask[0] all-ones so
                    # pad0_transparent holds for every bank
                    if c != 0:
                        mask[c, p // 32] &= ~bit
            # chain continuation words receive shift carry from their
            # predecessor
            e = g + len(seq) - 1
            for w in range(g // 32 + 1, e // 32 + 1):
                cont_mask[w] |= np.uint32(1)
            end_mask[e // 32] |= np.uint32(1 << (e % 32))
        self.mask = jnp.asarray(mask)
        self.start_clear = jnp.asarray(start_clear)
        self.end_mask = jnp.asarray(end_mask)
        self.has_chains = bool(cont_mask.any())
        self.cont_mask = jnp.asarray(cont_mask)
        # The hit term is ``hits |= (~d_new) & end_mask`` and
        # ``d_new = sh | mask[byte]`` — so a padding byte (0) can only
        # contribute a hit if some sequence's END position admits NUL
        # (its ``mask[0]`` bit is 0). When every end bit is set in
        # ``mask[0]`` the per-byte ``pos < length`` gating is a provable
        # no-op and the stepper drops it (two [B, W] selects per byte).
        # The builder above strips byte 0 from every byteset (NUL-bearing
        # lines are needs_host — encode.py), so today this is True for
        # every bank; the flag still computes the sound condition and the
        # gated stepper path is retained as the correctness fallback
        # should a future bank ever admit the padding byte.
        self.pad0_transparent = bool(((mask[0] & end_mask) == end_mask).all())

        # host copies for the host carry (ShiftOrHostCarry)
        self._np = {"mask": mask, "start_clear": start_clear,
                    "end_mask": end_mask, "cont_mask": cont_mask}

        # per-sequence extraction: hits[:, word] >> bit & 1 -> column OR
        ends = [g + len(seq) - 1 for (_, seq), g in zip(flat, starts)]
        self.seq_word = np.asarray([e // 32 for e in ends], dtype=np.int32)
        self.seq_bit = np.asarray([e % 32 for e in ends], dtype=np.int32)
        # map sequences onto output slots (position of column in self.columns)
        slot_of_col = {c: i for i, c in enumerate(self.columns)}
        self.seq_slot = np.asarray(
            [slot_of_col[col] for col, _ in flat], dtype=np.int32
        )

    # --------------------------------------------------------------- device

    def _row_select(self, bytes_t: jax.Array) -> jax.Array:
        return jnp.take(self.mask, bytes_t.astype(jnp.int32), axis=0)  # [B, W]

    def _s1(self, x: jax.Array) -> jax.Array:
        """Chain-aware shift by one position (device)."""
        sh = x << 1
        if self.has_chains:
            carry = jnp.concatenate(
                [jnp.zeros_like(x[:, :1]), x[:, :-1] >> 31], axis=1
            )
            sh = sh | (carry & self.cont_mask[None, :])
        return sh

    def pair_stepper(self, B: int, lengths: jax.Array):
        """(init, step(carry, b1, b2, t), finish). On the (universal
        today) ``pad0_transparent`` banks, steps per byte with an ungated
        hits carry (sound because a padding byte sets every end bit in
        ``d``, so past-end positions can never contribute a hit).
        Non-transparent banks keep the gated per-byte path."""
        if self.pad0_transparent:
            return self._ungated_hits_stepper(B)
        return self._perbyte_pair_stepper(B, lengths)

    def _ungated_hits_stepper(self, B: int):
        """Per-byte hits accumulation with NO length gating: one
        [256, W] row take plus four [B, W] vector ops per byte on the
        narrowest possible rows. The carry holds the COMPLEMENT (``nh``
        — 1 = never hit): the update
        ``nh & (d | ~e)`` is one op cheaper per byte than
        ``hits | (~d & e)`` because ``~e`` is a precomputed constant;
        one inversion at ``finish`` recovers the hit words."""
        select = self._row_select
        sc = self.start_clear[None, :]
        not_e = (~self.end_mask)[None, :]
        d0 = jnp.full((B, self.n_words), 0xFFFFFFFF, dtype=jnp.uint32)
        nh0 = jnp.full((B, self.n_words), 0xFFFFFFFF, dtype=jnp.uint32)

        def one(carry, b):
            d, nh = carry
            d = (self._s1(d) & sc) | select(b)
            return d, nh & (d | not_e)

        def step(carry, b1, b2, t):
            return one(one(carry, b1), b2)

        def finish(carry):
            _, nh = carry
            return self.columns_from_hits(~nh & self.end_mask[None, :])

        return (d0, nh0), step, finish

    def columns_from_hits(self, hits: jax.Array) -> jax.Array:
        """uint32 [N, W] accumulated hit words -> bool [N, n_columns]."""
        N = hits.shape[0]
        seq_hit = (
            jnp.take(hits, jnp.asarray(self.seq_word), axis=1)
            >> jnp.asarray(self.seq_bit)[None, :]
        ) & 1  # [N, n_seqs]
        out = jnp.zeros((N, max(1, len(self.columns))), dtype=jnp.int32)
        out = out.at[:, jnp.asarray(self.seq_slot)].max(
            seq_hit.astype(jnp.int32)
        )
        return out.astype(bool)

    def _perbyte_pair_stepper(self, B: int, lengths: jax.Array):
        """Per-byte gated fallback for banks whose padding byte is not
        provably transparent (unreachable for banks built by this module
        — the builder strips byte 0 — but kept as the correctness path
        should a future builder admit it)."""
        select = self._row_select
        d0 = jnp.full((B, self.n_words), 0xFFFFFFFF, dtype=jnp.uint32)
        hits0 = jnp.zeros((B, self.n_words), dtype=jnp.uint32)

        def one(carry, b, pos_ok):
            d, hits = carry
            m = select(b)
            d_new = (self._s1(d) & self.start_clear[None, :]) | m
            active = pos_ok[:, None]
            hits = jnp.where(
                active, hits | ((~d_new) & self.end_mask[None, :]), hits
            )
            return jnp.where(active, d_new, d), hits

        def step(carry, b1, b2, t):
            p0 = 2 * t
            carry = one(carry, b1, p0 < lengths)
            return one(carry, b2, p0 + 1 < lengths)

        def finish(carry):
            _, hits = carry
            return self.columns_from_hits(hits)

        return (d0, hits0), step, finish

    def _run(
        self, lines_tb: jax.Array, lengths: jax.Array
    ) -> jax.Array:
        """lines_tb: uint8 [T, B]; returns bool [B, n_columns_in_bank]."""
        from log_parser_tpu.ops.match import pack_byte_pairs

        T, B = lines_tb.shape
        init, step, finish = self.pair_stepper(B, lengths)
        pairs, ts = pack_byte_pairs(lines_tb)
        carry, _ = jax.lax.scan(
            lambda c, xs: (step(c, xs[0][0], xs[0][1], xs[1]), None),
            init,
            (pairs, ts),
        )
        return finish(carry)

    # ----------------------------------------------------------- host carry

    def host_carry(self) -> "ShiftOrHostCarry | None":
        """Resumable host-side scanner over ONE line's bytes, bit-exact
        with the device steppers (streaming follow-mode feeds a partial
        tail line chunk by chunk and snapshots the would-be cube row
        without rescanning from byte 0). None for banks whose padding
        byte is not provably transparent — those would need per-position
        gating that a byte-resumable carry cannot replay."""
        if not self.pad0_transparent:
            return None
        return ShiftOrHostCarry(self)


class ShiftOrHostCarry:
    """Carried Shift-Or registers for one growing line (host, numpy):
    accumulates complement-hits per byte exactly like
    ``_ungated_hits_stepper``, so padded width does not matter — the
    width-independence the line cache relies on."""

    def __init__(self, bank: ShiftOrBank):
        self.bank = bank
        c = bank._np
        self._mask = c["mask"]
        self._sc = c["start_clear"]
        self._not_e = ~c["end_mask"]
        self._em = c["end_mask"]
        self._cm = c["cont_mask"]
        self.reset()

    def _s1(self, x: np.ndarray) -> np.ndarray:
        sh = (x << 1).astype(np.uint32)
        if self.bank.has_chains:
            carry = np.concatenate([np.zeros(1, np.uint32), x[:-1] >> 31])
            sh = sh | (carry & self._cm)
        return sh

    def reset(self) -> None:
        W = self.bank.n_words
        self._d = np.full(W, 0xFFFFFFFF, dtype=np.uint32)
        self._nh = np.full(W, 0xFFFFFFFF, dtype=np.uint32)

    def feed(self, data: bytes) -> None:
        d, nh = self._d, self._nh
        for b in data:
            d = (self._s1(d) & self._sc) | self._mask[b]
            nh = nh & (d | self._not_e)
        self._d, self._nh = d, nh

    def snapshot_bits(self) -> np.ndarray:
        """bool [n_bank_columns]: the cube row the device would produce
        if the line ended at the bytes fed so far. Non-destructive."""
        bank = self.bank
        hits = ~self._nh & self._em
        seq_hit = (hits[bank.seq_word] >> bank.seq_bit.astype(np.uint32)) & 1
        out = np.zeros(max(1, len(bank.columns)), dtype=bool)
        np.maximum.at(out, bank.seq_slot, seq_hit.astype(bool))
        return out
