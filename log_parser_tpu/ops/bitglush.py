"""Gather-free bit-parallel regex engine (extended Shift-And).

Executes :class:`~log_parser_tpu.patterns.regex.bitprog.BitProgram`
columns — classes, ``+``/``*``/``?`` repeats, ``.*`` gaps, alternations,
``^``/``$``/``\\b``/``\\B`` — with NO per-element random gathers: per byte
the whole bank costs one contiguous ``[256, W]`` mask-row take plus
elementwise vector ops on ``[B, W]`` words. This replaces the union
multi-DFA tier's ``[B, G]`` transition gather (scalar-unit bound at ~9ns
per element, PERF.md §1) for every column whose regex fits the
bit-parallel fragment, turning the match cube's dominant cost into pure
VPU work.

Execution model (Glushkov positions, Shift-And active-high): bit ``g`` of
the state word means "some containment attempt has consumed exactly the
items up to and including position ``g``, ending at the current byte".
Per consumed byte:

1. candidates ``C`` = state shifted one position (cross-word carry; entry
   into ``^``-anchored start positions blocked) | start positions (find()
   restart at every byte — AnalysisService.java:93-95's substring
   semantics) | ``^`` starts at t=0 only;
2. ε-closure: a candidate at a skippable (``*``/``?``) position also
   makes the next position a candidate — unrolled ``max_skip_run`` times;
3. gate by the per-position assertion mask selected from the previous /
   current byte word-ness (``\\b``/``\\B``), AND with the byte's class
   mask row; OR with the self-loop survivors (``+``/``*`` positions whose
   class admits the byte);
4. accept: plain finals accumulate into ``hits``; ``$`` finals only at
   each row's last byte; trailing-``\\b`` finals when the NEXT byte
   breaks word-ness (checked one step later from the pre-update state,
   and at end-of-line against the final byte's word-ness).

Alternatives are first-fit word-packed (like Shift-Or): each
alternative's allocation (its positions, plus one sink bit in sink
mode) lives inside ONE 32-bit word whenever it fits, so the
one-position shift needs NO cross-word carry and the whole carry op
group (a concatenate per shift — a fusion breaker that measured 2.5x
the chainless stepper on v5e, tools/probe_chainless.py) disappears
from chain-free banks. Allocations over 32 bits take word-aligned
runs of whole words and turn the bank-wide carry back on
(``has_chains``) — ops/match.py keeps such alternatives out of the
builtin-library bank by truncating primary-only columns (necessity-
preserving, host-verified at assembly) and routing long literal
columns to Shift-Or's chain path.

Stray cross-allocation shifts are harmless by construction: within an
allocation the shift is the intended advance; the bit leaking OUT of an
allocation lands on the NEXT allocation's first bit — a start position
(re-injected every byte anyway for ``find()`` restart semantics, or
explicitly blocked when ``^``-anchored) — or on an unused fragmentation
bit, whose ``bmask`` row is all-zero so the byte-class AND kills it the
same step. A leaked bit therefore never travels more than one position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from log_parser_tpu.patterns.regex.bitprog import BitProgram


def _is_word(b32: jax.Array) -> jax.Array:
    """Elementwise [0-9A-Za-z_] test — no table lookup needed."""
    return (
        ((b32 >= 48) & (b32 <= 57))
        | ((b32 >= 65) & (b32 <= 90))
        | ((b32 >= 97) & (b32 <= 122))
        | (b32 == 95)
    )


class BitGlushBank:
    """Packed bit programs for a set of (column, BitProgram) entries."""

    @staticmethod
    def sink_eligible(programs) -> bool:
        """Sticky *sink* positions (see ``__init__``) drop the per-byte
        hit accumulation — and its ``[B, W]`` carry — from the stepper.
        A trailing ``\\b``/``\\B`` final would need a sink whose admission
        depends on the FINAL item's word-ness too, so those banks keep
        the per-byte hit path (in practice ``expand_asserts`` removes
        them before packing)."""
        return not any(
            a.post_assert in ("b", "B")
            for p in programs
            for a in p.alternatives
        )

    @staticmethod
    def _plan(allocs, budget: int | None = None):
        """First-fit packing plan over per-alternative allocation sizes
        (:func:`~log_parser_tpu.ops.shiftor.first_fit_plan` — shared
        with the Shift-Or tier; ``count_packed_words`` and ``__init__``
        both route through here so they cannot disagree)."""
        from log_parser_tpu.ops.shiftor import first_fit_plan

        return first_fit_plan(allocs, budget=budget)

    @staticmethod
    def alt_alloc(alt, sink: int) -> int:
        """Bits ONE alternative allocates under bank-wide sink mode
        ``sink`` (0/1): positions, plus the sink, plus one dead *guard*
        bit BEFORE every ``^``-anchored alternative.  THE per-alternative
        sizing formula — the tier admission gate in ops/match.py prices
        candidates through this too, so a new guard-style bit cannot
        silently diverge the gate from the constructor."""
        return alt.n_positions + sink + (1 if alt.caret else 0)

    @classmethod
    def _alt_allocs(cls, programs) -> list[int]:
        """Per-alternative allocation sizes: positions, plus one sink on
        sink-eligible banks, plus one dead *guard* bit BEFORE every
        ``^``-anchored alternative. The guard bit is never admitted by
        any byte row, so nothing can shift or skip-propagate into the
        caret start after t=0 — which lets the steppers drop their two
        per-byte ``& not_caret`` ops entirely."""
        sink = 1 if cls.sink_eligible(programs) else 0
        return [
            cls.alt_alloc(a, sink)
            for p in programs
            for a in p.alternatives
        ]

    @classmethod
    def count_packed_words(cls, programs, budget: int | None = None) -> int:
        """Exact packed word count the constructor would produce for
        ``programs`` — same first-fit plan, same sink/caret-guard
        allocations.  The tier admission gate in ops/match.py prices
        candidates through this (ADVICE r4: a positions/32 floor ignored
        guard bits and fragmentation, so a constructed bank could exceed
        the budget and cross a 128-lane tile).  ``budget`` bails the plan
        early once the count exceeds it."""
        return cls._plan(cls._alt_allocs(programs), budget=budget)[1]

    def __init__(self, column_programs: list[tuple[int, BitProgram]]):
        self.columns = [c for c, _ in column_programs]
        programs = [p for _, p in column_programs]
        # Sink mode: each alternative gets one extra position after its
        # last item. A sink admits every byte (``$``-final sinks admit
        # ONLY the padding byte 0 — i.e. they fire exactly at end of
        # line) and self-loops, so "some final position was ever alive"
        # becomes readable from the FINAL state: arrival rides the
        # existing shift/closure machinery (the trailing skippable
        # cascade that feeds multiple finals propagates into the sink the
        # same way), persistence rides ``s_static``, and the stepper
        # drops both per-byte hit ORs and the whole ``hits`` carry.
        self.use_sinks = self.sink_eligible(programs)
        allocs = self._alt_allocs(programs)
        alt_starts, self.n_words = self._plan(allocs)
        W = self.n_words
        self.n_positions = sum(allocs)
        # any word-straddling allocation turns the bank-wide shift carry
        # on; chain-free banks shift with a bare ``<< 1``
        self.has_chains = any(a > 32 for a in allocs)
        self.max_skip_run = max(
            (p.max_skip_run for _, p in column_programs), default=0
        )

        bmask = np.zeros((256, W), dtype=np.uint32)
        s_static = np.zeros(W, dtype=np.uint32)
        k_skip = np.zeros(W, dtype=np.uint32)
        start = np.zeros(W, dtype=np.uint32)
        caret_start = np.zeros(W, dtype=np.uint32)
        # allow4[pw*2+cw]: positions whose pre-assertion passes
        allow4 = np.zeros((4, W), dtype=np.uint32)
        f_plain = np.zeros(W, dtype=np.uint32)
        f_dollar = np.zeros(W, dtype=np.uint32)
        f_tb = np.zeros(W, dtype=np.uint32)
        f_tB = np.zeros(W, dtype=np.uint32)

        fin_word: list[int] = []
        fin_bit: list[int] = []
        fin_slot: list[int] = []
        snk_word: list[int] = []
        snk_bit: list[int] = []
        snk_slot: list[int] = []

        def setbit(arr, g):
            arr[g // 32] |= np.uint32(1) << np.uint32(g % 32)

        alt_iter = iter(alt_starts)
        for slot, (_col, prog) in enumerate(column_programs):
            for alt in prog.alternatives:
                # the caret guard bit (dead, leak-absorbing) is the
                # allocation's first bit; items start right after it
                base = g = next(alt_iter) + (1 if alt.caret else 0)
                for j, item in enumerate(alt.items):
                    for byte in item.byteset:
                        # NUL never reaches the device scan as content
                        # (NUL-bearing lines are needs_host — encode) so
                        # byte 0 stays padding-only: its bmask row is
                        # empty and the stepper's pad0_transparent fast
                        # path holds for every bank
                        if byte != 0:
                            setbit(bmask[byte], g)
                    if item.self_loop:
                        setbit(s_static, g)
                    if item.skippable:
                        setbit(k_skip, g)
                    if j == 0:
                        setbit(caret_start if alt.caret else start, g)
                    for combo in range(4):
                        pw, cw = combo >> 1, combo & 1
                        a = item.pre_assert
                        okc = (
                            a is None
                            or (a == "b" and pw != cw)
                            or (a == "B" and pw == cw)
                        )
                        if okc:
                            setbit(allow4[combo], g)
                    g += 1
                ftab = {None: f_plain, "$": f_dollar, "b": f_tb, "B": f_tB}[
                    alt.post_assert
                ]
                for j in alt.final_positions():
                    setbit(ftab, base + j)
                    fin_word.append((base + j) // 32)
                    fin_bit.append((base + j) % 32)
                    fin_slot.append(slot)
                if self.use_sinks:
                    # sink: one extra position after the alternative's
                    # last item. Arrival = shift/closure from any final;
                    # a plain final's sink admits every byte (padding
                    # included — completion at the last content byte
                    # still sweeps in), a ``$`` final's sink admits ONLY
                    # byte 0, so it fires exactly when the line ends.
                    # Self-loop makes it sticky; ``finish`` runs one
                    # virtual padding byte so full-width (length == T)
                    # lines sweep their finals in too.
                    bit = np.uint32(1) << np.uint32(g % 32)
                    if alt.post_assert == "$":
                        bmask[0, g // 32] |= bit
                    else:
                        bmask[:, g // 32] |= bit
                    setbit(s_static, g)
                    for combo in range(4):
                        setbit(allow4[combo], g)
                    snk_word.append(g // 32)
                    snk_bit.append(g % 32)
                    snk_slot.append(slot)
                    g += 1

        self.bmask = jnp.asarray(bmask)
        self.s_static = jnp.asarray(s_static)
        self.k_skip = jnp.asarray(k_skip)
        self.start = jnp.asarray(start)
        self.caret_start = jnp.asarray(caret_start)
        self.allow4 = jnp.asarray(allow4)
        self.f_plain = jnp.asarray(f_plain)
        self.f_dollar = jnp.asarray(f_dollar)
        self.f_tb = jnp.asarray(f_tb)
        self.f_tB = jnp.asarray(f_tB)
        self.has_tb = bool(f_tb.any() or f_tB.any())
        self.has_dollar = bool(f_dollar.any())
        # capability flags: the stepper drops whole op groups when no
        # program in the bank uses them. A bank mixing asserted and
        # assert-free programs pays the full path (a measured split into
        # two banks was slower — see the tier-assignment comment in
        # ops/match.py); a fully assert-free bank gets the light stepper
        self.has_caret = bool(caret_start.any())
        self.has_preassert = any(
            it.pre_assert is not None
            for _c, p in column_programs
            for a in p.alternatives
            for it in a.items
        )
        self.needs_wordness = self.has_preassert or self.has_tb
        self.fin_word = np.asarray(fin_word, dtype=np.int32)
        self.fin_bit = np.asarray(fin_bit, dtype=np.int32)
        self.fin_slot = np.asarray(fin_slot, dtype=np.int32)
        self.snk_word = np.asarray(snk_word, dtype=np.int32)
        self.snk_bit = np.asarray(snk_bit, dtype=np.int32)
        self.snk_slot = np.asarray(snk_slot, dtype=np.int32)

        # Assert-partition constants: the per-byte allow mask is the
        # TAKELESS combine ``where(pw != cw, allow_bc, allow_nb)`` —
        # replacing the allow4 row gather with one select between two
        # [W] constants. They ARE rows of allow4: combo pw*2+cw = 1 is a
        # boundary (no-assert ∪ \b positions), combo 0 is not (no-assert
        # ∪ \B); combos 2/1 and 3/0 are the same sets mirrored.
        # (Pair-composed and byte-class table variants of this stepper
        # were measured SLOWER and deleted — ops/shiftor.py docstring.)
        self.allow_bc = jnp.asarray(allow4[1])  # boundary present
        self.allow_nb = jnp.asarray(allow4[0])  # no boundary
        # fused start injection: one [W] select on the scalar ``pos == 0``
        # feeds a single broadcast OR instead of two (start, then a
        # caret-gated second OR)
        self.start_all = jnp.asarray(start | caret_start)
        # The ungated hit term is ``hits |= d & f_plain``, and after the
        # update ``d[fin] = bmask[byte][fin] & (...)`` — so a padding
        # byte (0) can only contribute a hit if some PLAIN-final
        # position's byteset admits NUL. When none does, the per-byte
        # ``pos < length`` gating of plain-final accumulation is a
        # provable no-op and the stepper drops it (gap/self-loop
        # positions may freely survive padding — they cannot hit).
        # ``$``/trailing-``\b`` finals keep their eol-equality gates,
        # which can never fire at a padding position. The builder above
        # strips byte 0 from every byteset (NUL-bearing lines are
        # needs_host — encode.py), so today this is True for every bank;
        # the flag still computes the sound condition and the gated
        # stepper path is retained as the correctness fallback should a
        # future bank ever admit the padding byte.
        self.pad0_transparent = not bool((bmask[0] & f_plain).any())

    # --------------------------------------------------------------- device

    def _shift1(self, d: jax.Array) -> jax.Array:
        """One-position shift. Chain-free banks (every allocation inside
        one word — the first-fit invariant) shift with a bare ``<< 1``;
        only banks holding a word-straddling allocation pay the carry.
        The carry stays UNCONDITIONAL across all word boundaries (no
        cont-mask): a carry landing outside a chained run hits the next
        allocation's start bit (re-injected / caret-blocked anyway) or an
        unused bit (killed by the ``bmask`` AND) — see module docstring."""
        sh = d << 1
        if self.has_chains and self.n_words > 1:
            carry = jnp.concatenate(
                [jnp.zeros_like(d[:, :1]), d[:, :-1] >> 31], axis=1
            )
            sh = sh | carry
        return sh

    def pair_stepper(self, B: int, lengths: jax.Array):
        """(init, step(carry, b1, b2, t), finish) — composable with the
        other banks into the single fused scan. Sink-mode banks (the
        default whenever no trailing ``\\b``/``\\B`` final exists) carry
        only (state [B, W] uint32, prev_wordness [B] bool) and read hits
        from sticky sink positions at the end; the rest carry (state,
        hits [B, W] uint32, prev_wordness) and accumulate per byte."""
        if self.use_sinks:
            return self._sink_pair_stepper(B, lengths)
        return self._hits_pair_stepper(B, lengths)

    def _sink_pair_stepper(self, B: int, lengths: jax.Array):
        """Sink-mode stepper: no hit terms, no ``hits`` carry, no
        end-of-line gating at all — ``$`` acceptance is the dollar
        sink's padding-byte admission, and plain finals sweep into
        always-admitting sinks. ``finish`` advances one virtual padding
        byte so lines that fill every scanned byte (length == T) sweep
        their last-byte finals in, then reads the sink bits."""
        W = self.n_words
        init = (
            jnp.zeros((B, W), jnp.uint32),
            jnp.zeros((B,), bool),
        )

        def one(d, pw, b, pos):
            b32 = b.astype(jnp.int32)
            c = self._shift1(d)
            # the guard bit before every ^-anchored alternative absorbs
            # shift/skip leaks (it is never admitted by any byte row), so
            # no ``& not_caret`` is needed anywhere — caret starts are
            # only ever injected by the pos==0 select below
            if self.has_caret:
                c = c | jnp.where(pos == 0, self.start_all, self.start)
            else:
                c = c | self.start
            for _ in range(self.max_skip_run):
                c = c | self._shift1(c & self.k_skip)
            brow = jnp.take(self.bmask, b32, axis=0)  # [B, W]
            if self.has_preassert:
                cw = _is_word(b32)
                bc = ((pw != cw))[:, None]
                allow = jnp.where(bc, self.allow_bc, self.allow_nb)
                d = brow & ((c & allow) | (d & self.s_static))
                # no end-of-line freeze: past the line end only sink
                # positions can stay alive (brow is empty elsewhere) and
                # sinks ignore the allow mask, so pw's padding word-ness
                # gates nothing that matters
                pw = cw
            else:
                d = brow & (c | (d & self.s_static))
            return d, pw

        def step(carry, b1, b2, t):
            d, pw = carry
            p0 = 2 * t
            d, pw = one(d, pw, b1, p0)
            d, pw = one(d, pw, b2, p0 + 1)
            return (d, pw)

        def finish(carry):
            d, pw = carry
            pad = jnp.zeros((B,), jnp.uint8)
            d, _ = one(d, pw, pad, jnp.int32(1))
            return self.columns_from_sinks(d)

        return init, step, finish

    def columns_from_sinks(self, d: jax.Array) -> jax.Array:
        """uint32 [N, W] final sink-mode state -> bool [N, n_columns]."""
        N = d.shape[0]
        alive = (
            jnp.take(d, jnp.asarray(self.snk_word), axis=1)
            >> jnp.asarray(self.snk_bit)[None, :]
        ) & 1  # [N, n_sinks]
        out = jnp.zeros((N, max(1, len(self.columns))), dtype=jnp.int32)
        out = out.at[:, jnp.asarray(self.snk_slot)].max(
            alive.astype(jnp.int32)
        )
        return out.astype(bool)

    def _hits_pair_stepper(self, B: int, lengths: jax.Array):
        """Per-byte hit accumulation — the path for banks with trailing
        ``\\b``/``\\B`` finals (no sink encoding). Carry: (state [B, W]
        uint32, hits [B, W] uint32, prev_wordness [B] bool). One
        ``bmask`` row take per byte; the \\b/\\B allow mask is the
        takeless two-constant select built in ``__init__``. The
        post-line-end state freeze is dropped — every hit term is gated
        by its byte's ``pos < length`` (or, on a ``pad0_transparent``
        bank, by the padding byte zeroing ``d`` itself) and positions
        only grow, so a polluted ``d`` past end-of-line can never
        contribute a hit."""
        W = self.n_words
        init = (
            jnp.zeros((B, W), jnp.uint32),
            jnp.zeros((B, W), jnp.uint32),
            jnp.zeros((B,), bool),
        )
        zero = jnp.uint32(0)

        def one(d, hits, pw, b, pos):
            b32 = b.astype(jnp.int32)
            cw = _is_word(b32) if self.needs_wordness else None
            if not self.pad0_transparent or self.needs_wordness:
                ok = pos < lengths
                okc = ok[:, None]
            if self.has_tb or self.has_preassert:
                bc = (pw != cw)[:, None]

            if self.has_tb:
                hits = hits | jnp.where(
                    okc, d & jnp.where(bc, self.f_tb, self.f_tB), zero
                )

            c = self._shift1(d)
            # ^-anchored starts inject only at each line's first byte;
            # the caret guard bit absorbs shift/skip leaks, so no
            # ``& not_caret`` anywhere (see _alt_allocs)
            if self.has_caret:
                c = c | jnp.where(pos == 0, self.start_all, self.start)
            else:
                c = c | self.start
            for _ in range(self.max_skip_run):
                c = c | self._shift1(c & self.k_skip)

            brow = jnp.take(self.bmask, b32, axis=0)  # [B, W]
            # factored: (c & brow) | (d & brow & s) == brow & (c | (d & s))
            # — one fewer [B, W] AND per byte
            if self.has_preassert:
                allow = jnp.where(bc, self.allow_bc, self.allow_nb)
                d = brow & ((c & allow) | (d & self.s_static))
            else:
                d = brow & (c | (d & self.s_static))

            if self.pad0_transparent:
                hits = hits | (d & self.f_plain)
            else:
                hits = hits | jnp.where(okc, d & self.f_plain, zero)
            if self.has_dollar or self.has_tb:
                eol = (pos == lengths - 1)[:, None]
            if self.has_dollar:
                hits = hits | jnp.where(eol, d & self.f_dollar, zero)
            if self.has_tb:
                cwc = cw[:, None]
                hits = hits | jnp.where(
                    eol, d & jnp.where(cwc, self.f_tb, self.f_tB), zero
                )
            if self.needs_wordness:
                pw = jnp.where(ok, cw, pw)
            return d, hits, pw

        def step(carry, b1, b2, t):
            d, hits, pw = carry
            p0 = 2 * t
            d, hits, pw = one(d, hits, pw, b1, p0)
            d, hits, pw = one(d, hits, pw, b2, p0 + 1)
            return (d, hits, pw)

        def finish(carry):
            _, hits, _ = carry
            return self.columns_from_hits(hits)

        return init, step, finish

    def columns_from_hits(self, hits: jax.Array) -> jax.Array:
        """uint32 [N, W] accumulated hit words -> bool [N, n_columns]."""
        N = hits.shape[0]
        fin = (
            jnp.take(hits, jnp.asarray(self.fin_word), axis=1)
            >> jnp.asarray(self.fin_bit)[None, :]
        ) & 1  # [N, n_fins]
        out = jnp.zeros((N, max(1, len(self.columns))), dtype=jnp.int32)
        out = out.at[:, jnp.asarray(self.fin_slot)].max(fin.astype(jnp.int32))
        return out.astype(bool)
