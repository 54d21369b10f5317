"""Batched automaton execution on device.

Two kernels, both shaped as a ``lax.scan`` over byte columns with one gather
per step — the TPU-native replacement for the reference's per-line
``Matcher.find()`` hot loop (AnalysisService.java:89-113):

- :class:`DfaBank` runs R independent per-regex DFAs over every line
  simultaneously (state tensor ``[B, R]``), producing the full boolean
  match cube the scoring kernel consumes.
- :class:`AcRunner` runs the single combined Aho-Corasick automaton (state
  tensor ``[B]``), producing literal-hit bitmask words per line — the cheap
  prefilter for large pattern libraries.

Scans carry int32 states only; byte columns are consumed in a transposed
``[T, B]`` layout so each scan step is a contiguous slice.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from log_parser_tpu.patterns.regex.ac import AhoCorasick
from log_parser_tpu.patterns.regex.dfa import CompiledDfa


# pair-stride transition tables beyond this many int32 entries fall back to
# single-stride (the table must stay comfortably HBM/VMEM-resident)
PAIR_TABLE_MAX_ENTRIES = 64 << 20


def unpack_hit_words(h: jax.Array, n_cols: int) -> jax.Array:
    """uint32 [N, W] per-column hit words -> bool [N, n_cols] (shared by
    the union multi-DFA and AC prefilter tiers)."""
    cols = jnp.arange(n_cols, dtype=jnp.int32)
    word = h[:, cols // 32]
    return (word >> (cols % 32).astype(jnp.uint32)) & 1 > 0


def pack_byte_pairs(lines_tb: jax.Array):
    """uint8 [T, B] -> ([T2, 2, B] byte pairs, [T2] step indexes), padding
    T to even so every scan step consumes exactly two bytes."""
    T, B = lines_tb.shape
    if T % 2:
        lines_tb = jnp.concatenate(
            [lines_tb, jnp.zeros((1, B), lines_tb.dtype)], axis=0
        )
        T += 1
    return lines_tb.reshape(T // 2, 2, B), jnp.arange(T // 2, dtype=jnp.int32)


class DfaBank:
    """R packed DFAs executed in lockstep over a line batch.

    The scan is the serial axis of the whole framework, so by default two
    bytes are consumed per step via precomposed pair transition tables
    ``trans2[s, c1, c2] = trans[trans[s, c1], c2]`` over byte classes
    extended with one identity "padding" class (consumed where a position
    is at/past the line end). That halves the sequential scan length for a
    table-size cost of ``(cmax+1)²/cmax`` — gated by
    ``PAIR_TABLE_MAX_ENTRIES`` for very large banks.
    """

    def __init__(self, dfas: list[CompiledDfa], stride: int = 2):
        self.n_regexes = len(dfas)
        r = max(1, self.n_regexes)
        smax = max([d.n_states for d in dfas], default=1)
        cmax = max([d.n_classes for d in dfas], default=1)
        trans = np.zeros((r, smax, cmax), dtype=np.int32)
        byte_class = np.zeros((r, 256), dtype=np.int32)
        accept = np.zeros((r, smax), dtype=bool)
        start = np.zeros(r, dtype=np.int32)
        for i, d in enumerate(dfas):
            trans[i, : d.n_states, : d.n_classes] = d.trans
            byte_class[i] = d.byte_class
            accept[i, : d.n_states] = d.accept_end
            start[i] = d.start
        self.smax, self.cmax = smax, cmax
        # Byte 0 maps to the identity padding class (index cmax): content
        # NULs never reach the device (encode routes them to host), so
        # padding bytes select identity through the class map itself and
        # the pair-stride scan needs no per-step ``pos < length`` selects.
        # The non-pair paths keep their gating; their (clamped,
        # out-of-range) byte-0 lookups only occur at gated padding bytes.
        byte_class[:, 0] = cmax
        # flat layout for a single fused gather per scan step
        self.flat_trans = jnp.asarray(trans.reshape(-1))
        self.byte_class = jnp.asarray(byte_class)
        self.flat_accept = jnp.asarray(accept.reshape(-1))
        self.start = jnp.asarray(start)

        self.pair_stride = (
            stride == 2
            and r * smax * (cmax + 1) * (cmax + 1) <= PAIR_TABLE_MAX_ENTRIES
        )
        if self.pair_stride:
            cpad = cmax + 1  # class cmax = identity padding class
            ext = np.zeros((r, smax, cpad), dtype=np.int32)
            ext[:, :, :cmax] = trans
            ext[:, :, cmax] = np.arange(smax, dtype=np.int32)[None, :]
            # trans2[r, s, c1, c2] = ext[r, ext[r, s, c1], c2]
            trans2 = np.empty((r, smax, cpad, cpad), dtype=np.int32)
            for i in range(r):
                trans2[i] = ext[i][ext[i], :]
            self.cpad = cpad
            self.flat_trans2 = jnp.asarray(trans2.reshape(-1))

        self._jit = jax.jit(self._run)

    def _run(self, lines_tb: jax.Array, lengths: jax.Array) -> jax.Array:
        """lines_tb: uint8 [T, B] (transposed); lengths: int32 [B].
        Returns bool [B, R]."""
        return self._run_pair(lines_tb, lengths)

    def _run_single(self, lines_tb: jax.Array, lengths: jax.Array) -> jax.Array:
        T, B = lines_tb.shape
        R = self.byte_class.shape[0]
        smax, cmax = self.smax, self.cmax
        states0 = jnp.broadcast_to(self.start[None, :], (B, R)).astype(jnp.int32)
        r_off = (jnp.arange(R, dtype=jnp.int32) * smax)[None, :]  # [1, R]

        def step(states, xs):
            bytes_t, t = xs
            cls = jnp.take(self.byte_class, bytes_t.astype(jnp.int32), axis=1)  # [R, B]
            idx = (r_off + states) * cmax + cls.T  # [B, R]
            nxt = jnp.take(self.flat_trans, idx.reshape(-1)).reshape(B, R)
            active = (t < lengths)[:, None]
            return jnp.where(active, nxt, states), None

        ts = jnp.arange(T, dtype=jnp.int32)
        states, _ = jax.lax.scan(step, states0, (lines_tb, ts))
        return jnp.take(self.flat_accept, (r_off + states).reshape(-1)).reshape(B, R)

    def _run_pair(self, lines_tb: jax.Array, lengths: jax.Array) -> jax.Array:
        """Two bytes per scan step through the precomposed pair tables;
        positions at/past each line's end consume the identity class, so no
        per-step boundary branch is needed."""
        T, B = lines_tb.shape
        init, step, finish = self.pair_stepper(B, lengths)
        pairs, ts = pack_byte_pairs(lines_tb)
        states, _ = jax.lax.scan(
            lambda s, xs: (step(s, xs[0][0], xs[0][1], xs[1]), None),
            init,
            (pairs, ts),
        )
        return finish(states)

    def pair_stepper(self, B: int, lengths: jax.Array):
        """(init, step(carry, b1, b2, t), finish) — one pair-consuming scan
        stage, composable with other banks into a single fused scan."""
        R = self.byte_class.shape[0]
        smax = self.smax
        states0 = jnp.broadcast_to(self.start[None, :], (B, R)).astype(jnp.int32)
        r_off = (jnp.arange(R, dtype=jnp.int32) * smax)[None, :]  # [1, R]

        if self.pair_stride:
            cpad = self.cpad

            def step(states, b1, b2, t):
                # gate-free: padding bytes (0) map to the identity class
                # through byte_class itself (see __init__)
                c1 = jnp.take(self.byte_class, b1.astype(jnp.int32), axis=1)  # [R, B]
                c2 = jnp.take(self.byte_class, b2.astype(jnp.int32), axis=1)
                idx = ((r_off + states) * cpad + c1.T) * cpad + c2.T  # [B, R]
                return jnp.take(self.flat_trans2, idx.reshape(-1)).reshape(B, R)

        else:
            cmax = self.cmax

            def one(states, b, pos_ok):
                cls = jnp.take(self.byte_class, b.astype(jnp.int32), axis=1)  # [R, B]
                idx = (r_off + states) * cmax + cls.T
                nxt = jnp.take(self.flat_trans, idx.reshape(-1)).reshape(B, R)
                return jnp.where(pos_ok[:, None], nxt, states)

            def step(states, b1, b2, t):
                p0 = 2 * t
                states = one(states, b1, p0 < lengths)
                return one(states, b2, p0 + 1 < lengths)

        def finish(states):
            return jnp.take(
                self.flat_accept, (r_off + states).reshape(-1)
            ).reshape(B, R)

        return states0, step, finish

    def match(self, lines_u8: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        """Host entry: uint8 [B, T] padded batch → bool [B, R] match cube."""
        if self.n_regexes == 0:
            return np.zeros((lines_u8.shape[0], 0), dtype=bool)
        out = self._jit(jnp.asarray(lines_u8.T), jnp.asarray(lengths))
        return np.asarray(out)[:, : self.n_regexes]


class MultiDfaBank:
    """One union multi-pattern DFA group on device (multidfa.py).

    R patterns ride ONE automaton. The hot scan is ONE ``[B]`` gather per
    byte: the byte-class map is precomposed into the transition table
    (``[S, 256]`` — at most 8 MB under the 8192-state budget), whose
    packed words carry a "this state can report a match" flag in bit 30 —
    cost independent of R, vs the dense tier's ``[B, R]`` gather (measured
    ~150ms/regex/200k lines on TPU v5e; per-element random
    gathers run on the scalar unit, so eliminating the separate
    byte→class gather halves the tier's hot cost). Exact per-pattern hit
    words are recovered after the scan by re-scanning ONLY the flagged
    rows (matching log lines are rare) through the full output-word
    tables, with an in-program ``lax.cond`` dense re-scan when the
    flagged-row capacity overflows — the same robustness shape as the
    prefilter tier.

    Steps one byte at a time: a pair-precomposed table would be S·256²
    per step and the union automaton's S is large.
    """

    _REPORT_BIT = 1 << 30
    _STATE_MASK = _REPORT_BIT - 1

    def __init__(self, md, cols: list[int]):
        self.cols = cols  # global column ids, bit order
        self.n_cols = len(cols)
        self.n_words = md.n_words
        S, C = md.trans.shape
        self.n_states, self.n_classes = S, C
        # word-ness per BYTE (precomposed through the class map): the out2
        # row index is state*2 + word-ness of the incoming byte
        self.byte_rw = jnp.asarray(md.cls_is_word[md.byte_class])
        self.out2 = jnp.asarray(md.out2)  # [S*2, W] uint32
        self.accept_words = jnp.asarray(md.accept_words)  # [S, W] uint32
        self.start = int(md.start)

        # reporting flags: state may emit out bits under either word-ness,
        # or accept at end-of-input — conservative OR so the flag alone
        # decides whether a row needs the exact second pass
        reports = (
            md.out2.reshape(S, 2, md.n_words).any(axis=(1, 2))
            | md.accept_words.any(axis=1)
        )
        # class-level tables kept host-side for the Pallas kernel's
        # byte-class-compressed planes (matchdfa_pallas._group_planes);
        # n_states_unmin feeds the plan's geometry report, and the
        # compiled automaton rides along (arrays shared, not copied) so
        # admission tooling can snapshot re-partitioned groups
        self._md = md
        self._trans_np = md.trans
        self._byte_class_np = md.byte_class
        self._reports_np = reports
        self.n_states_unmin = md.n_states_unmin or S
        packed = md.trans.astype(np.int64) | (
            reports.astype(np.int64)[md.trans] << 30
        )
        # byte-precomposition below spreads classes over the byte axis;
        # byte 0 is then overridden to a SELF-LOOP carrying the state's
        # own report flag: content NULs never reach the device (encode
        # routes them to host), so past a line's end the state freezes
        # itself and the any-hit flag OR is an idempotent re-OR — the
        # pair_stepper runs gate-free. The exact word_stepper keeps its
        # gating (out2 rows are word-ness-dependent, and a padding byte
        # must not re-emit them).
        # byte-precomposed: trans_byte[s, b] = packed[s, byte_class[b]].
        # Host-side until first use: when the group joins a
        # MultiDfaCluster, the cluster's concatenated device buffer is
        # shared back (via _adopt_table) so the table exists on device
        # exactly once however it is reached.
        packed_byte = packed[:, md.byte_class].astype(np.int32)
        s_idx = np.arange(S, dtype=np.int32)
        packed_byte[:, 0] = s_idx | (reports[s_idx].astype(np.int32) << 30)
        self._packed_byte_np = packed_byte.reshape(-1)
        self._flat: jax.Array | None = None
        self._flat_base = 0
        self.start_reports = bool(reports[md.start])

    def _table(self) -> tuple[jax.Array, int]:
        """(device buffer, base offset) of this group's byte-precomposed
        transition table, uploading it standalone on first use.  Never
        caches under an active jit trace (jnp.asarray would yield a
        trace-local constant whose escape poisons every later call).
        Inside MatcherBanks every group reads its cluster's buffer
        (``_adopt_table``); standalone groups upload here."""
        if self._flat is None:
            arr = jnp.asarray(self._packed_byte_np)
            if isinstance(arr, jax.core.Tracer):
                return arr, self._flat_base
            self._flat = arr
        return self._flat, self._flat_base

    def _adopt_table(self, flat: jax.Array, base: int) -> None:
        # the host copy is kept (host RAM, not HBM): a later cluster over
        # the same groups — re-tiering, probe tools — must be able to
        # rebuild the concatenated buffer
        self._flat = flat
        self._flat_base = int(base)

    # ------------------------------------------------------- hot scan stage

    def pair_stepper(self, B: int, lengths: jax.Array):
        """(init, step(carry, b1, b2, t), finish_carry) — carry is
        (state [B] int32, reported [B] bool). The cube slice is produced
        by :meth:`contribution` from the finished carry."""
        flat, base = self._table()
        init = (
            jnp.full((B,), self.start, jnp.int32),
            jnp.full((B,), self.start_reports, bool),
        )

        def one(s, rep, b):
            # gate-free: padding bytes (0) self-loop with the state's own
            # report flag (see the packed-table build)
            v = jnp.take(flat, base + s * 256 + b.astype(jnp.int32))
            return v & self._STATE_MASK, rep | (v >= self._REPORT_BIT)

        def step(carry, b1, b2, t):
            s, rep = carry
            s, rep = one(s, rep, b1)
            s, rep = one(s, rep, b2)
            return (s, rep)

        def finish(carry):
            return carry

        return init, step, finish

    # ------------------------------------------------- exact recovery stage

    def word_stepper(self, N: int, lengths: jax.Array):
        """Composable pair-stepper for the exact out-word pass. Carry:
        (state [N] int32, hit_words [N, W] uint32)."""
        flat, base = self._table()
        init = (
            jnp.full((N,), self.start, jnp.int32),
            jnp.zeros((N, self.n_words), jnp.uint32),
        )

        def one(s, h, b, ok):
            b32 = b.astype(jnp.int32)
            rw = jnp.take(self.byte_rw, b32)
            ow = jnp.take(self.out2, s * 2 + rw, axis=0)  # [N, W]
            h = h | jnp.where(ok[:, None], ow, jnp.uint32(0))
            v = jnp.take(flat, base + s * 256 + b32)
            s = jnp.where(ok, v & self._STATE_MASK, s)
            return s, h

        def step(carry, b1, b2, t):
            s, h = carry
            p0 = 2 * t
            s, h = one(s, h, b1, p0 < lengths)
            s, h = one(s, h, b2, p0 + 1 < lengths)
            return (s, h)

        def finish(carry):
            s, h = carry
            return h | jnp.take(self.accept_words, s, axis=0)

        return init, step, finish

    def unpack(self, h: jax.Array) -> jax.Array:
        """uint32 [N, W] hit words -> bool [N, n_cols]."""
        return unpack_hit_words(h, self.n_cols)


class MultiDfaCluster:
    """All union groups advanced by ONE ``[B, G]`` gather per byte.

    Running each group as its own stepper inside the fused scan measured
    ~2x the sum of the groups run alone (tools/probe_tiers.py: 4 groups at
    0.13-0.15s each alone, 1.03s fused — the scalar-unit gather code
    XLA emits for several independent gathers in one loop body schedules
    worse than one wider gather). Concatenating the groups'
    byte-precomposed tables and carrying states as ``[B, G]`` makes the
    whole tier one take per byte, restoring per-element throughput."""

    def __init__(self, groups: list[MultiDfaBank]):
        self.groups = groups
        sizes = [g.n_states * 256 for g in groups]
        base = np.zeros(len(groups), dtype=np.int64)
        base[1:] = np.cumsum(sizes[:-1])
        if base[-1] + sizes[-1] >= (1 << 31):
            # int32 gather indices would wrap into wrong transitions; this
            # must survive `python -O`, so no bare assert (group_dfa_states
            # caps keep real banks far below this)
            raise ValueError(
                "multi-DFA cluster table exceeds int32 index range: "
                f"{int(base[-1] + sizes[-1])} entries"
            )
        self._base = jnp.asarray(base.astype(np.int32))[None, :]  # [1, G]
        self._flat = jnp.asarray(
            np.concatenate([g._packed_byte_np for g in groups])
        )
        # share the concatenated buffer back so each group's word_stepper
        # reads the same device memory — the table lives on device once
        for g, b in zip(groups, base):
            g._adopt_table(self._flat, b)
        self._start = jnp.asarray(
            np.asarray([g.start for g in groups], np.int32)
        )
        self._start_reports = jnp.asarray(
            np.asarray([g.start_reports for g in groups], bool)
        )

    def pair_stepper(self, B: int, lengths: jax.Array):
        """Carry: (states [B, G] int32, reported [B, G] bool); finish
        returns the per-group reported columns in group order."""
        G = len(self.groups)
        mask = jnp.int32(MultiDfaBank._STATE_MASK)
        init = (
            jnp.broadcast_to(self._start[None, :], (B, G)).astype(jnp.int32),
            jnp.broadcast_to(self._start_reports[None, :], (B, G)),
        )

        def one(s, rep, b):
            # gate-free: each group's byte-0 column self-loops with the
            # state's own report flag (MultiDfaBank packed-table build)
            idx = self._base + s * 256 + b.astype(jnp.int32)[:, None]
            v = jnp.take(self._flat, idx)  # [B, G]
            return v & mask, rep | (v >= MultiDfaBank._REPORT_BIT)

        def step(carry, b1, b2, t):
            s, rep = carry
            s, rep = one(s, rep, b1)
            s, rep = one(s, rep, b2)
            return (s, rep)

        def finish(carry):
            _, rep = carry
            return [rep[:, i] for i in range(G)]

        return init, step, finish


class AcRunner:
    """Combined Aho-Corasick literal prefilter on device."""

    def __init__(self, ac: AhoCorasick):
        self.ac = ac
        self.n_words = ac.n_words
        self.goto = jnp.asarray(ac.goto)
        self.byte_class = jnp.asarray(ac.byte_class)
        self.out_words = jnp.asarray(ac.out_words.astype(np.uint32))
        self._jit = jax.jit(self._run)

    def _run(self, lines_tb: jax.Array, lengths: jax.Array) -> jax.Array:
        T, B = lines_tb.shape

        def step(carry, xs):
            states, hits = carry
            bytes_t, t = xs
            cls = jnp.take(self.byte_class, bytes_t.astype(jnp.int32))  # [B]
            nxt = self.goto[states, cls]  # [B]
            active = t < lengths
            states = jnp.where(active, nxt, states)
            step_hits = jnp.where(
                active[:, None], jnp.take(self.out_words, states, axis=0), jnp.uint32(0)
            )
            return (states, hits | step_hits), None

        states0 = jnp.zeros(B, dtype=jnp.int32)
        hits0 = jnp.zeros((B, self.n_words), dtype=jnp.uint32)
        ts = jnp.arange(T, dtype=jnp.int32)
        (_, hits), _ = jax.lax.scan(step, (states0, hits0), (lines_tb, ts))
        return hits

    def scan(self, lines_u8: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        """Host entry: uint8 [B, T] → uint32 [B, n_words] literal-hit masks."""
        out = self._jit(jnp.asarray(lines_u8.T), jnp.asarray(lengths))
        return np.asarray(out)


# the ``jax.named_scope`` of each device tier inside the cube program
TIER_SCOPES = {
    "dense": "tier.dense",
    "shiftor": "tier.shiftor",
    "bitglush": "tier.bitglush",
    "union": "tier.union",
    "prefilter": "tier.prefilter",
}


class MatcherBanks:
    """Tiered device matchers for one PatternBank's columns.

    Tier selection is static per column (patterns/bank.py) and the same
    on every backend: literal-shaped regexes go to the bit-parallel
    Shift-Or bank (cost independent of bank size); regexes in the bit
    fragment ride the bit-parallel extended Shift-And tier
    (ops/bitglush.py); in wide banks, regexes with required literals ride
    the AC prefilter + per-record verify tier (ops/prefilter.py — cost
    per byte independent of library width); the union multi-DFA takes
    what those leave; the rest go to the packed dense DFA bank;
    automaton-unsupported regexes stay host-side (the engine injects
    them as cube overrides).
    """

    # Measured on v5e (tools/profile_fused.py, 229k-row batch): a
    # dense-DFA column costs ~150ms per 200k lines — the [B, R]
    # transition gather is scalar-unit bound — while a Shift-Or column
    # costs ~3ms and the AC words tier has a fixed cost of roughly eight
    # dense columns. Literal-shaped columns therefore ALWAYS ride
    # Shift-Or, and the prefilter engages at 8 eligible columns.
    SHIFTOR_MIN_COLUMNS = 1
    PREFILTER_MIN_COLUMNS = 8

    # Shift-Or's per-byte cost is a [B, n_words] mask gather — linear in
    # the packed WORD count (≈ total literal bytes / 32), not the column
    # count. Beyond this word budget, DFA-backed literal columns join the
    # dense-eligible pool and ride the width policy (union / prefilter)
    # instead. Columns with no DFA stay on Shift-Or regardless — it is
    # their only device tier. 128 words keeps the builtin bank (66 words,
    # Shift-Or measured at 0.17s/59 columns on TPU) while rerouting the
    # 1000-word synthetic banks.
    SHIFTOR_MAX_WORDS = 128

    # Union multi-DFA tier: one [B] gather per byte beats a [B, R] gather
    # for R >= 2; the native builder makes group packing cheap. Above
    # MULTI_PREFERRED_MAX dense columns the union would need many groups
    # (each ~2 gathers/byte) — wide literal-BEARING sets ride the AC
    # prefilter instead, whose any-hit stage is O(1)/byte in width;
    # literal-free columns stay on the union whatever the width (their
    # only alternative is the dense bank at ~150ms/column/200k lines on
    # TPU).
    MULTI_MIN_COLUMNS = 2
    MULTI_STATE_BUDGET = 8192
    MULTI_MAX_GROUP = 64
    MULTI_PREFERRED_MAX = 128

    # Bit-parallel extended Shift-And tier (ops/bitglush.py): dense-eligible
    # columns whose regex compiles to the bit fragment run with NO random
    # gathers — one [256, W] mask-row take per byte for the whole tier —
    # ahead of every automaton tier. The word budget bounds the [B, W]
    # elementwise cost the same way SHIFTOR_MAX_WORDS does: the builtin
    # 49 dense-eligible columns pack ~74 words, while a 2k-pattern
    # synthetic bank would need ~600 and rides the prefilter instead.
    # Replacing the union tier with the bit tier measured the config-2
    # cube 0.62s -> 0.31s on v5e (random gathers are scalar-unit bound
    # there).
    BITGLUSH_MAX_WORDS = 192
    BITGLUSH_MAX_COLUMN_POSITIONS = 512

    def __init__(
        self,
        bank,
        stride: int = 2,
        shiftor_min_columns: int | None = None,
        prefilter_min_columns: int | None = None,
        multi_min_columns: int | None = None,
        shiftor_max_words: int | None = None,
        bitglush_max_words: int | None = None,
    ):
        import jax.numpy as jnp

        from log_parser_tpu.native import get_lib
        from log_parser_tpu.ops.prefilter import PrefilterBank
        from log_parser_tpu.ops.shiftor import ShiftOrBank

        self.bank = bank
        threshold = (
            self.SHIFTOR_MIN_COLUMNS
            if shiftor_min_columns is None
            else shiftor_min_columns
        )
        pref_threshold = (
            self.PREFILTER_MIN_COLUMNS
            if prefilter_min_columns is None
            else prefilter_min_columns
        )
        n_device = sum(
            1
            for c in bank.columns
            if c.dfa is not None or c.exact_seqs is not None
        )
        bit_budget = (
            self.BITGLUSH_MAX_WORDS
            if bitglush_max_words is None
            else bitglush_max_words
        )
        # Keep literal columns on Shift-Or even when the bit tier is on:
        # [B, W] arrays pad to 128 LANES, so per-scan-step cost is
        # ceil(W/128) x the stepper's op-chain length. Absorbing the
        # literal columns into bitglush (their regexes are trivially in
        # the bit fragment) was measured: the merged bank needs 140 words
        # — the second lane-tile doubles the heavy ~18-op bitglush chain
        # (cube 0.44s vs 0.27s split, config-2, v5e). Two banks, each one
        # tile, pay 18 + 8 op-tiles; that is the cheap shape.
        use_shiftor = n_device >= threshold
        # Word-budget gate (see SHIFTOR_MAX_WORDS): DFA-backed literal
        # columns only ride Shift-Or while the packed word count stays
        # small. Counted with ShiftOrBank's own first-fit fill (a bits/32
        # estimate undercounts fragmentation ~2x), over the REROUTABLE
        # columns only — no-DFA columns stay on Shift-Or either way, so
        # their words are a floor the reroute can't remove.
        word_budget = (
            self.SHIFTOR_MAX_WORDS
            if shiftor_max_words is None
            else shiftor_max_words
        )
        # DFA-backed columns with any sequence over 32 positions stay
        # off Shift-Or BY DEFAULT: chains would widen every Shift-Or
        # take row (take cost ∝ row width — 81→114 words measured
        # 0.088→0.154 s). Two exceptions ride its cont-mask chains
        # anyway: DFA-less literal columns (their only device tier) and
        # _chain_literal columns below (long literals in secondary/
        # sequence/context roles, where bitglush truncation would be
        # unsound — a couple of words of width beats re-chaining the
        # whole bitglush bank).
        def _short_seqs(c) -> bool:
            return all(len(s) <= 32 for s in c.exact_seqs)

        # Column roles. A cube column may serve several patterns and
        # roles; bitglush's truncation of over-long alternatives
        # (over-approximate device match + exact host repair in
        # runtime/engine.py) is sound for PRIMARY roles (flagged events
        # are re-verified with the host regex and dropped) and for
        # SECONDARY roles (a truncated secondary only feeds the
        # proximity distances, which the engine repairs exactly: the
        # device's claimed min-distance names at most two lines, both
        # host-verified, with a host window re-scan in the rare case
        # both were prefix-only false positives). Sequence-event and
        # context columns feed device-side factor extraction with no
        # cheap repair, so they are NEVER truncated: long literal ones
        # ride Shift-Or's cont-mask chain path; anything long,
        # non-literal, and non-truncatable keeps its exact chained
        # bitglush allocation (has_chains — correct, slower, absent
        # from the builtin library).
        from log_parser_tpu.patterns.bank import CTX_EXCEPTION

        exact_role_cols = {
            c for e in bank.sequences for c in e.event_columns
        } | set(range(CTX_EXCEPTION + 1))
        truncatable = (
            set(int(c) for c in bank.primary_columns)
            | {s.column for s in bank.secondaries}
        ) - exact_role_cols

        def _chain_literal(i, c) -> bool:
            # long-literal column that may NOT be truncated: its exact
            # home is the Shift-Or chain path
            return (
                c.exact_seqs is not None
                and not _short_seqs(c)
                and i in exact_role_cols
            )

        if use_shiftor:
            # count the whole candidate bank, INCLUDING the DFA-less
            # floor (those columns stay on Shift-Or either way, and with
            # chains they can be wide): rerouting the DFA-backed columns
            # must keep the combined bank under the budget, not just
            # their own share
            n_words = ShiftOrBank.count_packed_words(
                (
                    len(seq)
                    for i, c in enumerate(bank.columns)
                    if c.exact_seqs is not None
                    and (
                        c.dfa is None
                        or _short_seqs(c)
                        or _chain_literal(i, c)
                    )
                    for seq in c.exact_seqs
                ),
                budget=word_budget,
            )
            if n_words > word_budget:
                use_shiftor = False
        self.shiftor_cols = [
            i
            for i, c in enumerate(bank.columns)
            if c.exact_seqs is not None
            and (
                (use_shiftor and (_short_seqs(c) or _chain_literal(i, c)))
                or c.dfa is None
            )
        ]
        shiftor_set = set(self.shiftor_cols)
        dense_cols = [
            i
            for i, c in enumerate(bank.columns)
            if c.dfa is not None and i not in shiftor_set
        ]
        self.host_cols = [
            i
            for i, c in enumerate(bank.columns)
            if c.dfa is None and c.exact_seqs is None
        ]

        # union multi-DFA tier: pack remaining DFA columns into as few
        # union automata as the state budget allows — each group matches
        # its R patterns with ONE [B] gather per byte (multidfa.py). The
        # construction is native C++; without the lib the packing probes
        # would run the Python subset builder at O(seconds) per probe, so
        # the tier stays off and columns keep their prior tiers.
        multi_threshold = (
            self.MULTI_MIN_COLUMNS
            if multi_min_columns is None
            else multi_min_columns
        )
        use_multi = (
            len(dense_cols) >= multi_threshold and get_lib() is not None
        )

        # WIDE banks select the prefilter set FIRST (any-hit is O(1)/byte
        # in width), so that the union tier can absorb everything the
        # selection leaves behind — literal-free columns AND trie-budget
        # rejects — instead of stranding rejects on the dense bank.
        pref_selected: list = []
        if len(dense_cols) > self.MULTI_PREFERRED_MAX or not use_multi:
            if len(dense_cols) >= pref_threshold:
                eligible = [
                    (i, bank.columns[i])
                    for i in dense_cols
                    if bank.columns[i].literals
                ]
                selected, _rejected = PrefilterBank.select(eligible)
                if len(selected) >= pref_threshold:
                    pref_selected = selected
        pref_set = {g for g, _ in pref_selected}

        # bit-parallel tier: gather-free execution for columns in the
        # union pool (everything the prefilter selection left — wide-bank
        # literal-bearing columns stay on the width-independent AC trie)
        # whose regex compiles to the bit fragment, under the word budget
        from log_parser_tpu.ops.bitglush import BitGlushBank
        from log_parser_tpu.patterns.regex.bitprog import (
            BitUnsupportedError,
            compile_bitprog_regex,
            expand_asserts,
            has_asserts,
            truncate_long_alternatives,
        )

        # Admission prices the EXACT packed bank the constructor would
        # build — same first-fit plan, sink + caret-guard bits included
        # (ADVICE r4: the old positions/32 floor under-counted both, so
        # a constructed bank could exceed the budget and cross a
        # 128-lane tile, doubling per-byte scan cost).  Pricing is
        # incremental (one FirstFitPacker carried across candidates);
        # the one event that invalidates it — a candidate with a \b/\B
        # post-assert flipping the bank sink-INELIGIBLE, which strips a
        # bit from every admitted alternative — triggers a full repack,
        # and eligibility can only flip once (off) per admitted set.
        from log_parser_tpu.ops.shiftor import FirstFitPacker

        bit_entries: list[tuple[int, object]] = []
        bit_progs: list = []
        packer = FirstFitPacker()
        sink_on = True  # BitGlushBank.sink_eligible([]) — empty set
        for i in dense_cols if bit_budget > 0 else []:
            if i in pref_set:
                continue
            col = bank.columns[i]
            try:
                prog = compile_bitprog_regex(col.regex, col.case_insensitive)
            except (BitUnsupportedError, ValueError):
                continue
            if prog.n_positions > self.BITGLUSH_MAX_COLUMN_POSITIONS:
                continue
            flips = sink_on and not BitGlushBank.sink_eligible([prog])
            if flips:
                # eligibility flip strips a bit from every admitted
                # alternative: one full repack (at most once per
                # admitted set while ON; a rejected flip candidate
                # leaves eligibility as-is and pays the same one pass)
                trial = FirstFitPacker()
                allocs = BitGlushBank._alt_allocs(bit_progs + [prog])
            else:
                trial = packer.clone()
                allocs = [
                    BitGlushBank.alt_alloc(alt, 1 if sink_on else 0)
                    for alt in prog.alternatives
                ]
            over = False
            for a in allocs:
                trial.add(a)
                if trial.n_words > bit_budget:
                    over = True
                    break
            if over:
                continue
            packer = trial
            sink_on = sink_on and not flips
            bit_progs.append(prog)
            bit_entries.append((i, prog))
        # De-assert rewrite, all-or-nothing: the op-group savings are
        # BANK-wide capability flags, so expansion only pays if every
        # asserted program expands (and the expanded bank stays within
        # budget); one unexpandable column keeps the gated originals.
        if any(has_asserts(p) for _, p in bit_entries):
            try:
                expanded = [(i, expand_asserts(p)) for i, p in bit_entries]
            except BitUnsupportedError:
                expanded = None
            if expanded is not None and all(
                p.n_positions <= self.BITGLUSH_MAX_COLUMN_POSITIONS
                for _, p in expanded
            ) and BitGlushBank.count_packed_words(
                [p for _, p in expanded], budget=bit_budget
            ) <= bit_budget:
                bit_entries = expanded
        # Truncate over-long alternatives of primary/secondary-role
        # columns so their allocations fit one word and the bank stays
        # on the chainless shift (the carry's concat per shift measured 2.5x
        # the chainless stepper on v5e — tools/probe_chainless.py). The
        # per-alternative item budget reserves the sink bit
        # UNCONDITIONALLY (truncation drops \b/\B post-asserts, which
        # can flip a pre-truncation sink-ineligible bank eligible) and
        # the caret guard bit where the alternative is ^-anchored —
        # otherwise a truncated allocation could still straddle a word
        # and re-enable the bank-wide carry the truncation exists to
        # remove. The truncated column OVER-matches; the engine
        # re-verifies its rare flagged events with the exact host regex
        # at assembly (runtime/engine.py, approx_cols).
        # Non-truncatable long programs stay exact and keep the carry.
        def _item_budget(alt) -> int:
            return 31 - (1 if alt.caret else 0)

        approx: list[int] = []
        truncated_entries: list[tuple[int, object]] = []
        for i, p in bit_entries:
            if i in truncatable and any(
                a.n_positions > _item_budget(a) for a in p.alternatives
            ):
                cut = truncate_long_alternatives(p, _item_budget)
                if cut is not None:
                    p = cut[0]
                    approx.append(i)
            truncated_entries.append((i, p))
        bit_entries = truncated_entries
        # Truncation can FLIP the bank sink-eligible (it drops \b/\B
        # post-asserts), adding one sink bit per alternative BANK-wide —
        # so the admission-time price can be stale. Re-price the final
        # set and shed entries until the constructed bank fits again
        # (shed columns fall through to the union/prefilter/dense tiers
        # like any other reject); the loop re-prices every iteration, so
        # eligibility flips caused by the shedding itself are priced too.
        while bit_entries and BitGlushBank.count_packed_words(
            [p for _, p in bit_entries], budget=bit_budget
        ) > bit_budget:
            bit_entries.pop()
        kept = {i for i, _ in bit_entries}
        self.approx_cols = [i for i in approx if i in kept]
        # ONE bank for all bit programs. A measured A/B split the
        # assert-free programs into their own light bank (no word-ness /
        # allow / caret work): cube 0.31 → 0.39s on v5e — the asserted
        # remainder packs only ~5 words, so the extra stepper's scan
        # overhead outweighed the ops saved (same lesson as the union
        # groups: more carries in one fused scan schedule worse). The
        # capability flags still pay off whenever a whole bank is
        # assert-free (BitGlushBank skips those op groups bank-wide).
        self.bitglush = BitGlushBank(bit_entries) if bit_entries else None
        self.bitglush_cols = [i for i, _ in bit_entries]
        bit_set = set(self.bitglush_cols)
        dense_cols = [i for i in dense_cols if i not in bit_set]
        # experimental whole-tier Pallas kernel (bitglush_pallas.py):
        # measured at parity with the scan path on v5e, kept opt-in. Read
        # once here — cube() runs under jit, so an env read there would be
        # frozen at first-trace time anyway.
        self.bitglush_use_pallas = (
            self.bitglush is not None
            and os.environ.get("LOG_PARSER_TPU_PALLAS") == "1"
        )

        self.multi_groups: list[MultiDfaBank] = []
        self._multi_entries: list[list[tuple[int, str, bool]]] = []
        if use_multi:
            from log_parser_tpu.patterns.regex.multidfa import pack_union_groups

            take = [i for i in dense_cols if i not in pref_set]
            if take:
                entries = [
                    (i, bank.columns[i].regex, bank.columns[i].case_insensitive)
                    for i in take
                ]
                groups, rejected_entries = pack_union_groups(
                    entries,
                    max_states=self.MULTI_STATE_BUDGET,
                    max_group=self.MULTI_MAX_GROUP,
                )
                self.multi_groups = [
                    MultiDfaBank(md, keys) for keys, md in groups
                ]
                # per-group (key, regex, ci) in bit order — the kernel
                # plan builder re-splits groups from these when the
                # packed geometry exceeds the VMEM budget
                emap = {e[0]: e for e in entries}
                self._multi_entries = [
                    [emap[k] for k in keys] for keys, _ in groups
                ]
                taken = set(take)
                dense_cols = [k for k, _, _ in rejected_entries] + [
                    i for i in dense_cols if i not in taken and i not in pref_set
                ]
            else:
                dense_cols = [i for i in dense_cols if i not in pref_set]
        else:
            dense_cols = [i for i in dense_cols if i not in pref_set]

        # NARROW banks: the union already took everything; offer its
        # rejects (union-hostile regexes) to the prefilter if enough of
        # them carry literals
        if not pref_selected and len(dense_cols) >= pref_threshold:
            eligible = [
                (i, bank.columns[i])
                for i in dense_cols
                if bank.columns[i].literals
            ]
            selected, _rejected = PrefilterBank.select(eligible)
            if len(selected) >= pref_threshold:
                pref_selected = selected
                sel_set = {g for g, _ in pref_selected}
                dense_cols = [i for i in dense_cols if i not in sel_set]

        self.prefilter: PrefilterBank | None = None
        self.prefilter_cols: list[int] = []
        if pref_selected:
            self.prefilter = PrefilterBank(pref_selected)
            self.prefilter_cols = [g for g, _ in pref_selected]

        self.dfa_cols = dense_cols
        # opt-in Pallas union-DFA kernel (matchdfa_pallas.py): admitted
        # BEFORE the cluster build because an admissible plan may
        # RE-PARTITION the union groups (cheapest admissible split under
        # the VMEM budget) — the cluster, the scan-tier fallbacks, and
        # the kernel planes must all see the same group list. Env read
        # once for the same frozen-under-jit reason as
        # bitglush_use_pallas above.
        self._dfa_pallas_plan = None
        self.multidfa_pallas_reason = "off"
        if os.environ.get("LOG_PARSER_TPU_PALLAS_DFA") == "1":
            from log_parser_tpu.ops.matchdfa_pallas import build_dfa_plan

            plan, reason = build_dfa_plan(
                self.multi_groups,
                entries=self._multi_entries or None,
                max_states=self.MULTI_STATE_BUDGET,
            )
            self._dfa_pallas_plan = plan
            self.multidfa_pallas_reason = reason
            if plan is not None:
                self.multi_groups = list(plan.groups)
        self.multidfa_use_pallas = self._dfa_pallas_plan is not None
        # built once: cube() runs under jit, and constructing the cluster
        # there would re-run the table concatenation and bake a duplicate
        # copy of the fused table into every compiled executable. The
        # ONE-wide-gather cluster is how TPU schedules several groups well
        # (separate steppers cost 1.03 s vs 0.62 s clustered on v5e,
        # builtin bank, 200k lines).
        self.multi_cluster = (
            MultiDfaCluster(self.multi_groups) if self.multi_groups else None
        )
        self.dfa_bank = DfaBank(
            [bank.columns[i].dfa for i in self.dfa_cols], stride=stride
        )
        self.shiftor = (
            ShiftOrBank(
                [(i, bank.columns[i].exact_seqs) for i in self.shiftor_cols]
            )
            if self.shiftor_cols
            else None
        )
        self._jnp = jnp

    @property
    def multi_cols(self) -> list[int]:
        return [c for g in self.multi_groups for c in g.cols]

    def dfa_kernel_active(self, B: int) -> bool:
        """Host-side predicate: will cube() route the union groups
        through the Pallas kernel for a B-row batch (modulo runtime
        faults)? Used by the engine's kernel-tier counters — uses the
        nominal-T admission, same as cube()'s tile re-check for typical
        padded lengths."""
        if not self.multidfa_use_pallas:
            return False
        from log_parser_tpu.ops.matchdfa_pallas import dfa_tile

        return dfa_tile(self._dfa_pallas_plan, B) is not None

    @property
    def dfa_kernel_geometry(self) -> dict | None:
        """The admitted plan's geometry report (states before/after
        minimization, byte classes, plane bytes, chosen split) for the
        engine's /trace/last kernel block; None when no plan."""
        if self._dfa_pallas_plan is None:
            return None
        return self._dfa_pallas_plan.geometry

    @property
    def device_cols(self) -> list[int]:
        return (
            self.shiftor_cols
            + self.dfa_cols
            + self.bitglush_cols
            + self.multi_cols
            + self.prefilter_cols
        )

    def cube(self, lines_tb, lengths):
        """uint8 [T, B] + lengths -> bool [B, n_columns] match cube
        (device-computable columns only; host columns stay False for the
        engine's override pass).

        Both banks advance in ONE fused scan over byte pairs — the scan is
        the serial axis, so composing steppers instead of running two scans
        halves the sequential latency when both tiers are populated. Each
        tier's work, inside the scan and out, runs under its own
        ``jax.named_scope`` (:data:`TIER_SCOPES`), so a profile's device
        ops name their tier."""
        jnp = self._jnp
        B = lengths.shape[0]
        cube = jnp.zeros((B, self.bank.n_columns), dtype=bool)
        steppers = []
        if self.dfa_cols:
            with jax.named_scope(TIER_SCOPES["dense"]):
                steppers.append((
                    self.dfa_bank.pair_stepper(B, lengths), self.dfa_cols, True,
                    TIER_SCOPES["dense"],
                ))
        if self.shiftor is not None:
            with jax.named_scope(TIER_SCOPES["shiftor"]):
                steppers.append((
                    self.shiftor.pair_stepper(B, lengths), self.shiftor_cols,
                    False, TIER_SCOPES["shiftor"],
                ))
        if self.bitglush is not None:
            use_pallas = False
            if self.bitglush_use_pallas:
                # import only on the opt-in path: the default scan path
                # must not depend on the experimental pallas module
                from log_parser_tpu.ops.bitglush_pallas import (
                    bitglush_hits_pallas,
                    pick_tile,
                )

                use_pallas = pick_tile(B) is not None
            with jax.named_scope(TIER_SCOPES["bitglush"]):
                if use_pallas:
                    hits = bitglush_hits_pallas(self.bitglush, lines_tb, lengths)
                    cube = cube.at[
                        :, jnp.asarray(np.asarray(self.bitglush_cols))
                    ].set(self.bitglush.columns_from_hits(hits))
                else:
                    steppers.append(
                        (
                            self.bitglush.pair_stepper(B, lengths),
                            self.bitglush_cols,
                            False,
                            TIER_SCOPES["bitglush"],
                        )
                    )
        multi_pallas: list | None = None
        if self.multi_groups and self.multidfa_use_pallas:
            from log_parser_tpu.ops.matchdfa_pallas import (
                dfa_tile,
                multidfa_reported_pallas,
            )

            if dfa_tile(self._dfa_pallas_plan, B, lines_tb.shape[0]) is not None:
                # a kernel failure (injected or a real lowering error)
                # raises: the engine's golden fallback serves and counts it
                from log_parser_tpu.runtime import faults

                faults.fire("kernel")
                with jax.named_scope(TIER_SCOPES["union"]):
                    rep_bg = multidfa_reported_pallas(
                        self._dfa_pallas_plan, lines_tb
                    )
                    multi_pallas = [
                        rep_bg[:, i] != 0
                        for i in range(len(self.multi_groups))
                    ]
            else:
                self.multidfa_pallas_reason = "no_tile"
        if multi_pallas is not None:
            pass  # reported flags join multi_reps after the fused scan
        elif self.multi_cluster is not None:
            cluster = self.multi_cluster
            with jax.named_scope(TIER_SCOPES["union"]):
                steppers.append((
                    cluster.pair_stepper(B, lengths), cluster, False,
                    TIER_SCOPES["union"],
                ))
        if self.prefilter is not None:
            with jax.named_scope(TIER_SCOPES["prefilter"]):
                steppers.append((
                    self.prefilter.anyhit_stepper(B, lengths), None, False,
                    TIER_SCOPES["prefilter"],
                ))
        if not steppers:
            if multi_pallas is not None:
                cube = self._multi_contribution(
                    cube, lines_tb, lengths, multi_pallas
                )
            return cube

        inits = tuple(s[0][0] for s in steppers)
        pairs, ts = pack_byte_pairs(lines_tb)

        def fused_step(carries, xs):
            pair_t, t = xs
            new = []
            for s, c in zip(steppers, carries):
                with jax.named_scope(s[3]):
                    new.append(s[0][1](c, pair_t[0], pair_t[1], t))
            return tuple(new), None

        finals, _ = jax.lax.scan(fused_step, inits, (pairs, ts))
        multi_reps: list[jax.Array] = []
        for (stepper, cols, is_dfa, scope), carry in zip(steppers, finals):
            with jax.named_scope(scope):
                out = stepper[2](carry)
                if cols is None:  # prefilter: hit words -> verify stage
                    contrib = self.prefilter.contribution(lines_tb, lengths, out)
                    cube = cube.at[
                        :, jnp.asarray(np.asarray(self.prefilter_cols))
                    ].set(contrib)
                    continue
                if isinstance(cols, MultiDfaCluster):  # per-group reported cols
                    multi_reps.extend(out)
                    continue
                if is_dfa:
                    out = out[:, : len(cols)]
                # tier column sets are disjoint today, so .max equals .set;
                # .max keeps the scatter an OR if a column ever lands in two
                # tiers (an alternative-split experiment did exactly that
                # and was silently masked by .set)
                cube = cube.at[:, jnp.asarray(np.asarray(cols))].max(out)
        if multi_pallas is not None:
            multi_reps.extend(multi_pallas)
        if multi_reps:
            with jax.named_scope(TIER_SCOPES["union"]):
                cube = self._multi_contribution(
                    cube, lines_tb, lengths, multi_reps
                )
        return cube

    def _multi_word_pass(self, lines_tb, lengths, N: int):
        """ONE fused scan advancing every union group's exact out-word
        machinery over ``lines_tb``; returns the per-group hit words."""
        jnp = self._jnp
        steppers = [g.word_stepper(N, lengths) for g in self.multi_groups]
        pairs, ts = pack_byte_pairs(lines_tb)

        def step(carries, xs):
            pair, t = xs
            return tuple(
                st[1](c, pair[0], pair[1], t)
                for st, c in zip(steppers, carries)
            ), None

        finals, _ = jax.lax.scan(
            step, tuple(st[0] for st in steppers), (pairs, ts)
        )
        return [st[2](c) for st, c in zip(steppers, finals)]

    def _multi_contribution(self, cube, lines_tb, lengths, multi_reps):
        """Exact per-pattern bits for every union group via ONE shared
        second pass over the union of flagged rows (matching lines are
        rare), falling back in-program to a full-batch word pass when the
        flagged-row capacity overflows. Sharing one compaction across
        groups keeps the compiled program at two extra scans total,
        whatever the group count."""
        from log_parser_tpu.ops.prefilter import _compact

        jnp = self._jnp
        T, B = lines_tb.shape
        rep_any = multi_reps[0]
        for r in multi_reps[1:]:
            rep_any = rep_any | r
        K = min(B, max(1024, B // 64))
        n_rep, rows, valid = _compact(rep_any, K)

        def scatter(cube, bits_per_group, row_idx, valid_mask):
            safe = jnp.where(valid_mask, row_idx, B)
            for g, bits in zip(self.multi_groups, bits_per_group):
                out = jnp.zeros((B + 1, g.n_cols), bool)
                out = out.at[safe].set(bits & valid_mask[:, None])[:B]
                cube = cube.at[:, jnp.asarray(np.asarray(g.cols))].set(out)
            return cube

        def sparse(cube):
            sub_len = jnp.where(valid, lengths[rows], 0)
            words = self._multi_word_pass(lines_tb[:, rows], sub_len, K)
            bits = [g.unpack(h) for g, h in zip(self.multi_groups, words)]
            return scatter(cube, bits, rows, valid)

        def dense(cube):
            words = self._multi_word_pass(lines_tb, lengths, B)
            for g, h in zip(self.multi_groups, words):
                cube = cube.at[:, jnp.asarray(np.asarray(g.cols))].set(
                    g.unpack(h)
                )
            return cube

        return jax.lax.cond(n_rep <= K, sparse, dense, cube)

    # ------------------------------------------------------------ host carry

    def host_carry(self) -> "CubeHostCarry | None":
        """Resumable host scanner over one growing line, bit-exact with
        :meth:`cube` for device-eligible bytes (streaming follow-mode
        carries it across chunk boundaries instead of rescanning the
        partial tail line per chunk). None when a populated tier has no
        exact host-resumable form — the bit-parallel bitglush chain and
        the AC-prefilter verify stage are pair-scheduled device programs
        whose per-pair state is not byte-resumable; sessions then rescan
        the buffered tail from scratch per frame (exactness of the FINAL
        frame never depends on the carry either way)."""
        if self.bitglush is not None or self.prefilter is not None:
            return None
        if self.shiftor is not None and self.shiftor.host_carry() is None:
            return None
        return CubeHostCarry(self)


class DfaHostCarry:
    """Carried per-regex dense-DFA states for one growing line (host).

    Walks the SAME transition/byte-class/accept tables the device bank
    gathers from (numpy copies, materialized once). The pair-stride
    device path precomposes two single steps through an identity padding
    class, so a byte-at-a-time walk over the true bytes reaches the
    identical final state — padding never moves a dense DFA."""

    def __init__(self, bank: DfaBank):
        r = max(1, bank.n_regexes)
        self.n_regexes = bank.n_regexes
        self._trans = np.asarray(bank.flat_trans).reshape(r, bank.smax, bank.cmax)
        self._accept = np.asarray(bank.flat_accept).reshape(r, bank.smax)
        self._bc = np.asarray(bank.byte_class)
        self._start = np.asarray(bank.start)
        self._r_idx = np.arange(r)
        self.reset()

    def reset(self) -> None:
        self._s = self._start.copy()

    def feed(self, data: bytes) -> None:
        if not self.n_regexes:
            return
        trans, bc, r_idx = self._trans, self._bc, self._r_idx
        s = self._s
        for b in data:
            if b == 0:  # padding-only byte: identity (encode bars content NULs)
                continue
            s = trans[r_idx, s, bc[:, b]]
        self._s = s

    def snapshot_bits(self) -> np.ndarray:
        """bool [n_regexes]: accept-at-end per regex, as of the bytes fed."""
        return self._accept[self._r_idx, self._s][: self.n_regexes]


class MultiDfaHostCarry:
    """Carried union multi-DFA state + exact hit words for one growing
    line (host) — the single-row analogue of the group's ``word_stepper``
    (state, out-word accumulation, accept-at-end OR in snapshot)."""

    def __init__(self, group: MultiDfaBank):
        self.group = group
        self._packed = group._packed_byte_np
        self._byte_rw = np.asarray(group.byte_rw)
        self._out2 = np.asarray(group.out2)
        self._accept_words = np.asarray(group.accept_words)
        self.reset()

    def reset(self) -> None:
        self._s = self.group.start
        self._h = np.zeros(self.group.n_words, dtype=np.uint32)

    def feed(self, data: bytes) -> None:
        s, h = self._s, self._h
        packed, byte_rw, out2 = self._packed, self._byte_rw, self._out2
        for b in data:
            if b == 0:  # padding byte: word_stepper gates it off
                continue
            h = h | out2[s * 2 + int(byte_rw[b])]
            s = int(packed[s * 256 + b]) & MultiDfaBank._STATE_MASK
        self._s, self._h = s, h

    def snapshot_bits(self) -> np.ndarray:
        """bool [n_cols] for this group's columns, in ``group.cols`` order."""
        hw = self._h | self._accept_words[self._s]
        cols = np.arange(self.group.n_cols)
        return ((hw[cols // 32] >> (cols % 32).astype(np.uint32)) & 1).astype(bool)


class CubeHostCarry:
    """Carried scan state for every host-resumable tier of one
    MatcherBanks, over ONE growing line.

    ``feed`` advances the Shift-Or registers, the dense-DFA state
    vector, and each union group's (state, hit-words) carry by the new
    bytes only; ``snapshot_bits`` materializes the cube row the device
    would produce for the line as fed so far — pinned bit-identical to
    ``MatcherBanks.cube`` by tests/test_stream.py. Host-only columns
    stay False (the engine overrides them, same as the device cube)."""

    def __init__(self, matchers):
        self.matchers = matchers
        self.n_columns = matchers.bank.n_columns
        self._shiftor = (
            matchers.shiftor.host_carry() if matchers.shiftor is not None else None
        )
        self._dfa = DfaHostCarry(matchers.dfa_bank) if matchers.dfa_cols else None
        self._multi = [MultiDfaHostCarry(g) for g in matchers.multi_groups]
        self.n_bytes = 0

    def reset(self) -> None:
        if self._shiftor is not None:
            self._shiftor.reset()
        if self._dfa is not None:
            self._dfa.reset()
        for m in self._multi:
            m.reset()
        self.n_bytes = 0

    def feed(self, data: bytes) -> None:
        if not data:
            return
        self.n_bytes += len(data)
        if self._shiftor is not None:
            self._shiftor.feed(data)
        if self._dfa is not None:
            self._dfa.feed(data)
        for m in self._multi:
            m.feed(data)

    def snapshot_bits(self) -> np.ndarray:
        out = np.zeros(self.n_columns, dtype=bool)
        m = self.matchers
        if self._shiftor is not None:
            out[np.asarray(m.shiftor_cols, dtype=np.int64)] = (
                self._shiftor.snapshot_bits()[: len(m.shiftor_cols)]
            )
        if self._dfa is not None:
            out[np.asarray(m.dfa_cols, dtype=np.int64)] = self._dfa.snapshot_bits()
        for g, mc in zip(m.multi_groups, self._multi):
            out[np.asarray(g.cols, dtype=np.int64)] = mc.snapshot_bits()
        return out
