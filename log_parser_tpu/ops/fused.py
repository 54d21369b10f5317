"""Fused match + integer-factor extraction: ONE device program per batch.

TPU has no native float64 — XLA emulates it at a large cost, and the
seven-factor formula needs f64 for ≤1e-6 parity with the JVM's double
arithmetic (SURVEY.md §7 hard part 2). The resolution here is that every
scoring factor is a closed-form f64 function of a handful of *integers*:

==============  ======================================================
factor          integer components (exact)
==============  ======================================================
chronological   global line index, total line count
proximity       per-secondary distance to the nearest hit (int lines)
temporal        per-sequence matched flag (bool)
context         window counts: error / shadowed-warn / stack /
                exception lines + window total
frequency       in-batch prior match count per slot (recovered on host
                from the record stream itself) + persisted base count
==============  ======================================================

So the device program (this module) runs the DFA bank and extracts ONLY
those integers, compacted to a K-capped record buffer in discovery order
(line-major then pattern order — AnalysisService.java:89-113), and the
host finalizer (runtime/finalize.py) evaluates the formula in true f64 on
the M ≪ B·P matched records. No f64 ever touches the device, transfers
shrink from O(B·P) score matrices to O(K) integer records, and parity is
*better* than device-side f64 because the host math is the same IEEE
doubles the JVM uses (ScoringService.java:102-109).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.golden.engine import SEQUENCE_NEAR_WINDOW
from log_parser_tpu.ops.match import DfaBank
from log_parser_tpu.patterns.bank import (
    CTX_ERROR,
    CTX_EXCEPTION,
    CTX_STACK,
    CTX_WARN,
    PatternBank,
)
from log_parser_tpu.utils.trace import NO_TRACE

# "no hit" distance sentinel: larger than any window yet far from int32
# overflow when compared/subtracted
NO_HIT = np.int32(1 << 30)

# This jaxlib (0.4.x) ships no batching rule for optimization_barrier,
# which blocks vmap-ing _step over a leading request axis (the
# cross-request micro-batcher, runtime/batcher.py). The barrier is
# identity-shaped — a fusion hint with no data semantics — so the rule is
# the trivial one: bind the primitive on the batched operands and keep the
# batch dims. Registered defensively: if jax internals move, the batched
# program fails loudly at trace time and the serve path simply runs
# unbatched.
try:  # pragma: no cover - exercised implicitly by every vmapped _step
    from jax._src.lax.lax import optimization_barrier_p as _barrier_p
    from jax.interpreters import batching as _batching

    if _barrier_p not in _batching.primitive_batchers:

        def _barrier_batcher(args, dims, **params):
            return _barrier_p.bind(*args, **params), dims

        _batching.primitive_batchers[_barrier_p] = _barrier_batcher
except Exception:  # noqa: BLE001 - jax internals moved; vmap will raise
    pass

# K-capped record buffers: ladder of compiled bucket sizes; a batch whose
# match count overflows the chosen bucket re-runs at the next rung
K_LADDER = (4096, 32768, 262144, 2097152)


@dataclasses.dataclass
class MatchRecords:
    """Device outputs for one batch: integer factor components per match,
    in discovery order. Rows ≥ n_matches are garbage (unfilled buffer)."""

    n_matches: int
    line: np.ndarray  # int32 [K] 0-based global line index
    pattern: np.ndarray  # int32 [K] pattern index into bank.patterns
    sec_dist: np.ndarray  # int32 [K, S_max] distance per pattern secondary (NO_HIT pad)
    seq_ok: np.ndarray  # bool [K, Q_max] per pattern sequence matched
    ctx_counts: np.ndarray  # int32 [K, 5] err, warn-shadowed, stack, exc, total


class FusedStaticTables:
    """Per-bank static structure shared by the single-device and sharded
    fused programs: per-pattern padded index tables mapping each match
    record to its pattern's secondary entries / sequences / context shape."""

    def __init__(self, bank: PatternBank, config: ScoringConfig):
        self.bank = bank
        self.config = config

        # ---- secondaries: flat entry tables + per-pattern padded index ----
        self.sec_cols = np.asarray([e.column for e in bank.secondaries], dtype=np.int32)
        self.sec_weight = np.asarray([e.weight for e in bank.secondaries], dtype=np.float64)
        self.sec_window = np.asarray(
            [min(config.proximity_max_window, e.window) for e in bank.secondaries],
            dtype=np.int64,
        )
        per_pat: list[list[int]] = [[] for _ in range(bank.n_patterns)]
        for entry_idx, e in enumerate(bank.secondaries):
            per_pat[e.pattern_idx].append(entry_idx)
        self.s_max = max((len(v) for v in per_pat), default=0)
        self.pat_sec = np.full((max(1, bank.n_patterns), max(1, self.s_max)), -1, np.int32)
        for p, entries in enumerate(per_pat):
            self.pat_sec[p, : len(entries)] = entries

        # ---- sequences ----------------------------------------------------
        self.seq_bonus = np.asarray([s.bonus for s in bank.sequences], dtype=np.float64)
        self.seq_event_cols = sorted({c for s in bank.sequences for c in s.event_columns})
        self.seq_col_pos = {c: i for i, c in enumerate(self.seq_event_cols)}
        per_pat_q: list[list[int]] = [[] for _ in range(bank.n_patterns)]
        for q_idx, s in enumerate(bank.sequences):
            per_pat_q[s.pattern_idx].append(q_idx)
        self.q_max = max((len(v) for v in per_pat_q), default=0)
        self.pat_seq = np.full((max(1, bank.n_patterns), max(1, self.q_max)), -1, np.int32)
        for p, qs in enumerate(per_pat_q):
            self.pat_seq[p, : len(qs)] = qs

        # ---- context: unique (has_rules, before, after) shapes -------------
        shapes: list[tuple[bool, int, int]] = []
        shape_idx: dict[tuple[bool, int, int], int] = {}
        pattern_shape = []
        for p_idx in range(bank.n_patterns):
            key = (
                bool(bank.has_context_rules[p_idx]),
                int(bank.ctx_before[p_idx]),
                int(bank.ctx_after[p_idx]),
            )
            if key not in shape_idx:
                shape_idx[key] = len(shapes)
                shapes.append(key)
            pattern_shape.append(shape_idx[key])
        self.ctx_shapes = shapes
        self.pat_ctx_shape = np.asarray(pattern_shape, dtype=np.int32)


def _prev_next_dist(hits: jax.Array, row_idx: jax.Array) -> jax.Array:
    """[B, S] bool hit columns -> [B, S] int32 distance to the nearest hit
    on either side, own row excluded (strict prev/next — the primary line
    is skipped at ScoringService.java:326-328). NO_HIT where none."""
    col_idx = row_idx[:, None]
    prev_incl = jax.lax.cummax(jnp.where(hits, col_idx, -1), axis=0)
    prev = jnp.concatenate(
        [jnp.full((1, hits.shape[1]), -1, prev_incl.dtype), prev_incl[:-1]], axis=0
    )
    nxt_incl = jnp.flip(
        jax.lax.cummin(jnp.flip(jnp.where(hits, col_idx, NO_HIT), axis=0), axis=0),
        axis=0,
    )
    nxt = jnp.concatenate(
        [nxt_incl[1:], jnp.full((1, hits.shape[1]), NO_HIT, nxt_incl.dtype)], axis=0
    )
    d_prev = jnp.where(prev >= 0, col_idx - prev, NO_HIT)
    d_next = jnp.where(nxt < NO_HIT, nxt - col_idx, NO_HIT)
    return jnp.minimum(d_prev, d_next)


def _prefix(x: jax.Array) -> jax.Array:
    """[B, ...] -> [B+1, ...] exclusive prefix sums (window sum = 2 gathers)."""
    return jnp.concatenate(
        [jnp.zeros((1,) + x.shape[1:], x.dtype), jnp.cumsum(x, axis=0)]
    )


def sequence_flags_from_events(
    sequences, t: "FusedStaticTables", em: jax.Array, idx: jax.Array, n_lines
) -> jax.Array:
    """[len(idx), n_sequences] bool — sequence fully matched with the primary
    at each ``idx`` row of the (global) event-match matrix ``em`` [B, E]
    (ScoringService.java:230-305): last event within ±5 of the primary via a
    prefix-count range-any (:272-286), earlier events chained strictly
    backwards via inclusive prefix-cummax of last-hit line; the chain
    restarts at the *primary* line, not the near-window hit (:250).

    Shared by the single-device program (em local == global) and the
    sharded program (em all_gathered, idx = the shard's global rows)."""
    B = em.shape[0]
    eidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    prev_incl = jax.lax.cummax(jnp.where(em, eidx, -1), axis=0)  # [B, E]
    prefix_counts = _prefix(em.astype(jnp.int32))  # [B+1, E]

    w = SEQUENCE_NEAR_WINDOW
    outs = []
    for seq in sequences:
        if not seq.event_columns:
            outs.append(jnp.zeros(idx.shape, dtype=bool))
            continue
        last_e = t.seq_col_pos[seq.event_columns[-1]]
        lo = jnp.clip(idx - w, 0, B)
        hi = jnp.clip(jnp.minimum(idx + w + 1, n_lines), 0, B).astype(jnp.int32)
        ok = (prefix_counts[hi, last_e] - prefix_counts[lo, last_e]) > 0
        cur = idx
        for col in reversed(seq.event_columns[:-1]):
            e = t.seq_col_pos[col]
            g = jnp.where(cur >= 1, prev_incl[jnp.clip(cur - 1, 0, B - 1), e], -1)
            ok = ok & (g >= 0)
            cur = jnp.clip(g, 0, B - 1)
        outs.append(ok)
    return jnp.stack(outs, axis=1)


def compact_records(
    K: int,
    pm: jax.Array,
    t: "FusedStaticTables",
    emit_line: jax.Array,
    gather_line: jax.Array,
    sec_dist: jax.Array,
    seq_ok: jax.Array,
    ctx_counts: jax.Array,
):
    """K-capped record compaction in discovery order (line-major then
    pattern order — AnalysisService.java:89-113), shared by the
    single-device and sharded programs.

    ``emit_line``: per-row line index written into the records (global);
    ``gather_line``: per-row index into the dense factor tables (local).
    rank = exclusive match count in flat order == the record's output slot;
    slot K is the trash row for overflow (caller re-runs at a bigger K).

    Two-level: matching ROWS are compacted first (one [B]-sized pass),
    then (row, pattern) pairs rank/scatter over only ``K_rows x P``
    elements — the naive flat [B*P] cumsum + three scatters are
    per-element scalar-unit work on TPU (like the match-cube gathers,
    PERF.md §1) and dominated the extraction phase at 19M elements on
    config-2 shapes. ``K_rows = min(B, K)`` loses nothing: every
    compacted-out row holds >= 1 match, so row overflow implies
    ``n_matches > K`` — and ``n_matches`` is summed over the FULL cube,
    so the caller's ladder re-run triggers exactly as before."""
    from log_parser_tpu.ops.prefilter import _compact

    B, P = pm.shape
    n_matches = jnp.sum(pm.astype(jnp.int32))

    K_rows = min(B, K)
    _n_rows, rows, rows_valid = _compact(pm.any(axis=1), K_rows)
    sub_pm = pm[rows] & rows_valid[:, None]  # [K_rows, P]

    sub32 = sub_pm.astype(jnp.int32)
    flat = sub32.reshape(-1)
    rank = jnp.cumsum(flat) - flat
    out_pos = jnp.where(flat > 0, jnp.minimum(rank, K), K)

    emit_bp = jnp.broadcast_to(emit_line[rows][:, None], (K_rows, P)).reshape(-1)
    gather_bp = jnp.broadcast_to(
        gather_line[rows][:, None], (K_rows, P)
    ).reshape(-1)
    pats_bp = jnp.broadcast_to(
        jnp.arange(P, dtype=jnp.int32)[None, :], (K_rows, P)
    ).reshape(-1)
    rec_line = jnp.zeros((K + 1,), jnp.int32).at[out_pos].set(emit_bp)[:K]
    rec_grow = jnp.zeros((K + 1,), jnp.int32).at[out_pos].set(gather_bp)[:K]
    rec_pat = jnp.zeros((K + 1,), jnp.int32).at[out_pos].set(pats_bp)[:K]

    sec_idx = jnp.asarray(t.pat_sec)[rec_pat]  # [K, S_max]
    rec_dist = jnp.where(
        sec_idx >= 0,
        sec_dist[rec_grow[:, None], jnp.maximum(sec_idx, 0)],
        NO_HIT,
    )
    q_idx = jnp.asarray(t.pat_seq)[rec_pat]  # [K, Q_max]
    rec_seq = jnp.where(
        q_idx >= 0, seq_ok[rec_grow[:, None], jnp.maximum(q_idx, 0)], False
    )
    rec_ctx = ctx_counts[rec_grow, jnp.asarray(t.pat_ctx_shape)[rec_pat]]  # [K, 5]

    return n_matches.astype(jnp.int32), rec_line, rec_pat, rec_dist, rec_seq, rec_ctx


def pack_records(n_matches, rec_line, rec_pat, rec_dist, rec_seq, rec_ctx):
    """Concatenate one batch's record buffers into a single flat int32
    array: [n, line(K), pattern(K), sec_dist(K*S), seq_ok(K*Q), ctx(K*5)].

    One array == ONE device-to-host copy at resolve time: each transfer
    is its own synchronizing round-trip, and the 6-array layout made
    each request pay six of them."""
    return jnp.concatenate(
        [
            n_matches.reshape(1),
            rec_line,
            rec_pat,
            rec_dist.reshape(-1),
            rec_seq.astype(jnp.int32).reshape(-1),
            rec_ctx.reshape(-1),
        ]
    )


def unpack_records(arr: np.ndarray, s_w: int, q_w: int) -> MatchRecords | None:
    """Host-side inverse of :func:`pack_records`; None signals K overflow."""
    width = 2 + s_w + q_w + 5
    K = (arr.shape[0] - 1) // width
    n_matches = int(arr[0])
    if n_matches > K:
        return None
    off = 1
    line = arr[off : off + K]
    off += K
    pattern = arr[off : off + K]
    off += K
    sec_dist = arr[off : off + K * s_w].reshape(K, s_w)
    off += K * s_w
    seq_ok = arr[off : off + K * q_w].reshape(K, q_w).astype(bool)
    off += K * q_w
    ctx_counts = arr[off : off + K * 5].reshape(K, 5)
    return MatchRecords(
        n_matches=n_matches,
        line=line,
        pattern=pattern,
        sec_dist=sec_dist,
        seq_ok=seq_ok,
        ctx_counts=ctx_counts,
    )


class FusedMatchScore:
    """Single-device fused program: bytes → DFA cube → integer match records.

    Compiled once per (batch rows, K bucket, overrides?) combination; the
    engine picks the K bucket adaptively and re-runs on overflow.
    """

    def __init__(self, bank: PatternBank, config: ScoringConfig, matchers):
        self.bank = bank
        self.config = config
        self.matchers = matchers  # MatcherBanks: tiered Shift-Or + DFA cube
        self.t = FusedStaticTables(bank, config)

        # named functions, so a profile's module line names each program
        def logparser_step_ov(k, lines, lens, n, om, ov):
            return self._step(k, lines, lens, n, (om, ov))

        def logparser_step(k, lines, lens, n):
            return self._step(k, lines, lens, n, None)

        def logparser_cube_ov(lines, lens, n, om, ov):
            return self._cube_step(lines, lens, n, (om, ov))

        def logparser_cube(lines, lens, n):
            return self._cube_step(lines, lens, n, None)

        # K is a static arg: each bucket size is its own cached executable
        self._jit_ov = jax.jit(logparser_step_ov, static_argnums=(0,))
        self._jit_plain = jax.jit(logparser_step, static_argnums=(0,))
        # cube-only programs (the line-cache residual path): no extraction,
        # just the post-override bit matrix — extraction happens on the host
        # from cached + fresh rows together (runtime/linecache.py)
        self._jit_cube_ov = jax.jit(logparser_cube_ov)
        self._jit_cube_plain = jax.jit(logparser_cube)

    # ------------------------------------------------------------- host entry

    def dispatch(
        self,
        k: int,
        lines_u8: np.ndarray,
        lengths: np.ndarray,
        n_lines: int,
        override_mask: np.ndarray | None = None,
        override_val: np.ndarray | None = None,
        trace=NO_TRACE,
    ):
        """Launch the fused program asynchronously at record capacity ``k``
        and return the un-synchronized device outputs. Callers fan out
        several dispatches (e.g. one pattern block per device) before the
        first blocking read. ``trace`` (a PhaseTrace) times the
        ``device.upload`` and ``device.launch`` stages.

        The batch uploads in its contiguous [B, T] layout and transposes
        ON DEVICE (a free layout op inside the compiled program): a
        host-side ``.T`` copy before upload measured 82 ms vs 9 ms for
        the contiguous config-2 batch — ~10% of a serial request."""
        with trace.stage("device.upload"):
            args = _upload(lines_u8, lengths, n_lines, override_mask, override_val)
        with trace.stage("device.launch"):
            if override_mask is not None:
                return self._jit_ov(k, *args)
            return self._jit_plain(k, *args)

    def k_ladder(self, lines_u8: np.ndarray, k_hint: int = 0):
        """The record-capacity buckets to try, smallest viable first."""
        cap = lines_u8.shape[0] * max(1, self.bank.n_patterns)
        start = 0
        while start < len(K_LADDER) - 1 and K_LADDER[start] < k_hint:
            start += 1
        return [min(k, cap) for k in (*K_LADDER[start:], cap)], cap

    def resolve(self, out, trace=NO_TRACE) -> MatchRecords | None:
        """Synchronize one dispatch — a single packed-array transfer —
        and unpack; None signals K overflow (re-dispatch at the next
        ladder rung)."""
        return self.unpack(_read_back(out, trace))

    def unpack(self, arr: np.ndarray) -> MatchRecords | None:
        """:func:`unpack_records` of one host copy at this program's
        record widths."""
        return unpack_records(arr, max(1, self.t.s_max), max(1, self.t.q_max))

    def run(
        self,
        lines_u8: np.ndarray,
        lengths: np.ndarray,
        n_lines: int,
        override_mask: np.ndarray | None = None,
        override_val: np.ndarray | None = None,
        k_hint: int = 0,
        trace=NO_TRACE,
    ) -> MatchRecords:
        """Executes the fused program, growing the record buffer until the
        batch's matches fit. ``k_hint``: expected match count (e.g. the
        previous request's), used to pick the starting bucket."""
        ladder, cap = self.k_ladder(lines_u8, k_hint)
        for k in ladder:
            out = self.dispatch(
                k, lines_u8, lengths, n_lines, override_mask, override_val,
                trace=trace,
            )
            recs = self.resolve(out, trace)
            if recs is not None or k >= cap:
                if recs is None:  # cap rung can never truly overflow
                    raise AssertionError("unreachable: K ladder capped at B*P")
                return recs
        raise AssertionError("unreachable: K ladder capped at B*P")

    def host_carry(self):
        """Carried-scan-state entry point for streaming ingestion: a
        :class:`~log_parser_tpu.ops.match.CubeHostCarry` whose ``feed``/
        ``snapshot_bits`` advance this program's matcher tiers over one
        growing line and return the cube row the device would produce —
        union-DFA states, dense-DFA states, and Shift-Or bit registers
        all resume across chunk boundaries instead of rescanning.  None
        when a populated tier is not host-resumable (bitglush /
        prefilter); callers then rescan the buffered tail per frame."""
        return self.matchers.host_carry()

    def cube_rows(
        self,
        lines_u8: np.ndarray,
        lengths: np.ndarray,
        n_lines: int,
        override_mask: np.ndarray | None = None,
        override_val: np.ndarray | None = None,
        trace=NO_TRACE,
    ) -> np.ndarray:
        """Post-override match-bit matrix [B, n_columns] for a (residual)
        batch — the cacheable unit of the routing tier. Everything the
        fused extraction derives is a pure function of these bits plus the
        request's line count, so the line cache memoizes rows of THIS
        matrix and replays extraction on the host."""
        with trace.stage("device.upload"):
            args = _upload(lines_u8, lengths, n_lines, override_mask, override_val)
        with trace.stage("device.launch"):
            if override_mask is not None:
                out = self._jit_cube_ov(*args)
            else:
                out = self._jit_cube_plain(*args)
        return _read_back(out, trace)

    # ---------------------------------------------------------- device program

    def _cube_step(self, lines_bt, lengths, n_lines, overrides):
        """The shared front half of :meth:`_step`: tiered match cube,
        override splice, padding-row mask. Returns bool [B, n_columns]."""
        with jax.named_scope("logparser.cube"):
            lines_tb = lines_bt.T  # device-side layout change (see dispatch)
            B = lengths.shape[0]
            row_idx = jnp.arange(B, dtype=jnp.int32)
            valid = row_idx < n_lines
            cube = jax.lax.optimization_barrier(
                self.matchers.cube(lines_tb, lengths)
            )
            if overrides is not None:
                om, ov = overrides
                cube = jnp.where(om, ov, cube)
            return cube & valid[:, None]

    def _step(self, K, lines_bt, lengths, n_lines, overrides):
        B = lengths.shape[0]
        row_idx = jnp.arange(B, dtype=jnp.int32)

        # ---- match cube (tiered: Shift-Or + DFA banks) --------------------
        # the barrier (inside _cube_step) stops XLA from fusing extraction
        # work back into the scan loops: the compiled step alone measured
        # 0.417 → 0.374 s on v5e config-2 shapes (direct _jit_plain timing;
        # the end-to-end headline moves less). Padding rows contribute
        # nothing: empty-matching regexes (^$, \s*) would otherwise
        # produce phantom hits on zero-length padding.
        cube = self._cube_step(lines_bt, lengths, n_lines, overrides)
        with jax.named_scope("logparser.extract"):
            return self._extract(K, cube, row_idx, B, n_lines)

    def _extract(self, K, cube, row_idx, B, n_lines):
        """The back half of :meth:`_step`: integer factor components and
        the K-capped record compaction, packed into one array."""
        bank, t = self.bank, self.t
        P = bank.n_patterns
        if P == 0:
            z32 = jnp.zeros((K,), jnp.int32)
            return pack_records(
                jnp.int32(0),
                z32,
                z32,
                jnp.full((K, max(1, t.s_max)), NO_HIT, jnp.int32),
                jnp.zeros((K, max(1, t.q_max)), bool),
                jnp.zeros((K, 5), jnp.int32),
            )

        pm = cube[:, jnp.asarray(bank.primary_columns)]  # [B, P]

        # ---- dense integer factor components ------------------------------
        sec_dist = self._secondary_distances(cube, row_idx)  # [B, Smax-safe]
        em = (
            cube[:, jnp.asarray(t.seq_event_cols, dtype=np.int32)]
            if bank.sequences
            else jnp.zeros((B, 1), dtype=bool)
        )
        seq_ok = (
            sequence_flags_from_events(bank.sequences, t, em, row_idx, n_lines)
            if bank.sequences
            else jnp.zeros((B, 1), dtype=bool)
        )
        ctx_counts = self._context_counts(cube, row_idx, B, n_lines)  # [B, U, 5]

        # single-device: emit and gather coordinates coincide
        return pack_records(
            *compact_records(K, pm, t, row_idx, row_idx, sec_dist, seq_ok, ctx_counts)
        )

    # ------------------------------------------------------------ dense tables

    def _secondary_distances(self, cube, row_idx):
        """[B, n_sec_entries] int32 nearest-hit distances (NO_HIT if none).
        Exact for any window: the nearest hit overall is the nearest hit
        within the window (ScoringService.java:315-347)."""
        t = self.t
        if len(t.sec_cols) == 0:
            return jnp.full((cube.shape[0], 1), NO_HIT, jnp.int32)
        hits = cube[:, jnp.asarray(t.sec_cols)]  # [B, S_entries]
        return _prev_next_dist(hits, row_idx)

    def _context_counts(self, cube, row_idx, B, n_lines):
        """[B, U, 5] int32 — per unique context shape: error lines,
        shadowed-warn lines (else-if at ContextAnalysisService.java:64-70),
        stack lines, exception lines, window total."""
        t = self.t
        err = cube[:, CTX_ERROR]
        warn = cube[:, CTX_WARN] & ~err
        stack = cube[:, CTX_STACK]
        exc = cube[:, CTX_EXCEPTION]
        flags = jnp.stack(
            [err, warn, stack, exc], axis=1
        ).astype(jnp.int32)  # [B, 4]
        ps = _prefix(flags)  # [B+1, 4]

        per_shape = []
        for has_rules, before, after in t.ctx_shapes:
            if not has_rules:
                # context = the matched line only (AnalysisService.java:135-139)
                counts = flags
                total = jnp.ones((B,), jnp.int32)
            else:
                lo = jnp.clip(row_idx - before, 0, B)
                hi = jnp.clip(jnp.minimum(row_idx + 1 + after, n_lines), 0, B).astype(
                    jnp.int32
                )
                counts = ps[hi] - ps[lo]  # [B, 4]
                total = hi - lo
            per_shape.append(jnp.concatenate([counts, total[:, None]], axis=1))
        return jnp.stack(per_shape, axis=1)  # [B, U, 5]


def _upload(lines_u8, lengths, n_lines, override_mask, override_val) -> tuple:
    """The host → device copies of one dispatch's inputs, in the order
    the programs take them (the overrides only where there are some)."""
    args = (
        jnp.asarray(lines_u8),
        jnp.asarray(lengths),
        jnp.asarray(n_lines, dtype=jnp.int32),
    )
    if override_mask is None:
        return args
    return args + (jnp.asarray(override_mask), jnp.asarray(override_val))


def _read_back(out, trace) -> np.ndarray:
    """Wait for ``out`` (``device.wait``), then copy it to the host
    (``device.readback``). The copy is queued before the wait, so it
    starts when the program ends, as a bare ``np.asarray`` would start it,
    and not after the host has woken from the wait."""
    out.copy_to_host_async()
    with trace.stage("device.wait"):
        out.block_until_ready()
    with trace.stage("device.readback"):
        return np.asarray(out)


class FusedBatchMatchScore:
    """Cross-request batched fused program: ``vmap`` of
    :meth:`FusedMatchScore._step` over a leading request axis R.

    One dispatch serves R coalesced requests (runtime/batcher.py): inputs
    are ``lines_u8 [R, B, T]``, ``lengths [R, B]``, ``n_lines [R]`` and
    optionally stacked override cubes ``[R, B, C]``. Each vmapped instance
    sees ONLY its own rows and its own ``n_lines`` valid-mask, so match
    bits, distances, sequence chains, and context windows can never bleed
    across requests — and because the device math is integer-only, vmap
    cannot perturb results: per-request records are bit-identical to the
    unbatched program's (tests/test_batcher.py asserts equality, which
    subsumes the ≤1e-6 score-parity requirement).

    K (the record capacity) is a shared static arg: one rung serves the
    whole batch, sized by the engine's k_hint, and if ANY request
    overflows, the whole batch re-runs at the next rung (per-request caps
    are equal within a bucket — same B, same pattern count).
    """

    def __init__(self, fused: FusedMatchScore):
        self.fused = fused

        def logparser_batch_step(k, lines, lens, n):
            return jax.vmap(
                lambda L, le, nn: fused._step(k, L, le, nn, None)
            )(lines, lens, n)

        def logparser_batch_step_ov(k, lines, lens, n, om, ov):
            return jax.vmap(
                lambda L, le, nn, m, v: fused._step(k, L, le, nn, (m, v))
            )(lines, lens, n, om, ov)

        self._jit_plain = jax.jit(logparser_batch_step, static_argnums=(0,))
        self._jit_ov = jax.jit(logparser_batch_step_ov, static_argnums=(0,))

    def run(
        self,
        lines_u8: np.ndarray,  # [R, B, T] uint8
        lengths: np.ndarray,  # [R, B] int
        n_lines: np.ndarray,  # [R] int
        override_mask: np.ndarray | None = None,  # [R, B, C] bool
        override_val: np.ndarray | None = None,
        k_hint: int = 0,
        trace=NO_TRACE,
    ) -> list[MatchRecords]:
        """One batched dispatch per K rung; returns per-request records in
        request order. Overflow of any slot climbs the shared ladder.
        ``trace`` times the same device stages as the unbatched path."""
        R = lines_u8.shape[0]
        ladder, cap = self.fused.k_ladder(lines_u8[0], k_hint)
        with trace.stage("device.upload"):
            args = _upload(lines_u8, lengths, n_lines, override_mask, override_val)
        for k in ladder:
            with trace.stage("device.launch"):
                if override_mask is not None:
                    out = self._jit_ov(k, *args)
                else:
                    out = self._jit_plain(k, *args)
            arr = _read_back(out, trace)  # [R, packed] — ONE device→host transfer
            recs = [self.fused.unpack(arr[i]) for i in range(R)]
            if all(r is not None for r in recs):
                return recs
            if k >= cap:
                raise AssertionError("unreachable: K ladder capped at B*P")
        raise AssertionError("unreachable: K ladder capped at B*P")
