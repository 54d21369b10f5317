"""Pattern-axis sharding — the workload's tensor-parallel analogue.

SURVEY.md §2.2: for high-cardinality libraries (BASELINE config 4, 10k
regexes) the compiled automaton bank itself is the big operand, so it is
partitioned across devices instead of the lines: device d holds the DFA
bank of pattern block d and scans the *full* (replicated) line batch
through it. Blocks are embarrassingly parallel — JAX's async dispatch runs
all D programs concurrently, one per device — and there is no collective
at all: each block emits its own K-capped integer match records
(ops/fused.py) with *global* pattern indexes, the host merges the blocks
by (line, pattern) — restoring the reference's discovery order
(line-major, then pattern order, AnalysisService.java:89-113) — and the
shared exact-f64 finalizer recovers frequency priors from the merged
stream.

Matcher columns shared between patterns in different blocks (interned
regexes) are re-scanned per block: duplicated compute is the standard
tensor-parallel trade for never materializing a [lines × 10k-pattern]
cube on one chip.

Composes with line sharding: a 2D fleet runs this engine per line shard.

Tenant placement (multi-tenant fleets, runtime/tenancy.py) is the third
partitioning axis: each tenant's bank is DISJOINT, so there is nothing to
merge — :class:`TenantPlacement` round-robins whole tenant engines across
the visible chips and pins each engine's device step there. One tenant's
traffic then never contends for another tenant's chip, and a tenant bank
rebuild recompiles only on its own device.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from log_parser_tpu import _clock as pclock
from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.models.pattern import PatternSet, PatternSetMetadata
from log_parser_tpu.ops.fused import FusedMatchScore, MatchRecords
from log_parser_tpu.ops.match import MatcherBanks
from log_parser_tpu.patterns.bank import PatternBank
from log_parser_tpu.runtime.engine import AnalysisEngine
from log_parser_tpu.utils.trace import NO_TRACE


def partition_pattern_sets(
    pattern_sets: list[PatternSet], n_blocks: int
) -> list[list[PatternSet]]:
    """Split a library into ``n_blocks`` contiguous pattern blocks of
    near-equal pattern count, preserving set-major discovery order. Each
    block becomes a list of (synthetic, single-slice) PatternSets so every
    block's PatternBank sees the same per-set structure."""
    flat: list[tuple[PatternSet, object]] = []
    for ps in pattern_sets:
        for p in ps.patterns or []:
            flat.append((ps, p))
    n_blocks = max(1, min(n_blocks, max(1, len(flat))))
    base, extra = divmod(len(flat), n_blocks)  # balanced: no empty blocks
    blocks: list[list[PatternSet]] = []
    lo = 0
    for b in range(n_blocks):
        hi = lo + base + (1 if b < extra else 0)
        chunk = flat[lo:hi]
        lo = hi
        sets: list[PatternSet] = []
        for src, pattern in chunk:
            if sets and sets[-1].metadata is src.metadata:
                sets[-1].patterns.append(pattern)
            else:
                sets.append(
                    PatternSet(metadata=src.metadata, patterns=[pattern])
                )
        blocks.append(sets)
    return blocks


class PatternShardedEngine(AnalysisEngine):
    """AnalysisEngine whose device step fans the pattern blocks out over
    the visible devices (or ``devices``), one fused program per block."""

    def __init__(
        self,
        pattern_sets: list[PatternSet],
        config: ScoringConfig | None = None,
        devices: list | None = None,
        n_blocks: int | None = None,
        clock: Callable[[], float] = pclock.mono,
    ):
        # the base engine's bank carries the FULL library: finalization,
        # frequency slots, event assembly, and global pattern indexes all
        # come from it. Per-block banks drive only the device programs.
        super().__init__(pattern_sets, config, clock=clock)
        self.devices = devices if devices is not None else jax.devices()
        n = n_blocks if n_blocks is not None else len(self.devices)
        self.blocks = partition_pattern_sets(pattern_sets, n)

        self._block_engines: list[tuple[FusedMatchScore, np.ndarray, object]] = []
        offset = 0
        for b, block_sets in enumerate(self.blocks):
            # single-block partition == the full library: reuse the base
            # bank instead of compiling a duplicate (halves boot time on
            # one device; the 10k warm ctor measured 3.4 -> ~1.8 s)
            bank = self.bank if len(self.blocks) == 1 else PatternBank(block_sets)
            fused = FusedMatchScore(bank, self.config, MatcherBanks(bank))
            # block-local pattern idx -> global pattern idx (discovery order
            # is preserved by contiguous partitioning)
            global_idx = np.arange(offset, offset + bank.n_patterns, dtype=np.int32)
            offset += bank.n_patterns
            device = self.devices[b % len(self.devices)]
            self._block_engines.append((fused, global_idx, device))
        assert offset == self.bank.n_patterns, (
            "block partition must cover the full bank exactly "
            f"({offset} != {self.bank.n_patterns})"
        )

    def _approx_sources_token(self) -> tuple:
        return tuple(f.matchers for f, _g, _d in self._block_engines)

    def _approx_col_sources(self):
        """Each block's device program truncates against its OWN bank
        (role sets are computed per block, so a column primary-only in
        one block may stay exact in another); union every block's
        (approx_cols, bank, global pattern offset) so flagged events of
        any block get host-verified."""
        out = []
        offset = 0
        for fused, _global_idx, _dev in self._block_engines:
            out.append(
                (getattr(fused.matchers, "approx_cols", []), fused.bank, offset)
            )
            offset += fused.bank.n_patterns
        return out

    def _block_overrides(self, fused: FusedMatchScore, om, ov):
        """Overrides index the FULL bank's columns; each block re-derives
        its slice by interned regex key."""
        if om is None:
            return None, None
        cols = [
            self._col_index.get((c.regex, c.case_insensitive))
            for c in fused.bank.columns
        ]
        missing = [
            fused.bank.columns[i].regex for i, c in enumerate(cols) if c is None
        ]
        # block patterns are by construction a subset of the full bank; a
        # lookup miss means the intern table and the blocks diverged, and
        # defaulting would silently apply the wrong column's overrides.
        # RuntimeError, not assert: this invariant must hold under -O too
        # (ADVICE.md r2) — an object array of Nones would otherwise fail
        # obscurely downstream.
        if missing:
            raise RuntimeError(
                f"block columns missing from full bank: {missing[:3]}"
            )
        take = np.asarray(cols)
        return np.ascontiguousarray(om[:, take]), np.ascontiguousarray(ov[:, take])

    def _run_device(self, enc, n_lines: int, om, ov, trace=NO_TRACE):
        """Fan every block out asynchronously — one fused program per
        device — and only then start the blocking reads, so device work
        overlaps (wall-clock ≈ slowest block, not the sum). Blocks whose
        record buffer overflows re-dispatch at the next ladder rung."""
        k_hint = max(1, self._k_hint // max(1, len(self._block_engines)))
        pending = []
        for fused, global_idx, device in self._block_engines:
            b_om, b_ov = self._block_overrides(fused, om, ov)
            ladder, _ = fused.k_ladder(enc.u8, k_hint)
            with jax.default_device(device):
                out = fused.dispatch(
                    ladder[0], enc.u8, enc.lengths, n_lines, b_om, b_ov
                )
            pending.append((fused, global_idx, device, b_om, b_ov, ladder, out))

        outs: list[MatchRecords] = []
        for fused, global_idx, device, b_om, b_ov, ladder, out in pending:
            recs = fused.resolve(out)
            for k in ladder[1:]:
                if recs is not None:
                    break
                with jax.default_device(device):
                    out = fused.dispatch(k, enc.u8, enc.lengths, n_lines, b_om, b_ov)
                recs = fused.resolve(out)
            assert recs is not None, "K ladder is capped at B*P"
            outs.append(self._globalize(recs, global_idx))
        return self._merge(outs)

    @property
    def _col_index(self) -> dict:
        return self.bank._column_by_key

    def _approx_global_cols(self) -> set:
        """Union of every block's approximate columns, translated from
        block-local to full-bank indexes by interned (regex, ci) key —
        conservative (see AnalysisEngine._approx_secondaries): a column
        exact in the block that ran a given pattern repairs as a no-op."""
        out: set = set()
        for fused, _global_idx, _dev in self._block_engines:
            for c in getattr(fused.matchers, "approx_cols", []):
                col = fused.bank.columns[c]
                g = self._col_index.get((col.regex, col.case_insensitive))
                if g is not None:
                    out.add(g)
        return out

    def _globalize(self, recs: MatchRecords, global_idx: np.ndarray) -> MatchRecords:
        """Rewrite block-local pattern indexes to full-bank indexes."""
        m = recs.n_matches
        if m:
            recs.pattern = recs.pattern.copy()
            recs.pattern[:m] = global_idx[recs.pattern[:m]]
        return recs

    def _merge(self, outs: list[MatchRecords]) -> MatchRecords:
        """Merge block record streams into global discovery order. Records
        within a block are (line, pattern)-sorted already; blocks partition
        the pattern axis contiguously, so a stable sort on (line, pattern)
        restores line-major-then-pattern order."""
        t = self.tables
        s_max = max(1, t.s_max)
        q_max = max(1, t.q_max)
        line = np.concatenate([o.line[: o.n_matches] for o in outs])
        pat = np.concatenate([o.pattern[: o.n_matches] for o in outs])

        def pad(a: np.ndarray, width: int, fill) -> np.ndarray:
            if a.shape[1] == width:
                return a
            out = np.full((a.shape[0], width), fill, dtype=a.dtype)
            out[:, : a.shape[1]] = a
            return out

        from log_parser_tpu.ops.fused import NO_HIT

        # per-block S/Q pads differ; records carry the block's own pattern
        # tables' layout, which matches the global tables because blocks
        # preserve each pattern's own secondary/sequence lists
        sec = np.concatenate(
            [pad(o.sec_dist[: o.n_matches], s_max, NO_HIT) for o in outs]
        )
        seq = np.concatenate(
            [pad(o.seq_ok[: o.n_matches], q_max, False) for o in outs]
        )
        ctx = np.concatenate([o.ctx_counts[: o.n_matches] for o in outs])

        order = np.lexsort((pat, line))  # stable: line-major, then pattern
        return MatchRecords(
            n_matches=len(order),
            line=line[order],
            pattern=pat[order],
            sec_dist=sec[order],
            seq_ok=seq[order],
            ctx_counts=ctx[order],
        )


def pin_engine(engine: AnalysisEngine, device) -> AnalysisEngine:
    """Pin one engine's device step to ``device``: every fused dispatch
    (and its compilation cache) lands on that chip via
    ``jax.default_device``, while host phases (ingest, finalize, events)
    stay wherever the caller runs them. Idempotent re-pin: wraps the
    CURRENT step, so pinning twice just narrows to the newer device."""
    inner = engine._run_device

    def pinned(enc, n_lines, om, ov):
        with jax.default_device(device):
            return inner(enc, n_lines, om, ov)

    engine._run_device = pinned
    engine.placement_device = device
    return engine


class TenantPlacement:
    """Tenant-placement mode: disjoint per-tenant banks, one chip each.

    Unlike the pattern blocks above, tenant banks share NOTHING — no
    merge, no global index rewrite — so placement is pure scheduling:
    round-robin each new tenant engine onto the next device and pin its
    device step there. The ``assign`` method matches the
    ``engine_setup(engine, tenant_id)`` hook of
    :class:`~log_parser_tpu.runtime.tenancy.TenantRegistry`, so a serving
    fleet opts in with ``engine_setup=placement.assign`` (composed after
    any per-tenant cache/batcher setup). ``bench_mesh.py --tenants N``
    drives this mode end-to-end on a virtual or real mesh.
    """

    def __init__(self, devices: list | None = None, load=None):
        self.devices = list(devices) if devices is not None else jax.devices()
        if not self.devices:
            raise ValueError("TenantPlacement needs at least one device")
        self.assignments: dict[str, object] = {}
        self._next = 0
        # optional ``load(device) -> float``: when given, NEW tenants
        # prefer the least-loaded device — the single-process analogue
        # of the fleet placer (fleet/placement.py). No callback keeps
        # blind round-robin, which is also the fallback when the
        # callback itself fails (a broken load signal must not stop
        # placement).
        self.load = load

    def _pick(self):
        if self.load is not None:
            try:
                return min(self.devices, key=self.load)
            except Exception:
                pass
        device = self.devices[self._next % len(self.devices)]
        self._next += 1
        return device

    def assign(self, engine: AnalysisEngine, tenant_id: str) -> AnalysisEngine:
        """Place ``engine`` on the least-loaded device (with a load
        callback) or the next in rotation. A tenant re-assigned after
        eviction+rebuild lands back on ITS device, not the rotation's
        next one — placement stays stable under churn."""
        device = self.assignments.get(str(tenant_id))
        if device is None:
            device = self._pick()
            self.assignments[str(tenant_id)] = device
        return pin_engine(engine, device)

    def move(self, tenant_id: str, device=None) -> object:
        """Re-place a tenant: the next build of its engine pins to
        ``device`` (or the rotation's next chip). Placement moves are
        MIGRATIONS, not bare re-pins — the caller runs
        ``Migrator.migrate(tenant_id, LocalTarget(...))`` (runtime/
        migrate.py) so the tenant's frequency history, parked candidates
        and open sessions travel with it; this method only records where
        the rebuilt engine must land."""
        tid = str(tenant_id)
        if device is None:
            device = self._pick()
        self.assignments[tid] = device
        return device

    def stats(self) -> dict:
        return {
            "devices": len(self.devices),
            "placements": {t: str(d) for t, d in self.assignments.items()},
        }
