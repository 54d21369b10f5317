"""shard_map pipeline: fused match + integer-factor extraction on a
line-sharded batch.

One jitted SPMD program per library: every shard scans its own lines
through the DFA bank (zero communication — lines are independent for
matching, AnalysisService.java:89-113), then extracts the integer factor
components of ops/fused.py with the narrowest collective each one needs:

==================  =========================================================
factor component    communication
==================  =========================================================
chronological       none (global line index is shard offset + local index)
secondary dists     ``ppermute`` halo of the secondary-match columns
                    (window ≤ halo), or ``all_gather`` when shards are
                    smaller than the halo
context counts      same halo machinery over the four context-flag columns
sequence flags      ``all_gather`` of the (few) sequence-event columns —
                    the backward scan is unbounded (ScoringService.java:
                    296-305), so each shard keeps the full column and the
                    chain runs as local gathers
frequency           NONE — line-sharding is contiguous, so concatenating
                    per-shard record blocks in shard order reproduces global
                    discovery order, and the host finalizer recovers every
                    read-before-record prior from the stream itself
==================  =========================================================

Each shard compacts its matches into a local K-capped record buffer;
outputs are per-shard record blocks that the host concatenates (shard-major
= line-major = discovery order) and feeds to the same exact-f64 finalizer
as the single-device engine. No float64 — and no floating point at all —
ever runs on the devices.

Halo rows are masked-valid *before* exchange, so shard edges and batch
padding contribute nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from log_parser_tpu import _clock as pclock
from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.ops.fused import (
    K_LADDER,
    NO_HIT,
    FusedStaticTables,
    MatchRecords,
    _prefix,
    _prev_next_dist,
    compact_records,
    sequence_flags_from_events,
)
from log_parser_tpu.parallel.mesh import DATA_AXIS
from log_parser_tpu.patterns.bank import (
    CTX_ERROR,
    CTX_EXCEPTION,
    CTX_STACK,
    CTX_WARN,
    PatternBank,
)
from log_parser_tpu.runtime.engine import AnalysisEngine
from log_parser_tpu.utils.trace import NO_TRACE


def _ring_halo(x: jax.Array, h: int, d: int) -> jax.Array:
    """[Bl, K] -> [h + Bl + h, K]: h rows from each ring neighbor via
    ppermute; edge shards receive zeros (ppermute's missing-source fill).
    ``d`` is the mesh axis size — the permutation list must be static, so
    the caller passes it rather than querying the traced axis."""
    from_left = jax.lax.ppermute(
        x[-h:], DATA_AXIS, [(i, i + 1) for i in range(d - 1)]
    )
    from_right = jax.lax.ppermute(
        x[:h], DATA_AXIS, [(i + 1, i) for i in range(d - 1)]
    )
    return jnp.concatenate([from_left, x, from_right], axis=0)


class ShardedFusedStep:
    """The full per-batch SPMD program, shard_mapped over the mesh."""

    def __init__(
        self,
        bank: PatternBank,
        config: ScoringConfig,
        mesh,
        matchers,
        multiprocess: bool | None = None,
    ):
        self.bank = bank
        self.config = config
        self.mesh = mesh
        self.matchers = matchers  # MatcherBanks: tiered Shift-Or + DFA cube
        self.t = FusedStaticTables(bank, config)
        self.n_shards = mesh.devices.size

        # static halo requirement per factor family
        self.h_prox = int(self.t.sec_window.max()) if len(self.t.sec_window) else 0
        has_rules = bank.has_context_rules
        self.h_ctx = int(
            max(
                bank.ctx_before[has_rules].max(initial=0),
                bank.ctx_after[has_rules].max(initial=0),
            )
        ) if bank.n_patterns else 0

        self._jit = jax.jit(
            lambda kl, lines, lens, om, ov, n: self._sharded(kl)(lines, lens, om, ov, n),
            static_argnums=(0,),
        )
        # one mesh may span multiple processes (parallel/distributed.py);
        # then inputs must be assembled as global arrays (each process
        # donating its addressable shards) and outputs gathered across
        # processes before host assembly. A process-local mesh inside a
        # multi-process runtime (the degrade-to-local step) passes an
        # explicit False: its collectives must never leave this process.
        self.multiprocess = (
            jax.process_count() > 1 if multiprocess is None else multiprocess
        )

    # ------------------------------------------------- host<->device helpers

    def _put(self, x, spec) -> jax.Array:
        """Device-put respecting the multi-process mesh: every process holds
        the full host value (requests are replicated by broadcast), so each
        donates the shards it addresses."""
        if not self.multiprocess:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding

        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, NamedSharding(self.mesh, spec), lambda idx: arr[idx]
        )

    def _host(self, x) -> np.ndarray:
        """Fetch a (possibly process-spanning) device array to every host."""
        if not self.multiprocess:
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    def _sharded(self, k_local: int):
        return jax.shard_map(
            lambda lines, lens, om, ov, n: self._step(k_local, lines, lens, om, ov, n),
            mesh=self.mesh,
            in_specs=(
                P(DATA_AXIS, None),  # lines [B, T] (transposed on device)
                P(DATA_AXIS),  # lengths [B]
                P(DATA_AXIS, None),  # override_mask [B, C]
                P(DATA_AXIS, None),  # override_val [B, C]
                P(),  # n_lines
            ),
            out_specs=(
                P(DATA_AXIS),  # n_matches per shard [D]
                P(DATA_AXIS),  # rec line (global) [D*K_l]
                P(DATA_AXIS),  # rec pattern [D*K_l]
                P(DATA_AXIS, None),  # rec sec dists [D*K_l, S_max]
                P(DATA_AXIS, None),  # rec seq flags [D*K_l, Q_max]
                P(DATA_AXIS, None),  # rec ctx counts [D*K_l, 5]
            ),
            check_vma=False,
        )

    # ------------------------------------------------------------- host API

    def __call__(
        self,
        lines_u8: np.ndarray,
        lengths: np.ndarray,
        override_mask: np.ndarray,
        override_val: np.ndarray,
        n_lines: int,
        k_hint: int = 0,
    ) -> MatchRecords:
        """Runs the SPMD step, growing per-shard record buffers until every
        shard's matches fit; returns globally-ordered match records."""
        B = lines_u8.shape[0]
        D = self.n_shards
        cap_local = (B // D) * max(1, self.bank.n_patterns)
        # contiguous [B, T] upload; the step transposes on device (a host
        # .T copy measured ~9x the contiguous upload — ops/fused.py)
        lines_bt = self._put(lines_u8, P(DATA_AXIS, None))
        lens = self._put(lengths, P(DATA_AXIS))
        om = self._put(override_mask, P(DATA_AXIS, None))
        ov = self._put(override_val, P(DATA_AXIS, None))
        n = self._put(np.asarray(n_lines, dtype=np.int32), P())

        start = 0
        per_shard_hint = -(-max(1, k_hint) // D)
        while start < len(K_LADDER) - 1 and K_LADDER[start] < per_shard_hint:
            start += 1
        for k_bucket in (*K_LADDER[start:], cap_local):
            k_l = min(k_bucket, cap_local)
            out = self._jit(k_l, lines_bt, lens, om, ov, n)
            n_per_shard = self._host(out[0])
            if n_per_shard.max(initial=0) <= k_l or k_l >= cap_local:
                return self._assemble(k_l, n_per_shard, out)
        raise AssertionError("unreachable: ladder capped at per-shard B*P")

    def _assemble(self, k_l: int, n_per_shard: np.ndarray, out) -> MatchRecords:
        """Concatenate each shard's live records; shard-major order is
        line-major order because line sharding is contiguous."""
        D = self.n_shards
        line = self._host(out[1]).reshape(D, k_l)
        pat = self._host(out[2]).reshape(D, k_l)
        dist = self._host(out[3]).reshape(D, k_l, -1)
        seq = self._host(out[4]).reshape(D, k_l, -1)
        ctx = self._host(out[5]).reshape(D, k_l, -1)
        keep = [np.arange(min(int(n), k_l)) for n in n_per_shard]
        return MatchRecords(
            n_matches=int(sum(len(k) for k in keep)),
            line=np.concatenate([line[d, k] for d, k in enumerate(keep)] or [line[0, :0]]),
            pattern=np.concatenate([pat[d, k] for d, k in enumerate(keep)] or [pat[0, :0]]),
            sec_dist=np.concatenate([dist[d, k] for d, k in enumerate(keep)] or [dist[0, :0]]),
            seq_ok=np.concatenate([seq[d, k] for d, k in enumerate(keep)] or [seq[0, :0]]),
            ctx_counts=np.concatenate([ctx[d, k] for d, k in enumerate(keep)] or [ctx[0, :0]]),
        )

    # ------------------------------------------------------------ the step

    def _step(self, K, lines_bt, lengths, override_mask, override_val, n_lines):
        lines_tb = lines_bt.T  # device-side layout change (see run())
        bank, t = self.bank, self.t
        Bl = lengths.shape[0]
        P_ = bank.n_patterns
        d = jax.lax.axis_index(DATA_AXIS)
        lidx = jnp.arange(Bl, dtype=jnp.int32)
        gidx = (d * Bl + lidx).astype(jnp.int32)
        valid = gidx < n_lines

        # ---- local match (no communication; tiered Shift-Or + DFA) --------
        # barrier as in ops/fused.py: keep XLA from fusing factor
        # extraction back into the scan loops
        cube = jax.lax.optimization_barrier(
            self.matchers.cube(lines_tb, lengths)
        )
        cube = jnp.where(override_mask, override_val, cube)
        cube = cube & valid[:, None]

        if P_ == 0:
            z32 = jnp.zeros((K,), jnp.int32)
            return (
                jnp.zeros((1,), jnp.int32),
                z32,
                z32,
                jnp.full((K, max(1, t.s_max)), NO_HIT, jnp.int32),
                jnp.zeros((K, max(1, t.q_max)), bool),
                jnp.zeros((K, 5), jnp.int32),
            )

        pm = cube[:, jnp.asarray(bank.primary_columns)]  # [Bl, P]

        sec_dist = self._secondary_distances(cube, lidx, Bl)
        seq_ok = self._sequence_flags(cube, gidx, Bl, n_lines)
        ctx_counts = self._context_counts(cube, gidx, lidx, Bl, n_lines)

        # per-shard compaction: emit global line indexes, gather local rows
        n_matches, rec_gline, rec_pat, rec_dist, rec_seq, rec_ctx = compact_records(
            K, pm, t, gidx, lidx, sec_dist, seq_ok, ctx_counts
        )
        return n_matches[None], rec_gline, rec_pat, rec_dist, rec_seq, rec_ctx

    # ---------------------------------------------------------- factor parts

    def _extend(self, cols: jax.Array, h: int, Bl: int):
        """Neighborhood view of sharded columns: (extended array, offset of
        local row 0). ppermute halo when shards are big enough; all_gather
        when the halo would span multiple shards."""
        if h < Bl:
            return _ring_halo(cols, h, self.n_shards), h  # offset is static
        gathered = jax.lax.all_gather(cols, DATA_AXIS, axis=0, tiled=True)
        d = jax.lax.axis_index(DATA_AXIS)
        return gathered, d * Bl  # offset is traced

    def _secondary_distances(self, cube, lidx, Bl):
        """[Bl, n_sec_entries] int32 nearest-hit distance per local line.
        Exact for every in-window hit: any hit within window ≤ h is inside
        the extended view; farther hits report NO_HIT, which the finalizer
        treats identically to out-of-window (ScoringService.java:315-347)."""
        t = self.t
        if len(t.sec_cols) == 0:
            return jnp.full((Bl, 1), NO_HIT, jnp.int32)
        sm = cube[:, jnp.asarray(t.sec_cols)]  # [Bl, S]
        h = max(1, self.h_prox)
        ext, off = self._extend(sm, h, Bl)
        mine = off + lidx  # my rows in ext coordinates
        return _prev_next_dist(ext, jnp.arange(ext.shape[0], dtype=jnp.int32))[mine]

    def _sequence_flags(self, cube, gidx, Bl, n_lines):
        """[Bl, n_sequences] — the backward chain reads arbitrarily far back
        (ScoringService.java:296-305), so the event columns are all_gathered
        and the shared chain logic runs in global coordinates for local rows."""
        t = self.t
        if not self.bank.sequences:
            return jnp.zeros((Bl, 1), dtype=bool)
        em_local = cube[:, jnp.asarray(t.seq_event_cols, dtype=np.int32)]  # [Bl, E]
        em = jax.lax.all_gather(em_local, DATA_AXIS, axis=0, tiled=True)  # [B, E]
        return sequence_flags_from_events(self.bank.sequences, t, em, gidx, n_lines)

    def _context_counts(self, cube, gidx, lidx, Bl, n_lines):
        """[Bl, U, 5] int32 per unique context shape, window sums via
        halo-extended prefix sums with the global clamps of
        AnalysisService.java:142,148 expressed on the global index."""
        t = self.t
        err = cube[:, CTX_ERROR]
        warn = cube[:, CTX_WARN] & ~err
        stack = cube[:, CTX_STACK]
        exc = cube[:, CTX_EXCEPTION]
        flags = jnp.stack([err, warn, stack, exc], axis=1).astype(jnp.int32)  # [Bl, 4]

        h = max(1, self.h_ctx)
        ext, off = self._extend(flags, h, Bl)
        ps = _prefix(ext)  # [ext+1, 4]
        ext_len = ext.shape[0]
        mine = off + lidx

        per_shape = []
        for has_rules, before, after in t.ctx_shapes:
            if not has_rules:
                counts = flags
                total = jnp.ones((Bl,), jnp.int32)
            else:
                lo_g = jnp.maximum(gidx - before, 0)
                hi_g = jnp.minimum(gidx + 1 + after, n_lines).astype(jnp.int32)
                hi_g = jnp.maximum(hi_g, lo_g)
                total = hi_g - lo_g
                lo_e = jnp.clip(mine - (gidx - lo_g), 0, ext_len)
                hi_e = jnp.clip(mine + (hi_g - gidx), 0, ext_len)
                counts = ps[hi_e] - ps[lo_e]  # [Bl, 4]
            per_shape.append(jnp.concatenate([counts, total[:, None]], axis=1))
        return jnp.stack(per_shape, axis=1)  # [Bl, U, 5]


class ShardedEngine(AnalysisEngine):
    """AnalysisEngine whose device step is the shard_map program: the line
    batch is sharded over the mesh, and every other responsibility (ingest,
    host verification, frequency tracking, exact-f64 finalization, result
    assembly, observability) is the inherited shared pipeline."""

    def __init__(self, pattern_sets, config=None, mesh=None, clock=None):
        import time as _time

        super().__init__(pattern_sets, config, clock=clock or pclock.mono)
        if mesh is None:
            from log_parser_tpu.parallel.mesh import make_mesh

            mesh = make_mesh()
        self.mesh = mesh
        self.step = ShardedFusedStep(self.bank, self.config, mesh, self.matchers)
        self.tables = self.step.t

    def _install_library(self, source) -> None:
        # the SPMD program and its static tables are compiled against the
        # bank — rebuild both on the swapped library (hot reload)
        super()._install_library(source)
        self.step = ShardedFusedStep(
            self.bank, self.config, self.mesh, self.matchers
        )
        self.tables = self.step.t

    def _corpus_min_rows(self) -> int:
        # row padding must be divisible by the mesh size for shard_map
        return max(8, self.mesh.devices.size)

    def _run_device(self, enc, n_lines: int, om, ov, trace=NO_TRACE):
        B = enc.u8.shape[0]
        C = self.bank.n_columns
        if om is None:  # the SPMD program's in_specs always take overrides
            om = np.zeros((B, C), dtype=bool)
            ov = np.zeros((B, C), dtype=bool)
        return self.step(
            enc.u8, enc.lengths, om, ov, n_lines, k_hint=self._k_hint
        )
